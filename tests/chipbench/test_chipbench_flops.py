"""``chipbench/flops.py`` against the program's own parameter count."""
import dataclasses
import json

import pytest

from chipbench import common, flops
from repro.configs.seesaw_paper import SEESAW_150M, SEESAW_300M


def cfg(name):
    return json.loads((common.BENCH / "configs" / f"{name}.json")
                      .read_text())["model"]


def preset(model):
    """A registry preset as the model block of a configuration file."""
    return dataclasses.asdict(model)


@pytest.mark.parametrize("model", [SEESAW_150M, SEESAW_300M],
                         ids=lambda m: m.name)
def test_param_count_matches_the_program(model):
    assert flops.param_count(preset(model)) == model.param_count()


def test_config_file_matches_the_registry_preset():
    m = cfg("seesaw-300m")
    for k, v in m.items():
        assert getattr(SEESAW_300M, k) == v, k
    assert flops.param_count(m) == SEESAW_300M.param_count()


def test_required_work_convention():
    m = cfg("seesaw-300m")
    S = 1024
    fwd = flops.forward_flops_per_token(m, S)
    # 2 per multiply-add over every product weight, the head over the
    # logical vocabulary, causal attention at S / 2 keys per token
    layer = 2 * flops.layer_matmul_params(m) + 2 * 2 * 1024 * S / 2
    assert fwd == 24 * layer + 2 * 1024 * 32128
    assert flops.train_flops_per_token(m, S) == 3 * fwd
    assert flops.train_flops_per_token(m, S) == pytest.approx(2.766e9,
                                                              rel=1e-3)
    assert flops.train_flops_per_token(preset(SEESAW_150M), S) \
        == pytest.approx(1.481e9, rel=1e-3)


@pytest.mark.parametrize("model,want", [(SEESAW_150M, 201.4e6),
                                        (SEESAW_300M, 402.7e6)],
                         ids=["seesaw-150m", "seesaw-300m"])
def test_non_embedding_sizes_as_the_config_states(model, want):
    m = preset(model)
    non_emb = flops.param_count(m) - 2 * m["vocab_size"] * m["d_model"]
    assert non_emb == pytest.approx(want, rel=1e-3)
