"""Weights from the seed, drawn by the reference module the
configuration names, and the check that the program keeps its state in
the types the configuration states."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_tiny
from chipbench import common, weights
from chipbench.drivers import train

CELL = "train-300m-b512-4x1"


def _cfg(**kw):
    cfg = copy.deepcopy(chipbench_tiny.spec(CELL)["config"])
    cfg.update(kw)
    return cfg


def test_same_seed_same_weights_in_the_stated_type():
    a = weights.make(2 ** 31 + 7, _cfg())
    b = weights.make(2 ** 31 + 7, _cfg())
    c = weights.make(7, _cfg())
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.array_equal(x, y)
    assert not np.array_equal(a["embed"]["tok"], c["embed"]["tok"])
    assert {x.dtype for x in jax.tree.leaves(a)} == {jnp.dtype("float32")}
    low = weights.make(7, _cfg(param_dtype="bfloat16"))
    assert {x.dtype for x in jax.tree.leaves(low)} == {jnp.dtype("bfloat16")}


def test_the_tree_is_the_references_layout():
    cfg = _cfg()
    m = cfg["model"]
    w = weights.make(3, cfg)
    assert w["layers"]["mlp"]["w_gate"].shape == (m["n_layers"],
                                                  m["d_model"], m["d_ff"])
    n = sum(x.size for x in jax.tree.leaves(w))
    ref = common.reference(cfg)
    # the tree adds the padding of the vocabulary and the final norm
    pad = (ref.padded_vocab(m) - m["vocab_size"]) * m["d_model"] * 2
    assert n == ref.param_count(m) + pad + m["d_model"]


class _State:
    def __init__(self, params, opt_state):
        self.params, self.opt_state = params, opt_state


def test_a_state_in_another_type_than_stated_is_refused():
    cfg = _cfg()
    p = {"w": jnp.zeros((2,), jnp.float32)}
    opt = {"m": jnp.zeros((2,), jnp.float32),
           "count": jnp.zeros((), jnp.int32)}
    train.check_dtypes(cfg, _State(p, opt))
    with pytest.raises(ValueError, match="optimizer state"):
        train.check_dtypes(cfg, _State(p, {"m": p["w"].astype(
            jnp.bfloat16)}))
    with pytest.raises(ValueError, match="weights"):
        train.check_dtypes(cfg, _State({"w": p["w"].astype(jnp.bfloat16)},
                                       opt))
