"""What a traced run takes from the program for its readers, on a whole
run at a size the CPU holds (the look for a chip skipped): the op-name
map of the step that ran, and the step's own scalars over the window,
one of them planted in the step."""
import time

import jax.numpy as jnp
import pytest

import chipbench_tiny
from chipbench import program_trace as PT
from chipbench import run as R
from chipbench import scopes
from chipbench.drivers import train

CELL = "train-300m-b512-4x1"
SEED = 2 ** 31 + 303


def test_traced_run_gives_the_readers_the_programs_names_and_scalars(
        monkeypatch, tmp_path):
    from repro.models import registry
    loss_fn = registry.loss_fn

    def with_probe(*a, **kw):
        loss, metrics = loss_fn(*a, **kw)
        return loss, dict(metrics, probe=jnp.float32(7.0))

    monkeypatch.setattr(registry, "loss_fn", with_probe)
    extra = {}
    out = R.execute(CELL, SEED, 1.0, True, require_chip=False,
                    spec=chipbench_tiny.spec(CELL, chips=1),
                    t_proc=time.perf_counter(), extra=extra,
                    trace_dir=tmp_path / "trace")
    assert out["correct"], out["checks"]
    layer = extra["layer"]
    assert PT.counter(layer, "probe") == pytest.approx(7.0)
    assert PT.counter(layer, "grad_norm") > 0
    assert PT.counter(layer, "expert_load") is None
    assert not set(layer["counters"]) & train.HISTORY_KEYS
    # the map of the step that ran: every pass and the blocked core
    classes = {scopes.classify(i, n) for i, n in
               layer["op_names"].items()}
    assert {"forward", "backward", "recompute", "optimizer"} <= classes
    assert any("causal_blocks" in scopes.components(n)
               for n in layer["op_names"].values())
    assert layer["scope_work"]["causal_blocks"]["flops"] > 0
    # no device plane on the CPU: the device readers read nothing
    assert layer["scopes"] is None
    assert set(out["metrics"]) == {"loader_wait_share.train"}


def test_window_counters_average_the_steps_own_scalars():
    hist = [{"step": s, "tokens": 8 * s, "lr": 0.1, "batch_size": 8,
             "phase": 0, "loss": 3.0, "wall": 1.0, "grad_norm": float(s)}
            for s in range(1, 6)]
    hist[3]["probe"] = 4.0
    assert train.window_counters(hist, 2, 5) == {"grad_norm": 4.0,
                                                 "probe": 4.0}
    assert train.window_counters(hist, 5, 5) == {}
