"""The reduction from a profiler trace to device busy time, idle share,
exposed collectives and program time, on hand-made intervals, on a
trace recorded here on the CPU (the loader), and on a slice of a trace
recorded on a TPU v5e chip (``data/``)."""
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import TraceAnnotation

from chipbench import trace as T

DATA = Path(__file__).resolve().parent / "data"


def test_union_clip_subtract():
    u = T.union([(5, 7), (0, 2), (1, 3), (6, 9), (9, 9)])
    assert u == [(0, 3), (5, 9)]
    assert T.clip(u, 2, 6) == [(2, 3), (5, 6)]
    assert T.total(u) == 7
    assert T.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) \
        == [(0, 1), (2, 4), (6, 9)]
    assert T.gaps([(1, 2), (4, 6)], 0, 8) == [(0, 1), (2, 4), (6, 8)]


def _fake():
    # device 0: compute 0-40, all-reduce 30-60 (10 ns exposed... of 30),
    # idle 60-80, a decode program 80-100; device 1 idle but one op
    ops0 = [[0, 40, "fusion.1", "convolution"],
            [30, 60, "all-reduce.3", "collective"],
            [80, 100, "fusion.2", "loop fusion"]]
    ops1 = [[0, 30, "%while.2 = (s32[]) while(%t), body=%b", ""],
            [10, 20, "all-gather.1", ""]]
    return {"devices": {
        "0": {"ops": ops0, "modules": [[0, 60, "jit_fused(1)"],
                                       [80, 100, "jit__decode_impl(2)"]]},
        "1": {"ops": ops1, "modules": []}},
        "spans": [[0, 100, "chipbench.window"],
                  [55, 85, "chipbench.serve_step"],
                  [58, 70, "chipbench.loader_next"]]}


def test_reduce_on_hand_made_intervals():
    red = T.reduce(_fake(), 0, 100)
    d0, d1 = red["devices"]["0"], red["devices"]["1"]
    assert red["window_s"] == pytest.approx(100e-9)
    assert d0["busy_s"] == pytest.approx(80e-9)
    assert d0["idle_share"] == pytest.approx(0.2)
    assert d0["collective_s"] == pytest.approx(30e-9)
    assert d0["collective_exposed_s"] == pytest.approx(20e-9)
    assert d1["collective_exposed_s"] == pytest.approx(10e-9)
    # the while op spans its body: busy, but hides no collective
    assert d1["busy_s"] == pytest.approx(30e-9)
    assert red["busy_s"] == pytest.approx(55e-9)
    assert T.module_durations(red, "decode") == [pytest.approx(20e-9)]
    assert T.module_durations(red, "fused") == [pytest.approx(60e-9)]
    # the 60-80 gap falls in the loader span (innermost at its middle)
    assert red["idle_gaps"][0] == ("chipbench.loader_next",
                                   pytest.approx(20e-9))
    names = [n for n, _ in red["top_ops"]]
    assert names[0] == "fusion.1"
    assert not any("while" in n for n in names)


def test_window_of_and_span_at():
    tr = _fake()
    assert T.window_of(tr) == (0, 100)
    assert T.span_at(tr["spans"], 60) == "chipbench.loader_next"
    assert T.span_at(tr["spans"], 99) == "outside spans"
    with pytest.raises(ValueError):
        T.window_of({"spans": []})


def test_load_reads_benchmark_spans_from_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with TraceAnnotation("chipbench.window"):
        for _ in range(3):
            with TraceAnnotation("chipbench.serve_step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = T.load(T.find_xplane(tmp_path))
    names = [n for _, _, n in tr["spans"]]
    assert names.count("chipbench.serve_step") == 3
    t0, t1 = T.window_of(tr)
    assert t1 > t0
    assert tr["devices"] == {}          # the CPU has no TPU plane
    path = tmp_path / "t.json.gz"
    T.to_json(tr, path)
    assert T.from_json(path)["spans"] == [list(s) for s in tr["spans"]]


def test_recorded_v5e_serve_slice():
    """Half a second of a traced run of the serving engine (seesaw-150m,
    64 slots) on one v5e chip, its host loop in ``serve_step`` spans:
    the decode program (~127 ms an execution) and the prefill programs
    (~40 ms) between host gaps, attributed by program name."""
    tr = T.from_json(DATA / "v5e_serve_slice.json.gz")
    t0, t1 = T.window_of(tr)
    red = T.reduce(tr, t0, t1)
    d0 = red["devices"]["0"]
    assert red["window_s"] == pytest.approx(0.5)
    assert 0 < d0["busy_s"] < red["window_s"]
    assert d0["idle_share"] == pytest.approx(1 - d0["busy_s"] / 0.5)
    assert d0["idle_share"] == pytest.approx(0.2325, abs=1e-3)
    assert d0["collective_s"] == 0.0
    decode = T.module_durations(red, r"1683067639995215592")
    assert decode and all(0.1 < d < 0.15 for d in decode)
    prefill = [d for n, ds in d0["modules"].items()
               if n.startswith("jit__unknown") and "16830676" not in n
               for d in ds]
    assert prefill and all(0.03 < d < 0.05 for d in prefill)
    # every program execution but one lies inside a serve_step span
    assert len(T.span_durations(red, "chipbench.serve_step")) == 11
    assert all(name == "chipbench.serve_step"
               for name, _ in red["idle_gaps"][:3])
    assert math.isclose(sum(t for _, t in red["top_ops"][:1]), 0.0795,
                        rel_tol=1e-2)


def test_recorded_v5e_train_slice():
    """Half a second of the training cell's traced window on four v5e
    chips (4x1 mesh, one fused step program)."""
    tr = T.from_json(DATA / "v5e_train_slice.json.gz")
    t0, t1 = T.window_of(tr)
    red = T.reduce(tr, t0, t1)
    assert sorted(red["devices"]) == ["0", "1", "2", "3"]
    for v in red["devices"].values():
        assert v["idle_share"] == pytest.approx(0.0237, abs=1e-3)
        # the collectives' own ops: the all-to-all, collective-permutes,
        # an all-reduce and an all-gather, about 4 ms of the 0.5 s
        assert 0.003 < v["collective_s"] < 0.005
        # one op at a time runs on a chip's stream, so no other op
        # hides them
        assert v["collective_exposed_s"] == pytest.approx(
            v["collective_s"])
        assert v["collective_s"] < 0.1 * v["busy_s"]
    # fusions that only take a collective's output are not collectives
    fused = [n for _, _, n, _ in tr["devices"]["0"]["ops"]
             if n.startswith("%fusion") and "all-gather" in n]
    assert fused and not any(T.is_collective(n, "") for n in fused)
    # the slice holds the boundary between two executions of the fused
    # step: the device's longest idle gap lies between them
    steps = [(b, e) for b, e, n in tr["devices"]["0"]["modules"]
             if n.startswith("jit_fused")]
    assert len(steps) == 2
    gap = steps[1][0] - steps[0][1]
    assert 0.005e9 < gap < 0.02e9
    assert red["idle_gaps"][0][1] == pytest.approx(gap / 1e9, abs=1e-3)
    assert all(len(n) <= 120 for n, _ in red["top_ops"])
