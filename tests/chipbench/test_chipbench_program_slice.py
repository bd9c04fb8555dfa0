"""The accepted per-layer readers give the values they gave before the
program had spans, on both committed v5e slices, with and without the
program's spans in the trace; and the program's spans and scopes read
from a slice recorded with them."""
import copy
from pathlib import Path

import pytest

from chipbench import common
from chipbench import program_trace as PT
from chipbench import scopes
from chipbench import trace as T

DATA = Path(__file__).resolve().parent / "data"

# the readers' values on the committed slices, with the layer below
READ = {
    "v5e_train_slice": {
        "train_step_mfu": 21.81725888324873,
        "device_idle_share.train": 2.371210500000001,
        "collective_exposed_share.train": 0.81475885,
        "loader_wait_share.train": 0.027734375000000002},
    "v5e_serve_slice": {
        "train_step_mfu": 21.81725888324873,
        "device_idle_share.train": 23.250232599999997,
        "collective_exposed_share.train": None,
        "loader_wait_share.train": 0.027734375000000002},
}


def _read(tr):
    t0, t1 = T.window_of(tr)
    layer = {"kind": "train", "trace": T.reduce(tr, t0, t1),
             "window_s": 51.2, "loader_wait_s": 0.0142, "chips": 4,
             "peaks": common.peaks("TPU v5 lite"),
             "flops_per_token": 2.8e9, "tokens_per_s": 61400.0}
    return {m: common.metric_reader(m).read(layer) for m in READ[
        "v5e_train_slice"]}


@pytest.mark.parametrize("name", sorted(READ))
def test_accepted_readers_unchanged_with_program_spans(name):
    tr = T.from_json(DATA / f"{name}.json.gz")
    assert _read(tr) == pytest.approx(READ[name])
    t0, t1 = T.window_of(tr)
    mid = (t0 + t1) / 2
    spanned = copy.deepcopy(tr)
    spanned["spans"] = [[t0, mid, "repro.train.step"],
                        [t0, t0 + 1e6, "repro.train.sync"],
                        [mid - 1e6, mid, "repro.train.dispatch"]
                        ] + spanned["spans"]
    assert _read(spanned) == pytest.approx(READ[name])


# ------------------------------------------------------------------ #
# a recorded slice with the program's spans and scopes
# ------------------------------------------------------------------ #

GAP_S = (0.005, 0.02)      # the step-boundary gaps of the cell, 10-19 ms


def _program_slice():
    """Half a second around one step boundary of the training cell's
    traced window on four v5e chips, with the program's ``repro.train``
    spans and the op-name map of the step's instructions in it."""
    tr = T.from_json(DATA / "v5e_train_program_slice.json.gz")
    return tr, tr.pop("op_names")


def test_program_slice_step_boundary_by_span():
    tr, _ = _program_slice()
    t0, t1 = T.window_of(tr)
    red = PT.reduce(tr, t0, t1)
    assert sorted(red) == ["0", "1", "2", "3"]
    for v in red.values():
        # the end of one train-step execution and the start of the next
        assert len(v["steps"]) == 2
        gap, = v["step_gaps_s"]
        assert GAP_S[0] < gap < GAP_S[1]
        idle = v["idle_by_span"]
        assert sum(idle.values()) == pytest.approx(gap, rel=0.05)
        # the host was finishing the last step's sync, in the loader
        # and in the dispatch of the next: each a share of the gap
        for span in ("repro.train.sync", "chipbench.loader_next",
                     "repro.train.dispatch"):
            assert idle[span] > 0.1 * gap
        assert idle.get("outside spans", 0.0) < 0.01 * gap
    # the breakdown's longest gap is named by a span of the loop
    base = T.reduce(tr, t0, t1)
    assert base["idle_gaps"][0][0] != "outside spans"
    assert 1e3 * GAP_S[0] < PT.step_gap_ms(red) < 1e3 * GAP_S[1]


def test_program_slice_scopes_cover_the_busy_time():
    tr, names = _program_slice()
    t0, t1 = T.window_of(tr)
    red = PT.reduce(tr, t0, t1)
    for dev, v in red.items():
        busy = T.union(T.clip([(s, e) for s, e, _, _ in
                               tr["devices"][dev]["ops"]], t0, t1))
        in_steps = sum(T.total(T.clip(busy, s, e))
                       for s, e in v["steps"]) / 1e9
        # leaf ops inside the executions add up to their busy time
        assert sum(v["op_s"].values()) == pytest.approx(in_steps,
                                                        rel=0.01)
        by_cls = {}
        for instr, sec in v["op_s"].items():
            c = scopes.classify(instr, names.get(instr, ""))
            by_cls[c] = by_cls.get(c, 0.0) + sec
        # the last step's backward and optimizer, the next one's forward
        assert {"forward", "backward", "recompute",
                "optimizer"} <= set(by_cls)
        assert by_cls.get("unscoped", 0.0) < 0.1 * sum(by_cls.values())
    ms = PT.scope_ms(red, names)
    assert ms["attention"] > 0


# ------------------------------------------------------------------ #
# the program's readers on the slice, as a traced run assembles them
# ------------------------------------------------------------------ #

PASS_READERS = {"forward_device_ms.train": "forward",
                "backward_device_ms.train": "backward",
                "recompute_device_ms.train": "recompute",
                "optimizer_device_ms.train": "optimizer",
                "attention_device_ms.train": "attention"}
PROGRAM_READERS = sorted(PASS_READERS) + ["step_gap_ms.train",
                                          "attention_core_roofline.train"]


def _slice_layer(op_names=None):
    """The ``layer`` that ``run.execute`` gives the readers of a traced
    run of the four-chip cell, on the slice."""
    from chipbench.references import olmo_dense
    tr, names = _program_slice()
    names = names if op_names is None else op_names
    red = PT.reduce_window(tr)
    m = common.load_cell("train-300m-b512-4x1")["config"]["model"]
    return {"kind": "train", "trace": red, "op_names": names,
            "scopes": PT.scope_table(red["program"], names),
            "peaks": common.peaks("TPU v5 lite"), "chips": 4,
            "tokens_per_execution": 512 * 1024,
            "scope_work": olmo_dense.scope_work(m, 1024, itemsize=2)}


def _reader(name, layer):
    return common.metric_reader(name).read(layer)


def test_reduce_window_keeps_the_benchmarks_reduction():
    tr, _ = _program_slice()
    t0, t1 = T.window_of(tr)
    bare = dict(tr, spans=[s for s in tr["spans"]
                           if s[2].startswith("chipbench.")])
    red = PT.reduce_window(tr)
    assert {k: v for k, v in red.items() if k != "program"} \
        == T.reduce(bare, t0, t1)
    assert red["program"] == PT.reduce(tr, t0, t1)


def test_pass_readers_are_scope_ms_and_the_gap_is_step_gap_ms():
    layer = _slice_layer()
    ms = PT.scope_ms(layer["trace"]["program"], layer["op_names"])
    for name, cls in PASS_READERS.items():
        assert _reader(name, layer) == pytest.approx(ms[cls]), name
        assert ms[cls] > 0
    assert _reader("step_gap_ms.train", layer) == pytest.approx(
        PT.step_gap_ms(layer["trace"]["program"]))
    # the classes share out every leaf op of the step
    assert sum(layer["scopes"][PT.ALL].values()) == pytest.approx(
        sum(ms[c] for c in scopes.CLASSES))


def test_a_scope_the_program_does_not_write_reads_nothing():
    layer = _slice_layer()
    # the slice predates the blocked core's scope
    assert "causal_blocks" not in layer["scopes"]
    assert _reader("attention_core_roofline.train", layer) is None
    assert PT.scope_reading(layer, "moe") is None
    assert PT.counter(layer, "expert_load") is None
    bare = _slice_layer(op_names={})
    for name in ("forward_device_ms.train", "backward_device_ms.train",
                 "optimizer_device_ms.train", "attention_device_ms.train"):
        assert _reader(name, bare) is None, name
    untraced = {"kind": "train", "window_s": 51.2, "loader_wait_s": 0.01}
    for name in PROGRAM_READERS:
        assert _reader(name, untraced) is None, name


def test_attention_core_roofline_where_the_core_is_named():
    """The slice's attention ops renamed into the core's scope: the
    required work of a step over their time and the peaks."""
    _, names = _program_slice()
    renamed = {i: n.replace("/attention/", "/attention/causal_blocks/")
               for i, n in names.items()}
    layer = _slice_layer(op_names=renamed)
    ms = PT.scope_reading(layer, "causal_blocks")
    assert ms == pytest.approx(PT.scope_reading(layer, "attention"))
    work = layer["scope_work"]["causal_blocks"]
    # 24 layers x 3 x 2 x 2 x 1024 x 512 FLOPs a token
    assert work["flops"] == 24 * 3 * 2 * 2 * 1024 * 512
    tokens = 512 * 1024 / 4
    least = tokens * max(work["flops"] / 197e12, work["bytes"] / 819e9)
    got = _reader("attention_core_roofline.train", layer)
    assert got == pytest.approx(100 * least / (ms / 1e3))
    assert 0 < got < 100


def test_breakdown_labels_name_the_class_and_scopes():
    _, names = _program_slice()
    instr = next(i for i, n in names.items()
                 if "rematted_computation/attention/dot_general" in n)
    got = PT.op_label(f"%{instr} = bf16[8] fusion(%a)", names)
    assert got.startswith("recompute attention/dot_general: %" + instr)
    assert PT.op_label("%copy.9 = f32[] copy(%a)", names) \
        == "%copy.9 = f32[] copy(%a)"
