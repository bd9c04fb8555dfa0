"""The program's named scopes as the benchmark reads them: the op-name map
from the step's HLO, the one classification rule, and the reduction of
the program's spans and step executions on hand-made intervals."""
import jax.numpy as jnp
import pytest

import chipbench_tiny
from chipbench import program_trace as PT
from chipbench import scopes
from chipbench.drivers import train

CELL = "train-300m-b512-4x1"

HLO = "\n".join([
    "ENTRY %main {",
    '  %fusion.7 = bf16[4,8]{1,0} fusion(%p), kind=kLoop, calls=%f, '
    'metadata={op_name="jit(train_step)/cond/branch_1_fun/jvp(forward)/'
    'attention/dot_general" source_file="a.py" source_line=3}',
    '  ROOT %add.2 = f32[] add(%x, %y), '
    'metadata={op_name="jit(train_step)/optimizer/add"}',
    "  %copy.1 = f32[8]{0} copy(%z)",
    '  %b.3 = f32[8]{0} negate(%z), '
    'metadata={op_name="k[\\"a\\"]/mul" stack_frame_id=2}',
    "}"])


def test_op_names_from_hlo_text():
    got = scopes.op_names(HLO)
    assert got == {
        "fusion.7": "jit(train_step)/cond/branch_1_fun/jvp(forward)/"
                    "attention/dot_general",
        "add.2": "jit(train_step)/optimizer/add",
        "b.3": 'k[\\"a\\"]/mul'}
    assert scopes.instruction(
        "%fusion.7 = bf16[4,8]{1,0} fusion(%p), calls=%f") == "fusion.7"


@pytest.mark.parametrize("instr,op_name,cls", [
    ("fusion.1", "jit(train_step)/while/body/closed_call/cond/branch_1_fun"
                 "/jvp(forward)/while/body/closed_call/attention/dot",
     "forward"),
    ("fusion.2", "jit(train_step)/cond/branch_1_fun/transpose(jvp("
                 "forward))/while/body/closed_call/checkpoint/attention/dot",
     "backward"),
    ("fusion.3", "jit(train_step)/cond/branch_1_fun/transpose(jvp("
                 "forward))/while/body/closed_call/checkpoint/"
                 "rematted_computation/attention/dot", "recompute"),
    # a clone XLA's rematerialization made of a backward op
    ("fusion.4.remat2", "jit(train_step)/cond/branch_1_fun/transpose(jvp("
                        "forward))/dot", "recompute"),
    ("fusion.5", "jit(train_step)/cond/branch_1_fun/optimizer/mul",
     "optimizer"),
    ("fusion.6", "jit(train_step)/while/body/closed_call/add", "unscoped"),
    ("copy.1", "", "unscoped"),
    ("fusion.7", "jit(train_step)/forwarder/add", "unscoped"),
])
def test_classify(instr, op_name, cls):
    assert scopes.classify(instr, op_name) == cls


def test_attention_is_a_whole_scope_name():
    def covers(op_name):
        return "attention" in scopes.components(op_name)
    assert covers("a/jvp(forward)/attention/dot")
    assert covers("a/checkpoint/attention")
    assert not covers("a/dot_product_attention/dot")
    assert not covers("a/attention_bias/add")
    assert scopes.components("jit(train_step)/transpose(jvp(forward))/"
                             "attention/causal_blocks/dot") == [
        "jit", "train_step", "transpose", "jvp", "forward", "attention",
        "causal_blocks", "dot"]


def test_scope_map_of_the_programs_train_step():
    """The step the engine compiles for a reduced cell carries every
    scope, and the classes do not mix the passes."""
    spec = chipbench_tiny.spec(CELL)
    rc = train.run_config(spec["config"], spec["traffic"])
    from repro.core.seesaw import build_plan
    from repro.optim import optimizers as O
    from repro.train.engine import PhaseEngine
    plan = build_plan(kind="constant", base_lr=1e-3, total_tokens=10 ** 6,
                      warmup_frac=0.0, b0=rc.global_batch_size)
    eng = PhaseEngine(rc, O.from_config(rc.optimizer), plan,
                      max_device_batch=spec["traffic"]["max_device_batch"])
    B, S = rc.global_batch_size, rc.seq_len
    chunk = {"tokens": jnp.zeros((1, B, S), jnp.int32),
             "labels": jnp.zeros((1, B, S), jnp.int32)}
    names = scopes.train_step_op_names(eng, chunk)
    by_cls = {}
    for instr, name in names.items():
        by_cls.setdefault(scopes.classify(instr, name), []).append(name)
    assert set(by_cls) == set(scopes.CLASSES)
    assert all(by_cls[c] for c in scopes.CLASSES)
    assert not any("transpose(" in n for n in by_cls["forward"])
    assert all("transpose(jvp(forward))" in n for n in by_cls["backward"])
    assert all("rematted_computation" in n for n in by_cls["recompute"])
    attn = [n for n in names.values()
            if "attention" in scopes.components(n)]
    assert {scopes.classify("", n) for n in attn} >= {
        "forward", "backward", "recompute"}


# ------------------------------------------------------------------ #
# the reduction, on hand-made intervals (ns)
# ------------------------------------------------------------------ #

def _fake():
    ops = [[0, 30, "%fusion.1 = f32[] fusion(%a)", ""],
           [30, 40, "%fusion.2 = f32[] fusion(%a)", ""],
           [0, 40, "%while.1 = (s32[]) while(%t), body=%b", ""],
           [45, 47, "%convert.1 = s32[] convert(%c)", ""],
           [60, 90, "%fusion.1 = f32[] fusion(%a)", ""],
           [90, 95, "%fusion.3.remat2 = f32[] fusion(%a)", ""]]
    return {"devices": {"0": {"ops": ops, "modules": [
        [0, 40, "jit_train_step(7)"], [45, 47, "jit_convert(3)"],
        [60, 95, "jit_train_step(7)"]]}},
        "spans": [[0, 100, "chipbench.window"],
                  [38, 62, "repro.train.step"],
                  [40, 50, "repro.train.sync"],
                  [55, 61, "repro.train.dispatch"]]}


def test_by_span_splits_a_gap_over_the_spans_it_crosses():
    spans = _fake()["spans"]
    got = PT.by_span([(40, 60)], spans, 0, 100)
    assert got == pytest.approx({"repro.train.sync": 10e-9,
                                 "repro.train.step": 5e-9,
                                 "repro.train.dispatch": 5e-9})
    got = PT.by_span([(95, 100), (-5, 0)], spans, 0, 100)
    assert got == pytest.approx({"outside spans": 5e-9})


def test_reduce_steps_ops_gaps_and_idle_by_span():
    tr = _fake()
    red = PT.reduce(tr, 0, 100)["0"]
    assert red["steps"] == [[0, 40], [60, 95]]
    assert red["n_steps"] == pytest.approx(2.0)
    # leaf ops inside the two executions, by instruction; the while and
    # the convert program between the steps are left out
    assert red["op_s"] == pytest.approx({"fusion.1": 60e-9,
                                         "fusion.2": 10e-9,
                                         "fusion.3.remat2": 5e-9})
    # 40-60 between the executions, of which 45-47 busy
    assert red["step_gaps_s"] == pytest.approx([18e-9])
    assert red["idle_by_span"] == pytest.approx({
        "repro.train.sync": 8e-9, "repro.train.step": 5e-9,
        "repro.train.dispatch": 5e-9, "outside spans": 5e-9})
    assert PT.step_gap_ms(PT.reduce(tr, 0, 100)) == pytest.approx(18e-6)
    # a window that cuts the second execution counts its share
    cut = PT.reduce(tr, 0, 67)["0"]
    assert cut["n_steps"] == pytest.approx(1.2)
    assert cut["op_s"]["fusion.1"] == pytest.approx(37e-9)


def test_scope_ms_per_execution():
    red = PT.reduce(_fake(), 0, 100)
    names = {"fusion.1": "jit(train_step)/jvp(forward)/attention/dot",
             "fusion.2": "jit(train_step)/optimizer/mul",
             "fusion.3.remat2": "jit(train_step)/transpose(jvp(forward))"}
    ms = PT.scope_ms(red, names)
    assert ms == pytest.approx({"forward": 30e-6, "backward": 0.0,
                                "recompute": 2.5e-6, "optimizer": 5e-6,
                                "unscoped": 0.0, "attention": 30e-6})
    # an instruction missing from the map is unscoped, unless its own
    # name says XLA rematerialized it
    assert PT.scope_ms(red, {}) == pytest.approx(dict(
        dict.fromkeys(ms, 0.0), unscoped=35e-6, recompute=2.5e-6))
    assert PT.scope_ms({"0": dict(red["0"], steps=[])}, names) is None


def test_span_share():
    tr = _fake()
    assert PT.span_share(tr, "repro.train.sync", 0, 100) \
        == pytest.approx(10.0)
    assert PT.span_share(tr, "repro.train.sync", 45, 55) \
        == pytest.approx(50.0)
    assert PT.span_share(tr, "repro.train.next_chunk", 0, 100) is None


def test_load_keeps_the_programs_spans_beside_the_benchmarks(tmp_path):
    """A trace recorded here on the CPU: the benchmark's window and the
    trainer's loop, read back on the one clock."""
    import jax
    from jax.profiler import TraceAnnotation

    from chipbench import trace as T
    from repro.data import MarkovLM, PhaseDataLoader
    from repro.train.trainer import Trainer
    spec = chipbench_tiny.spec(CELL)
    rc = train.run_config(spec["config"], spec["traffic"])
    tr = Trainer(rc, fuse_steps=1)
    loader = PhaseDataLoader(MarkovLM(64, seed=0), tr.plan, rc.seq_len)
    tr.run(loader, max_steps=1)                  # compile outside
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("chipbench.window"):
        tr.run(loader, max_steps=4)
    jax.profiler.stop_trace()
    got = PT.load(T.find_xplane(tmp_path))
    names = [n for _, _, n in got["spans"]]
    assert names.count("chipbench.window") == 1
    assert names.count("repro.train.dispatch") == 3
    assert names.count("repro.train.step") == 4    # and the empty look
    t0, t1 = T.window_of(got)
    share = PT.span_share(got, "repro.train.next_chunk", t0, t1)
    assert 0 < share < 100
