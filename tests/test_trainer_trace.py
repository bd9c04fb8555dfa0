"""The trainer's host spans on the profiler's clock, and the time its
history gives each step."""
import jax
import pytest
from jax.profiler import ProfileData

from repro.configs import (ModelConfig, OptimizerConfig, RunConfig,
                           ScheduleConfig)
from repro.data import MarkovLM, PhaseDataLoader
from repro.train import trainer as trainer_mod
from repro.train.trainer import Trainer

TINY = ModelConfig(name="tiny", arch_type="dense", n_layers=2, d_model=64,
                   n_heads=2, n_kv_heads=2, head_dim=32, d_ff=128,
                   vocab_size=128, max_seq_len=64, rope_theta=1e4)
K = 2


def _trainer(steps=8, log_every=2):
    cfg = RunConfig(model=TINY,
                    schedule=ScheduleConfig(kind="constant", base_lr=1e-3),
                    optimizer=OptimizerConfig(kind="adamw"),
                    seq_len=16, global_batch_size=4,
                    total_tokens=16 * 4 * steps, remat=False,
                    log_every=log_every)
    tr = Trainer(cfg, fuse_steps=K)
    return tr, PhaseDataLoader(MarkovLM(128, seed=0), tr.plan, 16)


def _spans(directory):
    """``[(start, end, name, stats)]`` of the ``repro.*`` host events."""
    path = next(directory.glob("plugins/profile/*/*.xplane.pb"))
    pd = ProfileData.from_file(str(path))
    return sorted(((ev.start_ns, ev.end_ns, ev.name, dict(ev.stats))
                   for plane in pd.planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for ev in line.events
                   if ev.name.startswith("repro.")),
                  key=lambda s: s[:2])


def _inside(child, parent):
    return parent[0] <= child[0] and child[1] <= parent[1]


def _traced_run(tmp_path, tr, loader, **run_kw):
    jax.profiler.start_trace(str(tmp_path / "trace"))
    tr.run(loader, **run_kw)
    jax.profiler.stop_trace()
    return _spans(tmp_path / "trace")


def test_run_writes_one_step_span_per_chunk_with_its_parts(tmp_path):
    stops = []

    def stop_fn():
        stops.append(1)
        return False

    tr, loader = _trainer()
    spans = _traced_run(tmp_path, tr, loader, stop_fn=stop_fn,
                        checkpoint_path=str(tmp_path / "ck"),
                        save_every=4, async_save=False)
    tr.close()
    chunks = len(tr.history) // K
    assert chunks == 4
    steps = [s for s in spans if s[2] == "repro.train.step"]
    by_name = {}
    for s in spans:
        by_name.setdefault(s[2], []).append(s)
    # one step span per dispatch, and a last pass that finds the
    # stream empty
    assert len(steps) == chunks + 1
    assert len(by_name["repro.train.dispatch"]) == chunks
    assert [s[3]["step_num"] for s in steps] == [0, 2, 4, 6, 8]
    for s in steps[:-1]:
        kids = {c[2] for c in spans if c is not s and _inside(c, s)}
        assert {"repro.train.next_chunk", "repro.train.dispatch",
                "repro.train.hook"} <= kids
    assert {c[2] for c in spans if _inside(c, steps[-1])} == {
        "repro.train.step", "repro.train.next_chunk"}
    # every part lies in a step span, and the loop's own parts do not
    # overlap each other
    for name in ("next_chunk", "dispatch", "sync", "hook", "checkpoint"):
        got = by_name["repro.train." + name]
        assert got and all(any(_inside(c, s) for s in steps) for c in got)
    assert len(by_name["repro.train.hook"]) == len(stops) == chunks
    assert len(by_name["repro.train.sync"]) == chunks      # log_every K
    assert len(by_name["repro.train.checkpoint"]) == 2     # steps 4, 8
    parts = [c for c in spans if c[2] != "repro.train.step"]
    assert all(a[1] <= b[0] for a, b in zip(parts, parts[1:]))
    assert "repro.train.cut" not in by_name       # prescheduled plan


def test_stopped_run_ends_on_the_chunk_that_stopped(tmp_path):
    tr, loader = _trainer()
    spans = _traced_run(tmp_path, tr, loader,
                        stop_fn=lambda: tr.state.step >= 4)
    steps = [s for s in spans if s[2] == "repro.train.step"]
    assert [s[3]["step_num"] for s in steps] == [0, 2]
    assert len(tr.history) == 4


def test_history_wall_is_taken_when_the_metrics_reach_the_host(
        monkeypatch):
    """``wall`` is read after the step's metrics arrive, so it counts the
    step's own device time: here every transfer takes ten seconds of a
    fake clock, and each flushed step reads the clock after its own."""
    clock = [0.0]

    class FakeTime:
        @staticmethod
        def time():
            return clock[0]

    real_get = jax.device_get

    def slow_get(x):
        clock[0] += 10.0
        return real_get(x)

    monkeypatch.setattr(trainer_mod, "time", FakeTime)
    monkeypatch.setattr(jax, "device_get", slow_get)
    tr, loader = _trainer(steps=6, log_every=K)
    hist = tr.run(loader)
    assert [h["wall"] for h in hist] == pytest.approx(
        [10.0, 10.0, 20.0, 20.0, 30.0, 30.0])
