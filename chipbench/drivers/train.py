"""Training cells: ``Trainer.run`` over ``PhaseEngine``'s fused step.

Set-up builds one trainer on a data-parallel mesh over the cell's chips,
gives it weights made from the seed, and drives it through the checked
steps (``check_steps``) in the same ``Trainer.run`` call that then runs
the window: a ``stop_fn`` polled at every step boundary takes the
readings the comparison needs and then times whole steps until
``--seconds`` have passed.  ``log_every`` 1 makes the trainer fetch each
step's metrics, so a step boundary is a finished step on the device.

After the window the trainer is freed and the plain reference that the
configuration names (``common.reference``) retraces the checked steps
from the same weights and rows (``compare``).  A traced run first takes
what the per-layer readers read of the program (:func:`program_layer`):
the op-name map of the step that ran, the step's own scalars over the
window, and the work per scope that the reference counts.
"""
from __future__ import annotations

import gc
import math
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from chipbench import common, compare, scopes, weights
from chipbench.traffic_gen import MarkovSource

# the keys of a configuration and of a traffic file that this driver
# reads (besides ``common.DESCRIPTIVE_KEYS``)
CONFIG_KEYS = frozenset({
    "name", "arch_type", "reference", "model", "kernel_backend",
    "compute_dtype", "param_dtype", "optimizer_state_dtype", "remat",
    "init_std"})
TRAFFIC_KEYS = frozenset({
    "kind", "global_batch", "seq_len", "max_device_batch", "fuse_steps",
    "log_every", "schedule", "optimizer", "data", "check_steps",
    "reference_block_rows"})


def model_config(cfg: Dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(name=cfg["name"], arch_type=cfg["arch_type"],
                       kernel_backend=cfg["kernel_backend"],
                       **cfg["model"])


def run_config(cfg: Dict, traffic: Dict):
    from repro.configs.base import OptimizerConfig, RunConfig, \
        ScheduleConfig
    s = traffic["schedule"]
    o = traffic["optimizer"]
    return RunConfig(
        model=model_config(cfg),
        schedule=ScheduleConfig(kind=s["kind"], base_lr=s["base_lr"],
                                warmup_frac=s["warmup_frac"],
                                alpha=s["alpha"], beta=s["beta"],
                                n_cuts=s["n_cuts"]),
        optimizer=OptimizerConfig(**o),
        seq_len=traffic["seq_len"],
        global_batch_size=traffic["global_batch"],
        total_tokens=s["total_tokens_per_param"]
        * common.reference(cfg).param_count(cfg["model"]),
        dtype=cfg["compute_dtype"], remat=cfg["remat"],
        log_every=traffic["log_every"])


# the keys of a step's history row that the trainer writes itself; the
# others are scalars the step returns beside its loss
HISTORY_KEYS = frozenset({"step", "tokens", "lr", "batch_size", "phase",
                          "loss", "wall"})


class TimedLoader:
    """The program's loader with each ``next()`` of its chunk stream
    timed and wrapped in a ``chipbench.loader_next`` span.  A planted
    fault may rewrite the chunk the step receives; ``last`` is the last
    chunk the step received."""

    def __init__(self, loader, rewrite=None):
        self.loader = loader
        self.rewrite = rewrite
        self.waits: List[tuple] = []        # (start, seconds)
        self.last = None

    def __getattr__(self, name):
        return getattr(self.loader, name)

    def iter_chunks(self, k):
        it = self.loader.iter_chunks(k)
        while True:
            t = time.perf_counter()
            with TraceAnnotation("chipbench.loader_next"):
                try:
                    phase, chunk, m = next(it)
                except StopIteration:
                    return
            self.waits.append((t, time.perf_counter() - t))
            if self.rewrite is not None:
                chunk = self.rewrite(chunk)
            self.last = chunk
            yield phase, chunk, m


def build_program(ctx: Dict, fault: Optional[str] = None):
    """The trainer with the benchmark's weights, and its timed loader."""
    from repro.data.pipeline import PhaseDataLoader
    from repro.launch.mesh import make_launch_mesh
    from repro.train.trainer import Trainer, TrainState

    cfg, traffic, chips = ctx["config"], ctx["traffic"], ctx["chips"]
    rc = run_config(cfg, traffic)
    mesh = make_launch_mesh(f"{chips}x1") if chips > 1 else None
    if fault == "no_exchange":
        mesh = None          # chip 0 alone, on its own rows
    trainer = Trainer(rc, mesh=mesh,
                      max_device_batch=traffic["max_device_batch"],
                      fuse_steps=traffic["fuse_steps"])
    sh = trainer.engine.state_shardings()
    params = weights.make(ctx["seed"], cfg, None if sh is None else sh[0])
    init = jax.jit(trainer.optimizer.init,
                   out_shardings=None if sh is None else sh[1])
    trainer.state = TrainState(params, init(params))
    check_dtypes(cfg, trainer.state)
    source = MarkovSource(ctx["seed"], cfg["model"]["vocab_size"],
                          traffic["data"]["branching"],
                          traffic["data"]["zipf_a"])
    loader = PhaseDataLoader(source, trainer.plan, traffic["seq_len"],
                             mesh=mesh)
    rewrite = None
    if fault == "half_batch":
        def rewrite(chunk):
            t = chunk["tokens"]
            mask = np.ones(t.shape, np.float32)
            mask[:, 1::2] = 0.0     # every micro-batch loses half its rows
            return dict(chunk, mask=jax.device_put(mask, t.sharding))
    elif fault == "no_exchange":
        def rewrite(chunk):
            return {k: v[:, :v.shape[1] // chips] for k, v in chunk.items()}
    elif fault == "state_unchanged":
        eng = trainer.engine
        step = eng.run_chunk

        def frozen(params, opt_state, *a, **kw):
            keep = jax.tree.map(jnp.copy, (params, opt_state))
            return keep + tuple(step(params, opt_state, *a, **kw)[2:])
        eng.run_chunk = frozen
    return trainer, TimedLoader(loader, rewrite), source


def check_dtypes(cfg: Dict, state) -> None:
    """The program keeps its weights and optimizer state in the types
    the configuration states, or the run is no run of it."""
    for what, tree, want in (("weights", state.params, cfg["param_dtype"]),
                             ("optimizer state", state.opt_state,
                              cfg["optimizer_state_dtype"])):
        got = {str(x.dtype) for x in jax.tree.leaves(tree)
               if jnp.issubdtype(x.dtype, jnp.floating)}
        if got != {want}:
            raise ValueError(f"the program keeps its {what} in {sorted(got)}"
                             f"; the configuration states {want}")


def run(ctx: Dict) -> Dict:
    """One run of a training cell.  ``ctx``: config, traffic, chips,
    seed, seconds, t_proc (process start on the perf clock), tracer,
    limits, and optionally ``fault``."""
    traffic, cfg = ctx["traffic"], ctx["config"]
    tracer = ctx["tracer"]
    n_check = traffic["check_steps"]
    tok_per_step = traffic["global_batch"] * traffic["seq_len"]
    trainer, loader, source = build_program(ctx, ctx.get("fault"))
    prog: Dict = {}
    win: Dict = {}

    def stop_fn():
        st = trainer.state
        if st.step == 1:
            b1 = trainer.cfg.optimizer.beta1
            prog["grad_norms"] = compare.slice_norms(
                jax.tree.map(lambda x: x / (1 - b1), st.opt_state["m"]))
        if st.step == n_check:
            init = weights.make(ctx["seed"], cfg,
                                jax.tree.map(lambda x: x.sharding,
                                             st.params))
            prog["update_norms"] = compare.diff_norms(st.params, init)
            del init
            tracer.start()
            win["span"] = TraceAnnotation("chipbench.window")
            win["span"].__enter__()
            win["t0"] = time.perf_counter()
            win["step0"] = st.step
            return False
        if "t0" in win and time.perf_counter() - win["t0"] >= ctx["seconds"]:
            win["t1"] = time.perf_counter()
            win["step1"] = st.step
            return True
        return False

    trainer.run(loader, stop_fn=stop_fn)
    win["span"].__exit__(None, None, None)
    tracer.stop()
    hist = trainer.history
    losses = [r["loss"] for r in hist]
    prog["losses"] = losses[:n_check]
    window_s = win["t1"] - win["t0"]
    steps = win["step1"] - win["step0"]
    loader_wait = sum(s for t, s in loader.waits
                      if win["t0"] <= t < win["t1"])
    mem = common.memory_peak_bytes(jax.devices()[:ctx["chips"]])
    program = (program_layer(ctx, trainer, loader.last, win)
               if tracer.on else {})
    mesh = trainer.mesh
    del trainer, loader
    gc.collect()
    ref = reference(ctx, source, mesh)
    numbers = compare.train_numbers(prog, ref)
    if ctx.get("calibrate"):
        ctx["calibration"] = calibrate(ctx, source, mesh, ref)
    limits = ctx["limits"]
    checks = {k: common.check_entry(v, limits[k])
              for k, v in numbers.items()}
    failed = sum(not math.isfinite(x) for x in losses)
    tokens_per_s = steps * tok_per_step / window_s
    return {
        "setup_s": win["t0"] - ctx["t_proc"],
        "attempted": len(losses), "failed": failed,
        "checks": checks,
        "correct": failed == 0 and common.checks_pass(checks),
        "memory_peak_bytes": mem,
        "end_to_end": {"train_tokens_per_s": tokens_per_s},
        "layer": {"kind": "train", "tokens_per_s": tokens_per_s,
                  "window_s": window_s, "steps": steps,
                  "loader_wait_s": loader_wait,
                  "flops_per_token": common.reference(cfg)
                  .train_flops_per_token(cfg["model"], traffic["seq_len"]),
                  "tokens_per_execution": traffic["fuse_steps"]
                  * tok_per_step,
                  "chips": ctx["chips"], **program},
        "notes": {"window_steps": steps, "reference_s": ref["seconds"],
                  "checked_losses": prog["losses"],
                  "reference_losses": ref["losses"]},
    }


def program_layer(ctx: Dict, trainer, chunk, win: Dict) -> Dict:
    """What a traced run's readers take from the program, read once the
    window has closed: ``op_names``, the op-name map of the step that
    ran (from the persistent compilation cache); ``counters``, the mean
    over the window's steps of every scalar the step returns beside
    its loss; and ``scope_work``, the reference's required work per
    token in each scope it counts (where it counts any)."""
    cfg, traffic = ctx["config"], ctx["traffic"]
    out = {"op_names": scopes.train_step_op_names(trainer.engine, chunk),
           "counters": window_counters(trainer.history, win["step0"],
                                       win["step1"])}
    work = getattr(common.reference(cfg), "scope_work", None)
    if work is not None:
        out["scope_work"] = work(
            cfg["model"], traffic["seq_len"],
            itemsize=jnp.dtype(cfg["compute_dtype"]).itemsize)
    return out


def window_counters(history: List[Dict], step0: int, step1: int
                    ) -> Dict[str, float]:
    """Mean of each numeric key of the trainer's history rows other than
    :data:`HISTORY_KEYS`, over steps ``step0 + 1 .. step1``."""
    rows = [r for r in history if step0 < r["step"] <= step1]
    names = sorted({k for r in rows for k, v in r.items()
                    if k not in HISTORY_KEYS
                    and isinstance(v, (int, float))})
    return {k: float(np.mean([r[k] for r in rows if k in r]))
            for k in names}


def calibrate(ctx: Dict, source: MarkovSource, mesh, ref: Dict) -> Dict:
    """Readings of the control (the reference in int8) over the checked
    steps, and of two planted faults over the first step: half the rows
    left out, and the rows of chip 0 alone (no exchange)."""
    B, chips = ctx["traffic"]["global_batch"], ctx["chips"]
    out = {"control": compare.train_numbers(
        reference(ctx, source, mesh, mode="int8"), ref)}
    first = {"losses": ref["losses"][:1], "grad_norms": ref["grad_norms"]}
    for name, rows in (("half_batch", B // 2),
                       ("no_exchange", B // max(chips, 2))):
        got = reference(ctx, source, mesh, rows=rows, steps=1)
        out[name] = compare.train_numbers(
            {"losses": got["losses"], "grad_norms": got["grad_norms"]},
            first)
    return out


def reference(ctx: Dict, source: MarkovSource, mesh, mode: str = "f32",
              rows: Optional[int] = None,
              steps: Optional[int] = None) -> Dict:
    """The plain reference over the checked steps: losses, slice norms
    of the first clipped gradient, and of the weights' change.  ``rows``
    keeps only the first rows of every batch (planted faults)."""
    t = time.perf_counter()
    cfg, traffic = ctx["config"], ctx["traffic"]
    ref = common.reference(cfg)
    m, opt = cfg["model"], traffic["optimizer"]
    B, S = traffic["global_batch"], traffic["seq_len"]
    rows = rows or B
    block = min(traffic["reference_block_rows"], rows)
    n_params = ref.param_count(m)
    if mesh is not None:
        rep = NamedSharding(mesh, P())
        row_sh = NamedSharding(mesh, P("data", None))
    else:
        rep = row_sh = None
    params = weights.make(ctx["seed"], cfg, rep)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                    out_shardings=rep)
    mom, vel = zeros(params), zeros(params)

    def step(params, mom, vel, tokens, labels, count, lr):
        with jax.default_matmul_precision("highest"):
            loss, g = ref.grads(params, tokens, labels, m, mode, block)
            g = ref.clip(g, opt["grad_clip"])
            params, mom, vel = ref.adam(params, mom, vel, g, count, lr,
                                        opt)
        return params, mom, vel, loss, compare._slice_norms(g)

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    losses, grad_norms = [], None
    for i in range(steps or traffic["check_steps"]):
        batch = source.sample(i * B, rows, S)
        tok = jax.device_put(batch["tokens"], row_sh)
        lab = jax.device_put(batch["labels"], row_sh)
        lr = ref.warmup_lr(i, traffic["schedule"], B * S, n_params)
        params, mom, vel, loss, gn = step(params, mom, vel, tok, lab,
                                          jnp.float32(i + 1),
                                          jnp.float32(lr))
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = np.concatenate(
                [np.asarray(x) for x in jax.tree.leaves(gn)])
    init = weights.make(ctx["seed"], cfg, rep)
    out = {"losses": losses, "grad_norms": grad_norms,
           "update_norms": compare.diff_norms(params, init),
           "seconds": time.perf_counter() - t}
    return out
