#!/usr/bin/env python3
"""Smoke run of the trainer and the serving engine on a TPU, at the full
width of ``seesaw-150m`` (12 x 1024, vocab 32128, seq 1024; random
weights from a seed).

    python3 chip_smoke.py                # one chip: the phases below
    python3 chip_smoke.py --four-chips   # four chips of one host

Phases, in order, each printing its own lines:

1. ``device`` — JAX must find a TPU; there is no CPU fallback.
2. ``train/xla`` — a Seesaw plan with one cut (B 32 -> 64 sequences)
   through ``Trainer``/``PhaseEngine``, the path ``repro.launch.train``
   drives: finite losses falling from about ln(vocab), and exactly one
   executable per distinct batch size.
3. ``train/pallas`` — the same model, seed and first steps on the Pallas
   kernels: the compiled step holds ``tpu_custom_call``, the per-step
   losses stay inside a bf16 band of the xla run, and flash attention
   and RMSNorm (forward and grads) agree with the XLA ops on one
   full-width input.
4. ``serve`` — ``ServingEngine`` answers 8 seeded greedy requests; every
   token equals a plain ``registry.prefill`` + ``decode_step`` loop, the
   engine compiles one prefill executable per prompt bucket and one
   decode executable, and the Pallas paged-decode kernel agrees with
   the XLA op at the engine's shapes.

``--four-chips`` runs only the 4x1 data-parallel training run and the
same run on device 0, and compares their per-step losses.

The last line of stdout is ``{"ok": true, "device": {...}}``; a failed
phase exits non-zero before it.  The compile cache is
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/``.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import (OptimizerConfig, RunConfig,  # noqa: E402
                           ScheduleConfig)
from repro.configs.seesaw_paper import SEESAW_150M  # noqa: E402
from repro.data import MarkovLM, PhaseDataLoader  # noqa: E402
from repro.kernels import backend as KB  # noqa: E402
from repro.launch.compile_cache import (cache_entries,  # noqa: E402
                                        enable_compile_cache)
from repro.launch.mesh import make_launch_mesh  # noqa: E402
from repro.models import registry as R  # noqa: E402
from repro.serving import GenerationRequest, ServingEngine  # noqa: E402
from repro.train.trainer import Trainer  # noqa: E402

SEED = 0
B0, MAX_BATCH = 32, 64       # one Seesaw cut: B0 -> alpha * B0 (alpha 2)
MAX_DEVICE_BATCH = 16        # micro-batch (tests/test_tpu_compile.py)
FUSE_STEPS = 4
PLAN_STEPS_AT_B0 = 24        # total tokens = 24 * B0 * seq: 16 + 4 steps
PALLAS_STEPS = 8             # first two fused chunks, both at B0
# The paper's 3e-3 is tuned for a warmup of thousands of steps; with this
# plan's 2-step warmup it overshot on v5e (loss 10.0 -> 13.3 at the first
# full-LR step), and the spike amplified rounding differences between
# two runs to 0.2 nats.  1e-3 keeps the trajectory smooth.
LR = 1e-3
# Per-step loss agreement between two runs of the same plan that differ
# only in rounding: xla vs Pallas kernels, or 4-chip data parallel vs
# one chip.  Activations are bf16 (2^-8 relative per rounding); at
# ~10 nats one bf16 ulp of the loss is 0.06, but the loss is a mean over
# 32k tokens, so independent roundings shrink it by ~sqrt(32k).  The
# runs then drift apart through a few Adam steps; 0.05 nats (0.5 % of
# the initial loss) covers that drift and is well below the >1 nat the
# losses fall over the run.
LOSS_BAND = 0.05
# Kernel vs XLA op on one input: bf16 outputs differ by a few ulps
# (2^-8 relative each) because the two accumulate in different orders.
KERNEL_TOL = 2e-2
# The engine's decode executable (4 paged slots) and the reference's
# (one dense row) are different XLA programs; on v5e their bf16 logits
# differ in the last bits, so a greedy near-tie can flip (seen once in
# 256 tokens: the first 21 tokens of a request agreed, then a flip).
# A token counts as the reference's choice when its reference logit is
# within 0.05 of the maximum: ~3 bf16 ulps at the logits' magnitude,
# far below the ~0.6 spread of the random model's logits.
TIE_BAND = 0.05
N_REQUESTS, PROMPT_LEN, MAX_NEW = 8, (64, 512), 32
DECODE_SLOTS, PAGE_SIZE = 4, 16


class PhaseFailed(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise PhaseFailed(msg)


def say(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


class CompileLog:
    """Backend compile seconds and persistent-cache hits, read from
    JAX's monitoring events; :meth:`take` returns the totals since the
    last call."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, *a, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, *a, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def take(self) -> dict:
        out = {"compile_s": round(self.seconds, 2),
               "backend_compiles": self.compiles,
               "cache_hits": self.cache_hits}
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        return out


def peak_gib() -> list:
    """Peak HBM in use per local device (GiB), where the backend
    reports it."""
    out = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        b = st.get("peak_bytes_in_use")
        out.append(None if b is None else round(b / 2 ** 30, 3))
    return out


def max_rel_err(got, want) -> float:
    """max |got - want| over max(1, max |want|), in f32."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want))
                 / max(1.0, float(np.max(np.abs(want)))))


# --------------------------------------------------------------------- #
# training
# --------------------------------------------------------------------- #

def train(model, *, seq_len: int, backend: str = "xla", mesh=None,
          max_steps=None):
    """One Seesaw run (B0 -> 2·B0, one cut) through the launcher's
    stack.  Returns (trainer, history, wall seconds)."""
    cfg = RunConfig(
        model=model,
        schedule=ScheduleConfig(kind="seesaw", base_lr=LR, alpha=2.0,
                                beta=2.0, n_cuts=1,
                                max_batch_size=MAX_BATCH),
        optimizer=OptimizerConfig(),
        seq_len=seq_len, global_batch_size=B0,
        total_tokens=PLAN_STEPS_AT_B0 * B0 * seq_len, seed=SEED,
        kernel_backend=backend)
    trainer = Trainer(cfg, mesh=mesh, max_device_batch=MAX_DEVICE_BATCH,
                      fuse_steps=FUSE_STEPS)
    loader = PhaseDataLoader(
        MarkovLM(vocab_size=min(model.vocab_size, 2048), seed=SEED),
        trainer.plan, seq_len, mesh=mesh)
    t0 = time.perf_counter()
    hist = trainer.run(loader, max_steps=max_steps)
    return trainer, hist, time.perf_counter() - t0


def check_training(phase: str, trainer, hist, vocab: int,
                   min_drop: float) -> list:
    """Finite losses that start near ln(vocab) and fall by at least
    ``min_drop``; exactly one executable per distinct batch size, each
    traced once."""
    losses = [r["loss"] for r in hist]
    check(all(math.isfinite(x) for x in losses),
          f"{phase}: non-finite loss in {losses}")
    check(abs(losses[0] - math.log(vocab)) < 1.0,
          f"{phase}: first loss {losses[0]:.4f} is not near "
          f"ln({vocab}) = {math.log(vocab):.4f}")
    check(losses[-1] < losses[0] - min_drop,
          f"{phase}: loss did not fall by {min_drop} "
          f"({losses[0]:.4f} -> {losses[-1]:.4f})")
    sizes = sorted({r["batch_size"] for r in hist})
    cache = trainer.engine._cache
    check(sorted({key[0] for key in cache}) == sizes
          and len(cache) == len(sizes),
          f"{phase}: executables {sorted(cache)} for batch sizes {sizes}")
    traces = [fn._cache_size() for fn in cache.values()
              if hasattr(fn, "_cache_size")]
    check(all(n == 1 for n in traces),
          f"{phase}: a step was traced more than once: {traces}")
    return losses


def check_band(phase: str, got, want) -> float:
    n = min(len(got), len(want))
    check(n > 0, f"{phase}: no steps to compare")
    diff = max(abs(a - b) for a, b in zip(got[:n], want[:n]))
    check(diff <= LOSS_BAND,
          f"{phase}: per-step losses differ by {diff:.5f} > band "
          f"{LOSS_BAND} (got {got[:n]}, want {want[:n]})")
    return diff


def step_hlo(trainer, seq_len: int) -> str:
    """Compiled HLO of the trainer's first fused step, lowered with the
    arguments the run passed (so the persistent cache serves it)."""
    fn = next(iter(trainer.engine._cache.values()))
    loader = PhaseDataLoader(
        MarkovLM(vocab_size=min(trainer.cfg.model.vocab_size, 2048),
                 seed=SEED), trainer.plan, seq_len, mesh=trainer.mesh)
    _, stacked, _ = next(loader.iter_chunks(FUSE_STEPS))
    st = trainer.state
    with trainer.engine.mesh_context():
        return fn.lower(st.params, st.opt_state, jnp.float32(0.0),
                        jnp.int32(0), jnp.int32(FUSE_STEPS),
                        stacked).compile().as_text()


def kernel_checks(model, seq_len: int, backend: str, *,
                  batch: int = 2) -> dict:
    """Flash attention and RMSNorm on ``backend`` against the XLA ops on
    one seeded input at the model's widths: forward outputs and grads."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(SEED), 3)
    shape = (batch, seq_len, model.n_heads, model.head_dim)
    q = jax.random.normal(k1, shape, jnp.bfloat16)
    k = jax.random.normal(k2, shape, jnp.bfloat16)
    v = jax.random.normal(k3, shape, jnp.bfloat16)
    x = jax.random.normal(k1, (batch, seq_len, model.d_model),
                          jnp.bfloat16)
    s = 0.1 * jax.random.normal(k2, (model.d_model,), jnp.float32)

    def attn(be):
        f = lambda q, k, v: KB.attention(q, k, v, causal=True,  # noqa
                                         backend=be)
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(f(*a).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2)))(q, k, v), jax.jit(f)(q, k, v)

    def norm(be):
        f = lambda x, s: KB.rmsnorm(x, s, backend=be)  # noqa: E731
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(f(*a).astype(jnp.float32) ** 2),
            argnums=(0, 1)))(x, s), jax.jit(f)(x, s)

    errs = {}
    for name, run in (("flash", attn), ("rmsnorm", norm)):
        (_, g_got), out_got = run(backend)
        (_, g_want), out_want = run("xla")
        errs[f"{name}_fwd"] = max_rel_err(out_got, out_want)
        errs[f"{name}_grad"] = max(max_rel_err(a, b)
                                   for a, b in zip(g_got, g_want))
    bad = {k_: e for k_, e in errs.items() if e > KERNEL_TOL}
    check(not bad, f"kernels vs XLA beyond {KERNEL_TOL}: {bad}")
    return {k_: f"{e:.2e}" for k_, e in errs.items()}


def phase_train(model, seq_len: int, log: CompileLog) -> list:
    tr, hist, wall = train(model, seq_len=seq_len)
    losses = check_training("train/xla", tr, hist, model.vocab_size,
                            min_drop=1.0)
    say("train/xla", steps=len(hist), tokens=hist[-1]["tokens"],
        batch_sizes=sorted({r["batch_size"] for r in hist}),
        executables=len(tr.engine._cache), wall_s=round(wall, 2),
        **log.take(), peak_hbm_gib=peak_gib())
    say("train/xla", losses=[round(x, 4) for x in losses])
    return losses


def phase_pallas(model, seq_len: int, xla_losses, log: CompileLog):
    tr, hist, wall = train(model, seq_len=seq_len, backend="pallas",
                           max_steps=PALLAS_STEPS)
    losses = check_training("train/pallas", tr, hist, model.vocab_size,
                            min_drop=0.0)
    diff = check_band("train/pallas", losses, xla_losses)
    check("tpu_custom_call" in step_hlo(tr, seq_len),
          "train/pallas: compiled step holds no tpu_custom_call — the "
          "kernels are not running natively")
    say("train/pallas", steps=len(hist), tokens=hist[-1]["tokens"],
        executables=len(tr.engine._cache), tpu_custom_call=True,
        max_loss_diff_vs_xla=f"{diff:.5f}", band=LOSS_BAND,
        wall_s=round(wall, 2), **log.take(), peak_hbm_gib=peak_gib())
    say("train/pallas", losses=[round(x, 4) for x in losses])
    say("train/pallas", **kernel_checks(model, seq_len, "pallas"),
        tol=KERNEL_TOL, **log.take())


# --------------------------------------------------------------------- #
# serving
# --------------------------------------------------------------------- #

def reference_loop(model, width: int):
    """``(params, prompt, tokens) -> (argmax, gap)`` per step of a plain
    ``registry.prefill`` + ``decode_step`` loop over a dense cache as
    wide as the engine's page window, fed the engine's own ``tokens``
    (teacher forcing, so one near-tie cannot derail the rest): the
    reference's greedy choice, and how far the engine's token's logit
    sits below the reference's maximum."""
    prefill = jax.jit(lambda p, t: R.prefill(p, model, t,
                                             cache_len_cap=width))
    decode = jax.jit(lambda p, c, t: R.decode_step(p, model, c, t))

    def run(params, prompt, tokens):
        logits, cache = prefill(params, jnp.asarray(prompt[None]))
        best, gaps = [], []
        for i, t in enumerate(tokens):
            lg = np.asarray(logits[0, -1], np.float32)
            best.append(int(lg.argmax()))
            gaps.append(float(lg.max() - lg[t]))
            if i + 1 < len(tokens):
                logits, cache = decode(params, cache,
                                       jnp.asarray([[t]], jnp.int32))
        return np.asarray(best, np.int32), np.asarray(gaps)

    return run


def phase_serve(model, log: CompileLog, *, prompt_len=PROMPT_LEN,
                backend: str = "pallas") -> None:
    params = R.init_params(jax.random.PRNGKey(SEED), model)
    lo, hi = prompt_len
    eng = ServingEngine(model, params, decode_slots=DECODE_SLOTS,
                        page_size=PAGE_SIZE, max_len=hi + MAX_NEW)
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, model.vocab_size,
                            (int(rng.integers(lo, hi + 1)),))
               .astype(np.int32) for _ in range(N_REQUESTS)]
    rids = [eng.submit(GenerationRequest(prompt=p, max_new_tokens=MAX_NEW))
            for p in prompts]
    t0 = time.perf_counter()
    done = {r.rid: r for r in eng.drain(max_steps=10_000)}
    wall = time.perf_counter() - t0
    check(sorted(done) == sorted(rids),
          f"serve: finished {sorted(done)} of {sorted(rids)}")
    buckets = {min(b for b in eng.buckets if b >= len(p)) for p in prompts}
    check(eng.n_prefill_executables == len(buckets)
          and eng.n_decode_executables == 1,
          f"serve: {eng.n_prefill_executables} prefill executables for "
          f"buckets {sorted(buckets)}, {eng.n_decode_executables} decode")
    say("serve", requests=len(done),
        prompt_lens=[len(p) for p in prompts],
        new_tokens=sum(len(r.tokens) for r in done.values()),
        prefill_executables=eng.n_prefill_executables,
        buckets=sorted(buckets),
        decode_executables=eng.n_decode_executables,
        engine_steps=eng.steps, occupancy=round(eng.mean_occupancy(), 3),
        wall_s=round(wall, 2), **log.take(), peak_hbm_gib=peak_gib())

    width = eng.pages_per_slot * eng.page_size
    reference = reference_loop(model, width)
    n_equal, ties = 0, []
    for rid, p in zip(rids, prompts):
        got = np.asarray(done[rid].tokens)
        check(len(got) == MAX_NEW,
              f"serve: rid {rid} produced {len(got)} of {MAX_NEW} tokens")
        best, gaps = reference(params, p, got)
        n_equal += int((best == got).sum())
        ties += [float(g) for g in gaps[best != got]]
        check(bool((gaps <= TIE_BAND).all()),
              f"serve: rid {rid} (prompt {len(p)}) token "
              f"{int(np.argmax(gaps))} sits {float(gaps.max()):.4f} below "
              f"the reference's best logit (tie band {TIE_BAND}): got "
              f"{list(got)}, reference argmax {list(best)}")
    say("serve", reference="registry.prefill+decode_step",
        tokens_equal=f"{n_equal}/{N_REQUESTS * MAX_NEW}",
        near_ties=len(ties),
        max_tie_gap=f"{max(ties):.4f}" if ties else 0, tie_band=TIE_BAND,
        **log.take())

    # the Pallas paged-decode kernel against the XLA op, engine shapes
    keys = jax.random.split(jax.random.PRNGKey(SEED), 3)
    q = jax.random.normal(keys[0], (DECODE_SLOTS, 1, model.n_heads,
                                    model.head_dim), jnp.bfloat16)
    kv_shape = (DECODE_SLOTS, width, model.n_kv_heads, model.head_dim)
    k = jax.random.normal(keys[1], kv_shape, jnp.bfloat16)
    v = jax.random.normal(keys[2], kv_shape, jnp.bfloat16)
    lengths = jnp.asarray(rng.integers(0, width, (DECODE_SLOTS,)),
                          jnp.int32)

    def decode_attn(be):
        return jax.jit(lambda *a: KB.paged_decode_attention(
            *a, backend=be))(q, k, v, lengths)

    err = max_rel_err(decode_attn(backend), decode_attn("xla"))
    check(err <= KERNEL_TOL,
          f"serve: paged-decode kernel vs XLA error {err:.2e} > "
          f"{KERNEL_TOL}")
    say("serve", paged_decode_kernel_err=f"{err:.2e}", tol=KERNEL_TOL,
        **log.take())


# --------------------------------------------------------------------- #
# four chips
# --------------------------------------------------------------------- #

def phase_four_chips(model, seq_len: int, log: CompileLog) -> None:
    n = len(jax.devices())
    check(n == 4, f"four-chips: expected 4 devices, JAX reports {n}")
    mesh = make_launch_mesh("4x1")
    tr4, h4, w4 = train(model, seq_len=seq_len, mesh=mesh)
    l4 = check_training("four-chips/4x1", tr4, h4, model.vocab_size,
                        min_drop=1.0)
    say("four-chips/4x1", mesh=dict(mesh.shape), steps=len(h4),
        tokens=h4[-1]["tokens"], executables=len(tr4.engine._cache),
        wall_s=round(w4, 2), **log.take(), peak_hbm_gib=peak_gib())
    del tr4
    gc.collect()
    tr1, h1, w1 = train(model, seq_len=seq_len)
    l1 = check_training("four-chips/1x1", tr1, h1, model.vocab_size,
                        min_drop=1.0)
    say("four-chips/1x1", device=str(jax.devices()[0]), steps=len(h1),
        executables=len(tr1.engine._cache), wall_s=round(w1, 2),
        **log.take())
    diff = check_band("four-chips", l4, l1)
    say("four-chips", losses_4x1=[round(x, 4) for x in l4])
    say("four-chips", losses_1x1=[round(x, 4) for x in l1])
    say("four-chips", max_loss_diff=f"{diff:.5f}", band=LOSS_BAND)


# --------------------------------------------------------------------- #

def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4x1 data-parallel comparison")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU chip found — JAX reports platform "
            f"{dev.platform!r} with {len(devices)} device(s); this "
            f"script runs only on the chip")
    say("device", platform=dev.platform, kind=repr(dev.device_kind),
        count=len(devices), jax=jax.__version__)
    cache = enable_compile_cache()
    say("cache", dir=cache, entries_at_start=cache_entries(cache))
    log = CompileLog()

    model, seq_len = SEESAW_150M, SEESAW_150M.max_seq_len
    say("model", name=model.name, layers=model.n_layers,
        d_model=model.d_model, heads=model.n_heads, vocab=model.vocab_size,
        seq_len=seq_len, params_m=round(model.param_count() / 1e6, 1))
    if args.four_chips:
        phase_four_chips(model, seq_len, log)
    else:
        xla_losses = phase_train(model, seq_len, log)
        gc.collect()         # free the previous phase's train state
        phase_pallas(model, seq_len, xla_losses, log)
        gc.collect()
        phase_serve(model, log)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
