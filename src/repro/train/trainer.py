"""The Seesaw training runtime, driving the phase execution engine.

The batch ramp is a first-class feature: the trainer walks the plan's
phases and lets :class:`repro.train.engine.PhaseEngine` keep one
donated, sharding-annotated compiled step per distinct global batch
size (shape change ⇒ one retrace, then cached).  Params and optimizer
state cross phase boundaries untouched.

Unlike the old eager loop, nothing schedule-related happens on host per
step: the token-indexed LR curve is evaluated inside the jitted step,
K steps are fused into one dispatch (``fuse_steps``), and metrics stay
on device until a ``log_every`` boundary forces a transfer.  Gradient
accumulation (phase batch > ``max_device_batch``) is a ``lax.scan``
over microbatches, so the ramp changes a trip count, not the trace.
The loader's chunk stream is merged across same-batch-size phases and
tail-padded to ``fuse_steps``, so a whole run compiles exactly one
fused program per distinct batch size; ``tokens_seen`` is carried as
an exact integer on the host.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import RunConfig
from repro.core.seesaw import build_plan
from repro.models import registry as R
from repro.optim import optimizers as O
from repro.train import checkpoint as CKPT
from repro.train import engine as E

Params = Any


@dataclass
class TrainState:
    params: Params
    opt_state: Params
    step: int = 0
    # exact integer token count — the host is the source of truth; the
    # device only ever sees a once-rounded f32 base plus an int32
    # per-chunk offset, so the carry never drifts however long the run
    tokens_seen: int = 0
    # adaptive-seesaw only: the device-accumulated loss EMA after the
    # last chunk (None = unseeded); carried into the next chunk and
    # through checkpoints so resume replays the controller bitwise
    loss_ema: Optional[float] = None


def _place_like(tree, shardings):
    """Initial state placement onto the mesh: in a multi-process run a
    process-private (single-device) array cannot feed a jitted step
    whose ``in_shardings`` span other processes, so each process
    contributes its addressable blocks of the identically-seeded host
    value and jax assembles the global array."""
    def place(x, s):
        host = np.asarray(x)
        return jax.make_array_from_callback(host.shape, s,
                                            lambda idx: host[idx])
    return jax.tree.map(place, tree, shardings)


def make_train_step(cfg: RunConfig, optimizer: O.Optimizer, *,
                    multi_pod: bool = False,
                    micro_batches: int = 1) -> Callable:
    """Compatibility wrapper over the engine's single step builder:
    step(params, opt_state, batch, lr) → (params, opt_state, metrics)."""
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    return E.make_grad_step(cfg.resolved_model(), optimizer,
                            micro_batches=micro_batches,
                            z_loss=cfg.z_loss, dtype=dtype,
                            remat=cfg.remat, multi_pod=multi_pod)


class Trainer:
    def __init__(self, cfg: RunConfig, *, mesh=None, multi_pod: bool = False,
                 max_device_batch: Optional[int] = None, seed: int = 0,
                 fuse_steps: Optional[int] = None):
        self.cfg = cfg
        self.mesh = mesh
        self.multi_pod = multi_pod
        self.max_device_batch = max_device_batch
        self.fuse_steps = max(int(fuse_steps or getattr(cfg, "fuse_steps",
                                                        1) or 1), 1)
        total = cfg.resolved_total_tokens()
        sch = cfg.schedule
        self.plan = build_plan(
            kind=sch.kind, base_lr=sch.base_lr, total_tokens=total,
            warmup_frac=sch.warmup_frac, b0=cfg.global_batch_size,
            alpha=sch.alpha,
            beta=(sch.beta if sch.kind in ("seesaw-general", "naive-ramp")
                  else None),
            n_cuts=sch.n_cuts, max_batch_size=sch.max_batch_size)
        # the adaptive plan grows at runtime; keep the single-phase
        # seed so a resume can rebuild the extended plan by replaying
        # the checkpointed cut tokens through extend_at
        self._base_plan = self.plan
        self.controller = None
        self.cut_tokens: List[int] = []
        if sch.kind == "adaptive-seesaw":
            from repro.core.adaptive import AdaptiveSeesaw
            mn = getattr(sch, "plateau_min_steps", None)
            self.controller = AdaptiveSeesaw(
                alpha=sch.alpha,
                window=int(getattr(sch, "plateau_window", 50)),
                rel_threshold=float(getattr(sch, "plateau_threshold",
                                            2e-3)),
                max_cuts=int(sch.n_cuts),
                min_steps_between=int(
                    mn if mn is not None
                    else getattr(sch, "plateau_window", 50)))
        self.optimizer = O.from_config(cfg.optimizer)
        self.engine = E.PhaseEngine(cfg, self.optimizer, self.plan,
                                    mesh=mesh, multi_pod=multi_pod,
                                    max_device_batch=max_device_batch)
        key = jax.random.PRNGKey(cfg.seed + seed)
        # resolved_model() also fail-fasts a bad --kernel-backend here
        params = R.init_params(key, cfg.resolved_model())
        opt_state = self.optimizer.init(params)
        # on a mesh the state starts on its step shardings: a first
        # call that placed it through in_shardings would hand the second
        # call differently-sharded arguments, and the step would trace
        # and compile twice
        sh = self.engine.state_shardings()
        if sh is not None and jax.process_count() > 1:
            params = _place_like(params, sh[0])
            opt_state = _place_like(opt_state, sh[1])
        elif sh is not None:
            params, opt_state = jax.device_put((params, opt_state), sh)
        self.state = TrainState(params, opt_state)
        self.history: List[Dict[str, float]] = []
        self._ckpt_manager: Optional[CKPT.CheckpointManager] = None

    # ------------------------------------------------------------------ #
    @property
    def _step_cache(self):
        return self.engine._cache

    def lr_at(self, tokens: float) -> float:
        """Host-side probe of the exact curve the jitted step evaluates
        on device (``engine.plan_lr_fn`` — piecewise cuts land on the
        realized step-quantized phase boundaries, not the plan's ideal
        token cut points).  For adaptive plans the engine supplies the
        current runtime LR tables, so this reflects every cut fired so
        far."""
        return self.engine.host_lr(tokens)

    def _micro(self, batch_size: int) -> int:
        return self.engine.micro_batches(batch_size)

    # -- checkpointing -------------------------------------------------- #
    @property
    def checkpoint_manager(self) -> "CKPT.CheckpointManager":
        """The trainer's async checkpoint writer, built lazily from the
        engine (so runs that never save pay nothing)."""
        if self._ckpt_manager is None:
            self._ckpt_manager = self.engine.make_checkpoint_manager()
        return self._ckpt_manager

    def save_checkpoint(self, path: str,
                        chunk_bytes: int = CKPT.DEFAULT_CHUNK_BYTES,
                        block: bool = True):
        """Write a sharded streaming checkpoint directory (collective
        in a multi-process run: every process writes only the shards it
        owns, in ``chunk_bytes``-bounded device→host slices).
        ``block=False`` snapshots the state on device and returns
        immediately while the :attr:`checkpoint_manager`'s writer
        thread streams it to disk."""
        extra = self._adaptive_extra()
        if not block:
            self.checkpoint_manager.request_save(
                path, self.state.params, self.state.opt_state,
                self.state.step, self.state.tokens_seen, extra)
            return
        if self._ckpt_manager is not None:
            # an in-flight async save of an older snapshot must land
            # first: generations are sequential per directory
            self._ckpt_manager.finalize()
        CKPT.save_phase_checkpoint(path, self.state.params,
                                   self.state.opt_state, self.state.step,
                                   self.state.tokens_seen, plan=self.plan,
                                   seq_len=self.cfg.seq_len, extra=extra,
                                   chunk_bytes=chunk_bytes)

    def _adaptive_extra(self) -> Optional[Dict[str, Any]]:
        """Checkpoint metadata that lets a resume replay the adaptive
        run bitwise: the controller's window state, every cut's token
        boundary (to rebuild the extended plan), and the carried loss
        EMA."""
        if self.controller is None:
            return None
        return {"adaptive": {
            "controller": self.controller.state_dict(),
            "cut_tokens": list(self.cut_tokens),
            "loss_ema": self.state.loss_ema}}

    def restore_checkpoint(self, path: str,
                           verify: bool = False) -> Dict[str, Any]:
        """Restore sharded-directory or legacy ``.npz`` checkpoints.
        With a mesh, each process reads only its addressable block of
        every array and the global state is reassembled across
        processes — no host ever holds a full replica of a sharded
        leaf.  The save-time topology need not match this run's
        (elastic resume).  ``verify=True`` checks every block's crc32
        first.

        An adaptive trainer first reads the checkpoint's metadata
        alone: the saved cut tokens rebuild the extended plan (by
        replaying :meth:`SeesawPlan.extend_at` from the single-phase
        base plan), and the controller's window state is reloaded — so
        the phase/batch validation below runs against the plan the run
        actually had at save time, and subsequent cuts re-fire at
        identical steps."""
        if self.controller is not None:
            ad = CKPT.read_meta(path).get("adaptive")
            if ad is None:
                raise ValueError(
                    f"checkpoint {path!r} carries no adaptive "
                    f"controller state — it was saved by a "
                    f"prescheduled run and cannot resume an "
                    f"adaptive-seesaw trainer")
            plan = self._base_plan
            for ct in ad["cut_tokens"]:
                plan = plan.extend_at(
                    int(ct), seq_len=self.cfg.seq_len,
                    max_batch_size=self.cfg.schedule.max_batch_size)
            self.plan = plan
            self.engine.update_plan(plan)
            if self._ckpt_manager is not None:
                self._ckpt_manager.plan = plan
            self.controller.load_state_dict(ad["controller"])
            self.cut_tokens = [int(ct) for ct in ad["cut_tokens"]]
            ema = ad.get("loss_ema")
            self.state.loss_ema = None if ema is None else float(ema)
        p, s, meta = CKPT.restore_phase_checkpoint(
            path, self.state.params, self.state.opt_state, plan=self.plan,
            seq_len=self.cfg.seq_len,
            shardings=self.engine.state_shardings(), verify=verify)
        self.state.params, self.state.opt_state = p, s
        self.state.step = int(meta["step"])
        self.state.tokens_seen = CKPT.exact_tokens(meta["tokens_seen"])
        return meta

    def close(self):
        """Join the async checkpoint writer (if any) and surface any
        writer-thread error.  Call at the end of a run that used async
        saves; idempotent."""
        if self._ckpt_manager is not None:
            self._ckpt_manager.finalize()

    # -- fused run loop ------------------------------------------------- #
    def _chunks(self, loader, max_steps):
        """Yield (head phase, stacked_batches, n): chunks with ≤
        fuse_steps real steps.  Uses the loader's double-buffered
        ``iter_chunks`` when available — those chunks always have
        leading dim fuse_steps (merged across same-batch-size phases,
        tail-padded), so truncating to a ``max_steps`` budget just
        lowers ``n`` (the engine masks the tail via ``n_valid``) and
        never creates a new chunk shape to compile.  Any plain (phase,
        step, batch) iterator works as a fallback (chunked by stacking
        on device, breaking at phase boundaries)."""
        k = self.fuse_steps
        st = self.state

        def budget():
            return None if max_steps is None else max_steps - st.step

        if hasattr(loader, "iter_chunks"):
            for phase, stacked, n in loader.iter_chunks(k):
                r = budget()
                if r is not None and r <= 0:
                    return
                if r is not None and n > r:
                    n = r
                yield phase, stacked, n
            return

        buf: List[Any] = []
        cur_phase = None
        for phase, _pstep, batch in loader:
            if max_steps is not None and st.step + len(buf) >= max_steps:
                break
            if buf and (phase.index != cur_phase.index or len(buf) == k):
                yield (cur_phase,
                       jax.tree.map(lambda *xs: jnp.stack(xs), *buf),
                       len(buf))
                buf = []
            cur_phase = phase
            buf.append(batch)
        if buf:
            r = budget()
            if r is not None and len(buf) > r:
                buf = buf[:r]
            if buf:
                yield (cur_phase,
                       jax.tree.map(lambda *xs: jnp.stack(xs), *buf),
                       len(buf))

    def _flush(self, pending, log_cb):
        """Device→host metric transfer, deferred to log boundaries.
        A merged chunk can span a phase boundary (same batch size,
        different LR scale), so each step's phase is attributed from
        its token count, not the chunk's head phase.  Metric rows past
        a chunk's ``n`` real steps are device-side padding and are
        never read."""
        le = max(self.cfg.log_every, 1)
        for base_step, base_tok, phase, wall, metrics, n in pending:
            host = jax.device_get(metrics)
            tok_per_step = phase.batch_size * self.cfg.seq_len
            for i in range(n):
                tok_start = base_tok + i * tok_per_step
                ph = self.plan.realized_phase_at(tok_start,
                                                 self.cfg.seq_len)
                rec = {"step": base_step + i + 1,
                       "tokens": base_tok + (i + 1) * tok_per_step,
                       "lr": float(host["lr"][i]),
                       "batch_size": phase.batch_size,
                       "phase": ph.index,
                       "loss": float(host["loss"][i]),
                       "wall": wall}
                for name, v in host.items():
                    if name not in ("loss", "lr"):
                        rec[name] = float(v[i])
                self.history.append(rec)
                if log_cb and rec["step"] % le == 0:
                    log_cb(rec)
        pending.clear()

    def run(self, loader, max_steps: Optional[int] = None,
            log_cb: Optional[Callable] = None, *,
            checkpoint_path: Optional[str] = None,
            save_every: Optional[int] = None,
            async_save: bool = True,
            stop_fn: Optional[Callable[[], bool]] = None
            ) -> List[Dict[str, float]]:
        """Run the fused chunk loop.  ``checkpoint_path`` +
        ``save_every`` turn on periodic saves at chunk boundaries
        (every chunk crossing a ``save_every``-step boundary) — async
        by default: the state is snapshotted on device and the writer
        thread streams it while the next chunks train; writer errors
        surface at the next chunk boundary.  ``stop_fn`` is polled at
        each chunk boundary (the preemption hook): returning True ends
        the loop cleanly with the state on an exact chunk boundary, so
        a final save/resume is bitwise-consistent.  In multi-process
        runs all of these fire at the same boundary on every process
        (the chunk stream is deterministic and save/stop decisions are
        functions of the shared step count).

        Adaptive plans add one decision per chunk boundary: the fused
        step's device loss EMA is transferred (one scalar — the
        controller's entire per-chunk host traffic) and fed to the
        plateau controller; a fired cut extends the plan, re-chunks
        the loader from this exact token boundary and restarts the
        chunk stream (the outer loop).  The cut decision runs *before*
        the boundary's save, so a checkpoint always captures the
        post-decision plan and controller — resume replays the
        remaining cuts at identical steps."""
        st = self.state
        t0 = time.time()
        le = max(self.cfg.log_every, 1)
        se = max(save_every, 1) if save_every else None
        pending: List[Tuple] = []
        stop = False
        rechunk = True
        while rechunk and not stop:
            rechunk = False
            for phase, stacked, n in self._chunks(loader, max_steps):
                if self._ckpt_manager is not None:
                    self._ckpt_manager.check()
                out = self.engine.run_chunk(
                    st.params, st.opt_state, st.tokens_seen, stacked,
                    n_valid=n, step=st.step, loss_ema=st.loss_ema)
                if self.controller is not None:
                    params, opt_state, metrics, ema = out
                    st.loss_ema = float(jax.device_get(ema))
                else:
                    params, opt_state, metrics = out
                base_step, base_tok = st.step, st.tokens_seen
                st.params, st.opt_state = params, opt_state
                st.step += n
                st.tokens_seen += n * phase.batch_size * self.cfg.seq_len
                pending.append((base_step, base_tok, phase,
                                time.time() - t0, metrics, n))
                if (self.controller is not None
                        and self.controller.observe_smoothed(
                            st.loss_ema, n)):
                    self._fire_cut(loader, stacked)
                    rechunk = True
                if st.step // le > base_step // le:
                    self._flush(pending, log_cb)
                if (se and checkpoint_path
                        and st.step // se > base_step // se):
                    self.save_checkpoint(checkpoint_path,
                                         block=not async_save)
                if stop_fn is not None and stop_fn():
                    stop = True
                if rechunk or stop:
                    break
        self._flush(pending, log_cb)
        return self.history

    def _fire_cut(self, loader, stacked) -> None:
        """Apply one adaptive cut at the current chunk boundary:
        extend the plan with a (√α LR cut, ×α batch) phase starting at
        ``tokens_seen``, validate the new ramp stage is feedable on
        this topology (fail fast at cut time, not mid-ramp), swap the
        plan into the engine / checkpoint manager / loader, and kick
        off a background AOT compile of the next batch size's fused
        step so the ramp stage starts without a dispatch stall."""
        st = self.state
        sch = self.cfg.schedule
        old_b = self.plan.phases[-1].batch_size
        new_plan = self.plan.extend_at(
            st.tokens_seen, seq_len=self.cfg.seq_len,
            max_batch_size=sch.max_batch_size)
        new_b = new_plan.phases[-1].batch_size
        if isinstance(self.mesh, jax.sharding.Mesh):
            from repro.launch.steps import validate_feeding
            validate_feeding(new_plan, self.mesh,
                             start_tokens=st.tokens_seen,
                             seq_len=self.cfg.seq_len)
        else:
            from repro.data.pipeline import validate_per_host_plan
            validate_per_host_plan(
                new_plan, getattr(loader, "_pcount", 1) or 1,
                self.engine.n_data_devices(),
                start_phase=len(new_plan.phases) - 1)
        self.plan = new_plan
        self.engine.update_plan(new_plan)
        if self._ckpt_manager is not None:
            self._ckpt_manager.plan = new_plan
        self.cut_tokens.append(int(st.tokens_seen))
        if not hasattr(loader, "rechunk"):
            raise ValueError(
                "adaptive-seesaw fired a cut but the loader cannot "
                "re-chunk mid-stream — use PhaseDataLoader (or any "
                "loader with rechunk(plan, tokens_seen))")
        loader.rechunk(new_plan, st.tokens_seen)
        if new_b != old_b:
            self.engine.prewarm_async(new_b, self.fuse_steps, stacked)
