"""The phase execution engine — the single train-step stack.

Every entry point (``Trainer``, ``launch.train``, ``launch.dryrun`` via
``launch.steps``, benchmarks, examples) drives the same step builder,
so there is exactly one ``value_and_grad`` call site for training in
the repo.  The engine owns four layers:

1. ``make_grad_step`` — the inner step ``(params, opt_state, batch, lr)
   → (params, opt_state, metrics)``.  Gradient accumulation is a
   ``lax.scan`` over microbatches: the trace size is constant at any
   accumulation count, so the batch ramp changes a scan trip count,
   never the program size.
2. ``plan_lr_fn`` — the token-indexed LR schedule as a traced device
   function of ``tokens_seen``.  Cosine (continuous) and
   step/seesaw/constant (piecewise) share one code path inside the
   jitted step; no host LR computation happens per step.
3. ``make_fused_step`` — K-step fused dispatch: ``lax.scan`` over a
   stacked chunk of K batches per host round-trip.  The carry is an
   exact int32 step counter (the host keeps ``tokens_seen`` as a
   Python int), ``n_valid`` masks the padded tail of a short chunk so
   one executable serves every chunk of a batch size, and metrics come
   back stacked ``(K,)`` on device, only transferred at ``log_every``
   boundaries (the caller decides when to ``device_get``).
4. ``PhaseEngine`` — per-(batch_size, micro, K) compile cache of
   donated, ``NamedSharding``-annotated jitted steps.  A batch-size
   change is one retrace; K=1 is the eager path and runs through the
   identical scan body, so fused and eager trajectories match bitwise.

Sharding-tree helpers (``param_structs`` / ``opt_structs`` /
``opt_state_specs`` / ``named_shardings``) live here too and are
re-exported by ``launch.steps`` for the dry-run.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, OptimizerConfig, RunConfig
from repro.core import schedules as S
from repro.core.seesaw import SeesawPlan
from repro.models import registry as R
from repro.optim import optimizers as O

Params = Any


# --------------------------------------------------------------------- #
# sharding-tree helpers (shared with launch.steps)
# --------------------------------------------------------------------- #

def named_shardings(mesh, tree):
    """PartitionSpec tree → NamedSharding tree on ``mesh``."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))


def param_structs(cfg: ModelConfig):
    return jax.eval_shape(
        lambda: R.init_params(jax.random.PRNGKey(0), cfg))


def opt_structs(cfg: ModelConfig, params_struct, kind: str = "adamw"):
    opt = O.from_config(OptimizerConfig(kind=kind))
    return opt, jax.eval_shape(opt.init, params_struct)


def opt_state_specs(param_spec_tree, opt_state_struct):
    """Mirror param specs onto the m/v/mu slots; scalars replicated."""
    out = {}
    for k in opt_state_struct:
        if k in ("m", "v", "mu"):
            out[k] = param_spec_tree
        else:
            out[k] = P()
    return out


# --------------------------------------------------------------------- #
# 1. the single grad step
# --------------------------------------------------------------------- #

def make_grad_step(cfg: ModelConfig, optimizer: O.Optimizer, *,
                   micro_batches: int = 1, z_loss: float = 0.0,
                   dtype=jnp.bfloat16, remat: bool = True,
                   multi_pod: bool = False, **loss_kw) -> Callable:
    """The one training step builder: ``step(params, opt_state, batch,
    lr) → (params, opt_state, metrics)``.  jit-able; batch shapes decide
    the compile cache key.  Extra ``loss_kw`` (seq_shard, remat_policy,
    …) forward to the family loss function."""

    def loss_of(params, batch):
        # named scopes mark the HLO metadata only: the device trace
        # splits the step into forward, its transpose (backward) and
        # the optimizer by these names
        with jax.named_scope("forward"):
            return R.loss_fn(params, cfg, batch, z_loss=z_loss,
                             dtype=dtype, remat=remat, multi_pod=multi_pod,
                             **loss_kw)

    grad_fn = jax.value_and_grad(loss_of, has_aux=True)

    def step(params, opt_state, batch, lr):
        if micro_batches > 1:
            def split(x):
                b = x.shape[0] // micro_batches
                return x.reshape(micro_batches, b, *x.shape[1:])

            micro = jax.tree.map(split, batch)

            def accum(carry, mb):
                (l_aux, g) = grad_fn(params, mb)
                gacc = jax.tree.map(jnp.add, carry, g)
                l, aux = l_aux
                return gacc, dict(aux, loss=l)

            gacc, metrics = jax.lax.scan(
                accum, jax.tree.map(jnp.zeros_like, params), micro)
            grads = jax.tree.map(lambda g: g / micro_batches, gacc)
            metrics = jax.tree.map(jnp.mean, metrics)
        else:
            (loss, metrics), grads = grad_fn(params, batch)
        with jax.named_scope("optimizer"):
            new_params, new_opt = optimizer.update(grads, opt_state,
                                                   params, lr)
            grad_norm = O._global_norm(grads)
        metrics = {k: jnp.asarray(v, jnp.float32)
                   for k, v in metrics.items()}
        metrics["grad_norm"] = grad_norm
        return new_params, new_opt, metrics

    return step


# --------------------------------------------------------------------- #
# 2. device-side token-indexed LR
# --------------------------------------------------------------------- #

def plan_lr_fn(plan: SeesawPlan,
               seq_len: Optional[int] = None) -> Callable:
    """The plan's LR curve as a traced function ``lr(tokens_seen,
    step=None)``.  Cosine plans get the continuous quarter-cosine
    (Lemma 1); every piecewise kind gets :func:`schedules.piecewise_lr`
    over the phase table.

    With ``seq_len`` the cut thresholds are the *realized* phase starts
    (step-quantized via ``steps_per_phase``), not the ideal token cut
    points — the loader switches batch size on step boundaries, and the
    LR cut must land on the same step so each step trains with its
    phase's (lr, batch) pair.  The realized ends are accumulated in
    exact integer arithmetic and the cumulative *step* boundaries are
    handed to ``piecewise_lr`` too, so a jitted step that knows its
    global step index selects the cut by exact int32 compare (immune to
    f32 rounding past 2^24 tokens)."""
    if plan.kind == "cosine":
        return S.quarter_cosine_lr(plan.base_lr, plan.total_tokens,
                                   plan.warmup_tokens)
    if seq_len:
        ends, step_ends, tok, n_cum = [], [], 0, 0
        for p, n in zip(plan.phases, plan.steps_per_phase(seq_len)):
            tok += n * p.batch_size * seq_len
            n_cum += n
            ends.append(tok)
            step_ends.append(n_cum)
    else:
        ends = [p.end_tokens for p in plan.phases]
        step_ends = None
    return S.piecewise_lr(plan.base_lr, plan.warmup_tokens, ends,
                          [p.lr_scale for p in plan.phases],
                          phase_end_steps=step_ends)


# --------------------------------------------------------------------- #
# 3. K-step fused dispatch
# --------------------------------------------------------------------- #

def make_fused_step(grad_step: Callable, lr_fn: Callable,
                    tokens_per_step: float, *,
                    ema_decay: Optional[float] = None,
                    n_lr_args: int = 0) -> Callable:
    """Wrap a grad step into ``train_step(params, opt_state,
    tokens_seen, step0, n_valid, batches)`` where ``batches`` has a
    leading K dim (both variants carry that name, so the compiled
    program is ``jit_train_step``).
    One host dispatch covers up to K optimizer steps; metrics (plus the
    per-step ``lr``) return stacked ``(K,)``.

    Two extensions serve the adaptive-Seesaw path (both default off,
    leaving the signature and compiled program of prescheduled runs
    untouched):

    - ``ema_decay`` — carry a loss EMA through the scan:  the signature
      becomes ``train_step(params, opt_state, tokens_seen, step0, n_valid,
      ema0, batches, *lr_args)`` returning ``(params, opt_state,
      metrics, ema)``.  The EMA is one f32 scalar updated per *valid*
      step (``ema ← d·ema + (1−d)·loss``; padded tail steps leave it
      unchanged), so the plateau controller reads one smoothed scalar
      per chunk with zero per-step host transfers.  A negative ``ema0``
      is the "unseeded" sentinel: the first valid loss seeds it.
    - ``n_lr_args`` — the LR schedule's phase table as that many extra
      traced arguments (see :func:`schedules.adaptive_piecewise_lr`):
      extending the plan at a cut changes argument *values* only, so
      the per-batch-size executables compiled before the cut stay
      valid.

    The scan carry is an exact int32 step counter, not an f32 token
    accumulator: step i's token count is ``tokens_seen + i *
    tokens_per_step`` with the offset computed in int32 (exact for any
    chunk under 2^31 tokens; the old ``tok + tps`` f32 carry drifted
    once a chunk crossed 2^24 tokens).  The exact running total lives
    on the host as a Python int; ``tokens_seen`` arrives here already
    rounded once to f32, and the device LR receives the global step
    index ``step0 + i`` so piecewise cuts are selected by integer
    compare (see :func:`plan_lr_fn`).

    ``n_valid`` masks the tail of a padded chunk: steps with
    ``i >= n_valid`` take a ``lax.cond`` branch that returns params and
    opt state untouched (and zero metrics), so a merged chunk stream
    can pad every tail chunk up to K and reuse the single compiled
    executable — no remainder programs — without perturbing training.
    ``n_valid`` is a traced scalar, so varying it never recompiles."""
    tps = jnp.int32(int(tokens_per_step))
    takes_step = _takes_step(lr_fn)

    def _make_real(params, opt_state, batches):
        def real(operand):
            params, opt_state, batch, lr = operand
            p, o, m = grad_step(params, opt_state, batch, lr)
            return p, o, dict(m, lr=jnp.asarray(lr, jnp.float32))

        # metrics pytree structure for the skip branch, from one
        # abstract eval of the real step (scan traces the body once,
        # so this costs a single extra abstract pass per compile)
        m_struct = jax.eval_shape(
            real, (params, opt_state,
                   jax.tree.map(lambda x: x[0], batches),
                   jnp.float32(0)))[2]

        def skip(operand):
            params, opt_state, _, _ = operand
            zeros = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), m_struct)
            return params, opt_state, zeros

        return real, skip

    def _step_lr(tokens_seen, step0, i, lr_args):
        tok = (jnp.asarray(tokens_seen, jnp.float32)
               + (i * tps).astype(jnp.float32))
        # a negative step0 means "step index unknown": keep the
        # sentinel for EVERY step of the chunk (step0 + i would
        # turn non-negative from i=1 on and silently select the
        # wrong piecewise phase)
        stepi = jnp.where(step0 < 0, jnp.int32(-1), step0 + i)
        if lr_args:
            return lr_fn(tok, stepi, *lr_args)
        return lr_fn(tok, stepi) if takes_step else lr_fn(tok)

    if ema_decay is None:
        def train_step(params, opt_state, tokens_seen, step0, n_valid,
                       batches, *lr_args):
            real, skip = _make_real(params, opt_state, batches)

            def body(carry, batch):
                params, opt_state, i = carry
                lr = _step_lr(tokens_seen, step0, i, lr_args)
                params, opt_state, metrics = jax.lax.cond(
                    i < n_valid, real, skip,
                    (params, opt_state, batch, lr))
                return (params, opt_state, i + jnp.int32(1)), metrics

            carry = (params, opt_state, jnp.int32(0))
            (params, opt_state, _), metrics = jax.lax.scan(body, carry,
                                                           batches)
            return params, opt_state, metrics

        return train_step

    decay = jnp.float32(ema_decay)

    def train_step(params, opt_state, tokens_seen, step0, n_valid,
                   ema0, batches, *lr_args):
        real, skip = _make_real(params, opt_state, batches)

        def body(carry, batch):
            params, opt_state, i, ema = carry
            lr = _step_lr(tokens_seen, step0, i, lr_args)
            params, opt_state, metrics = jax.lax.cond(
                i < n_valid, real, skip,
                (params, opt_state, batch, lr))
            loss = jnp.asarray(metrics["loss"], jnp.float32)
            # ema0 < 0 = unseeded: the first valid loss seeds the EMA;
            # padded tail steps (masked loss = 0) leave it unchanged
            upd = jnp.where(ema < 0, loss,
                            decay * ema + (1.0 - decay) * loss)
            ema = jnp.where(i < n_valid, upd, ema)
            return (params, opt_state, i + jnp.int32(1), ema), metrics

        carry = (params, opt_state, jnp.int32(0),
                 jnp.asarray(ema0, jnp.float32))
        (params, opt_state, _, ema), metrics = jax.lax.scan(
            body, carry, batches)
        return params, opt_state, metrics, ema

    return train_step


def _takes_step(lr_fn: Callable) -> bool:
    """Whether ``lr_fn`` accepts the global step index as a second
    argument (every :mod:`repro.core.schedules` curve does; ad-hoc
    token-only callables keep working)."""
    try:
        import inspect
        sig = inspect.signature(lr_fn)
    except (TypeError, ValueError):
        return False
    if len(sig.parameters) >= 2:
        return True
    return any(p.kind is inspect.Parameter.VAR_POSITIONAL
               for p in sig.parameters.values())


# --------------------------------------------------------------------- #
# 4. the engine
# --------------------------------------------------------------------- #

class PhaseEngine:
    """Compile cache + dispatcher for one run.

    Keys are ``(batch_size, micro_batches, K)``; each entry is one
    donated jitted fused step, sharding-annotated when a mesh is given.
    The batch ramp walks batch sizes, so a plan fed by the loader's
    merged, tail-padded chunk stream compiles exactly one program per
    *distinct* batch size — remainder chunks reuse the K-sized program
    with ``n_valid`` masking the padded tail.

    ``adaptive-seesaw`` plans get three extra behaviours: the fused
    step carries a device loss EMA (returned as a fourth output of
    :meth:`run_chunk`), the LR phase table is passed as runtime
    arguments (:meth:`_lr_tables`) so :meth:`update_plan` can swap in
    an extended plan without invalidating any cached executable, and
    :meth:`prewarm_async` AOT-compiles the next ramp stage's program in
    a background thread so a fired cut costs one background compile
    instead of a stall at the next batch size's first chunk.
    """

    def __init__(self, cfg: RunConfig, optimizer: O.Optimizer,
                 plan: SeesawPlan, *, mesh=None, multi_pod: bool = False,
                 max_device_batch: Optional[int] = None):
        self.cfg = cfg
        # run-level --kernel-backend override folded into the model
        # config here, so every compiled step (and its param/opt-state
        # spec derivation) sees one consistent backend
        self.model = cfg.resolved_model()
        self.optimizer = optimizer
        self.plan = plan
        from repro.launch.mesh import gspmd_mesh
        self.mesh = gspmd_mesh(mesh)
        self.multi_pod = multi_pod
        self.max_device_batch = max_device_batch
        self.adaptive = plan.kind == "adaptive-seesaw"
        if self.adaptive:
            sch = cfg.schedule
            self.ema_decay = float(
                getattr(sch, "ema_decay", 0.98) or 0.98)
            # fixed-width runtime LR tables: one slot per phase the
            # controller can ever create (n_cuts cuts ⇒ n_cuts + 1
            # phases) plus one slack slot — fixed width means a cut
            # never changes an argument shape, hence never recompiles
            self._table_width = max(int(sch.n_cuts) + 2, 2)
            self.lr_fn = S.adaptive_piecewise_lr(plan.base_lr,
                                                 plan.warmup_tokens)
        else:
            self.lr_fn = plan_lr_fn(plan, cfg.seq_len)
        self.dtype = (jnp.bfloat16 if cfg.dtype == "bfloat16"
                      else jnp.float32)
        self._cache: Dict[Tuple[int, int, int], Callable] = {}
        self._prewarm: Dict[Tuple[int, int, int],
                            threading.Thread] = {}

    # -- mesh geometry -------------------------------------------------- #
    def mesh_context(self):
        """The context every step traces, compiles and runs under: on a
        mesh, ``jax.set_mesh`` — the bare ``PartitionSpec``s of the
        model's sharding constraints resolve against it."""
        if isinstance(self.mesh, jax.sharding.Mesh):
            return jax.set_mesh(self.mesh)
        return contextlib.nullcontext()

    def n_data_devices(self) -> int:
        from repro.launch.mesh import data_parallel_size
        return data_parallel_size(self.mesh)

    def micro_batches(self, batch_size: int) -> int:
        """Accumulation count for a global batch.  The microbatch is a
        slice of the *global* batch, so it must both divide the global
        batch and still split evenly across the data devices — checking
        only ``batch_size % micro`` (the old trainer bug) can pick a
        micro whose per-device share is fractional.

        When NO accumulation count satisfies both divisibility
        constraints (e.g. a global batch not divisible by the data
        device count), raise instead of silently returning
        ``micro == batch_size`` — that fallthrough had exactly the
        fractional per-device share this method exists to rule out."""
        if not self.max_device_batch:
            return 1
        n_dev = max(self.n_data_devices(), 1)
        per_dev = batch_size // n_dev
        micro = max(-(-per_dev // self.max_device_batch), 1)
        while micro <= batch_size:
            if (batch_size % micro == 0
                    and (batch_size // micro) % n_dev == 0):
                return micro
            micro += 1
        raise ValueError(
            f"no gradient-accumulation count splits global batch "
            f"{batch_size} into microbatches of <= "
            f"{self.max_device_batch} rows per device across {n_dev} "
            f"data devices: every divisor of {batch_size} leaves a "
            f"per-device share that is fractional — use a batch size "
            f"divisible by {n_dev}")

    # -- adaptive runtime LR tables ------------------------------------- #
    def _lr_tables(self):
        """The adaptive schedule's phase table as runtime arrays:
        realized cumulative cut steps (i32), cut token boundaries (f32)
        and per-phase LR scales (f32), each padded to the fixed
        ``_table_width`` — ``INT32_MAX`` / ``+inf`` cut slots never
        match, and the scale pad repeats the last phase.  Fixed width
        means extending the plan changes argument *values* only; no
        cached executable is invalidated by a cut.

        Cut boundaries are the *realized* (step-quantized) phase
        starts, accumulated in exact integer arithmetic — the same
        convention as :func:`plan_lr_fn` — so the LR cut lands on the
        step where the loader actually switches batch size."""
        plan, seq = self.plan, self.cfg.seq_len
        W = self._table_width
        if len(plan.phases) > W:
            raise ValueError(
                f"plan has {len(plan.phases)} phases but the runtime "
                f"LR table was sized for {W} (schedule.n_cuts + 2) — "
                f"raise n_cuts to allow more adaptive cuts")
        cut_steps, cut_toks, tok, n_cum = [], [], 0, 0
        for p, n in zip(plan.phases[:-1],
                        plan.steps_per_phase(seq)[:-1]):
            tok += n * p.batch_size * seq
            n_cum += n
            cut_steps.append(n_cum)
            cut_toks.append(float(tok))
        scales = [p.lr_scale for p in plan.phases]
        pad = W - len(cut_steps)
        cut_steps += [2 ** 31 - 1] * pad
        cut_toks += [float("inf")] * pad
        scales += [scales[-1]] * (W - len(scales))
        return (jnp.asarray(cut_steps, jnp.int32),
                jnp.asarray(cut_toks, jnp.float32),
                jnp.asarray(scales, jnp.float32))

    def update_plan(self, plan: SeesawPlan) -> None:
        """Swap in an extended plan after an adaptive cut.  Only valid
        for the adaptive kind — prescheduled engines bake their LR
        table into the compiled program, so swapping their plan would
        silently train on stale cuts."""
        if not self.adaptive:
            raise ValueError(
                "update_plan is only valid for adaptive-seesaw "
                "engines; prescheduled plans are baked into the "
                "compiled step")
        self.plan = plan
        self._lr_tables()    # fail fast on table-width overflow

    def host_lr(self, tokens: float,
                step: Optional[int] = None) -> float:
        """The schedule's LR at a host-known position (logging /
        probes) — hides the adaptive runtime-table calling convention
        from callers."""
        if self.adaptive:
            return float(self.lr_fn(
                float(tokens), -1 if step is None else int(step),
                *self._lr_tables()))
        return float(self.lr_fn(float(tokens)))

    # -- sharding specs ------------------------------------------------- #
    def _batch_axes(self):
        return ("pod", "data") if self.multi_pod else ("data",)

    def _state_specs(self):
        """(param PartitionSpec tree, opt-state PartitionSpec tree)."""
        pspec = R.param_specs(self.model, self.multi_pod)
        pstruct = param_structs(self.model)
        ostruct = jax.eval_shape(self.optimizer.init, pstruct)
        return pspec, opt_state_specs(pspec, ostruct)

    def state_shardings(self):
        """``(param NamedSharding tree, opt-state NamedSharding tree)``
        of the run's train state on this engine's mesh — what the
        checkpoint layer needs to restore each process's addressable
        shards only (``checkpoint.restore(..., shardings=...)``).
        ``None`` without a mesh (single-device placement) — or with a
        duck-typed mesh stand-in (only real meshes can build
        ``NamedSharding``s; geometry helpers accept anything with a
        ``.shape``)."""
        if not isinstance(self.mesh, jax.sharding.Mesh):
            return None
        pspec, ospec = self._state_specs()
        return (named_shardings(self.mesh, pspec),
                named_shardings(self.mesh, ospec))

    def _shardings(self, stacked_batch):
        """(in_shardings, out_shardings) for the fused step.  Inputs:
        (params, opt_state, tokens, step0, n_valid, batches) with the
        three control scalars replicated.  Outputs pin params/opt state
        to the same specs as the inputs — without the constraint XLA
        is free to return a donated output with whatever sharding
        propagation inferred, and the *next* compiled program (a new
        batch size in the ramp) would then reject the arg as
        mismatched mid-run."""
        pspec, ospec = self._state_specs()
        axes = self._batch_axes()

        def bspec(x):
            # leading K dim replicated, batch dim sharded over data axes
            return P(None, axes, *([None] * (x.ndim - 2)))

        bspecs = jax.tree.map(bspec, stacked_batch)
        if self.adaptive:
            # extra replicated leaves: ema0 before the batches, the
            # three LR-table arrays after, and the EMA scalar output
            in_sh = named_shardings(
                self.mesh, (pspec, ospec, P(), P(), P(), P(), bspecs,
                            P(), P(), P()))
            out_sh = (named_shardings(self.mesh, pspec),
                      named_shardings(self.mesh, ospec),
                      NamedSharding(self.mesh, P()),  # stacked metrics
                      NamedSharding(self.mesh, P()))  # loss EMA
        else:
            in_sh = named_shardings(
                self.mesh, (pspec, ospec, P(), P(), P(), bspecs))
            out_sh = (named_shardings(self.mesh, pspec),
                      named_shardings(self.mesh, ospec),
                      NamedSharding(self.mesh, P()))  # stacked metrics
        return in_sh, out_sh

    # -- compile cache -------------------------------------------------- #
    def _build_jit(self, batch_size: int, micro: int,
                   batch_structs=None) -> Callable:
        """The jitted (not yet traced) fused step for a batch size —
        shared by the lazy :meth:`compiled_step` path and the AOT
        :meth:`prewarm_async` path so both produce the identical
        program.  ``batch_structs`` (arrays or ShapeDtypeStructs with
        the stacked ``(K, B, ...)`` shapes) is only needed to derive
        shardings on a mesh."""
        grad = make_grad_step(self.model, self.optimizer,
                              micro_batches=micro,
                              z_loss=self.cfg.z_loss,
                              dtype=self.dtype,
                              remat=self.cfg.remat,
                              multi_pod=self.multi_pod)
        fused = make_fused_step(
            grad, self.lr_fn, batch_size * self.cfg.seq_len,
            ema_decay=self.ema_decay if self.adaptive else None,
            n_lr_args=3 if self.adaptive else 0)
        kw = {}
        if self.mesh is not None and batch_structs is not None:
            kw["in_shardings"], kw["out_shardings"] = \
                self._shardings(batch_structs)
        return jax.jit(fused, donate_argnums=(0, 1), **kw)

    def compiled_step(self, batch_size: int, k: int,
                      stacked_batch=None) -> Callable:
        micro = self.micro_batches(batch_size)
        key = (batch_size, micro, k)
        if key not in self._cache and key in self._prewarm:
            # a background AOT compile for this key is in flight —
            # join it rather than compiling the same program twice
            self._prewarm.pop(key).join()
        if key not in self._cache:
            self._cache[key] = self._build_jit(batch_size, micro,
                                               stacked_batch)
        return self._cache[key]

    def _arg_structs(self, batch_size: int, k: int, stacked_batch):
        """ShapeDtypeStructs of one fused-step call at ``(batch_size,
        k)`` — the AOT lowering inputs for :meth:`prewarm_async`.  The
        batch structs reshape the *current* chunk's per-example shapes
        to the target batch size, so prewarm needs no example data."""
        pstruct = param_structs(self.model)
        ostruct = jax.eval_shape(self.optimizer.init, pstruct)
        bstruct = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                (k, batch_size) + tuple(x.shape[2:]), x.dtype),
            stacked_batch)

        def scal(dt):
            return jax.ShapeDtypeStruct((), dt)

        args = [pstruct, ostruct, scal(jnp.float32), scal(jnp.int32),
                scal(jnp.int32)]
        if self.adaptive:
            args.append(scal(jnp.float32))       # ema0
        args.append(bstruct)
        if self.adaptive:
            W = self._table_width
            args += [jax.ShapeDtypeStruct((W,), jnp.int32),
                     jax.ShapeDtypeStruct((W,), jnp.float32),
                     jax.ShapeDtypeStruct((W,), jnp.float32)]
        return tuple(args)

    def prewarm_async(self, batch_size: int, k: int, stacked_batch):
        """AOT-compile the fused step for a *future* batch size in a
        background thread (``jit(...).lower(structs).compile()``), so
        an adaptive cut's ramp stage is already compiled when its first
        chunk arrives — the cut costs one background compile instead of
        a dispatch stall.  ``stacked_batch`` is the current chunk,
        used only for its per-example shapes/dtypes.

        Returns the started thread, or ``None`` when the program is
        already cached or warming.  :meth:`compiled_step` joins an
        in-flight thread for its key before falling back to a lazy
        compile, so racing a prewarm never compiles twice.  A failed
        background compile (e.g. an AOT-unsupported backend) degrades
        to the lazy jit path at first dispatch."""
        micro = self.micro_batches(batch_size)
        key = (batch_size, micro, k)
        if key in self._cache or key in self._prewarm:
            return None
        structs = self._arg_structs(batch_size, k, stacked_batch)
        bstruct = structs[6 if self.adaptive else 5]
        jitted = self._build_jit(batch_size, micro, bstruct)

        def work():
            try:
                with self.mesh_context():
                    self._cache[key] = jitted.lower(*structs).compile()
            except Exception:
                self._cache.setdefault(key, jitted)

        t = threading.Thread(target=work, daemon=True,
                             name=f"prewarm-b{batch_size}")
        t.start()
        self._prewarm[key] = t
        return t

    # -- checkpointing -------------------------------------------------- #
    def make_checkpoint_manager(self, **kw):
        """An async :class:`repro.train.checkpoint.CheckpointManager`
        bound to this engine's plan and seq_len, so its saves carry the
        same phase metadata as the trainer's sync path.  ``kw`` passes
        through (``chunk_bytes``, ``commit_timeout``)."""
        from repro.train.checkpoint import CheckpointManager
        return CheckpointManager(plan=self.plan,
                                 seq_len=self.cfg.seq_len, **kw)

    # -- dispatch ------------------------------------------------------- #
    def run_chunk(self, params, opt_state, tokens_seen,
                  stacked_batch, n_valid: Optional[int] = None,
                  step: Optional[int] = None, loss_ema=None):
        """One host round-trip: up to K fused optimizer steps.  Returns
        (params, opt_state, stacked device metrics) without forcing a
        transfer — the caller flushes metrics at log boundaries.  An
        adaptive engine returns a fourth element: the device loss EMA
        after the chunk (a scalar DeviceArray; one ``device_get`` per
        chunk is the controller's entire host traffic).

        ``tokens_seen`` is the host's exact integer token count (a
        float on a step boundary also works); it is rounded once to
        f32 here.  ``n_valid`` (default: all K) is the number of
        leading real steps in a tail-padded chunk — metric rows past it
        are zeros and must be discarded.  ``step`` is the global step
        index of the chunk's first step; when given, piecewise LR cuts
        are selected by exact integer compare on device.  ``loss_ema``
        (adaptive only) is the EMA carried from the previous chunk;
        ``None`` means unseeded — the first valid loss seeds it."""
        leaves = jax.tree.leaves(stacked_batch)
        k, batch_size = leaves[0].shape[0], leaves[0].shape[1]
        if n_valid is None:
            n_valid = k
        if k * batch_size * self.cfg.seq_len >= 2 ** 31:
            raise ValueError(
                f"chunk of {k}x{batch_size}x{self.cfg.seq_len} tokens "
                f"overflows the int32 on-device token offset — lower "
                f"fuse_steps")
        fn = self.compiled_step(batch_size, k, stacked_batch)
        scalars = (jnp.float32(float(tokens_seen)),
                   jnp.int32(-1 if step is None else int(step)),
                   jnp.int32(int(n_valid)))
        with self.mesh_context():
            if self.adaptive:
                ema0 = jnp.float32(
                    -1.0 if loss_ema is None else float(loss_ema))
                return fn(params, opt_state, *scalars, ema0,
                          stacked_batch, *self._lr_tables())
            return fn(params, opt_state, *scalars, stacked_batch)
