"""Production training launcher, driving the phase execution engine.

    PYTHONPATH=src python -m repro.launch.train --arch seesaw-150m \
        --schedule seesaw --steps 200 [--mesh 2x2] [--multipod] \
        [--fuse-steps 16] [--checkpoint ckpt] [--resume] \
        [--per-host] [--coordinator HOST:PORT --num-processes N \
         --process-id I]

Multi-process launch: run the same command on every host with
``--coordinator`` (process 0's address), ``--num-processes`` and a
distinct ``--process-id`` — or the equivalent environment variables
``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` /
``JAX_PROCESS_ID`` (flags win).  :func:`maybe_init_distributed` wires
``jax.distributed.initialize`` before any device use and is skipped
automatically for single-process runs; on CPU it selects the gloo
cross-process collective backend.  In a multi-process run per-host
data feeding is forced on, the default mesh spans all global devices
as ``(device_count, 1) = data x model``, and
``launch.mesh.assert_per_host_row_blocks`` verifies — from the actual
``NamedSharding`` — that each process owns a contiguous row block of
the data axis, so custom ``--mesh`` layouts that would misassign rows
fail fast instead of training on the wrong data.

``--per-host`` turns on multi-host data feeding: each process samples
only its ``jax.process_index()`` shard of the global batch and the
global arrays are assembled across processes
(``jax.make_array_from_process_local_data``); the ramp is validated up
front so every phase's batch divides over processes and data devices.

``--checkpoint`` names a sharded streaming checkpoint *directory*
(an atomically-committed ``manifest.json`` + one ``arrays/<gen>/*.npy``
per distinct global block; see :mod:`repro.train.checkpoint` and
``docs/checkpointing.md``): block writers are assigned round-robin
across every process holding an addressable replica, each streams its
blocks to disk in bounded chunks, and process 0 commits the manifest
in a single rename (an interrupted save leaves the previous checkpoint
restorable) — so save/restore never materializes a full replica per
host and legacy single-file ``.npz`` checkpoints still restore.
``--save-every N`` adds periodic saves at chunk boundaries —
asynchronous by default (the state is snapshotted on device and a
background writer streams it while training continues; ``--sync-save``
reverts to blocking saves), and ``--verify-restore`` checks every
block's crc32 against the manifest before resuming.

Elastic + preemption-safe operation: ``--resume`` restores onto
WHATEVER topology this launch has — the on-disk format is
topology-independent, the loader re-derives this host's feed shard
and stream position from the exact ``tokens_seen``, and the remainder
of the ramp is re-validated for the new process count (a clear error
names the first phase the new mesh cannot feed).  SIGTERM/SIGINT
request a best-effort final save at the next chunk boundary within a
``--grace`` deadline instead of dying mid-step, and
``jax.distributed.initialize`` retries with exponential backoff
(``--connect-attempts`` / ``--connect-backoff``) so a restarted pod
waits out a slow-to-restart coordinator.

On real hardware the mesh comes from the platform; on this container a
small host-device mesh (--host-devices N) exercises the identical pjit
path.  The runtime is the same ``Trainer``/``PhaseEngine`` stack the
quickstart example uses: per-phase compile cache, batch ramp, LR curve
evaluated on device, ``--fuse-steps K`` batches per host dispatch, and
phase-aware checkpointing (``--resume`` repositions the data stream on
the exact step boundary of the saved run).
"""
from __future__ import annotations

import argparse
import os
import signal
import time


class PreemptionGuard:
    """Turns SIGTERM/SIGINT into a cooperative stop request.

    ``install()`` replaces the handlers; the trainer polls
    :meth:`should_stop` at each chunk boundary, so the run always stops
    on an exact chunk boundary — the state a final grace save writes is
    bitwise-resumable.  In a multi-process run the stop decision is
    made *collectively* (an all-gather of the local flags): the
    preempted pod's signal stops every process at the same boundary,
    since a lone process leaving the loop would strand its peers in the
    next chunk's collectives.  :meth:`grace_remaining` counts down the
    save budget from the first signal."""

    def __init__(self, grace: float = 60.0):
        self.grace = float(grace)
        self._signaled_at: float | None = None
        self._prev: dict = {}

    def install(self, signals=(signal.SIGTERM, signal.SIGINT)):
        for s in signals:
            self._prev[s] = signal.signal(s, self._handler)
        return self

    def uninstall(self):
        for s, h in self._prev.items():
            signal.signal(s, h)
        self._prev.clear()

    def _handler(self, signum, frame):
        if self._signaled_at is None:
            self._signaled_at = time.monotonic()

    def requested(self) -> bool:
        """This process received a signal (local, collective-free)."""
        return self._signaled_at is not None

    def grace_remaining(self) -> float:
        if self._signaled_at is None:
            return self.grace
        return max(self.grace - (time.monotonic() - self._signaled_at),
                   0.0)

    def should_stop(self) -> bool:
        """Collective stop poll for the chunk loop — every process
        returns the same answer at the same boundary."""
        import jax
        if jax.process_count() <= 1:
            return self.requested()
        from jax.experimental import multihost_utils
        import numpy as np
        flags = multihost_utils.process_allgather(
            np.int32(1 if self.requested() else 0))
        return bool(np.any(flags))


def init_distributed_with_retry(init_fn, *, attempts: int = 4,
                                backoff: float = 1.0,
                                sleep=time.sleep, log=print):
    """Run ``init_fn`` (a zero-arg ``jax.distributed.initialize``
    closure) with exponential backoff: a restarted pod whose
    coordinator is still coming back up retries instead of crashing
    the whole relaunch.  Delays are ``backoff * 2**i``; the last
    failure propagates."""
    for i in range(max(int(attempts), 1)):
        try:
            return init_fn()
        except Exception as e:                 # noqa: BLE001
            if i + 1 >= attempts:
                raise
            delay = backoff * (2 ** i)
            log(f"jax.distributed.initialize failed "
                f"(attempt {i + 1}/{attempts}): {e}; retrying in "
                f"{delay:.1f}s")
            sleep(delay)


def maybe_init_distributed(coordinator=None, num_processes=None,
                           process_id=None, *,
                           connect_attempts: int = 1,
                           connect_backoff: float = 1.0) -> bool:
    """Wire ``jax.distributed.initialize`` from flags/environment;
    returns True when a multi-process runtime was initialized.

    Single-process runs (no coordinator, ``num_processes`` absent or
    1) skip initialization entirely, so the launcher keeps working
    with plain ``python -m repro.launch.train``.  Must be called
    before any jax device use.  On an explicitly-CPU platform
    (``JAX_PLATFORMS=cpu``) the gloo collective backend is selected —
    without it cross-process collectives on CPU fail at the first
    all-reduce."""
    env = os.environ
    coordinator = coordinator or env.get("JAX_COORDINATOR_ADDRESS")
    if num_processes is None and env.get("JAX_NUM_PROCESSES"):
        num_processes = int(env["JAX_NUM_PROCESSES"])
    if process_id is None and env.get("JAX_PROCESS_ID"):
        process_id = int(env["JAX_PROCESS_ID"])
    if not coordinator and not num_processes:
        return False
    if num_processes is not None and num_processes <= 1 \
            and not coordinator:
        return False
    if not (coordinator and num_processes and process_id is not None):
        raise ValueError(
            "multi-process launch needs all three of coordinator "
            "address, num_processes and process_id (flags or "
            "JAX_COORDINATOR_ADDRESS/JAX_NUM_PROCESSES/"
            f"JAX_PROCESS_ID); got coordinator={coordinator!r}, "
            f"num_processes={num_processes!r}, "
            f"process_id={process_id!r}")
    import jax
    if "cpu" in env.get("JAX_PLATFORMS", "").split(","):
        try:
            jax.config.update("jax_cpu_collectives_implementation",
                              "gloo")
        except (AttributeError, ValueError):   # jaxlib without gloo
            pass
    init_distributed_with_retry(
        lambda: jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes, process_id=process_id),
        attempts=connect_attempts, backoff=connect_backoff)
    return True


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="seesaw-150m")
    ap.add_argument("--schedule", default="seesaw",
                    choices=["seesaw", "cosine", "step", "constant",
                             "seesaw-general", "naive-ramp",
                             "adaptive-seesaw"])
    ap.add_argument("--alpha", type=float, default=2.0)
    ap.add_argument("--beta", type=float, default=None)
    # adaptive-seesaw controller knobs (ignored by other schedules;
    # see docs/adaptive.md)
    ap.add_argument("--ema-decay", type=float, default=0.98,
                    help="device loss-EMA decay per step")
    ap.add_argument("--plateau-window", type=int, default=50,
                    help="steps per plateau test")
    ap.add_argument("--plateau-threshold", type=float, default=2e-3,
                    help="relative improvement below which a window "
                         "counts as a plateau")
    ap.add_argument("--plateau-min-steps", type=int, default=None,
                    help="minimum steps between cuts (default: one "
                         "plateau window)")
    ap.add_argument("--max-cuts", type=int, default=8,
                    help="adaptive: most cuts the controller may fire "
                         "(sizes the runtime LR table); prescheduled: "
                         "step-decay approximation depth")
    ap.add_argument("--max-batch-size", type=int, default=None,
                    help="hardware cap on the batch ramp")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--batch-size", type=int, default=None)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--total-tokens", type=int, default=None)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale variant of the arch")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="simulate N devices on CPU (sets XLA_FLAGS)")
    ap.add_argument("--mesh", default=None, help="e.g. 2x2 = data x model")
    ap.add_argument("--z-loss", type=float, default=0.0)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--resume", action="store_true",
                    help="restore --checkpoint and continue the run "
                         "(elastic: the process count/mesh may differ "
                         "from the saving run's)")
    ap.add_argument("--save-every", type=int, default=None,
                    help="periodic checkpoint every N steps (at chunk "
                         "boundaries), async by default")
    ap.add_argument("--sync-save", action="store_true",
                    help="block the step loop during periodic saves "
                         "instead of streaming from a writer thread")
    ap.add_argument("--verify-restore", action="store_true",
                    help="verify every block's crc32 against the "
                         "manifest before resuming")
    ap.add_argument("--grace", type=float, default=60.0,
                    help="seconds allowed for the final save after "
                         "SIGTERM/SIGINT")
    ap.add_argument("--connect-attempts", type=int, default=4,
                    help="jax.distributed.initialize retries (slow "
                         "coordinator restart)")
    ap.add_argument("--connect-backoff", type=float, default=1.0,
                    help="initial retry delay, doubled per attempt")
    ap.add_argument("--fuse-steps", type=int, default=1,
                    help="K batches per fused dispatch (1 = eager)")
    ap.add_argument("--per-host", action="store_true",
                    help="each process feeds only its "
                         "jax.process_index() shard of the global "
                         "batch (multi-host data feeding; forced on "
                         "in multi-process runs)")
    ap.add_argument("--coordinator", default=None,
                    help="process 0's host:port for "
                         "jax.distributed.initialize (or "
                         "JAX_COORDINATOR_ADDRESS)")
    ap.add_argument("--num-processes", type=int, default=None,
                    help="total process count of the multi-process "
                         "run (or JAX_NUM_PROCESSES)")
    ap.add_argument("--process-id", type=int, default=None,
                    help="this process's index (or JAX_PROCESS_ID)")
    ap.add_argument("--max-device-batch", type=int, default=None)
    ap.add_argument("--kernel-backend", default=None,
                    choices=["xla", "pallas", "pallas_interpret"],
                    help="hot-path op backend (attention / rmsnorm / "
                         "SSD) the fused step compiles against; "
                         "default: the model config's (xla)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.host_devices}")

    distributed = maybe_init_distributed(
        args.coordinator, args.num_processes, args.process_id,
        connect_attempts=args.connect_attempts,
        connect_backoff=args.connect_backoff)

    import jax
    from repro.configs import (OptimizerConfig, RunConfig, ScheduleConfig,
                               get_config)
    from repro.data import MarkovLM, PhaseDataLoader
    from repro.launch.compile_cache import enable_compile_cache
    from repro.train.trainer import Trainer

    enable_compile_cache()

    if distributed:
        print(f"jax.distributed: process {jax.process_index()}"
              f"/{jax.process_count()}, "
              f"{jax.local_device_count()} local of "
              f"{jax.device_count()} global devices")
        if not args.per_host:
            # one process cannot feed (or even address) the whole
            # global batch in a real multi-process run
            args.per_host = True
            print("per-host data feeding forced on (multi-process)")

    model = get_config(args.arch)
    if args.reduced:
        model = model.reduced()
    seq_len = args.seq_len or min(model.max_seq_len, 1024)
    b0 = args.batch_size or 32
    total = args.total_tokens or (
        args.steps * b0 * seq_len if args.steps else 20 * model.param_count())

    cfg = RunConfig(
        model=model,
        schedule=ScheduleConfig(kind=args.schedule, base_lr=args.lr,
                                alpha=args.alpha,
                                beta=args.beta or args.alpha,
                                n_cuts=args.max_cuts,
                                max_batch_size=args.max_batch_size,
                                ema_decay=args.ema_decay,
                                plateau_window=args.plateau_window,
                                plateau_threshold=args.plateau_threshold,
                                plateau_min_steps=args.plateau_min_steps),
        optimizer=OptimizerConfig(kind=args.optimizer),
        seq_len=seq_len, global_batch_size=b0, total_tokens=total,
        z_loss=args.z_loss, seed=args.seed,
        kernel_backend=args.kernel_backend)

    from repro.launch.mesh import make_launch_mesh
    mesh = make_launch_mesh(args.mesh, distributed=distributed)

    trainer = Trainer(cfg, mesh=mesh, fuse_steps=args.fuse_steps,
                      max_device_batch=args.max_device_batch)
    print(f"arch={model.name} N={model.param_count()/1e6:.0f}M "
          f"schedule={args.schedule} phases={len(trainer.plan.phases)} "
          f"steps={trainer.plan.total_steps(seq_len)} "
          f"batches={trainer.plan.batch_sizes()} "
          f"fuse_steps={trainer.fuse_steps}")
    start_tokens = None
    if args.resume:
        # restore BEFORE ramp validation: an elastic resume (new
        # process count) only has to feed the ramp from the restored
        # position on, and that position comes from the checkpoint
        assert args.checkpoint, "--resume needs --checkpoint"
        meta = trainer.restore_checkpoint(args.checkpoint,
                                          verify=args.verify_restore)
        start_tokens = trainer.state.tokens_seen
        print(f"resumed step {trainer.state.step} "
              f"(phase {meta.get('phase')}, B={meta.get('batch_size')}, "
              f"tokens {trainer.state.tokens_seen:.0f}, saved from "
              f"{meta.get('save_process_count', '?')} processes)")
    if args.per_host:
        # fail fast if any phase still ahead cannot shard over the
        # processes/devices (from the resume point in elastic resumes;
        # the whole ramp otherwise)
        from repro.launch.steps import validate_feeding
        validate_feeding(trainer.plan, mesh, start_tokens=start_tokens,
                         seq_len=seq_len)
        print(f"per-host feeding: process {jax.process_index()}"
              f"/{jax.process_count()}, local batch shards "
              f"{[b // jax.process_count() for b in trainer.plan.batch_sizes()]}")
    src = MarkovLM(vocab_size=min(model.vocab_size, 2048), seed=args.seed)
    loader = PhaseDataLoader(src, trainer.plan, seq_len, mesh=mesh,
                             per_host=args.per_host,
                             validate=not args.resume)
    if args.resume:
        loader.resume(start_tokens)

    def log(rec):
        print(f"step {rec['step']:5d} phase {rec['phase']} "
              f"B={rec['batch_size']:4d} lr={rec['lr']:.2e} "
              f"loss={rec['loss']:.4f} ({rec['wall']:.1f}s)")

    guard = PreemptionGuard(grace=args.grace).install()
    try:
        hist = trainer.run(loader, max_steps=args.steps, log_cb=log,
                           checkpoint_path=args.checkpoint,
                           save_every=args.save_every,
                           async_save=not args.sync_save,
                           stop_fn=guard.should_stop)
    finally:
        guard.uninstall()
    if guard.requested():
        print(f"preemption signal: stopped at step "
              f"{trainer.state.step} (chunk boundary)")
    if hist:
        print(f"done: {len(hist)} steps, final loss "
              f"{hist[-1]['loss']:.4f}")
    else:
        print("done: nothing to run (plan already consumed)")
    if args.checkpoint:
        if guard.requested() and guard.grace_remaining() <= 0:
            print("grace deadline exceeded — skipping the final save "
                  "(the last periodic checkpoint is the resume point)")
        else:
            trainer.save_checkpoint(args.checkpoint)
            print(f"checkpoint → {args.checkpoint}")
    trainer.close()


if __name__ == "__main__":
    main()
