"""Phase-aware data loading for the fused execution engine.

Follows a SeesawPlan's batch ramp, shards batches onto the mesh, and
guarantees equal-token data order across schedulers (same underlying
stream indexed by absolute sequence number, different batch
partitioning).  Two consumption modes:

- ``__iter__`` — one (phase, step, batch) at a time (legacy eager path
  and generic consumers);
- ``iter_chunks(k)`` — stacked (K, B, ...) chunks feeding the engine's
  K-step fused dispatch.  The chunk stream is *phase-boundary-free*:
  adjacent phases with the same batch size (β=1 'step' plans, a ramp
  clamped by ``max_batch_size``) merge into one contiguous segment
  (the device LR is token/step-indexed, so crossing the boundary
  mid-chunk is exact), and the tail chunk of every segment is padded
  up to K by repeating its last step.  Consumers receive ``m`` — the
  number of *real* leading steps — and must pass it to the engine as
  ``n_valid``; the padded rows are masked on device.  Net effect: one
  compiled executable per distinct batch size, no remainder programs.

Two feeding modes:

- default — this process samples and owns the full global batch;
- ``per_host=True`` — each process samples only its
  ``jax.process_index()`` shard (a contiguous block of ``B /
  process_count`` rows per step) and the global (K, B, ...) arrays are
  assembled from the per-process blocks via
  ``jax.make_array_from_process_local_data``, which is what makes a
  real multi-host run feasible (one process can no longer feed the
  whole ramp).  Row blocks follow mesh device order, the standard
  layout ``jax.make_mesh`` produces on multi-host.  Pass an explicit
  ``process_count``/``process_index`` to *simulate* N-host feeding
  inside one process (mesh-less, host-level arrays only — the
  equivalence tests concatenate the simulated shards and compare
  against the single-process stream).

Both modes double-buffer: a daemon thread runs the (Python-loop-heavy)
synthetic sampling ahead of the consumer through a bounded queue, so
host data production overlaps device compute.  ``resume(tokens_seen)``
repositions the stream exactly on the step boundary a checkpoint was
saved at, in the correct phase.
"""
from __future__ import annotations

import queue
import threading
from typing import Any, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.seesaw import SeesawPlan
from repro.data.synthetic import MarkovLM

_DONE = object()


def validate_per_host_plan(plan: SeesawPlan, process_count: int,
                           n_data_devices: int = 1, *,
                           start_phase: int = 0) -> SeesawPlan:
    """Check the per-host shard divides evenly across the whole ramp.

    Every phase's global batch must split into ``process_count`` equal
    per-process blocks, and still shard over all ``n_data_devices``
    data-parallel devices — a ramp that only divides in its early
    phases would crash mid-run, so this is validated up front (launch
    wiring and the dry-run both call it).  An elastic resume passes
    ``start_phase``: phases the checkpoint already consumed are skipped
    — the NEW topology only has to feed the remainder of the ramp, and
    a ramp stage it cannot feed is reported against the resume point,
    not a phase the run will never revisit."""
    suffix = (f" (resuming at phase {start_phase})"
              if start_phase > 0 else "")
    for p in plan.phases:
        if p.index < start_phase:
            continue
        if p.batch_size % max(process_count, 1):
            raise ValueError(
                f"phase {p.index}: global batch {p.batch_size} does "
                f"not divide across {process_count} host "
                f"processes{suffix}")
        if n_data_devices and p.batch_size % n_data_devices:
            raise ValueError(
                f"phase {p.index}: global batch {p.batch_size} does "
                f"not divide across {n_data_devices} data "
                f"devices{suffix}")
    return plan


class PhaseDataLoader:
    """Iterates a plan's (phase, step, batch) stream.

    The token stream is indexed by absolute sequence number, so a cosine
    run (constant B) and a Seesaw run (ramped B) consume identical
    sequences in identical order at equal token counts — and a resumed
    run continues the exact stream of the uninterrupted one.  In
    per-host mode the same invariant holds for the assembled *global*
    batch: process p contributes rows ``[p*B/N, (p+1)*B/N)`` of every
    step's global batch, so the concatenation over processes equals the
    single-process stream row for row.
    """

    def __init__(self, source: MarkovLM, plan: SeesawPlan, seq_len: int,
                 mesh=None, multi_pod: bool = False, prefetch: int = 2,
                 per_host: bool = False,
                 process_index: Optional[int] = None,
                 process_count: Optional[int] = None,
                 validate: bool = True):
        self.source = source
        self.plan = plan
        self.seq_len = seq_len
        from repro.launch.mesh import gspmd_mesh
        self.mesh = gspmd_mesh(mesh)
        self.multi_pod = multi_pod
        self.prefetch = prefetch
        self.per_host = per_host
        if per_host:
            self._pcount = process_count or jax.process_count()
            self._pidx = (jax.process_index() if process_index is None
                          else process_index)
            # validate=False defers the whole-ramp check to resume():
            # an elastic resume onto a new topology must not fail on a
            # phase the checkpoint already consumed
            if validate:
                validate_per_host_plan(plan, self._pcount)
            if not 0 <= self._pidx < self._pcount:
                raise ValueError(
                    f"process_index {self._pidx} outside "
                    f"[0, {self._pcount})")
            if mesh is not None and self._pcount != jax.process_count():
                raise ValueError(
                    "a simulated process_count only makes sense "
                    "mesh-less (host-level arrays); with a mesh the "
                    "process layout comes from the jax runtime")
            if mesh is not None and self._pcount > 1:
                # verified from the actual NamedSharding, so per-host
                # feeding is safe on custom meshes too, not just the
                # layout jax.make_mesh produces
                from repro.launch.mesh import assert_per_host_row_blocks
                assert_per_host_row_blocks(mesh, self._pcount)
        else:
            self._pcount, self._pidx = 1, 0
        # (phase_idx, steps_done_in_phase, absolute seq cursor)
        self._start: Tuple[int, int, int] = (0, 0, 0)

    # -- resume --------------------------------------------------------- #
    def position_at(self, tokens_seen) -> Tuple[int, int, int]:
        """(phase_idx, steps_done_in_phase, seq_cursor) for a token
        count that lies on a step boundary of the plan.  Step
        boundaries are exact integers, so the arithmetic is integral
        (a float within 0.5 of a boundary is accepted for backward
        compatibility with f32-era checkpoints)."""
        from repro.train.checkpoint import exact_tokens
        steps = self.plan.steps_per_phase(self.seq_len)
        tok = exact_tokens(tokens_seen)
        cursor = 0
        for pi, (p, n) in enumerate(zip(self.plan.phases, steps)):
            per = p.batch_size * self.seq_len
            if tok < n * per:
                done, rem = divmod(tok, per)
                if rem:
                    raise ValueError(
                        f"tokens_seen={tokens_seen} is not on a step "
                        f"boundary of phase {pi} (B={p.batch_size})")
                return pi, done, cursor + done * p.batch_size
            tok -= n * per
            cursor += n * p.batch_size
        return len(steps), 0, cursor

    def resume(self, tokens_seen) -> "PhaseDataLoader":
        """Reposition the stream to continue a checkpointed run.  The
        remainder of the ramp is (re-)validated against THIS loader's
        topology from the resumed phase on — the elastic-resume check:
        the new process count need not match the saving one, but it
        must be able to feed every phase still ahead."""
        self._start = self.position_at(tokens_seen)
        if self.per_host:
            validate_per_host_plan(self.plan, self._pcount,
                                   start_phase=self._start[0])
        return self

    def rechunk(self, plan, tokens_seen) -> "PhaseDataLoader":
        """Swap in an extended plan mid-stream (an adaptive Seesaw cut)
        and reposition to the exact ``tokens_seen`` boundary.  The
        sequence stream is indexed by *absolute* sequence number, so
        the examples after the cut are the same ones the old plan would
        have produced — only the batch grouping changes.  A live
        ``iter_chunks`` generator keeps its creation-time position;
        create a fresh one after rechunking (the trainer's re-chunk
        loop does), and the old prefetch thread parks harmlessly on its
        queue.  Per-host feasibility of the *remaining* phases is
        re-validated, so a cut that creates an unfeedable ramp stage
        fails here — at cut time — rather than mid-ramp."""
        self.plan = plan
        return self.resume(tokens_seen)

    # -- sharding -------------------------------------------------------- #
    def _batch_axes(self):
        return ("pod", "data") if self.multi_pod else ("data",)

    def _shard(self, batch: Dict[str, np.ndarray], leading_dims: int = 1):
        """Put a host batch onto devices; dims before the batch dim
        (the K chunk dim) replicate, the batch dim shards over the data
        axes.  In per-host mode the local array is this process's row
        block and the global array is assembled across processes via
        ``jax.make_array_from_process_local_data``."""
        if self.mesh is None:
            return {k: jnp.asarray(v) for k, v in batch.items()}
        axes = self._batch_axes()
        bdim = leading_dims - 1
        out = {}
        for k, v in batch.items():
            spec = P(*([None] * bdim), axes,
                     *([None] * (v.ndim - leading_dims)))
            sharding = NamedSharding(self.mesh, spec)
            if self.per_host:
                gshape = list(v.shape)
                gshape[bdim] = v.shape[bdim] * self._pcount
                out[k] = jax.make_array_from_process_local_data(
                    sharding, v, tuple(gshape))
            else:
                out[k] = jax.device_put(v, sharding)
        return out

    # -- host-side production ------------------------------------------- #
    def _local_rows(self, batch_size: int) -> Tuple[int, int]:
        """(row offset within the step's global batch, rows to sample)
        for this process — the whole batch outside per-host mode."""
        bl = batch_size // self._pcount
        return self._pidx * bl, bl

    def _host_steps(self) -> Iterator[Tuple[Any, int, Dict]]:
        steps = self.plan.steps_per_phase(self.seq_len)
        p0, s0, cursor = self._start
        for pi in range(p0, len(self.plan.phases)):
            phase, n = self.plan.phases[pi], steps[pi]
            for s in range(s0 if pi == p0 else 0, n):
                off, bl = self._local_rows(phase.batch_size)
                batch = self.source.sample(cursor + off, bl,
                                           self.seq_len)
                cursor += phase.batch_size
                yield phase, s, batch

    def _resume_segments(self):
        """The plan's merged same-batch-size segments with the resume
        offset applied (phases before the start dropped, the start
        phase's already-consumed steps removed)."""
        p0, s0, _ = self._start
        segs = []
        for b, entries in self.plan.merged_segments(self.seq_len):
            cur = []
            for phase, n in entries:
                if phase.index < p0:
                    continue
                if phase.index == p0:
                    n -= s0
                if n > 0:
                    cur.append((phase, n))
            if cur:
                segs.append((b, cur))
        return segs

    def _sample_chunk(self, cursor: int, m: int, b: int) -> Dict:
        """m steps × (this process's rows of) the global batch b,
        stacked to (m, local_b, ...)."""
        if self._pcount == 1:
            raw = self.source.sample(cursor, m * b, self.seq_len)
            return {key: v.reshape(m, b, *v.shape[1:])
                    for key, v in raw.items()}
        off, bl = self._local_rows(b)
        parts = [self.source.sample(cursor + s * b + off, bl,
                                    self.seq_len) for s in range(m)]
        return {key: np.stack([p[key] for p in parts])
                for key in parts[0]}

    def _host_chunks(self, k: int) -> Iterator[Tuple[Any, Dict, int]]:
        """The merged chunk stream: k steps at a time across each
        same-batch-size segment, the segment's tail chunk padded up to
        k by repeating its last step (padding consumes no cursor and is
        masked on device via ``n_valid``)."""
        _, _, cursor = self._start
        for b, entries in self._resume_segments():
            qi, qoff = 0, 0                 # phase pointer in segment
            remaining = sum(n for _, n in entries)
            while remaining:
                m = min(k, remaining)
                chunk = self._sample_chunk(cursor, m, b)
                if m < k:
                    chunk = {key: np.concatenate(
                        [v, np.repeat(v[-1:], k - m, axis=0)])
                        for key, v in chunk.items()}
                phase = entries[qi][0]      # phase of the chunk's head
                adv = m
                while adv:
                    take = min(adv, entries[qi][1] - qoff)
                    qoff += take
                    adv -= take
                    if qoff == entries[qi][1]:
                        qi, qoff = qi + 1, 0
                cursor += m * b
                remaining -= m
                yield phase, chunk, m

    @staticmethod
    def _prefetched(gen, depth: int):
        """Run ``gen`` in a daemon thread, ``depth`` items ahead — the
        double buffer that overlaps sampling with device compute.  (An
        abandoned iterator parks the thread on the bounded queue; it is
        a daemon and holds at most ``depth`` batches.)"""
        q: queue.Queue = queue.Queue(maxsize=max(depth, 1))

        def worker():
            try:
                for item in gen:
                    q.put(item)
                q.put(_DONE)
            except BaseException as e:            # propagate to consumer
                q.put(e)

        threading.Thread(target=worker, daemon=True).start()
        while True:
            item = q.get()
            if item is _DONE:
                return
            if isinstance(item, BaseException):
                raise item
            yield item

    # -- consumption ---------------------------------------------------- #
    def __iter__(self) -> Iterator[Tuple[Any, int, Dict[str, Any]]]:
        gen = self._host_steps()
        if self.prefetch:
            gen = self._prefetched(gen, self.prefetch)
        for phase, s, batch in gen:
            yield phase, s, self._shard(batch)

    def iter_chunks(self, k: int) -> Iterator[Tuple[Any, Dict, int]]:
        """Yield (phase of the first step, stacked sharded (k, B, ...)
        chunk, m) for the engine's fused dispatch.  Every chunk has
        leading dim exactly ``k``; only the first ``m`` steps are real
        — pass ``m`` to ``PhaseEngine.run_chunk`` as ``n_valid``.  A
        chunk may span a phase boundary (the merged stream): the batch
        size is constant within it, but per-step phase attribution must
        come from the token count, not the head phase tag."""
        gen = self._host_chunks(k)
        if self.prefetch:
            gen = self._prefetched(gen, self.prefetch)
        for phase, chunk, m in gen:
            yield phase, self._shard(chunk, leading_dims=2), m
