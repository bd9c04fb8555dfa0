"""What the program's own spans and scopes add to the trace reduction
of :mod:`chipbench.trace`.

The trainer writes host spans named ``repro.train.*`` (one
``repro.train.step`` per pass of its chunk loop, with ``next_chunk``,
``dispatch``, ``sync``, ``hook``, ``checkpoint`` and ``cut`` inside)
and names its step program ``jit_train_step``; the step's HLO metadata
carries the ``forward``/``optimizer``/``attention`` scopes that
:mod:`chipbench.scopes` classifies.  All on the one clock of the trace.

:func:`load` is :func:`trace.load` with the program's spans added to the
benchmark's.  :func:`reduce` gives, per device and over a window, the
train-step executions, leaf-op seconds by HLO instruction inside them,
the idle time between consecutive executions, and idle seconds by the
innermost span (time-weighted: a gap that crosses spans is split among
them).  The per-layer numbers come from these: :func:`scope_ms`,
:func:`step_gap_ms` and :func:`span_share`.
"""
from __future__ import annotations

import bisect
import statistics
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional

from chipbench import scopes
from chipbench import trace as T

PROGRAM_PREFIX = "repro."
TRAIN_STEP = "jit_train_step"


def program_spans(path: Path) -> List[list]:
    """The program's host spans, ``[[start, end, name]]`` in ns."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    return [[ev.start_ns, ev.end_ns, ev.name]
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(PROGRAM_PREFIX)]


def load(path: Path) -> Dict:
    tr = T.load(path)
    tr["spans"] = tr["spans"] + program_spans(path)
    return tr


def by_span(intervals, spans, t0: float, t1: float) -> Dict[str, float]:
    """Seconds of the disjoint sorted ``intervals`` (ns) in [t0, t1],
    each moment under the innermost span that covers it
    (:func:`trace.span_at`)."""
    cuts = sorted({t0, t1} | {x for s, e, _ in spans for x in (s, e)
                              if t0 < x < t1})
    segs = [(a, b, T.span_at(spans, (a + b) / 2))
            for a, b in zip(cuts, cuts[1:])]
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for s, e in T.clip(intervals, t0, t1):
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < e:
            out[segs[k][2]] += (min(e, segs[k][1])
                                - max(s, segs[k][0])) / 1e9
            k += 1
    return dict(out)


def _module(name: str) -> str:
    return name.split("(", 1)[0]     # "jit_train_step(1761...)"


def reduce(tr: Dict, t0: float, t1: float,
           module: str = TRAIN_STEP) -> Dict[str, Dict]:
    """Per device: ``steps`` (``[start, end]`` ns of each execution of
    ``module`` that overlaps the window), ``n_steps`` (their number,
    one cut by the window counting by the share inside it), ``op_s``
    (leaf-op seconds inside them and the window, by HLO instruction),
    ``step_gaps_s`` (idle seconds between consecutive executions, gaps
    inside the window) and ``idle_by_span`` (idle seconds of the window
    by innermost span)."""
    out = {}
    for dev, d in sorted(tr["devices"].items()):
        steps = sorted((s, e) for s, e, n in d["modules"]
                       if _module(n) == module and e > t0 and s < t1)
        starts = [s for s, _ in steps]
        op_s: Dict[str, float] = defaultdict(float)
        for s, e, n, _ in d["ops"]:
            i = bisect.bisect_right(starts, s) - 1
            if (i >= 0 and e <= steps[i][1] and e > t0 and s < t1
                    and not T.is_container(n)):
                op_s[scopes.instruction(n)] += (min(e, t1)
                                                - max(s, t0)) / 1e9
        busy = T.union(T.clip([(s, e) for s, e, _, _ in d["ops"]],
                              t0, t1))
        step_gaps = [
            (b[0] - a[1] - T.total(T.clip(busy, a[1], b[0]))) / 1e9
            for a, b in zip(steps, steps[1:])
            if a[1] >= t0 and b[0] <= t1]
        out[dev] = {
            "steps": [[s, e] for s, e in steps],
            "n_steps": sum((min(e, t1) - max(s, t0)) / (e - s)
                           for s, e in steps),
            "op_s": dict(op_s),
            "step_gaps_s": step_gaps,
            "idle_by_span": by_span(T.gaps(busy, t0, t1), tr["spans"],
                                    t0, t1),
        }
    return out


def scope_ms(red: Dict[str, Dict], op_names: Dict[str, str]
             ) -> Optional[Dict[str, float]]:
    """Device milliseconds per train-step execution in each of
    :data:`scopes.CLASSES` and in ``attention``, mean over the devices
    that ran the step; ``None`` where none did.  Instructions missing
    from ``op_names`` count as unscoped."""
    per_dev = []
    for v in red.values():
        if not v["steps"]:
            continue
        ms = dict.fromkeys(scopes.CLASSES + ("attention",), 0.0)
        for instr, sec in v["op_s"].items():
            name = op_names.get(instr, "")
            ms[scopes.classify(instr, name)] += sec
            if scopes.is_attention(name):
                ms["attention"] += sec
        per_dev.append({k: 1e3 * x / v["n_steps"]
                        for k, x in ms.items()})
    if not per_dev:
        return None
    return {k: sum(d[k] for d in per_dev) / len(per_dev)
            for k in per_dev[0]}


def step_gap_ms(red: Dict[str, Dict]) -> Optional[float]:
    """Median idle milliseconds between consecutive train-step
    executions, over the gaps of every device."""
    gaps = [g for v in red.values() for g in v["step_gaps_s"]]
    return 1e3 * statistics.median(gaps) if gaps else None


def span_share(tr: Dict, name: str, t0: float, t1: float
               ) -> Optional[float]:
    """Percent of the window [t0, t1] spent inside host spans ``name``;
    ``None`` where the trace has no such span."""
    hits = [(s, e) for s, e, n in tr["spans"] if n == name]
    if not hits or t1 <= t0:
        return None
    return 100.0 * T.total(T.clip(T.union(hits), t0, t1)) / (t1 - t0)
