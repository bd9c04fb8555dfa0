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
them).  The per-layer numbers come from these: :func:`scope_table` (and
:func:`scope_ms`, its classes), :func:`step_gap_ms` and
:func:`span_share`.

A traced run's ``layer`` (``chipbench/run.py``) carries the step's
op-name map, its scope table (``layer["scopes"]``) and the step's own
scalars averaged over the window (``layer["counters"]``); a per-layer
reader takes its number with :func:`scope_reading` or :func:`counter`,
which give ``None`` where the program wrote no such scope or scalar.
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
# the scope table's row for every leaf op of the step, mapped or not
ALL = "*"


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


def reduce_window(tr: Dict) -> Dict:
    """The benchmark window's :func:`trace.reduce`, over the benchmark's
    own spans as before the program had spans (so its values do not
    move), with :func:`reduce` of the whole trace under
    ``"program"``."""
    t0, t1 = T.window_of(tr)
    bench = dict(tr, spans=[s for s in tr["spans"]
                            if s[2].startswith(T.SPAN_PREFIX)])
    red = T.reduce(bench, t0, t1)
    red["program"] = reduce(tr, t0, t1)
    return red


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


def scope_table(red: Dict[str, Dict], op_names: Dict[str, str]
                ) -> Optional[Dict[str, Dict[str, float]]]:
    """Leaf-op milliseconds per train-step execution, mean over the
    devices that ran the step, for every name-stack component of the
    op-name map, split by :data:`scopes.CLASSES`: ``{component: {class:
    ms}}``, a class only where an op of it ran.  Row :data:`ALL` holds
    every leaf op (instructions missing from ``op_names`` are unscoped,
    unless their own name says XLA rematerialized them).  ``None``
    where no device ran the step."""
    per_dev = []
    for v in red.values():
        if not v["steps"]:
            continue
        t: Dict[str, Dict[str, float]] = defaultdict(
            lambda: defaultdict(float))
        for instr, sec in v["op_s"].items():
            name = op_names.get(instr, "")
            cls = scopes.classify(instr, name)
            for c in {ALL, *scopes.components(name)}:
                t[c][cls] += 1e3 * sec / v["n_steps"]
        per_dev.append(t)
    if not per_dev:
        return None
    cells = {(c, k) for t in per_dev for c, row in t.items() for k in row}
    out: Dict[str, Dict[str, float]] = defaultdict(dict)
    for c, k in sorted(cells):
        out[c][k] = sum(t.get(c, {}).get(k, 0.0)
                        for t in per_dev) / len(per_dev)
    return dict(out)


def scope_ms(red: Dict[str, Dict], op_names: Dict[str, str]
             ) -> Optional[Dict[str, float]]:
    """Device milliseconds per train-step execution in each of
    :data:`scopes.CLASSES` and in ``attention``, mean over the devices
    that ran the step; ``None`` where none did.  Instructions missing
    from ``op_names`` count as unscoped."""
    table = scope_table(red, op_names)
    if table is None:
        return None
    out = {k: table[ALL].get(k, 0.0) for k in scopes.CLASSES}
    out["attention"] = sum(table.get("attention", {}).values())
    return out


def scope_reading(layer: Dict, scope: str,
                  cls: Optional[str] = None) -> Optional[float]:
    """Milliseconds per step execution of ``scope`` (:data:`ALL` for
    the whole step) in class ``cls``, or in all classes; ``None`` where
    the run was not traced on a device, or no op of it ran."""
    row = (layer.get("scopes") or {}).get(scope, {})
    ms = row.get(cls) if cls else sum(row.values())
    return ms or None


# name-stack components that JAX and the step's control flow add, and
# those the class already says (which pass an op ran in)
_PLUMBING = {"jit", "train_step", "while", "body", "closed_call", "cond",
             "branch_0_fun", "branch_1_fun", "checkpoint",
             "rematted_computation", "transpose", "jvp", "forward",
             "optimizer"}


def op_label(event_name: str, op_names: Dict[str, str]) -> str:
    """An op event's name led by its class and the program's scopes
    (``backward attention/causal_blocks/dot_general: %fusion.12 =
    ...``), for the breakdown; the bare name where the map lacks it."""
    instr = scopes.instruction(event_name)
    op_name = op_names.get(instr)
    if not op_name:
        return event_name
    path = "/".join(c for c in scopes.components(op_name)
                    if c not in _PLUMBING)
    return T.short(f"{scopes.classify(instr, op_name)} {path}: "
                   f"{event_name}")


def counter(layer: Dict, name: str) -> Optional[float]:
    """The mean over the window's steps of scalar ``name`` that the
    step returns beside its loss; ``None`` where it returns none."""
    return (layer.get("counters") or {}).get(name)


def summary(layer: Dict) -> Dict:
    """What a traced run prints of its program beside the result: the
    scope table's rows for the whole step and for the program's own
    scopes, the mean execution of the step (ms, executions inside the
    window, mean over devices) and the counters."""
    table = layer.get("scopes") or {}
    execs = [[e - s for s, e in v["steps"][1:-1]]
             for v in layer["trace"]["program"].values()]
    execs = [sum(x) / len(x) for x in execs if x]
    return {"scopes": {k: table[k] for k in (ALL, "forward", "optimizer",
                                             "attention", "causal_blocks")
                       if k in table},
            "step_ms": 1e-6 * sum(execs) / len(execs) if execs else None,
            "counters": layer.get("counters")}


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
