"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

:func:`load` keeps what the reduction needs, as plain lists: for each
TPU device plane its op events (the ``XLA Ops`` line) and its program
executions (the ``XLA Modules`` line), and the host spans that the
benchmark's own ``TraceAnnotation``s wrote (names starting with
``chipbench.``).  All times are nanoseconds on the trace's one clock.
:func:`to_json`/:func:`from_json` keep that form on disk, which is how
the tests hold a recorded chip trace.

:func:`reduce` turns it into, per device and over a window:

- busy seconds: the union of op intervals; idle share = 1 - busy/window;
- collective seconds, and the exposed part: time in which a collective
  (all-reduce, all-gather, reduce-scatter, collective-permute,
  all-to-all) runs and no other op does (a ``while``, ``conditional``
  or ``call`` op spans its body's ops and counts as neither);
- program time by module name, and by the benchmark span each
  execution falls in, with each execution's duration;
- the top ops by time (containers left out, names cut to 120
  characters), and the longest idle gaps named by the innermost host
  span they fall in.
"""
from __future__ import annotations

import gzip
import json
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "chipbench."
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|reducescatter", re.IGNORECASE)
# ops whose event spans the ops of their body on the same line
CONTAINER = re.compile(r"\b(while|conditional|call)\(")


def _stat(ev, key) -> Optional[str]:
    for k, v in ev.stats:
        if k == key:
            return str(v)
    return None


def load(path: str) -> Dict:
    """Read one ``.xplane.pb`` into ``{"devices": {id: {"ops": [[start,
    end, name, category]], "modules": [[start, end, name]]}}, "spans":
    [[start, end, name]]}``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices: Dict[str, Dict] = {}
    spans: List[list] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        cat = _stat(ev, "hlo_category") or ""
                        dev["ops"].append([ev.start_ns, ev.end_ns,
                                           ev.name, cat])
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        dev["modules"].append([ev.start_ns, ev.end_ns,
                                               ev.name])
            devices[m.group(1)] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append([ev.start_ns, ev.end_ns, ev.name])
    return {"devices": devices, "spans": spans}


def find_xplane(directory: Path) -> Path:
    found = sorted(Path(directory).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def to_json(tr: Dict, path: Path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(tr, f)


def from_json(path: Path) -> Dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# --------------------------------------------------------------------- #
# interval arithmetic
# --------------------------------------------------------------------- #

def union(intervals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted((float(s), float(e)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, t0: float, t1: float) -> List[Tuple[float, float]]:
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> List[Tuple[float, float]]:
    """Parts of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, t0: float, t1: float) -> List[Tuple[float, float]]:
    return subtract([(t0, t1)], busy)


# --------------------------------------------------------------------- #
# the reduction
# --------------------------------------------------------------------- #

def is_collective(name: str, category: str) -> bool:
    """By the op's own name (an op event's name is its HLO instruction:
    ``%all-gather-done.3 = ... all-gather-done(%x, ...)``; operands that
    name a collective do not make an op one)."""
    own = name.split(" = ", 1)[0]
    return bool(COLLECTIVE.search(own) or COLLECTIVE.search(category))


def is_container(name: str) -> bool:
    return bool(CONTAINER.search(name))


def short(name: str, width: int = 120) -> str:
    return name if len(name) <= width else name[:width - 3] + "..."


def window_of(tr: Dict, span: str = "chipbench.window"
              ) -> Tuple[float, float]:
    hits = [(s, e) for s, e, n in tr["spans"] if n == span]
    if not hits:
        raise ValueError(f"no host span {span!r} in the trace")
    return hits[-1]


def span_at(spans, t: float) -> str:
    """The innermost (shortest) benchmark span covering time ``t``."""
    best = None
    for s, e, n in spans:
        if s <= t <= e and n != "chipbench.window" and (
                best is None or e - s < best[1] - best[0]):
            best = (s, e, n)
    return best[2] if best else "outside spans"


def reduce(tr: Dict, t0: float, t1: float, top: int = 10) -> Dict:
    window = t1 - t0
    per_dev: Dict[str, Dict] = {}
    op_time: Dict[str, float] = defaultdict(float)
    gap_list: List[Tuple[float, float, float]] = []
    for dev, d in sorted(tr["devices"].items()):
        ops = [(s, e, n, c) for s, e, n, c in d["ops"]
               if e > t0 and s < t1]
        busy = union(clip([(s, e) for s, e, _, _ in ops], t0, t1))
        leaves = [op for op in ops if not is_container(op[2])]
        coll = union(clip([(s, e) for s, e, n, c in leaves
                           if is_collective(n, c)], t0, t1))
        other = union(clip([(s, e) for s, e, n, c in leaves
                            if not is_collective(n, c)], t0, t1))
        modules: Dict[str, List[float]] = defaultdict(list)
        by_span: Dict[str, List[float]] = defaultdict(list)
        for s, e, n in d["modules"]:
            if s >= t0 and e <= t1:
                modules[n].append((e - s) / 1e9)
                by_span[span_at(tr["spans"], (s + e) / 2)].append(
                    (e - s) / 1e9)
        for s, e, n, _ in leaves:
            op_time[short(n)] += (min(e, t1) - max(s, t0)) / 1e9
        idle = gaps(busy, t0, t1)
        if dev == min(tr["devices"]):
            gap_list = [(e - s, s, e) for s, e in idle]
        per_dev[dev] = {
            "busy_s": total(busy) / 1e9,
            "idle_share": 1.0 - total(busy) / window if window else 0.0,
            "collective_s": total(coll) / 1e9,
            "collective_exposed_s": total(subtract(coll, other)) / 1e9,
            "modules": dict(modules),
            "programs_by_span": dict(by_span),
            "n_ops": len(ops),
        }
    n_dev = max(len(per_dev), 1)
    gap_list.sort(reverse=True)
    return {
        "window_s": window / 1e9,
        "devices": per_dev,
        "busy_s": sum(v["busy_s"] for v in per_dev.values()) / n_dev,
        "top_ops": sorted(((n, t / n_dev) for n, t in op_time.items()),
                          key=lambda x: -x[1])[:top],
        "idle_gaps": [(span_at(tr["spans"], (s + e) / 2), g / 1e9)
                      for g, s, e in gap_list[:top]],
    }


def module_durations(red: Dict, pattern: str) -> List[float]:
    """Execution times (s) of programs whose module name matches
    ``pattern``, over all devices."""
    rx = re.compile(pattern)
    return [d for v in red["devices"].values()
            for n, ds in v["modules"].items() if rx.search(n) for d in ds]


def span_durations(red: Dict, span: str) -> List[float]:
    """Execution times (s) of programs that ran inside host span
    ``span`` (by the middle of each execution), over all devices."""
    return [d for v in red["devices"].values()
            for d in v["programs_by_span"].get(span, [])]

