"""What every cell shares: the manifest, the device check, the compile
cache, memory and compile readings, and the result line.

Nothing here imports JAX at module level: ``run.py`` must fix the
compile-cache directory in the environment before JAX is imported.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import math
import os
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]      # the checkout
BENCH = ROOT / "chipbench"
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".chipbench_trace"


# keys of a configuration or traffic file that describe it for its
# reader (and, ``reduced``, for the manifest's check); every other key
# is one the cell's driver reads
DESCRIPTIVE_KEYS = frozenset({"source", "assumed", "reduced", "deployment",
                              "why"})


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def manifest(root: Path = ROOT) -> Dict[str, Any]:
    return load_json(root / "BENCHMARK.json")


def load_cell(name: str, root: Path = ROOT) -> Dict[str, Any]:
    """The cell ``name`` with its configuration, traffic and limits
    files read in: ``{"cell", "config", "traffic", "limits",
    "end_to_end", "per_layer"}``."""
    man = manifest(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; the manifest has "
                         f"{sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in man["configs"]}
    cfg_entry = configs[cell["config"]]
    bench = root / "chipbench"
    return {
        "cell": cell,
        "config": load_json(root / cfg_entry["file"]),
        "traffic": load_json(bench / "traffic" / f"{cell['traffic']}.json"),
        "limits": load_json(bench / "limits" / f"{name}.json"),
        "end_to_end": [m for m in man["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": per_layer_for(man, name),
    }


def per_layer_for(man: Dict[str, Any], name: str) -> List[Dict]:
    """Per-layer metrics a cell reports: those that list it, and those
    without a list whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in man["end_to_end"]
           if name in m.get("workloads", [name])}
    return [m for m in man["per_layer"]
            if name in m.get("workloads", [name] if m["moves"] in e2e
                             else [])]


def load_module(path: Path, name: str):
    """Import a file of the benchmark by path (metric files carry dots
    in their names, so they are not importable as modules)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def check_keys(what: str, data: Dict[str, Any], keys) -> None:
    """Refuse a file whose keys are not the driver's ``keys`` and
    descriptive ones: a key nothing reads would look like a knob."""
    unread = set(data) - set(keys) - DESCRIPTIVE_KEYS
    missing = set(keys) - set(data)
    if unread or missing:
        raise ValueError(f"{what}: keys nothing reads {sorted(unread)}, "
                         f"keys missing {sorted(missing)}")


def reference(cfg: Dict[str, Any]):
    """The plain reference module that configuration file ``cfg`` names
    (``chipbench/references/<reference>.py``): its weights, its counts
    and the computation the program is compared with."""
    return importlib.import_module(
        f"chipbench.references.{cfg['reference']}")


def driver(kind: str, root: Path = ROOT):
    return load_module(root / "chipbench" / "drivers" / f"{kind}.py",
                       f"chipbench_driver_{kind}")


def metric_reader(name: str, root: Path = ROOT):
    return load_module(root / "chipbench" / "metrics" / f"{name}.py",
                       "chipbench_metric_" + name.replace(".", "_"))


def use_compile_cache() -> Path:
    """Point JAX's persistent compilation cache at ``.jax_cache/`` in
    the checkout, whatever the environment says, before JAX is
    imported; the program's own ``enable_compile_cache`` then takes
    this directory too."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    return CACHE_DIR


def check_devices(chips: int) -> Dict[str, Any]:
    """The device block of the result line; raises :class:`NoChip`
    unless JAX sees at least ``chips`` TPU chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX reports platform {devs[0].platform!r} with "
                     f"{len(devs)} device(s); the benchmark runs only "
                     f"on TPU chips")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX reports "
                     f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def peaks(kind: str, root: Path = ROOT) -> Dict[str, Any]:
    table = load_json(root / "chipbench" / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; "
                       f"chipbench/peaks.json has {sorted(table)}")
    return table[kind]


def memory_peak_bytes(devices) -> Optional[int]:
    """Peak bytes in use on the fullest of ``devices``, where the
    backend reports it."""
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in devices]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None


class CompileLog:
    """Backend compile seconds and persistent-cache hits, read from
    JAX's monitoring events (after ``chip_smoke.CompileLog``)."""

    def __init__(self):
        import jax
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

    def snapshot(self) -> Dict[str, Any]:
        return {"compile_s": self.seconds,
                "backend_compiles": self.compiles,
                "cache_hits": self.cache_hits}


def check_entry(value: float, limit: float) -> Dict[str, float]:
    return {"value": value, "limit": limit}


def checks_pass(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def print_checks(checks: Dict[str, Dict[str, float]]) -> None:
    """The compared numbers beside their limits, as the last lines on
    standard error."""
    for name, c in checks.items():
        verdict = "ok" if (math.isfinite(c["value"])
                           and c["value"] <= c["limit"]) else "FAIL"
        print(f"check {name} = {c['value']!r} limit {c['limit']!r} "
              f"{verdict}", file=sys.stderr, flush=True)


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Dict[str, Any]],
                device: Dict[str, Any],
                breakdown: Optional[Dict[str, Any]],
                checks: Dict[str, Dict[str, float]]) -> str:
    out: Dict[str, Any] = {"correct": bool(correct),
                           "attempted": int(attempted),
                           "failed": int(failed), "metrics": metrics,
                           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks             # last key, by the contract
    return json.dumps(out, allow_nan=True)
