#!/usr/bin/env python3
"""Run one cell of the benchmark once, in this process.

    python3 chipbench/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and limits are read from
``BENCHMARK.json`` and the files it names; the window loop is
``chipbench/drivers/<kind>.py`` for the traffic's ``kind``, and each
per-layer metric is read by ``chipbench/metrics/<name>.py``.  Without a
TPU, or with fewer chips than the cell asks for, it exits 3 and prints
no result.  The last line of standard output is the result object; the
numbers compared for ``correct`` end standard error.
"""
from __future__ import annotations

import time

T_PROC = time.perf_counter()          # process start, for setup_s

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import common  # noqa: E402


class Tracer:
    """The profiler around the measured window (``--trace 1``); a no-op
    otherwise.  The trace is read and deleted at the end of the run."""

    def __init__(self, on: bool, directory: Path = common.TRACE_DIR):
        self.on = on
        self.dir = directory

    def start(self):
        if self.on:
            import jax
            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # no per-call Python events
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)

    def stop(self):
        if self.on:
            import jax
            jax.profiler.stop_trace()

    def reduce(self):
        from chipbench import program_trace, trace
        tr = program_trace.load(trace.find_xplane(self.dir))
        shutil.rmtree(self.dir, ignore_errors=True)
        return program_trace.reduce_window(tr)


def execute(name: str, seed: int, seconds: float, traced: bool, *,
            root: Path = ROOT, require_chip: bool = True,
            fault: str = None, spec: dict = None,
            t_proc: float = T_PROC, extra: dict = None,
            trace_dir: Path = common.TRACE_DIR) -> dict:
    """Run the cell and return the result object.  ``spec`` (tests)
    replaces what :func:`common.load_cell` reads; ``require_chip=False``
    (tests) skips the look for TPU chips; ``extra`` is merged into the
    driver's context and receives its ``calibration`` readings
    (``calibrate.py``) and the readers' ``layer``."""
    import jax
    spec = spec or common.load_cell(name, root)
    chips = spec["cell"]["chips"]
    if require_chip:
        device = common.check_devices(chips)
    else:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": chips}
    if require_chip:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log = common.CompileLog()
    drv = common.driver(spec["traffic"]["kind"], root)
    common.check_keys(f"configuration of {name}", spec["config"],
                      drv.CONFIG_KEYS)
    common.check_keys(f"traffic of {name}", spec["traffic"],
                      drv.TRAFFIC_KEYS)
    tracer = Tracer(traced, trace_dir)
    ctx = {"config": spec["config"], "traffic": spec["traffic"],
           "limits": spec["limits"], "chips": chips, "seed": seed,
           "seconds": seconds, "t_proc": t_proc, "tracer": tracer,
           "fault": fault, **(extra or {})}
    rec = drv.run(ctx)
    if extra is not None:
        extra["calibration"] = ctx.get("calibration") or {}
        extra["layer"] = rec["layer"]
    print(json.dumps({"setup": log.snapshot(),
                      "notes": rec.get("notes", {})}, default=str),
          flush=True)
    device["memory_peak_bytes"] = rec["memory_peak_bytes"]
    breakdown = None
    if traced:
        from chipbench import program_trace
        layer = rec["layer"]
        red = layer["trace"] = tracer.reduce()
        layer["peaks"] = (common.peaks(device["kind"], root)
                          if device["platform"] == "tpu" else None)
        top = red["top_ops"]
        if "op_names" in layer:
            layer["scopes"] = program_trace.scope_table(red["program"],
                                                        layer["op_names"])
            top = [(program_trace.op_label(n, layer["op_names"]), t)
                   for n, t in top]
            print(json.dumps(program_trace.summary(layer)), flush=True)
        metrics = {}
        for m in spec["per_layer"]:
            v = common.metric_reader(m["name"], root).read(layer)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": [list(x) for x in top],
                     "idle_gaps": [list(x) for x in red["idle_gaps"]]}
    else:
        values = dict(rec["end_to_end"], setup_s=rec["setup_s"])
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    common.print_checks(rec["checks"])
    return json.loads(common.result_line(
        correct=rec["correct"], attempted=rec["attempted"],
        failed=rec["failed"], metrics=metrics, device=device,
        breakdown=breakdown, checks=rec["checks"]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    common.use_compile_cache()
    try:
        out = execute(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    except common.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
