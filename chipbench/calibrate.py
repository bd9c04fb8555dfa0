#!/usr/bin/env python3
"""Readings that set a cell's limits (not part of a benchmark run).

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        [--control-seeds 1,2,3] [--seconds 5]

For every seed, one run of the cell in this process (a short window:
the readings come from the checked work, not the window), printing one
JSON line with the numbers the comparison computes for the program; on
the control seeds also the control's numbers (the reference in int8 in
the program's place) and, for training, those of planted faults.  The
limits in ``chipbench/limits/<cell>.json`` are set from these lines:
above the program's largest reading and below the control's smallest.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import common  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    common.use_compile_cache()
    import time

    from chipbench import run as R
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    spec = common.load_cell(args.workload)
    for seed in sorted(set(seeds) | controls):
        ctx_extra = {"calibrate": seed in controls}
        out = R.execute(args.workload, seed, args.seconds, False,
                        spec=spec, t_proc=time.perf_counter(),
                        extra=ctx_extra)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "program": {k: v["value"] for k, v in
                                      out["checks"].items()},
                          **ctx_extra["calibration"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
