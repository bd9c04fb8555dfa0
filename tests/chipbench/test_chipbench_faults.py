"""Whole runs of each cell at a size the CPU holds, the look for a chip
skipped: a sound run comes out correct, and each fault the cell can
have, planted in the timed path, comes out not correct under the cell's
own limits."""
import json
import os
import subprocess
import sys
import time

import pytest

import chipbench_tiny
from chipbench import common
from chipbench import run as R

TRAIN = "train-300m-b512-4x1"
TRAIN_CELLS = chipbench_tiny.train_cells()
SEED = 2 ** 31 + 101


def _run(cell, fault=None, chips=1):
    return R.execute(cell, SEED, 1.0, False, require_chip=False,
                     spec=chipbench_tiny.spec(cell, chips=chips),
                     fault=fault, t_proc=time.perf_counter())


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_train_sound_run_is_correct(cell):
    out = _run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 2 and out["failed"] == 0
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("cell", TRAIN_CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_fault_is_not_correct(fault, cell):
    out = _run(cell, fault)
    assert not out["correct"], out["checks"]


FOUR = """
import json, sys, time
sys.path[:0] = [{root!r}, {src!r}, {here!r}]
import chipbench_tiny
from chipbench import run as R
for fault in (None, "no_exchange"):
    out = R.execute("train-300m-b512-4x1", {seed}, 1.0, False,
                    require_chip=False, fault=fault,
                    spec=chipbench_tiny.spec("train-300m-b512-4x1", chips=4),
                    t_proc=time.perf_counter())
    print("FOUR", json.dumps({{"fault": fault, "correct": out["correct"],
                               "checks": out["checks"]}}), flush=True)
"""


def test_four_chips_without_the_exchange_is_not_correct():
    here = os.path.dirname(os.path.abspath(__file__))
    code = FOUR.format(root=str(common.ROOT),
                       src=str(common.ROOT / "src"), here=here, seed=SEED)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = {r["fault"]: r for r in
           (json.loads(line[5:]) for line in p.stdout.splitlines()
            if line.startswith("FOUR "))}
    assert got[None]["correct"], got[None]["checks"]
    assert not got["no_exchange"]["correct"], got["no_exchange"]["checks"]
