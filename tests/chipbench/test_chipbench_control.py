"""The control — the plain reference computed in int8, put in the
program's place — comes out not correct under the cell's own limits,
while the program in the same run comes out correct.  At sizes the CPU
holds; on the chip the same readings are taken at the cell's size by
``chipbench/calibrate.py`` (PERF.md §6)."""
import time

import pytest

import chipbench_tiny
from chipbench import common
from chipbench import run as R

SEED = 2 ** 31 + 202


def _calibrate(cell, spec):
    extra = {"calibrate": True}
    out = R.execute(cell, SEED, 1.0, False, require_chip=False,
                    spec=spec, t_proc=time.perf_counter(), extra=extra)
    return out, extra["calibration"]


def _fails(numbers, limits):
    return any(v > limits[k] for k, v in numbers.items())


TRAIN_CELLS = chipbench_tiny.train_cells()


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_train_control_and_faults_fail_the_limits(cell):
    """On the CPU's one device; the exchange between chips is left out
    only where the cell as committed has more than one chip."""
    chips = common.load_cell(cell)["cell"]["chips"]
    spec = chipbench_tiny.spec(cell, chips=1)
    out, cal = _calibrate(cell, spec)
    assert out["correct"], out["checks"]
    for name in ("control", "half_batch") + (
            ("no_exchange",) if chips > 1 else ()):
        assert _fails(cal[name], spec["limits"]), (name, cal[name])

