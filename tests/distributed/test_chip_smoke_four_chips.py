"""``chip_smoke.py --four-chips`` rehearsed on four host devices: the
4x1 data-parallel Seesaw run through the engine's mesh context and the
same run on device 0 agree within the script's loss band (reduced
``seesaw-150m`` widths)."""
import os

import pytest

pytestmark = [pytest.mark.slow, pytest.mark.subprocess]

SCRIPT = r"""
import importlib.util, json, os, sys
spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(sys.argv[1], "chip_smoke.py"))
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
from repro.configs.seesaw_paper import SEESAW_150M

cs.phase_four_chips(SEESAW_150M.reduced(), 64, cs.CompileLog())
print(json.dumps({"ok": True}))
"""


ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_four_chip_phase_on_host_devices(run_subprocess):
    assert run_subprocess(SCRIPT, ROOT, devices=4)["ok"]
