"""Cells of the benchmark cut to a size the CPU holds: the same files'
keys with tiny widths, for the tests that drive whole runs."""
from __future__ import annotations

import copy
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "chipbench"

TINY_MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4,
              "n_kv_heads": 4, "head_dim": 16, "d_ff": 128,
              "vocab_size": 256, "max_seq_len": 64}


def _load(path):
    return json.loads(Path(path).read_text())


def spec(cell: str, chips: int = None) -> dict:
    """The cell as ``common.load_cell`` reads it, with its model and
    traffic shrunk; limits and everything else as committed."""
    from chipbench import common
    s = common.load_cell(cell)
    s = copy.deepcopy(s)
    s["config"]["model"].update(TINY_MODEL)
    s["traffic"].update(global_batch=16, seq_len=32, max_device_batch=2,
                        reference_block_rows=4)
    if chips is not None:
        s["cell"] = dict(s["cell"], chips=chips)
    return s


def train_cells() -> list:
    """The manifest's training cells."""
    from chipbench import common
    return [w["name"] for w in common.manifest()["workloads"]
            if common.load_cell(w["name"])["traffic"]["kind"] == "train"]
