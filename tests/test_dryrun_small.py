"""Distribution tests: the exact dry-run machinery (build_workload →
jit(in_shardings).lower().compile()) on an 8-device host mesh, run in a
subprocess so the main test process keeps its single-device world."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
import jax
import jax.numpy as jnp
from repro.configs import get_config
from repro.configs.base import InputShape
from repro.launch import steps as ST
from repro.launch.mesh import make_test_mesh

arch, mode, multipod = sys.argv[1], sys.argv[2], sys.argv[3] == "1"
cfg = get_config(arch).reduced()
shape = {
    "train":   InputShape("t", 64, 8, "train"),
    "prefill": InputShape("p", 64, 8, "prefill"),
    "decode":  InputShape("d", 64, 8, "decode"),
}[mode]
mesh = make_test_mesh(2, 2, multi_pod=multipod)
fn, args, in_specs, out_specs = ST.build_workload(
    cfg, shape, multi_pod=multipod)
with jax.set_mesh(mesh):
    in_sh = ST._named(mesh, in_specs)
    out_sh = ST._named(mesh, out_specs)
    lowered = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh).lower(*args)
    compiled = lowered.compile()
ca = compiled.cost_analysis()
print(json.dumps({"ok": True, "flops": float(ca.get("flops", -1))}))
"""


def _run(arch, mode, multipod=False):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    # forced host devices are a CPU feature; without the pin jax
    # probes for a TPU backend ~5 min per subprocess on this image
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT, arch, mode, "1" if multipod else "0"],
        capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok"]
    return rec


@pytest.mark.parametrize("arch", ["llama3.2-3b", "phi3.5-moe-42b-a6.6b",
                                  "mamba2-2.7b", "recurrentgemma-9b",
                                  "seamless-m4t-medium"])
def test_train_lowers_and_compiles(arch):
    _run(arch, "train")


@pytest.mark.parametrize("arch", ["llama3.2-3b", "mamba2-2.7b"])
def test_decode_lowers_and_compiles(arch):
    _run(arch, "decode")


def test_prefill_lowers_and_compiles():
    _run("starcoder2-3b", "prefill")


def test_multipod_mesh_lowers():
    _run("llama3.2-3b", "train", multipod=True)


def test_shape_support_table():
    """long_500k is only for sub-quadratic archs (DESIGN.md §6)."""
    from repro.configs import INPUT_SHAPES, get_config
    from repro.launch.steps import shape_supported
    long = INPUT_SHAPES["long_500k"]
    ok, _ = shape_supported(get_config("mamba2-2.7b"), long)
    assert ok
    ok, why = shape_supported(get_config("yi-34b"), long)
    assert not ok and "full-attention" in why
    ok, _ = shape_supported(get_config("starcoder2-3b"), long)
    assert ok  # native sliding window
