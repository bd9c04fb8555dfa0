"""Distributed numerical correctness: the pjit-sharded loss/grads on an
8-device host mesh equal the single-device computation — run through
the shared subprocess runner (JAX_PLATFORMS=cpu pinned; without the pin
each subprocess stalls ~5 min probing for a TPU backend)."""
import pytest

pytestmark = [pytest.mark.slow, pytest.mark.subprocess]

SCRIPT = r"""
import json, sys
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import registry as R
from repro.launch.mesh import make_test_mesh

arch = sys.argv[1]
cfg = get_config(arch).reduced()
params = R.init_params(jax.random.PRNGKey(0), cfg)
batch = R.concrete_inputs(cfg, "train", 8, 64)

def loss_of(p, b):
    return R.loss_fn(p, cfg, b, remat=True, dtype=jnp.float32)

# single device reference
(loss_ref, _), grads_ref = jax.value_and_grad(loss_of, has_aux=True)(
    params, batch)

# sharded: params sharded per param_specs, batch over data
mesh = make_test_mesh(2, 2)
pspec = R.param_specs(cfg)
with jax.set_mesh(mesh):
    p_sh = jax.device_put(params, jax.tree.map(
        lambda s: NamedSharding(mesh, s), pspec,
        is_leaf=lambda x: isinstance(x, P)))
    b_sh = jax.device_put(batch, NamedSharding(mesh, P("data")))
    f = jax.jit(jax.value_and_grad(loss_of, has_aux=True))
    (loss_sh, _), grads_sh = f(p_sh, b_sh)

err_loss = abs(float(loss_ref) - float(loss_sh))
gerr = max(float(jnp.max(jnp.abs(a - b)))
           for a, b in zip(jax.tree.leaves(grads_ref),
                           jax.tree.leaves(grads_sh)))
print(json.dumps({"loss_err": err_loss, "grad_err": gerr,
                  "loss": float(loss_ref)}))
"""


@pytest.mark.parametrize("arch", ["llama3.2-3b", "granite-moe-1b-a400m",
                                  "mamba2-2.7b"])
def test_sharded_loss_and_grads_match_single_device(arch,
                                                    run_subprocess):
    rec = run_subprocess(SCRIPT, arch, devices=8)
    assert rec["loss_err"] < 1e-4, rec
    assert rec["grad_err"] < 5e-3, rec
