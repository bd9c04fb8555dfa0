"""Ahead-of-time compiles for a described TPU v5e — no chip needed.

Interpret mode (every parity test in ``test_kernels.py``) cannot show
that Mosaic accepts a kernel's block tiling or VMEM use; compiling for
a described ``v5e:2x2`` topology can.  Each kernel of the main path is
compiled through the backend registry at ``seesaw-150m`` widths (SSD at
``mamba2-2.7b`` widths, the registry's SSM family), and the full-width
``seesaw-150m`` loss and grad must fit one chip's 16 GiB of HBM.

The topology is described inside a module fixture, never at import:
every pytest-xdist worker then collects the same tests, and only the
worker that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.seesaw_paper import SEESAW_150M
from repro.kernels import backend as KB
from repro.models import registry as R

HBM_BYTES = 16 * 2 ** 30        # one v5e chip
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a persistent-cache entry compiled for a described chip cannot be
    # read back without one: keep these compiles out of any cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *structs):
    return jax.jit(fn).lower(*structs).compile()


def _with_grads(fn, argnums):
    """Forward value plus grads, so the forward kernel and the custom-VJP
    backward kernels are all in one program."""
    return jax.value_and_grad(
        lambda *a: fn(*a).astype(jnp.float32).sum(), argnums=argnums)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("with_grads", [False, True],
                         ids=["fwd", "fwd_bwd"])
def test_flash_attention_compiles(one_chip, head_dim, with_grads):
    cfg = SEESAW_150M
    shape = (2, cfg.max_seq_len, cfg.n_heads, head_dim)
    q = jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)

    def attn(q, k, v):
        return KB.attention(q, k, v, causal=True, backend="pallas")

    fn = _with_grads(attn, (0, 1, 2)) if with_grads else attn
    _assert_kernel(_compile(fn, q, q, q))


@pytest.mark.parametrize("with_grads", [False, True],
                         ids=["fwd", "fwd_bwd"])
def test_rmsnorm_compiles(one_chip, with_grads):
    cfg = SEESAW_150M
    x = jax.ShapeDtypeStruct((16, cfg.max_seq_len, cfg.d_model), BF16,
                             sharding=one_chip)
    s = jax.ShapeDtypeStruct((cfg.d_model,), jnp.float32,
                             sharding=one_chip)

    def norm(x, s):
        return KB.rmsnorm(x, s, backend="pallas")

    fn = _with_grads(norm, (0, 1)) if with_grads else norm
    _assert_kernel(_compile(fn, x, s))


def test_paged_decode_compiles(one_chip):
    cfg = SEESAW_150M
    B, Skv = 8, 544        # decode slots x (512-token prompt + 32 new)

    def sds(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q = sds((B, 1, cfg.n_heads, cfg.head_dim))
    kv = sds((B, Skv, cfg.n_kv_heads, cfg.head_dim))
    lengths = sds((B,), jnp.int32)

    def decode(q, k, v, lengths):
        return KB.paged_decode_attention(q, k, v, lengths,
                                         backend="pallas")

    _assert_kernel(_compile(decode, q, kv, kv, lengths))


@pytest.mark.parametrize("with_grads", [False, True],
                         ids=["fwd", "fwd_bwd"])
def test_ssd_compiles(one_chip, with_grads):
    cfg = get_config("mamba2-2.7b")
    s = cfg.ssm
    H = s.n_ssm_heads(cfg.d_model)

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    B, S = 1, 1024
    args = (sds((B, S, H, s.head_dim), BF16), sds((B, S, H)), sds((H,)),
            sds((B, S, s.d_state), BF16), sds((B, S, s.d_state), BF16),
            sds((H,)))

    def ssd(*a):
        return KB.ssd(*a, chunk=s.chunk_size, backend="pallas")[0]

    fn = _with_grads(ssd, (0, 1, 3, 4)) if with_grads else ssd
    _assert_kernel(_compile(fn, *args))


def test_seesaw_150m_loss_and_grad_fit_one_chip(one_chip):
    """The full-width step at micro-batch 16 (the smoke run's
    ``max_device_batch``): parameters, batch, grads and temporaries
    together fit one chip."""
    cfg = SEESAW_150M
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=one_chip),
        jax.eval_shape(lambda: R.init_params(jax.random.PRNGKey(0), cfg)))
    tok = jax.ShapeDtypeStruct((16, cfg.max_seq_len), jnp.int32,
                               sharding=one_chip)
    batch = {"tokens": tok, "labels": tok}

    def loss(p, b):
        return R.loss_fn(p, cfg, b, dtype=BF16, remat=True)[0]

    mem = _compile(jax.value_and_grad(loss), params, batch) \
        .memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 2 ** 30:.2f} GiB"
