"""XLA-path attention correctness: the causal blocks with their
saved-statistics backward, the chunked scan (causal, windows, GQA),
ring and full caches."""
import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ModelConfig
from repro.kernels import ref
from repro.models import attention as A
from repro.models import registry as R

KEY = jax.random.PRNGKey(3)


def _qkv(B, H, Hkv, S, hd):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd), jnp.float32)
    return q, k, v


def _ref(q, k, v, causal=True, window=None):
    """Oracle in (B,S,H,hd) layout with optional sliding window."""
    qq = q.transpose(0, 2, 1, 3)
    kk = k.transpose(0, 2, 1, 3)
    vv = v.transpose(0, 2, 1, 3)
    B, H, S, hd = qq.shape
    Hkv = kk.shape[1]
    G = H // Hkv
    kk = jnp.repeat(kk, G, axis=1)
    vv = jnp.repeat(vv, G, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", qq, kk) / np.sqrt(hd)
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    m = jnp.ones((S, S), bool)
    if causal:
        m &= kpos <= qpos
    if window is not None:
        m &= kpos > qpos - window
    s = jnp.where(m, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vv)
    return o.transpose(0, 2, 1, 3)


@pytest.mark.parametrize("chunk", [16, 64, 1024])
@pytest.mark.parametrize("causal", [True, False])
def test_chunked_matches_ref(chunk, causal):
    q, k, v = _qkv(2, 4, 2, 96, 32)
    out = A.chunked_attention(q, k, v, causal=causal, chunk=chunk)
    want = _ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("window", [8, 32, 1000])
def test_sliding_window(window):
    q, k, v = _qkv(1, 2, 1, 64, 16)
    out = A.chunked_attention(q, k, v, causal=True, window=window,
                              chunk=16)
    want = _ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def _close(got, want, tol):
    """Largest gap within ``tol`` of the larger of 1 and max |want|."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    scale = max(1.0, float(np.abs(want).max()))
    gap = float(np.abs(got - want).max())
    assert gap <= tol * scale, (gap, tol * scale)


# S: two blocks, one (shorter than a block), ragged (padded to two)
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("S", [512, 96, 300])
@pytest.mark.parametrize("G", [1, 2, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_causal_attention_matches_oracles(dtype, G, S, remat):
    """``causal_attention`` (custom VJP) against the f32 oracle ``_ref``
    and against autodiff of ``chunked_attention``, outputs and grads."""
    Hkv = 2
    q, k, v = _qkv(2, Hkv * G, Hkv, S, 16)
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    w = jax.random.normal(jax.random.PRNGKey(7), q.shape, jnp.float32)

    def value_and_grads(fn):
        if remat:
            fn = jax.checkpoint(fn)

        def loss(q, k, v):
            return jnp.sum(jnp.sin(fn(q, k, v).astype(jnp.float32)) * w)
        out = jax.jit(fn)(q, k, v)
        return out, jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    out, grads = value_and_grads(A.causal_attention)
    assert out.dtype == dtype
    assert [g.dtype for g in grads] == [dtype] * 3
    # bf16: the oracle in f32 on the same values within 2.5 bf16 ulps,
    # the chunked scan (same f32 arithmetic inside) within one
    bf16 = dtype == jnp.bfloat16
    for oracle, tol in (
            (lambda q, k, v: _ref(*(x.astype(jnp.float32)
                                    for x in (q, k, v))),
             1e-2 if bf16 else 1e-5),
            (lambda q, k, v: A.chunked_attention(q, k, v, causal=True),
             4e-3 if bf16 else 1e-5)):
        out_o, grads_o = value_and_grads(oracle)
        _close(out, out_o, tol)
        for g, g_o in zip(grads, grads_o):
            _close(g, g_o, tol)


TINY_1K = ModelConfig(name="tiny-1k", arch_type="dense", n_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                      d_ff=128, vocab_size=128, max_seq_len=1024,
                      rope_theta=1e4)


def _dense_grad(S=1024):
    params = R.init_params(jax.random.PRNGKey(0), TINY_1K)
    batch = R.concrete_inputs(TINY_1K, "train", 1, S)
    return (jax.grad(lambda p: R.loss_fn(p, TINY_1K, batch,
                                         remat=True)[0]), params)


def test_dense_step_holds_no_square_scores():
    """The lowered loss+grad of the dense family at S = 1024 has no
    tensor whose last two dims are both S."""
    grad, params = _dense_grad()
    text = jax.jit(grad).lower(params).as_text()
    assert not re.findall(r"tensor<(?:\d+x)*1024x1024x", text)
    assert "tensor<1x2x2x256x1024xf32>" in text     # the widest block


def test_dense_remat_grad_evaluates_softmax_twice():
    """Under the layer's remat, each query block's scores are
    normalised (``reduce_max``) twice, forward and recompute, and
    exponentiated once more, by the backward's P recompute."""
    grad, params = _dense_grad()
    eqns = []

    def walk(jaxpr):
        for e in jaxpr.eqns:
            eqns.append(e)
            for sub in jax.core.jaxprs_in_params(e.params):
                walk(sub)

    walk(jax.make_jaxpr(grad)(params).jaxpr)
    count = collections.Counter()
    for e in eqns:
        if e.primitive.name not in ("exp", "reduce_max"):
            continue
        shape = e.invars[0].aval.shape
        if len(shape) == 5:                # score blocks (B,Hkv,G,q,k)
            count[e.primitive.name, shape[-1]] += 1
    prefixes = {p for _, p in count}
    assert prefixes == {256, 512, 768, 1024}
    for p in prefixes:
        assert count["reduce_max", p] == 2
        assert count["exp", p] == 3


def test_ring_cache_decode_matches_full():
    """Ring-cache decode (windowed) ≡ full-cache decode with window mask,
    across a run of steps that wraps the ring."""
    B, H, Hkv, hd, W = 1, 2, 1, 16, 8
    params = A.init_attention(jax.random.PRNGKey(0), 32, H, Hkv, hd, 2)
    S0 = 12
    xs = jax.random.normal(jax.random.PRNGKey(1), (B, S0 + 6, 32),
                           jnp.float32)
    # full-forward oracle with window
    out_full, _ = A.attn_forward(params, xs, n_heads=H, n_kv_heads=Hkv,
                                 head_dim=hd, rope_theta=10.0,
                                 causal=True, window=W, chunk=8)
    # prefill S0 then decode 6 with the ring cache
    h_pre = xs[:, :S0]
    _, (k, v) = A.attn_forward(params, h_pre, n_heads=H, n_kv_heads=Hkv,
                               head_dim=hd, rope_theta=10.0, causal=True,
                               window=W, chunk=8)
    cache = A.ring_from_prefill(k, v, S0, W, dtype=jnp.float32)
    for t in range(6):
        o, cache = A.decode_attn(params, xs[:, S0 + t:S0 + t + 1], cache,
                                 jnp.asarray(S0 + t), n_heads=H,
                                 n_kv_heads=Hkv, head_dim=hd,
                                 rope_theta=10.0, window=W)
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(out_full[:, S0 + t:S0 + t + 1]),
            atol=1e-4, rtol=1e-4)


def test_full_cache_decode_matches_forward():
    B, H, Hkv, hd = 2, 4, 2, 16
    params = A.init_attention(jax.random.PRNGKey(0), 32, H, Hkv, hd, 2)
    S = 20
    xs = jax.random.normal(jax.random.PRNGKey(1), (B, S + 1, 32))
    out_full, _ = A.attn_forward(params, xs, n_heads=H, n_kv_heads=Hkv,
                                 head_dim=hd, rope_theta=100.0,
                                 causal=True, chunk=8)
    _, (k, v) = A.attn_forward(params, xs[:, :S], n_heads=H,
                               n_kv_heads=Hkv, head_dim=hd,
                               rope_theta=100.0, causal=True, chunk=8)
    pad = 8
    cache = {"k": jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
             "v": jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))}
    o, _ = A.decode_attn(params, xs[:, S:S + 1], cache, jnp.asarray(S),
                         n_heads=H, n_kv_heads=Hkv, head_dim=hd,
                         rope_theta=100.0)
    np.testing.assert_allclose(np.asarray(o),
                               np.asarray(out_full[:, S:S + 1]),
                               atol=1e-4, rtol=1e-4)
