"""Plain reference of the OLMo-style dense decoder and its Adam step.

Written from the architecture's description, in float32 with every
matrix product at ``Precision.HIGHEST``, and importing nothing of the
program:

    x = embed[tokens]
    per layer:  h = rms(x) * (1 + g1);  x += attn(h)
                h = rms(x) * (1 + g2);  x += W_down (silu(h W_gate) * h W_up)
    logits = (rms(x) * (1 + g_f)) W_head      (logical vocabulary only)

attention is causal multi-head softmax attention with rotary positions
applied to the two halves of each head (the GPT-NeoX layout); the loss
is the token mean of the cross-entropy; Adam clips the global gradient
norm, keeps bias-corrected moments, and takes the learning rate of the
linear warm-up over tokens seen before the step.

``mode="int8"`` is the control: the same arithmetic with every matrix
product (forward and backward) taken on int8 operands with one
symmetric absmax scale per tensor and int32 accumulation.

A configuration names its reference module (``"reference"``); the
harness takes from it the random weights (:func:`init_tree`, in the
layout the program's ``dense`` transformer trains), the counts of
parameters and required FLOPs (:mod:`chipbench.flops`'s dense decoder),
and the optimizer step it compares with.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from chipbench.flops import (causal_core_per_token, param_count,  # noqa: F401
                             train_flops_per_token)

HIGHEST = jax.lax.Precision.HIGHEST


def scope_work(m: Dict, seq_len: int, itemsize: int
               ) -> Dict[str, Dict[str, float]]:
    """Required work per token by the program's scope that does it
    (:mod:`chipbench.flops`): the blocked causal core,
    ``causal_blocks``."""
    return {"causal_blocks": causal_core_per_token(m, seq_len, itemsize)}


# --------------------------------------------------------------------- #
# random weights
# --------------------------------------------------------------------- #

def padded_vocab(m: Dict) -> int:
    return -(-m["vocab_size"] // 128) * 128


def init_tree(key, m: Dict, std: float, dtype=jnp.float32):
    """Weights stacked on a leading axis of ``n_layers``: truncated
    normals of std ``std``, output projections scaled by ``1 / sqrt(2 *
    n_layers)``, norm gains 0 (the gain is ``1 + scale``)."""
    L, d = m["n_layers"], m["d_model"]
    q = m["n_heads"] * m["head_dim"]
    kv = m["n_kv_heads"] * m["head_dim"]
    f, V = m["d_ff"], padded_vocab(m)
    out_std = std / math.sqrt(2 * L)
    ks = iter(jax.random.split(key, 9))

    def tn(shape, s):
        return (s * jax.random.truncated_normal(
            next(ks), -3.0, 3.0, shape, jnp.float32)).astype(dtype)

    embed = {"tok": tn((V, d), std)}
    if not m["tie_embeddings"]:
        embed["lm_head"] = tn((d, V), std)
    return {
        "embed": embed,
        "layers": {
            "attn": {"w_q": tn((L, d, q), std), "w_k": tn((L, d, kv), std),
                     "w_v": tn((L, d, kv), std),
                     "w_o": tn((L, q, d), out_std)},
            "norm1": jnp.zeros((L, d), dtype),
            "norm2": jnp.zeros((L, d), dtype),
            "mlp": {"w_gate": tn((L, d, f), std), "w_up": tn((L, d, f), std),
                    "w_down": tn((L, f, d), out_std)},
        },
        "final_norm": jnp.zeros((d,), dtype),
    }


# --------------------------------------------------------------------- #
# matrix products: float32 at HIGHEST, or the int8 control
# --------------------------------------------------------------------- #

def _f32_mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _quant(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8), s


def _int8_raw(a, b):
    qa, sa = _quant(a)
    qb, sb = _quant(b)
    dims = (((a.ndim - 1,), (b.ndim - 2,)),
            (tuple(range(a.ndim - 2)), tuple(range(b.ndim - 2))))
    out = jax.lax.dot_general(qa, qb, dims,
                              preferred_element_type=jnp.int32)
    return out.astype(jnp.float32) * (sa * sb)


@jax.custom_vjp
def _int8_bmm(a, b):
    """(..., m, k) x (..., k, n) with equal leading dims, in int8."""
    return _int8_raw(a, b)


def _bmm_fwd(a, b):
    return _int8_raw(a, b), (a, b)


def _bmm_bwd(res, g):
    a, b = res
    return (_int8_raw(g, jnp.swapaxes(b, -1, -2)),
            _int8_raw(jnp.swapaxes(a, -1, -2), g))


_int8_bmm.defvjp(_bmm_fwd, _bmm_bwd)


def _int8_wmm_raw(x, w):
    return _int8_raw(x.reshape(-1, x.shape[-1]), w).reshape(
        *x.shape[:-1], w.shape[-1])


@jax.custom_vjp
def _int8_wmm(x, w):
    """(..., k) x (k, n) in int8."""
    return _int8_wmm_raw(x, w)


def _wmm_fwd(x, w):
    return _int8_wmm_raw(x, w), (x, w)


def _wmm_bwd(res, g):
    x, w = res
    x2 = x.reshape(-1, x.shape[-1])
    g2 = g.reshape(-1, g.shape[-1])
    return (_int8_raw(g2, w.T).reshape(x.shape), _int8_raw(x2.T, g2))


_int8_wmm.defvjp(_wmm_fwd, _wmm_bwd)


def products(mode: str):
    """``(wmm, bmm)``: activation x weight, and batched activation
    products."""
    if mode == "f32":
        return _f32_mm, _f32_mm
    if mode == "int8":
        return _int8_wmm, _int8_bmm
    raise ValueError(f"unknown reference mode {mode!r}")


# --------------------------------------------------------------------- #
# the model
# --------------------------------------------------------------------- #

def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g)


def _rope(x, theta):
    """x: (B, S, H, hd) — rotate the two halves of each head."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs  # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(x, p, m, wmm, bmm):
    B, S, _ = x.shape
    H, Hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps = m["norm_eps"]
    a = p["attn"]
    h = _rms(x, p["norm1"], eps)
    q = _rope(wmm(h, a["w_q"]).reshape(B, S, H, hd), m["rope_theta"])
    k = _rope(wmm(h, a["w_k"]).reshape(B, S, Hkv, hd), m["rope_theta"])
    v = wmm(h, a["w_v"]).reshape(B, S, Hkv, hd)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    qh, kh, vh = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))  # (B,H,S,hd)
    s = bmm(qh, jnp.swapaxes(kh, -1, -2)) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = bmm(jax.nn.softmax(s, axis=-1), vh)                  # (B,H,S,hd)
    o = jnp.swapaxes(o, 1, 2).reshape(B, S, H * hd)
    x = x + wmm(o, a["w_o"])
    f = p["mlp"]
    h = _rms(x, p["norm2"], eps)
    x = x + wmm(jax.nn.silu(wmm(h, f["w_gate"])) * wmm(h, f["w_up"]),
                f["w_down"])
    return x


def _trunk(params, tokens, m, wmm, bmm):
    x = params["embed"]["tok"][tokens]

    @jax.checkpoint
    def body(x, p):
        return _layer(x, p, m, wmm, bmm), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return _rms(x, params["final_norm"], m["norm_eps"])


def loss(params, tokens, labels, m, mode: str = "f32"):
    """Mean token cross-entropy of a block of rows."""
    wmm, bmm = products(mode)
    h = _trunk(params, tokens, m, wmm, bmm)
    head = params["embed"].get("lm_head")
    if head is None:
        head = params["embed"]["tok"].T
    lg = wmm(h, head)[..., :m["vocab_size"]]
    lse = jax.nn.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


# --------------------------------------------------------------------- #
# one optimizer step over a whole global batch, in blocks of rows
# --------------------------------------------------------------------- #

def grads(params, tokens, labels, m, mode: str, block: int):
    """Mean loss and mean gradient over all rows, ``block`` rows at a
    time."""
    n = tokens.shape[0] // block
    # block i holds rows i, i + n, i + 2n, ...: rows laid out in
    # contiguous per-device shares give every device a part of each block
    tb = jnp.swapaxes(tokens.reshape(block, n, -1), 0, 1)
    lb = jnp.swapaxes(labels.reshape(block, n, -1), 0, 1)
    vg = jax.value_and_grad(lambda p, t, y: loss(p, t, y, m, mode))

    def body(acc, xs):
        lv, g = vg(params, *xs)
        return (acc[0] + lv, jax.tree.map(jnp.add, acc[1], g)), None

    zero = (jnp.float32(0), jax.tree.map(jnp.zeros_like, params))
    (tot, g), _ = jax.lax.scan(body, zero, (tb, lb))
    return tot / n, jax.tree.map(lambda x: x / n, g)


def clip(g, max_norm: float):
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda x: x * scale, g)


def adam(params, mom, vel, g, count: int, lr: float, opt: Dict):
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["eps"]
    mom = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, mom, g)
    vel = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, vel, g)
    bc1, bc2 = 1 - b1 ** count, 1 - b2 ** count
    wd = opt["weight_decay"]
    params = jax.tree.map(
        lambda p, a, b: p - lr * ((a / bc1) / (jnp.sqrt(b / bc2) + eps)
                                  + wd * p), params, mom, vel)
    return params, mom, vel


def warmup_lr(step: int, sched: Dict, tokens_per_step: int,
              n_params: int) -> float:
    """The learning rate of 0-based ``step`` inside the warm-up: base
    times the tokens seen before the step over the warm-up's tokens."""
    total = sched["total_tokens_per_param"] * n_params
    warm = sched["warmup_frac"] * total
    seen = step * tokens_per_step
    if seen >= warm:
        raise ValueError("the reference covers the warm-up only")
    return sched["base_lr"] * seen / max(warm, 1.0)
