"""GQA attention on the XLA path, RoPE, sliding windows, full and ring
KV caches.

Two cores:

- ``causal_attention``: causal self-attention from position 0 (training
  and prefill).  Query blocks attend to their key prefix only, so the
  blocks above the diagonal are never computed, and a custom VJP keeps
  only ``q, k, v, o`` and the row log-sum-exp for the backward, which
  recomputes the probabilities block by block: no S x S tensor exists
  in either pass.
- ``chunked_attention``: online softmax over kv chunks in a
  ``lax.scan``, for everything else (decode at scalar or per-request
  offsets, ring caches, sliding windows, non-causal encoders).

The Pallas kernel in ``repro.kernels.flash_attention`` is the fused
TPU version of the first.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import backend as KB
from repro.models.layers import apply_rope, dense_init

Params = Dict[str, Any]

NEG_INF = -1e30


def init_attention(key, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, n_layers_scale: int,
                   stack: Tuple[int, ...] = ()) -> Params:
    kq, kk, kv, ko = jax.random.split(key, 4)
    out_std = 0.02 / math.sqrt(2 * max(n_layers_scale, 1))
    return {
        "w_q": dense_init(kq, d_model, n_heads * head_dim, std=0.02,
                          stack=stack),
        "w_k": dense_init(kk, d_model, n_kv_heads * head_dim, std=0.02,
                          stack=stack),
        "w_v": dense_init(kv, d_model, n_kv_heads * head_dim, std=0.02,
                          stack=stack),
        "w_o": dense_init(ko, n_heads * head_dim, d_model, std=out_std,
                          stack=stack),
    }


def attention_specs(fsdp, lead: Tuple = ()) -> Params:
    return {"w_q": P(*lead, fsdp, "model"),
            "w_k": P(*lead, fsdp, "model"),
            "w_v": P(*lead, fsdp, "model"),
            "w_o": P(*lead, "model", fsdp)}


# --------------------------------------------------------------------- #
# core chunked attention
# --------------------------------------------------------------------- #

def _mask(qpos, kpos, *, causal: bool, window: Optional[int], kv_len=None):
    """(..., Sq, Sk) boolean mask from absolute positions."""
    q = qpos[..., :, None]
    k = kpos[..., None, :]
    m = k >= 0
    if causal:
        m &= k <= q
    if window is not None:
        m &= k > q - window
    if kv_len is not None:
        m &= k < kv_len
    return m


def _attend(q, k, v, qpos, kpos, *, causal, window, kv_len, scale):
    """One (q-block × kv-block) attention with GQA grouping.

    q: (B, Sq, H, hd); k,v: (B, Sk, Hkv, hd).
    Returns un-normalized (o, m, l) online-softmax stats.
    """
    B, Sq, H, hd = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Sq, Hkv, G, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = _mask(qpos, kpos, causal=causal, window=window, kv_len=kv_len)
    s = jnp.where(mask[:, None, None] if mask.ndim == 3 else mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)                              # (B,Hkv,G,Sq)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", p, v.astype(jnp.float32))
    return o, m, l


def chunked_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                      kv_len=None, kpos=None, chunk=1024):
    """Online-softmax attention, scanning kv chunks.

    q: (B, Sq, H, hd); k,v: (B, Sk, Hkv, hd).
    q_offset: absolute position of q[0] (traced ok).  kpos: optional
    explicit absolute positions of keys (B-independent, (Sk,)) — used by
    ring caches; defaults to arange(Sk).
    """
    B, Sq, H, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    scale = 1.0 / math.sqrt(hd)
    qpos = q_offset + jnp.arange(Sq)
    if kpos is None:
        kpos = jnp.arange(Sk)

    chunk = min(chunk, Sk)
    if Sk % chunk != 0:  # pad keys to a chunk multiple with invalid slots
        pad = chunk - Sk % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kpos = jnp.concatenate([kpos, jnp.full((pad,), -1, kpos.dtype)])
        Sk += pad
    n_kv = Sk // chunk

    ks = jnp.moveaxis(k.reshape(B, n_kv, chunk, Hkv, hd), 1, 0)
    vs = jnp.moveaxis(v.reshape(B, n_kv, chunk, Hkv, hd), 1, 0)
    kps = kpos.reshape(n_kv, chunk)

    G = H // Hkv
    acc0 = jnp.zeros((B, Hkv, G, Sq, hd), jnp.float32)
    m0 = jnp.full((B, Hkv, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)

    def body(carry, xs):
        acc, m, l = carry
        kc, vc, kp = xs
        o_c, m_c, l_c = _attend(q, kc, vc, qpos, kp, causal=causal,
                                window=window, kv_len=kv_len, scale=scale)
        m_new = jnp.maximum(m, m_c)
        corr = jnp.exp(m - m_new)
        corr_c = jnp.exp(m_c - m_new)
        acc = acc * corr[..., None] + o_c * corr_c[..., None]
        l = l * corr + l_c * corr_c
        return (acc, m_new, l), None

    # flash-attention-style backward: recompute the (Sq × chunk) score/
    # prob blocks instead of saving one per chunk iteration — the scan's
    # saved residuals were the dominant per-device temp (e.g. 17 GB of
    # f32 p-blocks for recurrentgemma train_4k)
    body = jax.checkpoint(body)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), (ks, vs, kps))
    out = acc / jnp.maximum(l, 1e-30)[..., None]          # (B,Hkv,G,Sq,hd)
    out = jnp.moveaxis(out, 3, 1).reshape(B, Sq, H, hd)
    return out.astype(q.dtype)


# --------------------------------------------------------------------- #
# causal blocks with a saved-statistics backward
# --------------------------------------------------------------------- #

def _block(S: int) -> int:
    """Query-block length for a sequence of S: 256 rows (one block when
    S is shorter), widened so that no sequence has more than 32 blocks
    (the blocks unroll into the program)."""
    if S <= 256:
        return S
    return max(256, 128 * -(-S // (32 * 128)))


def causal_attention(q, k, v):
    """Causal self-attention from position 0: q (B, S, H, hd), k/v
    (B, S, Hkv, hd) → (B, S, H, hd).

    Query block i (``_block(S)`` rows) attends to keys
    ``[0, (i+1)·blk)`` with a full softmax over that prefix; only its
    diagonal block is masked, and the blocks above the diagonal are
    never computed.  Scores, probabilities and the statistics are
    float32; ``QKᵀ`` takes the inputs as they are with a float32
    result.  A ragged S is zero-padded to a block multiple: the padded
    keys sit above every real query's causal horizon, and the padded
    query rows are sliced off."""
    S = q.shape[1]
    blk = _block(S)
    pad = (-S) % blk
    if pad:
        cfg = ((0, 0), (0, pad), (0, 0), (0, 0))
        q, k, v = (jnp.pad(x, cfg) for x in (q, k, v))
    with jax.named_scope("causal_blocks"):
        o = _causal_blocks(q, k, v, blk)
    return o[:, :S] if pad else o


def _scores(qi, k, i: int, blk: int, scale: float):
    """Masked f32 scores (B, Hkv, G, blk, e) of query block i, qi
    (B, blk, Hkv, G, hd), against its key prefix k (B, e, Hkv, hd)."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qi, k,
                   preferred_element_type=jnp.float32) * scale
    qpos = i * blk + jnp.arange(blk)
    kpos = jnp.arange(k.shape[1])
    return jnp.where(kpos[None, :] <= qpos[:, None], s, NEG_INF)


def _split(x, Hkv: int):
    """(B, S, H, hd) → (B, S, Hkv, G, hd)."""
    B, S, H, hd = x.shape
    return x.reshape(B, S, Hkv, H // Hkv, hd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _causal_blocks(q, k, v, blk: int):
    return _causal_fwd(q, k, v, blk)[0]


def _causal_fwd(q, k, v, blk: int):
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    scale = 1.0 / math.sqrt(hd)
    qg = _split(q, Hkv)
    outs, lses = [], []
    for i in range(S // blk):
        e = (i + 1) * blk
        s = _scores(qg[:, i * blk:e], k[:, :e], i, blk, scale)
        m = jnp.max(s, axis=-1)                          # (B,Hkv,G,blk)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p,
                       v[:, :e].astype(jnp.float32))
        outs.append(o / jnp.moveaxis(l, 3, 1)[..., None])
        lses.append(m + jnp.log(l))
    o = jnp.concatenate(outs, axis=1).reshape(B, S, H, hd)
    lse = jnp.concatenate(lses, axis=-1)                 # (B,Hkv,G,S)
    return o.astype(q.dtype), (q, k, v, o, lse)


def _causal_bwd(blk: int, res, do):
    q, k, v, o, lse = res
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    scale = 1.0 / math.sqrt(hd)
    qg = _split(q, Hkv)
    dog = _split(do.astype(jnp.float32), Hkv)
    # Δ = rowsum(dO ∘ O), once: (B,Hkv,G,S)
    delta = jnp.moveaxis(jnp.sum(dog * _split(o, Hkv), axis=-1), 1, 3)
    n = S // blk
    dqs, dks, dvs = [], [], []
    for i in range(n):
        e = (i + 1) * blk
        rows = slice(i * blk, e)
        qi, doi = qg[:, rows], dog[:, rows]
        kp = k[:, :e].astype(jnp.float32)
        s = _scores(qi, k[:, :e], i, blk, scale)
        p = jnp.exp(s - lse[..., rows, None])
        dvs.append(jnp.einsum("bhgqk,bqhgd->bkhd", p, doi))
        dp = jnp.einsum("bqhgd,bkhd->bhgqk", doi,
                        v[:, :e].astype(jnp.float32))
        ds = p * (dp - delta[..., rows, None])
        dqs.append(jnp.einsum("bhgqk,bkhd->bqhgd", ds, kp) * scale)
        dks.append(jnp.einsum("bhgqk,bqhgd->bkhd", ds,
                              qi.astype(jnp.float32)) * scale)

    def by_key_block(parts):
        # key block j gathers the contributions of query blocks i >= j
        return jnp.concatenate(
            [sum(parts[i][:, j * blk:(j + 1) * blk] for i in range(j, n))
             for j in range(n)], axis=1)

    dq = jnp.concatenate(dqs, axis=1).reshape(B, S, H, hd)
    return (dq.astype(q.dtype), by_key_block(dks).astype(k.dtype),
            by_key_block(dvs).astype(v.dtype))


_causal_blocks.defvjp(_causal_fwd, _causal_bwd)


# --------------------------------------------------------------------- #
# attention block (projections + rope + cache plumbing)
# --------------------------------------------------------------------- #

def attn_forward(params: Params, x, *, n_heads: int, n_kv_heads: int,
                 head_dim: int, rope_theta: float, causal: bool = True,
                 window: Optional[int] = None, positions=None,
                 chunk: int = 1024, backend: str = "xla"):
    """Training/prefill self-attention over x: (B, S, d).

    Causal attention without a window goes through the kernel registry
    (``backend``; its ``xla`` entry is ``causal_attention``); windowed
    and non-causal layers take the chunked scan."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q = (x @ params["w_q"].astype(x.dtype)).reshape(B, S, n_heads, head_dim)
    k = (x @ params["w_k"].astype(x.dtype)).reshape(B, S, n_kv_heads,
                                                    head_dim)
    v = (x @ params["w_v"].astype(x.dtype)).reshape(B, S, n_kv_heads,
                                                    head_dim)
    if rope_theta:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    if window is None and causal:
        o = KB.attention(q, k, v, causal=True, backend=backend)
    else:
        o = chunked_attention(q, k, v, causal=causal, window=window,
                              chunk=chunk)
    o = o.reshape(B, S, n_heads * head_dim)
    out = o @ params["w_o"].astype(x.dtype)
    return out, (k, v)


def init_cache(batch: int, max_len: int, n_kv_heads: int, head_dim: int,
               dtype=jnp.bfloat16) -> Params:
    """Full (non-ring) KV cache."""
    return {
        "k": jnp.zeros((batch, max_len, n_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, n_kv_heads, head_dim), dtype),
    }


def init_ring_cache(batch: int, window: int, n_kv_heads: int, head_dim: int,
                    dtype=jnp.bfloat16) -> Params:
    return {
        "k": jnp.zeros((batch, window, n_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, window, n_kv_heads, head_dim), dtype),
        "pos": jnp.full((window,), -1, jnp.int32),
    }


def ring_from_prefill(k, v, S: int, W: int, dtype=None) -> Params:
    """Build a modular-layout ring cache of capacity W from prefill K/V
    of length S (position p lives at slot p % W, so decode's
    ``slot = pos % W`` overwrites exactly the expired entry)."""
    dtype = dtype or k.dtype
    if S >= W:
        idx = (jnp.arange(W) - S) % W          # slot j ← k_last[idx[j]]
        pos = S - W + idx
        k_ring = k[:, -W:][:, idx]
        v_ring = v[:, -W:][:, idx]
    else:
        pad = W - S
        k_ring = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_ring = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pos = jnp.concatenate([jnp.arange(S, dtype=jnp.int32),
                               jnp.full((pad,), -1, jnp.int32)])
    return {"k": k_ring.astype(dtype), "v": v_ring.astype(dtype),
            "pos": pos.astype(jnp.int32)}


def decode_attn(params: Params, x, cache: Params, cache_len, *,
                n_heads: int, n_kv_heads: int, head_dim: int,
                rope_theta: float, window: Optional[int] = None,
                chunk: int = 4096):
    """One-token decode: x (B, 1, d); cache holds ``cache_len`` valid
    entries (full cache) or is a ring buffer with a ``pos`` array.

    ``cache_len`` may be a scalar (all rows at the same depth — the
    training/eval decode path) or a (B,) int32 array of per-request
    depths (the serving path: one fixed-shape executable steps requests
    at ragged positions).  The ragged form supports the full cache only;
    ring caches share one ``pos`` array across the batch, so their
    depths cannot diverge.  Returns (out (B,1,d), new_cache)."""
    B = x.shape[0]
    pos = cache_len                             # scalar or (B,) int32
    ragged = jnp.ndim(pos) == 1
    q = (x @ params["w_q"].astype(x.dtype)).reshape(B, 1, n_heads, head_dim)
    k = (x @ params["w_k"].astype(x.dtype)).reshape(B, 1, n_kv_heads,
                                                    head_dim)
    v = (x @ params["w_v"].astype(x.dtype)).reshape(B, 1, n_kv_heads,
                                                    head_dim)
    if rope_theta:
        ppos = pos[:, None] if ragged else jnp.full((B, 1), pos)
        q = apply_rope(q, ppos, rope_theta)
        k = apply_rope(k, ppos, rope_theta)

    ring = "pos" in cache
    if ragged:
        if ring:
            raise ValueError(
                "per-request cache_len needs a full cache; ring caches "
                "share one position array across the batch")
        # scatter row b's token at its own depth, then mask per request:
        # the same promoted q_offset/kv_len arithmetic as the paged
        # backend, so dense-vs-paged decode is bitwise at equal width
        new_cache = {
            "k": cache["k"].at[jnp.arange(B), pos].set(
                k[:, 0].astype(cache["k"].dtype)),
            "v": cache["v"].at[jnp.arange(B), pos].set(
                v[:, 0].astype(cache["v"].dtype))}
        o = chunked_attention(q, new_cache["k"].astype(q.dtype),
                              new_cache["v"].astype(q.dtype), causal=True,
                              window=window, q_offset=pos[:, None],
                              kv_len=(pos + 1)[:, None, None], chunk=chunk)
        o = o.reshape(B, 1, n_heads * head_dim)
        return o @ params["w_o"].astype(x.dtype), new_cache
    if ring:
        W = cache["k"].shape[1]
        slot = jnp.mod(pos, W)
        new_k = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
        new_v = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
        new_pos = cache["pos"].at[slot].set(pos)
        new_cache = {"k": new_k, "v": new_v, "pos": new_pos}
        o = chunked_attention(q, new_k.astype(q.dtype),
                              new_v.astype(q.dtype), causal=True,
                              window=window, q_offset=pos,
                              kpos=new_pos, chunk=min(chunk, W))
    else:
        new_k = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), pos, axis=1)
        new_v = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), pos, axis=1)
        new_cache = {"k": new_k, "v": new_v}
        o = chunked_attention(q, new_k.astype(q.dtype),
                              new_v.astype(q.dtype), causal=True,
                              window=window, q_offset=pos,
                              kv_len=pos + 1, chunk=chunk)
    o = o.reshape(B, 1, n_heads * head_dim)
    return o @ params["w_o"].astype(x.dtype), new_cache
