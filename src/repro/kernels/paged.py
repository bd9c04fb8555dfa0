"""Ragged decode attention as a Pallas TPU kernel — the serving-side
counterpart of ``flash_attention``.

One query token per sequence (the token just written at position
``lengths[b]``) attends over a gathered page window k/v whose slot ``s``
holds absolute position ``s``.  Grid is (batch·heads, k-blocks) with the
online-softmax running stats (m, l, acc) in VMEM scratch across the
k iterations, exactly like the flash forward; GQA is folded into the K/V
BlockSpec index map (query head h reads kv head h // group).  The
query and output rows are ``(1, 1, hd)`` blocks of ``(B·H, 1, hd)``
arrays (full-width last two dims, as Mosaic's tiling requires) and the
running stats are ``(1, 1)``.  Per-request lengths are scalar-prefetched
into SMEM — the mask ``kpos <= lengths[b]`` implements the
repo's zero-padding convention: page remainders, stale slots from evicted
requests, and block padding all live at positions the causal reach never
touches, so they contribute exactly zero.

Fully-masked k-blocks (``ki·block_k > lengths[b]``) are skipped via
``pl.when`` — a request early in its decode reads only the pages it has
actually filled.  Decode is inference-only, so there is no backward.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ragged_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, block_k: int, n_k: int, n_heads: int,
                   scale: float):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ln = len_ref[pl.program_id(0) // n_heads]       # this request's length

    def compute():
        q = q_ref[0].astype(jnp.float32)            # (1, hd)
        k = k_ref[0].astype(jnp.float32)            # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = q @ k.T * scale                         # (1, bk)
        kpos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        s = jnp.where(kpos <= ln, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)   # (1, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * corr + p @ v

    # a k-block is fully masked iff its first key position exceeds the
    # request's causal reach (position `ln` holds the newest token)
    pl.when(ki * block_k <= ln)(compute)

    @pl.when(ki == n_k - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def ragged_decode_attention(q, k, v, lengths, *, block_k: int = 128,
                            interpret: bool = False):
    """q: (B, H, hd) single-token queries at per-request positions
    ``lengths``; k, v: (B, Hkv, Skv, hd) with H % Hkv == 0; lengths:
    (B,) int32 — valid keys for request b are slots 0..lengths[b]
    inclusive.  Returns (B, H, hd).  Skv is padded here to a block_k
    multiple (padding positions are always beyond every causal reach)."""
    B, H, hd = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    if Hkv < 1 or H % Hkv:
        raise ValueError(
            f"ragged_decode_attention: n_heads={H} not a multiple of "
            f"n_kv_heads={Hkv}")
    G = H // Hkv
    block_k = min(block_k, Skv)
    pad = (-Skv) % block_k
    if pad:
        cfgp = ((0, 0), (0, 0), (0, pad), (0, 0))
        k = jnp.pad(k, cfgp)
        v = jnp.pad(v, cfgp)
        Skv += pad
    n_k = Skv // block_k
    scale = 1.0 / math.sqrt(hd)

    qf = q.reshape(B * H, 1, hd)
    kf = k.reshape(B * Hkv, Skv, hd)
    vf = v.reshape(B * Hkv, Skv, hd)
    lens = jnp.reshape(lengths, (B,)).astype(jnp.int32)

    # index maps receive the scalar-prefetched lengths as a last arg
    def kv_index(bh, ki, _lens):
        b = bh // H
        hkv = (bh % H) // G
        return (b * Hkv + hkv, ki, 0)

    def row_index(bh, ki, _lens):
        return (bh, 0, 0)

    out = pl.pallas_call(
        functools.partial(_ragged_kernel, block_k=block_k, n_k=n_k,
                          n_heads=H, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * H, n_k),
            in_specs=[
                pl.BlockSpec((1, 1, hd), row_index),
                pl.BlockSpec((1, block_k, hd), kv_index),
                pl.BlockSpec((1, block_k, hd), kv_index),
            ],
            out_specs=pl.BlockSpec((1, 1, hd), row_index),
            scratch_shapes=[
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B * H, 1, hd), q.dtype),
        interpret=interpret,
    )(lens, qf, kf, vf)
    return out.reshape(B, H, hd)
