"""Flash attention as a Pallas TPU kernel, with a custom-VJP backward.

Forward grid: (batch·heads, q-blocks, k-blocks) — k is the innermost
(fastest) grid dim, so the online-softmax running stats (m, l, acc) live
in VMEM scratch across k iterations; block shapes are MXU-aligned (128
where the sequence allows).  GQA is handled in the K/V BlockSpec
index_map (query head h reads kv head h // group) — no materialized
repeat.  Alongside the output the forward emits the log-sum-exp rows
``lse = m + log(l)`` that the backward needs to rebuild probabilities.

Backward is the standard flash recompute scheme — no (S, S) tensor is
ever materialized:

- ``dq`` kernel, grid (B·H, q-blocks, k-blocks) with a (bq, hd) VMEM
  accumulator: p = exp(s − lse); ds = p·(do·vᵀ − Δ)·scale; dq += ds·k,
  where Δ = rowsum(do ⊙ o) is computed once in XLA.
- ``dk/dv`` kernel, grid (B·H, k-blocks, q-blocks) with (bk, hd)
  accumulators: dv += pᵀ·do and dk += dsᵀ·q.  GQA runs this at full
  query-head resolution, then the per-group sum reduces (B, Hkv, G, …)
  → (B, Hkv, …) in XLA.

Causal masking skips fully-masked blocks via pl.when in both passes.

Layout for Mosaic's (8, 128) tiling: the per-row statistics (lse, Δ)
live in HBM as lane-dense rows ``(B·H, 1, S)`` read and written in
``(1, 1, block)`` blocks.  Inside the kernels the online-softmax stats
are ``(bq, 1)`` columns; :func:`_col_to_row` / :func:`_row_to_col`
convert between the two with an exact masked reduction (one nonzero
per output element), so no in-kernel transpose is needed.  The dk/dv
kernel works in the transposed frame (``sᵀ = k·qᵀ``), where the row
stats broadcast directly.

VMEM budget per step: q(bq·hd) + k,v(bk·hd) + acc(bq·hd) + s(bq·bk) +
the (bq, bq) conversion mask, all f32 ⇒ with bq=bk=128, hd=128:
~0.5 MB, well inside the 16 MiB default scoped VMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _col_to_row(col):
    """(n, 1) → (1, n) as a masked sublane sum (exact)."""
    n = col.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _row_to_col(row):
    """(1, n) → (n, 1) as a masked lane sum (exact)."""
    n = row.shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _validate_blocks(S: int, block_q: int, block_k: int):
    """Raise a clear error for block/sequence mismatches instead of an
    opaque Pallas lowering failure (empty or out-of-range grid)."""
    if S < 1:
        raise ValueError(f"flash_attention: sequence length {S} < 1")
    if block_q < 1 or block_k < 1:
        raise ValueError(
            f"flash_attention: block sizes must be >= 1, got "
            f"block_q={block_q}, block_k={block_k}")
    if S % block_q or S % block_k:
        raise ValueError(
            f"flash_attention: sequence length {S} is not a multiple of "
            f"block_q={block_q} / block_k={block_k}; pick blocks that "
            f"divide the sequence (or pad it — "
            f"repro.kernels.backend.attention pads causal sequences "
            f"automatically)")


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                  acc_scr, *, sm_scale: float, block_q: int, block_k: int,
                  n_k: int, causal: bool):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def compute():
        q = q_ref[0].astype(jnp.float32)            # (bq, hd)
        k = k_ref[0].astype(jnp.float32)            # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = q @ k.T * sm_scale                      # (bq, bk)
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_scr[...]                         # (bq, 1)
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * corr + p @ v

    if causal:
        # a k-block is fully masked iff its first key position exceeds
        # the last query position of this q-block
        pl.when(ki * block_k <= qi * block_q + block_q - 1)(compute)
    else:
        compute()

    @pl.when(ki == n_k - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0] = _col_to_row(m_scr[...] + jnp.log(l))


def _kv_index(H: int, Hkv: int, G: int):
    """Index map for K/V operands: the GQA head fold plus the kv-block
    index, which is the LAST grid argument (ki is innermost in the
    forward/dq grids; the dkv call site reorders its args to match)."""
    def kv_index(bh, i, j):
        b = bh // H
        hkv = (bh % H) // G
        return (b * Hkv + hkv, j, 0)
    return kv_index


def _flash_fwd(q, k, v, *, causal: bool, block_q: int, block_k: int,
               interpret: bool):
    """Returns (out (B,H,S,hd), lse (B·H, 1, S) f32)."""
    B, H, S, hd = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    sm_scale = 1.0 / math.sqrt(hd)
    n_q, n_k = S // block_q, S // block_k

    qf = q.reshape(B * H, S, hd)
    kf = k.reshape(B * Hkv, S, hd)
    vf = v.reshape(B * Hkv, S, hd)
    kv_index = _kv_index(H, Hkv, G)

    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, block_q=block_q,
        block_k=block_k, n_k=n_k, causal=causal)

    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, hd), kv_index),
            pl.BlockSpec((1, block_k, hd), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, qi, ki: (bh, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, S), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, hd), lse


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr, *, sm_scale: float, block_q: int,
                   block_k: int, n_k: int, causal: bool):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def compute():
        q = q_ref[0].astype(jnp.float32)            # (bq, hd)
        k = k_ref[0].astype(jnp.float32)            # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)          # (bq, hd)
        lse = _row_to_col(lse_ref[0])               # (bq, 1) f32
        delta = _row_to_col(delta_ref[0])           # (bq, 1) f32
        s = q @ k.T * sm_scale
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        p = jnp.exp(s - lse)                        # masked entries → 0
        dp = do @ v.T                               # (bq, bk)
        ds = p * (dp - delta) * sm_scale
        dq_scr[...] += ds @ k

    if causal:
        pl.when(ki * block_k <= qi * block_q + block_q - 1)(compute)
    else:
        compute()

    @pl.when(ki == n_k - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale: float,
                    block_q: int, block_k: int, n_q: int, causal: bool):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def compute():
        q = q_ref[0].astype(jnp.float32)            # (bq, hd)
        k = k_ref[0].astype(jnp.float32)            # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)          # (bq, hd)
        lse = lse_ref[0]                            # (1, bq) f32
        delta = delta_ref[0]
        st = k @ q.T * sm_scale                     # sᵀ: (bk, bq)
        if causal:
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            st = jnp.where(kpos <= qpos, st, NEG_INF)
        pt = jnp.exp(st - lse)                      # pᵀ
        dv_scr[...] += pt @ do                      # (bk, hd)
        dpt = v @ do.T                              # dpᵀ: (bk, bq)
        dst = pt * (dpt - delta) * sm_scale
        dk_scr[...] += dst @ q

    if causal:
        # a q-block contributes iff its last query can see this k-block
        pl.when(qi * block_q + block_q - 1 >= ki * block_k)(compute)
    else:
        compute()

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, do, *, causal: bool, block_q: int,
               block_k: int, interpret: bool):
    B, H, S, hd = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    sm_scale = 1.0 / math.sqrt(hd)
    n_q, n_k = S // block_q, S // block_k

    qf = q.reshape(B * H, S, hd)
    kf = k.reshape(B * Hkv, S, hd)
    vf = v.reshape(B * Hkv, S, hd)
    dof = do.reshape(B * H, S, hd)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(B * H, 1, S)
    kv_index = _kv_index(H, Hkv, G)

    q_spec = pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0))
    row_spec = pl.BlockSpec((1, 1, block_q),
                            lambda bh, qi, ki: (bh, 0, qi))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k, n_k=n_k,
                          causal=causal),
        grid=(B * H, n_q, n_k),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, block_k, hd), kv_index),
            pl.BlockSpec((1, block_k, hd), kv_index),
            q_spec,
            row_spec,
            row_spec,
        ],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)

    # dk/dv grid iterates q innermost; q-indexed operands read block qi
    # (grid position 2), kv-indexed operands block ki (position 1)
    qT_spec = pl.BlockSpec((1, block_q, hd), lambda bh, ki, qi: (bh, qi, 0))
    rowT_spec = pl.BlockSpec((1, 1, block_q),
                             lambda bh, ki, qi: (bh, 0, qi))
    kvT_index = _kv_index(H, Hkv, G)
    k_spec = pl.BlockSpec((1, block_k, hd),
                          lambda bh, ki, qi: kvT_index(bh, qi, ki))
    dkv_spec = pl.BlockSpec((1, block_k, hd), lambda bh, ki, qi: (bh, ki, 0))

    dk_h, dv_h = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k, n_q=n_q,
                          causal=causal),
        grid=(B * H, n_k, n_q),
        in_specs=[qT_spec, k_spec, k_spec, qT_spec, rowT_spec, rowT_spec],
        out_specs=[dkv_spec, dkv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, hd), jnp.float32),
            jax.ShapeDtypeStruct((B * H, S, hd), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, hd), jnp.float32),
            pltpu.VMEM((block_k, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)

    # GQA: per-query-head dk/dv reduce over the group in XLA
    dk = dk_h.reshape(B, Hkv, G, S, hd).sum(axis=2).astype(k.dtype)
    dv = dv_h.reshape(B, Hkv, G, S, hd).sum(axis=2).astype(v.dtype)
    return dq.reshape(B, H, S, hd), dk, dv


@functools.lru_cache(maxsize=None)
def _flash_with_vjp(causal: bool, block_q: int, block_k: int,
                    interpret: bool):
    """custom_vjp flash attention specialized on the static config; the
    lru_cache keeps the jit cache keyed on one stable callable per
    (causal, blocks, interpret) combination."""

    @jax.custom_vjp
    def attn(q, k, v):
        out, _ = _flash_fwd(q, k, v, causal=causal, block_q=block_q,
                            block_k=block_k, interpret=interpret)
        return out

    def fwd(q, k, v):
        out, lse = _flash_fwd(q, k, v, causal=causal, block_q=block_q,
                              block_k=block_k, interpret=interpret)
        return out, (q, k, v, out, lse)

    def bwd(res, do):
        q, k, v, out, lse = res
        return _flash_bwd(q, k, v, out, lse, do, causal=causal,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret)

    attn.defvjp(fwd, bwd)
    return attn


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q: (B, H, S, hd); k, v: (B, Hkv, S, hd) with H % Hkv == 0.
    Returns (B, H, S, hd).  Differentiable (custom-VJP flash backward);
    the sequence must be a multiple of both block sizes — the backend
    registry (repro.kernels.backend.attention) pads causal sequences
    automatically."""
    B, H, S, hd = q.shape
    Hkv = k.shape[1]
    if Hkv < 1 or H % Hkv:
        raise ValueError(
            f"flash_attention: n_heads={H} not a multiple of "
            f"n_kv_heads={Hkv}")
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    _validate_blocks(S, block_q, block_k)
    return _flash_with_vjp(bool(causal), int(block_q), int(block_k),
                           bool(interpret))(q, k, v)
