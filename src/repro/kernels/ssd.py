"""Chunked Mamba-2 SSD as a Pallas TPU kernel.

The hot part of SSD is the per-chunk quadratic form (masked C·Bᵀ kernel
against the chunk's values) plus the chunk-state contraction — both are
MXU matmuls over (Q × Q) and (Q × N) tiles.  The kernel computes, per
(batch, chunk, head) grid cell with everything VMEM-resident:

  y_intra[c]  = (CBᵀ ⊙ decay ⊙ dt) x[c]          (Q,P)
  S_chunk[c]  = Σ_j exp(T_c − cum_j) dt_j B_j ⊗ x_j   (N,P)
  T[c]        = Σ_j dt_j A                        scalar per head

The cheap cross-chunk recurrence (nc sequential steps on (N,P) states)
and the rank-1 inter-chunk correction stay in XLA — they are O(S·N·P)
vs the kernel's O(S·Q·(N+P)) and do not benefit from manual tiling.

VMEM per cell (Q=256, N=128, P=64, f32): x 64 KB + B/C 2·128 KB +
five (Q,Q) tiles (masks, decay, CB, M) 5·256 KB + outputs ~96 KB
⇒ < 2 MB, inside the 16 MiB default scoped VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_chunk_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, s_ref,
                      t_ref, *, n_heads: int):
    """Blocks: a (H,) in SMEM; x (Q,P); dt (1,Q) as a lane row; b, c
    (Q,N) shared by the chunk's heads; outputs y (Q,P), s (N,P),
    t (1,1).

    The chunk cumsum is a masked lane sum over a (Q, Q) tile, and the
    row↔column moves are masked sums over the identity — every step is
    a plain VPU reduction, so the kernel needs no in-kernel transpose
    or 1-D vector and stays exact in f32."""
    A = a_ref[pl.program_id(0) % n_heads]
    x = x_ref[0].astype(jnp.float32)                      # (Q,P)
    dt = dt_ref[0].astype(jnp.float32)                    # (1,Q)
    Bm = b_ref[0].astype(jnp.float32)                     # (Q,N)
    Cm = c_ref[0].astype(jnp.float32)

    Q = x.shape[0]
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = jj <= ii
    eye = jj == ii
    l = dt * A                                            # (1,Q)
    cum = jnp.sum(jnp.where(causal, l, 0.0), axis=1,
                  keepdims=True)                          # (Q,1) inclusive
    cum_row = jnp.sum(jnp.where(eye, cum, 0.0), axis=0,
                      keepdims=True)                      # (1,Q)
    dt_col = jnp.sum(jnp.where(eye, dt, 0.0), axis=1, keepdims=True)
    T = jnp.sum(l, axis=1, keepdims=True)                 # (1,1)

    decay = jnp.where(causal, jnp.exp(cum - cum_row), 0.0)  # (i,j)
    CB = Cm @ Bm.T                                        # (Q,Q) MXU
    M = CB * decay * dt
    y_ref[0] = (M @ x).astype(y_ref.dtype)                # (Q,P) MXU

    sdecay = jnp.exp(T - cum) * dt_col                    # (Q,1)
    s_ref[0] = ((Bm * sdecay).T @ x).astype(s_ref.dtype)
    t_ref[0] = T.astype(t_ref.dtype)


def ssd_chunk(xh, dt, A, Bm, Cm, *, chunk: int = 128,
              interpret: bool = False):
    """Intra-chunk SSD terms via Pallas.

    xh: (B,S,H,P); dt: (B,S,H) (post-softplus); A: (H,);
    Bm, Cm: (B,S,N).  S must be a multiple of ``chunk``.
    Returns (y_intra (B,S,H,P), states (B,nc,H,N,P), T (B,nc,H)).
    """
    B, S, H, Pd = xh.shape
    N = Bm.shape[-1]
    Q = chunk
    assert S % Q == 0
    nc = S // Q

    # layout: (B, nc, H, Q, ...) so each grid cell is one (b, c, h);
    # B and C are shared by the heads of a chunk, so their index map
    # reads block g // H instead of materializing H copies
    x_l = jnp.moveaxis(xh.reshape(B, nc, Q, H, Pd), 3, 2) \
        .reshape(B * nc * H, Q, Pd)
    dt_l = jnp.moveaxis(dt.reshape(B, nc, Q, H), 3, 2) \
        .reshape(B * nc * H, 1, Q)
    b_l = Bm.reshape(B * nc, Q, N)
    c_l = Cm.reshape(B * nc, Q, N)

    y, s, t = pl.pallas_call(
        functools.partial(_ssd_chunk_kernel, n_heads=H),
        grid=(B * nc * H,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, Q, Pd), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, 1, Q), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda g: (g // H, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda g: (g // H, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, Pd), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, N, Pd), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda g: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * nc * H, Q, Pd), jnp.float32),
            jax.ShapeDtypeStruct((B * nc * H, N, Pd), jnp.float32),
            jax.ShapeDtypeStruct((B * nc * H, 1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(A.astype(jnp.float32), x_l, dt_l, b_l, c_l)

    y = jnp.moveaxis(y.reshape(B, nc, H, Q, Pd), 2, 3).reshape(B, S, H, Pd)
    s = s.reshape(B, nc, H, N, Pd)
    t = t.reshape(B, nc, H)
    return y, s, t


def _ssd_forward(xh, dt, A, Bm, Cm, D, *, chunk: int = 128,
                 interpret: bool = False):
    """Full SSD output: Pallas intra-chunk terms + XLA cross-chunk scan.
    Mirrors models.mamba2.ssd_chunked (the oracle path)."""
    B, S, H, Pd = xh.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    S_orig = S
    if S % Q:
        pad = Q - S % Q
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
        S += pad
    nc = S // Q
    y_intra, states, T = ssd_chunk(xh, dt, A, Bm, Cm, chunk=Q,
                                   interpret=interpret)

    def body(h, xs):
        s_c, t_c = xs
        h_prev = h
        # states from the kernel are (N,P); carried state is (H,N,P)
        h = h * jnp.exp(t_c)[:, :, None, None] + s_c
        return h, h_prev

    h0 = jnp.zeros((B, H, N, Pd), jnp.float32)
    h_fin, h_prevs = jax.lax.scan(
        body, h0, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(T, 1, 0)))
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)                 # (B,nc,H,N,P)

    cum = jnp.cumsum(dt.astype(jnp.float32).reshape(B, nc, Q, H)
                     * A, axis=2)
    y_inter = jnp.einsum("bcin,bchnp,bcih->bcihp",
                         Cm.astype(jnp.float32).reshape(B, nc, Q, N),
                         h_prevs, jnp.exp(cum))
    y = y_intra.reshape(B, nc, Q, H, Pd) + y_inter
    y = y.reshape(B, S, H, Pd) \
        + D[None, None, :, None] * xh.astype(jnp.float32)
    return y[:, :S_orig].astype(xh.dtype), h_fin.swapaxes(-1, -2)


@functools.lru_cache(maxsize=None)
def _ssd_with_vjp(chunk: int, interpret: bool):
    """custom_vjp SSD: Pallas forward, XLA-recompute backward.

    The backward re-runs ``models.mamba2.ssd_chunked`` (the XLA oracle
    path, whose reverse ``lax.scan`` IS the state-gradient scan) under
    ``jax.vjp`` and pulls the cotangents through it — so the gradient
    through the Pallas backend is bitwise-equal to the XLA backend's,
    at the cost of one forward recompute (the standard flash-style
    trade: recompute beats materializing per-chunk residuals in HBM).
    """

    @jax.custom_vjp
    def ssd(xh, dt, A, Bm, Cm, D):
        return _ssd_forward(xh, dt, A, Bm, Cm, D, chunk=chunk,
                            interpret=interpret)

    def fwd(xh, dt, A, Bm, Cm, D):
        out = _ssd_forward(xh, dt, A, Bm, Cm, D, chunk=chunk,
                           interpret=interpret)
        return out, (xh, dt, A, Bm, Cm, D)

    def bwd(res, cts):
        from repro.models.mamba2 import ssd_chunked  # avoid import cycle
        _, pull = jax.vjp(
            lambda *a: ssd_chunked(*a, chunk=chunk), *res)
        return pull(cts)

    ssd.defvjp(fwd, bwd)
    return ssd


def ssd_full(xh, dt, A, Bm, Cm, D, *, chunk: int = 128,
             interpret: bool = False):
    """Differentiable full SSD (see ``_ssd_with_vjp``).  Same contract
    as ``models.mamba2.ssd_chunked`` with ``h0=None``:
    returns (y (B,S,H,P), h_final (B,H,P,N))."""
    return _ssd_with_vjp(int(chunk), bool(interpret))(
        xh, dt, A, Bm, Cm, D)
