"""Kernel backend registry: one switch for every hot-path op.

Routes attention, RMSNorm, and the SSD chunk scan through a selectable
backend:

  ``xla``              — the stock jnp/lax paths
                         (``models.attention.causal_attention``, or
                         ``chunked_attention`` when not causal,
                         ``ref.rmsnorm_ref``, ``models.mamba2.
                         ssd_chunked``); the default.
  ``pallas``           — the fused Pallas TPU kernels in this package,
                         compiled natively (TPU only).
  ``pallas_interpret`` — the same kernels under ``interpret=True``, so
                         the full training stack runs (and CI tests) on
                         CPU with identical kernel semantics.

The backend is threaded from ``ModelConfig.kernel_backend`` (or the
``--kernel-backend`` launcher flag via ``RunConfig``) down through the
model forward passes, so the fused K-step executable in
``train.engine`` compiles against the chosen kernels.  All Pallas ops
carry custom-VJP backwards (see flash_attention / rmsnorm / ssd), so
every backend is trainable, not just runnable.

Ops here take the MODELS' tensor layouts (attention: (B, S, H, hd)),
not the kernels' — the registry owns the transposes and the pad/slice
bookkeeping so call sites stay layout-agnostic.

The default can also be set process-wide with the
``REPRO_KERNEL_BACKEND`` env var (explicit arguments win).
"""
from __future__ import annotations

import os

import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import paged as _paged
from repro.kernels import ref as _ref
from repro.kernels import rmsnorm as _rn
from repro.kernels import ssd as _ssd

BACKENDS = ("xla", "pallas", "pallas_interpret")


def resolve(backend: str | None = None) -> str:
    """Resolve an explicit/env/default backend name, validating it."""
    if backend is None:
        backend = os.environ.get("REPRO_KERNEL_BACKEND") or "xla"
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown kernel backend {backend!r}; expected one of "
            f"{BACKENDS}")
    return backend


def _interp(backend: str) -> bool:
    return backend == "pallas_interpret"


def rmsnorm(x, scale, *, eps: float = 1e-5, backend: str | None = None,
            block_rows: int = 256):
    """x: (..., d); scale: (d,).  The ``xla`` entry is
    ``ref.rmsnorm_ref`` — the single source of truth that
    ``models.layers.rmsnorm`` also delegates to."""
    backend = resolve(backend)
    if backend == "xla":
        return _ref.rmsnorm_ref(x, scale, eps)
    return _rn.rmsnorm(x, scale, eps=eps, block_rows=block_rows,
                       interpret=_interp(backend))


def attention(q, k, v, *, causal: bool = True,
              backend: str | None = None, block_q: int = 128,
              block_k: int = 128):
    """Self-attention in the models' layout: q (B, S, H, hd),
    k/v (B, S, Hkv, hd) → (B, S, H, hd).

    The Pallas flash kernel needs S to divide the block sizes; causal
    sequences are zero-padded up to the next block multiple (padded
    keys sit at positions > every real query, so the causal mask zeroes
    them — outputs and gradients for real rows are unaffected, and the
    padded query rows are sliced off).  Non-causal ragged tails would
    attend to the padding, so they fall back to the XLA path instead.
    The ``xla`` entry is ``causal_attention`` (which pads the same way
    to its own block), or ``chunked_attention`` when not causal.
    """
    backend = resolve(backend)
    S = q.shape[1]
    if backend != "xla":
        bq, bk = min(block_q, S), min(block_k, S)
        pad = max((-S) % bq, (-S) % bk)
        # pad to a common multiple of both blocks (bq, bk are powers of
        # two in practice; lcm = max when one divides the other)
        Sp = S + pad
        while Sp % bq or Sp % bk:
            Sp += 1
        pad = Sp - S
        if pad and not causal:
            backend = "xla"  # padded keys would be attended to
        else:
            qt = jnp.swapaxes(q, 1, 2)
            kt = jnp.swapaxes(k, 1, 2)
            vt = jnp.swapaxes(v, 1, 2)
            if pad:
                cfg = ((0, 0), (0, 0), (0, pad), (0, 0))
                qt = jnp.pad(qt, cfg)
                kt = jnp.pad(kt, cfg)
                vt = jnp.pad(vt, cfg)
            out = _fa.flash_attention(
                qt, kt, vt, causal=causal, block_q=bq, block_k=bk,
                interpret=_interp(backend))
            if pad:
                out = out[:, :, :S]
            return jnp.swapaxes(out, 1, 2)
    from repro.models import attention as A  # import cycle
    if causal:
        return A.causal_attention(q, k, v)
    return A.chunked_attention(q, k, v, causal=False)


def paged_decode_attention(q, k, v, lengths, *, backend: str | None = None,
                           chunk: int = 4096, block_k: int = 128):
    """Ragged single-token decode attention over a gathered paged KV
    window (the serving hot path; see ``repro.serving.cache``).

    q: (B, 1, H, hd) — the new token's query, sitting at per-request
    absolute position ``lengths[b]``.  k, v: (B, Skv, Hkv, hd) gathered
    page windows whose slot ``s`` holds absolute position ``s``.  Valid
    keys for request b are slots 0..lengths[b] inclusive (slot
    ``lengths[b]`` is the token just written); everything later — page
    remainders, stale slots from evicted requests, zero padding — sits at
    positions beyond the causal reach and is masked by the same
    zero-padding convention as ``attention``, so it contributes exactly
    zero on every backend.  Returns (B, 1, H, hd)."""
    backend = resolve(backend)
    if backend == "xla":
        # the dense decode path's op, with the scalar offset/length
        # promoted to per-request arrays — identical arithmetic, so the
        # paged lookup is bitwise against a dense cache of equal width
        from repro.models.attention import chunked_attention  # import cycle
        return chunked_attention(
            q, k, v, causal=True, q_offset=lengths[:, None],
            kv_len=(lengths + 1)[:, None, None], chunk=chunk)
    out = _paged.ragged_decode_attention(
        jnp.swapaxes(q, 1, 2)[:, :, 0], jnp.swapaxes(k, 1, 2),
        jnp.swapaxes(v, 1, 2), lengths, block_k=block_k,
        interpret=_interp(backend))
    return out[:, None]


def ssd(xh, dt, A, Bm, Cm, D, *, chunk: int = 128,
        backend: str | None = None):
    """Full SSD scan: xh (B,S,H,P), dt (B,S,H), A (H,), Bm/Cm (B,S,N),
    D (H,) → (y (B,S,H,P), h_final (B,H,P,N)).  Same contract as
    ``models.mamba2.ssd_chunked`` with h0=None on every backend."""
    backend = resolve(backend)
    if backend == "xla":
        from repro.models.mamba2 import ssd_chunked  # import cycle
        return ssd_chunked(xh, dt, A, Bm, Cm, D, chunk=chunk)
    return _ssd.ssd_full(xh, dt, A, Bm, Cm, D, chunk=chunk,
                         interpret=_interp(backend))
