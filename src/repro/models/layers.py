"""Shared building blocks: RMSNorm, RoPE, SwiGLU/GeLU MLPs, initializers,
sharding helpers.  Pure-functional: params are pytrees of jnp arrays."""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import backend as KB

Params = Dict[str, Any]

# --------------------------------------------------------------------- #
# sharding helpers
# --------------------------------------------------------------------- #

def fsdp_axis(multi_pod: bool):
    """The axis (or axes) weights/batches are FSDP/data sharded over."""
    return ("pod", "data") if multi_pod else "data"


def constrain(x, spec: P):
    """``with_sharding_constraint`` against the context mesh
    (``jax.set_mesh``).  Skipped only when no mesh is set — the
    single-device path; on a mesh every error surfaces."""
    if jax.sharding.get_abstract_mesh().empty:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def residual_spec(batch_axes, seq_len: int) -> P:
    """Sharding for the residual stream between blocks.  Sequence
    parallelism (Megatron-SP): shard the seq dim over 'model' so the
    per-layer saved activations (what jax.checkpoint keeps for backward)
    are 16× smaller; XLA inserts the all-gather before attention and the
    reduce-scatter after the output projection automatically."""
    if seq_len % 16 == 0:
        return P(batch_axes, "model", None)
    return P(batch_axes, None, None)


# --------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------- #

def trunc_normal(key, shape, std=0.02, dtype=jnp.float32):
    return std * jax.random.truncated_normal(key, -3.0, 3.0, shape, dtype)


def dense_init(key, d_in: int, d_out: int, *, std: Optional[float] = None,
               stack: Tuple[int, ...] = ()):
    std = std if std is not None else 1.0 / math.sqrt(d_in)
    return trunc_normal(key, (*stack, d_in, d_out), std=std)


# --------------------------------------------------------------------- #
# norms / activations
# --------------------------------------------------------------------- #

def rmsnorm(x, scale, eps: float = 1e-5, backend: str = "xla"):
    """Delegates to the kernel backend registry; the ``xla`` entry is
    ``kernels.ref.rmsnorm_ref`` — the single RMSNorm source of truth."""
    return KB.rmsnorm(x, scale, eps=eps, backend=backend)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[name]


# --------------------------------------------------------------------- #
# RoPE
# --------------------------------------------------------------------- #

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (...,S,hd/2)
    angles = angles[..., None, :]                       # (...,S,1,hd/2)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------- #
# MLP
# --------------------------------------------------------------------- #

def init_mlp(key, d_model: int, d_ff: int, act: str, n_layers_scale: int,
             stack: Tuple[int, ...] = ()) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    out_std = 0.02 / math.sqrt(2 * max(n_layers_scale, 1))
    p = {"w_up": dense_init(k2, d_model, d_ff, std=0.02, stack=stack),
         "w_down": dense_init(k3, d_ff, d_model, std=out_std, stack=stack)}
    if act == "silu":  # SwiGLU
        p["w_gate"] = dense_init(k1, d_model, d_ff, std=0.02, stack=stack)
    return p


def mlp(params: Params, x, act: str):
    up = x @ params["w_up"].astype(x.dtype)
    if "w_gate" in params:
        gate = x @ params["w_gate"].astype(x.dtype)
        h = act_fn(act)(gate) * up
    else:
        h = act_fn(act)(up)
    return h @ params["w_down"].astype(x.dtype)


def mlp_specs(act: str, fsdp, lead: Tuple = ()) -> Params:
    base = {"w_up": P(*lead, fsdp, "model"),
            "w_down": P(*lead, "model", fsdp)}
    if act == "silu":
        base["w_gate"] = P(*lead, fsdp, "model")
    return base


# --------------------------------------------------------------------- #
# embeddings / lm head
# --------------------------------------------------------------------- #

def init_embed(key, vocab: int, d_model: int, tie: bool) -> Params:
    k1, k2 = jax.random.split(key)
    p = {"tok": trunc_normal(k1, (vocab, d_model), std=0.02)}
    if not tie:
        p["lm_head"] = trunc_normal(k2, (d_model, vocab), std=0.02)
    return p


def embed_specs(tie: bool, fsdp) -> Params:
    p = {"tok": P("model", fsdp)}
    if not tie:
        p["lm_head"] = P(fsdp, "model")
    return p


def lm_head_matrix(embed_params: Params):
    if "lm_head" in embed_params:
        return embed_params["lm_head"]
    return embed_params["tok"].T


def cross_entropy_chunked(h, embed_params: Params, labels, mask,
                          logical_vocab: int, *, z_loss: float = 0.0,
                          chunk: int = 512, logits_spec: Optional[P] = None):
    """Loss over (B,S,d) hiddens vs (B,S) labels without materializing
    (B,S,V) logits: lax.scan over sequence chunks, each chunk's f32
    logits sharded over 'model' on the vocab dim (a 256k vocab chunk
    would otherwise be 8+ GB/device) and rematted in backward.
    Returns (loss, z_sq) token-means in f32."""
    B, S, d = h.shape
    W = lm_head_matrix(embed_params)
    V = W.shape[-1]
    chunk = min(chunk, S)
    n_chunks = S // chunk
    rem = S - n_chunks * chunk
    vocab_ok = (jnp.arange(V) < logical_vocab)

    def chunk_loss(hc, yc, mc):
        logits = (hc @ W.astype(hc.dtype)).astype(jnp.float32)
        if logits_spec is not None:
            logits = constrain(logits, logits_spec)
        logits = jnp.where(vocab_ok, logits, -1e30)
        z = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        nll = (z - ll) * mc
        return jnp.sum(nll), jnp.sum(jnp.square(z) * mc), jnp.sum(mc)

    def body(carry, xs):
        hc, yc, mc = xs
        l, zs, n = chunk_loss(hc, yc, mc)
        return (carry[0] + l, carry[1] + zs, carry[2] + n), None

    body = jax.checkpoint(body)   # recompute chunk logits in backward

    hs = h[:, :n_chunks * chunk].reshape(B, n_chunks, chunk, d)
    ys = labels[:, :n_chunks * chunk].reshape(B, n_chunks, chunk)
    ms = mask[:, :n_chunks * chunk].reshape(B, n_chunks, chunk)
    xs = (jnp.moveaxis(hs, 1, 0), jnp.moveaxis(ys, 1, 0),
          jnp.moveaxis(ms, 1, 0))
    (tot, z_sq, n_tok), _ = jax.lax.scan(body, (0.0, 0.0, 0.0), xs)
    if rem:
        l, zs, n = chunk_loss(h[:, -rem:], labels[:, -rem:], mask[:, -rem:])
        tot, z_sq, n_tok = tot + l, z_sq + zs, n_tok + n
    n_tok = jnp.maximum(n_tok, 1.0)
    loss = tot / n_tok
    if z_loss:
        loss = loss + z_loss * z_sq / n_tok
    return loss, z_sq / n_tok
