"""Dense / MoE / multimodal-prefix decoder-only transformer.

Layers are stacked (leading L dim) and executed with ``lax.scan`` so the
HLO stays one-layer-sized regardless of depth; ``jax.checkpoint`` wraps
the scanned body for training (remat).  Supports:

- GQA + RoPE + optional sliding window (starcoder2)
- MoE FFN (phi3.5, granite) with aux losses accumulated through the scan
- multimodal prefix embeddings (internvl2 VLM / seamless audio-as-prefix
  is handled by encdec.py; VLM uses this module)
- serve: ``prefill`` (build KV cache) and ``decode_step`` (one token)
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import moe as M
from repro.models.layers import (Params, constrain, cross_entropy_chunked,
                                 embed_specs, fsdp_axis, init_embed,
                                 init_mlp, mlp, mlp_specs, residual_spec,
                                 rmsnorm)


# --------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------- #

def init_layer_stack(key, cfg: ModelConfig) -> Params:
    L = cfg.n_layers
    ka, km, kn = jax.random.split(key, 3)
    p: Params = {
        "attn": A.init_attention(ka, cfg.d_model, cfg.n_heads,
                                 cfg.n_kv_heads, cfg.head_dim,
                                 cfg.n_layers, stack=(L,)),
        "norm1": jnp.zeros((L, cfg.d_model)),
        "norm2": jnp.zeros((L, cfg.d_model)),
    }
    if cfg.arch_type == "moe":
        p["moe"] = M.init_moe(km, cfg, stack=(L,))
    else:
        p["mlp"] = init_mlp(km, cfg.d_model, cfg.d_ff, cfg.act,
                            cfg.n_layers, stack=(L,))
    return p


def init_params(key, cfg: ModelConfig) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "embed": init_embed(k1, cfg.padded_vocab, cfg.d_model,
                            cfg.tie_embeddings),
        "layers": init_layer_stack(k2, cfg),
        "final_norm": jnp.zeros((cfg.d_model,)),
    }


def param_specs(cfg: ModelConfig, multi_pod: bool = False) -> Params:
    f = fsdp_axis(multi_pod)
    layers = {
        "attn": A.attention_specs(f, lead=(None,)),
        "norm1": P(None, None),
        "norm2": P(None, None),
    }
    if cfg.arch_type == "moe":
        layers["moe"] = M.moe_specs(f, lead=(None,))
    else:
        layers["mlp"] = mlp_specs(cfg.act, f, lead=(None,))
    return {
        "embed": embed_specs(cfg.tie_embeddings, f),
        "layers": layers,
        "final_norm": P(None),
    }


# --------------------------------------------------------------------- #
# forward (training / prefill trunk)
# --------------------------------------------------------------------- #

def _layer(pl: Params, x, cfg: ModelConfig, *, res_spec,
           chunk: int = 1024):
    batch_axes = res_spec[0] if isinstance(res_spec, P) else None
    kb = cfg.kernel_backend
    h = rmsnorm(x, pl["norm1"], cfg.norm_eps, backend=kb)
    with jax.named_scope("attention"):
        a, _ = A.attn_forward(pl["attn"], h, n_heads=cfg.n_heads,
                              n_kv_heads=cfg.n_kv_heads,
                              head_dim=cfg.head_dim,
                              rope_theta=cfg.rope_theta, causal=True,
                              window=cfg.sliding_window, chunk=chunk,
                              backend=kb)
    x = x + a
    x = constrain(x, res_spec)
    h = rmsnorm(x, pl["norm2"], cfg.norm_eps, backend=kb)
    aux = {}
    if cfg.arch_type == "moe":
        f, aux = M.moe_forward(pl["moe"], h, cfg, batch_axes=batch_axes)
    else:
        # sub-layer remat: recompute the MLP separately from attention in
        # backward so the peak live set is max(attn, mlp) interiors, not
        # their sum (internvl2-76b: (B,S,28672) gate/up/act tensors)
        f = jax.checkpoint(lambda hh, pm: mlp(pm, hh, cfg.act))(
            h, pl["mlp"])
    x = x + f
    x = constrain(x, res_spec)
    return x, aux


def forward_hidden(params: Params, cfg: ModelConfig, tokens, *,
                   prefix_emb=None, dtype=jnp.bfloat16, remat: bool = True,
                   multi_pod: bool = False,
                   attn_chunk: int = 1024, seq_shard: bool = True,
                   remat_policy: str = ""):
    """tokens: (B, S_text) int32 → final hidden states (B, S, d) where
    S = prefix + S_text.  prefix_emb: (B, S_prefix, d) from the frontend
    stub (VLM patches)."""
    batch_spec = fsdp_axis(multi_pod)
    emb = params["embed"]["tok"].astype(dtype)
    x = emb[tokens]
    if prefix_emb is not None:
        x = jnp.concatenate([prefix_emb.astype(dtype), x], axis=1)
    res_spec = (residual_spec(batch_spec, x.shape[1]) if seq_shard
                else P(batch_spec, None, None))
    x = constrain(x, res_spec)

    def body(x, pl):
        y, aux = _layer(pl, x, cfg, res_spec=res_spec, chunk=attn_chunk)
        aux = {k: jnp.asarray(v, jnp.float32) for k, v in aux.items()}
        return y, aux

    if remat:
        if remat_policy == "dots":
            # save matmul outputs, recompute elementwise only — trades
            # saved-activation HBM for a ~25% cut of recompute FLOPs
            pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            body = jax.checkpoint(body, policy=pol)
        else:
            body = jax.checkpoint(body)
    x, auxs = jax.lax.scan(body, x, params["layers"])
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps,
                backend=cfg.kernel_backend)
    aux = {k: jnp.mean(v) for k, v in auxs.items()} if auxs else {}
    return x, aux


def loss_fn(params: Params, cfg: ModelConfig, batch: Params, *,
            z_loss: float = 0.0, dtype=jnp.bfloat16, remat: bool = True,
            multi_pod: bool = False,
            seq_shard: bool = True, remat_policy: str = ""):
    """batch: tokens (B,S_text), labels (B,S_text), optional prefix_emb.
    Returns (loss, metrics)."""
    tokens = batch["tokens"]
    prefix = batch.get("prefix_emb")
    h, aux = forward_hidden(params, cfg, tokens, prefix_emb=prefix,
                            dtype=dtype, remat=remat, multi_pod=multi_pod,
                            seq_shard=seq_shard, remat_policy=remat_policy)
    labels = batch["labels"]
    mask = batch.get("mask", jnp.ones(labels.shape, jnp.float32))
    h = constrain(h, P(fsdp_axis(multi_pod), None, None))
    if prefix is not None:                      # loss only on text tokens
        h = h[:, prefix.shape[1]:]
    loss, z_sq = cross_entropy_chunked(
        h, params["embed"], labels, mask, cfg.vocab_size, z_loss=z_loss,
        logits_spec=P(fsdp_axis(multi_pod), None, "model"))
    metrics = {"ce_loss": loss, "z_sq": z_sq}
    if cfg.arch_type == "moe":
        loss = loss + M.moe_aux_total(aux, cfg)
        metrics.update(aux)
    metrics["loss"] = loss
    return loss, metrics


# --------------------------------------------------------------------- #
# serving
# --------------------------------------------------------------------- #

def _cache_struct(cfg: ModelConfig, batch: int, max_len: int,
                  dtype=jnp.bfloat16) -> Params:
    L = cfg.n_layers
    if cfg.sliding_window is not None:
        W = min(cfg.sliding_window, max_len)
        return {
            "k": jnp.zeros((L, batch, W, cfg.n_kv_heads, cfg.head_dim),
                           dtype),
            "v": jnp.zeros((L, batch, W, cfg.n_kv_heads, cfg.head_dim),
                           dtype),
            "pos": jnp.full((L, W), -1, jnp.int32),
        }
    return {
        "k": jnp.zeros((L, batch, max_len, cfg.n_kv_heads, cfg.head_dim),
                       dtype),
        "v": jnp.zeros((L, batch, max_len, cfg.n_kv_heads, cfg.head_dim),
                       dtype),
    }


def logits_from_hidden(params: Params, cfg: ModelConfig, h):
    W = params["embed"].get("lm_head")
    if W is None:
        W = params["embed"]["tok"].T
    logits = (h @ W.astype(h.dtype)).astype(jnp.float32)
    return logits


def prefill(params: Params, cfg: ModelConfig, tokens, *, prefix_emb=None,
            cache_len_cap: int, dtype=jnp.bfloat16, multi_pod: bool = False,
            attn_chunk: int = 1024, seq_shard: bool = True):
    """Run the prompt, return (last-token logits, kv cache, length)."""
    batch_spec = fsdp_axis(multi_pod)
    emb = params["embed"]["tok"].astype(dtype)
    x = emb[tokens]
    if prefix_emb is not None:
        x = jnp.concatenate([prefix_emb.astype(dtype), x], axis=1)
    B, S, _ = x.shape
    # sequence-parallel prefill: TP partial sums lower to reduce-scatter
    # + bf16 gather instead of full-width f32 all-reduce per layer
    res_spec = (residual_spec(batch_spec, S) if seq_shard
                else P(batch_spec, None, None))
    x = constrain(x, res_spec)

    def body(x, pl):
        h = rmsnorm(x, pl["norm1"], cfg.norm_eps)
        a, (k, v) = A.attn_forward(
            pl["attn"], h, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim, rope_theta=cfg.rope_theta, causal=True,
            window=cfg.sliding_window, chunk=attn_chunk)
        x = x + a
        h = rmsnorm(x, pl["norm2"], cfg.norm_eps)
        if cfg.arch_type == "moe":
            f, _ = M.moe_forward(pl["moe"], h, cfg,
                                 batch_axes=batch_spec)
        else:
            f = mlp(pl["mlp"], h, cfg.act)
        x = constrain(x + f, res_spec)
        if cfg.sliding_window is not None:
            W = min(cfg.sliding_window, cache_len_cap)
            return x, A.ring_from_prefill(k, v, S, W, dtype=dtype)
        pad = cache_len_cap - S
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return x, {"k": k, "v": v}

    x, cache = jax.lax.scan(body, x, params["layers"])
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_from_hidden(params, cfg, x[:, -1:])
    return logits, cache, jnp.asarray(S, jnp.int32)


def prefill_ragged(params: Params, cfg: ModelConfig, tokens, lengths, *,
                   prefix_emb=None, dtype=jnp.bfloat16,
                   multi_pod: bool = False, attn_chunk: int = 1024,
                   seq_shard: bool = True):
    """Bucketed prefill: tokens (B, S_bucket) right-padded to a shared
    bucket length, lengths (B,) true lengths (frontend prefix included).
    Causality makes every real position independent of the padding rows,
    so one executable serves every prompt length in the bucket.

    Returns (logits (B, 1, V) at each request's last real token,
    k, v (L, B, S, Hkv, hd)) — the raw per-layer K/V, unpadded; rows at
    positions >= lengths[b] hold padding-token junk the cache layer must
    mask (the dense cache masks by ``kv_len``, the page pool by the
    causal reach)."""
    if cfg.sliding_window is not None:
        raise NotImplementedError(
            "ragged bucketed prefill supports full attention only; "
            "sliding-window (ring-cache) archs keep the exact-length "
            "prefill path")
    batch_spec = fsdp_axis(multi_pod)
    emb = params["embed"]["tok"].astype(dtype)
    x = emb[tokens]
    if prefix_emb is not None:
        x = jnp.concatenate([prefix_emb.astype(dtype), x], axis=1)
    B, S, d = x.shape
    res_spec = (residual_spec(batch_spec, S) if seq_shard
                else P(batch_spec, None, None))
    x = constrain(x, res_spec)

    def body(x, pl):
        h = rmsnorm(x, pl["norm1"], cfg.norm_eps)
        a, (k, v) = A.attn_forward(
            pl["attn"], h, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim, rope_theta=cfg.rope_theta, causal=True,
            window=None, chunk=attn_chunk)
        x = x + a
        h = rmsnorm(x, pl["norm2"], cfg.norm_eps)
        if cfg.arch_type == "moe":
            f, _ = M.moe_forward(pl["moe"], h, cfg, batch_axes=batch_spec)
        else:
            f = mlp(pl["mlp"], h, cfg.act)
        x = constrain(x + f, res_spec)
        return x, (k, v)

    x, (k, v) = jax.lax.scan(body, x, params["layers"])
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    idx = jnp.clip(lengths - 1, 0, S - 1)[:, None, None]
    h_last = jnp.take_along_axis(x, jnp.broadcast_to(idx, (B, 1, d)),
                                 axis=1)
    logits = logits_from_hidden(params, cfg, h_last)
    return logits, k, v


def decode_step(params: Params, cfg: ModelConfig, cache: Params, cache_len,
                token, *, dtype=jnp.bfloat16, multi_pod: bool = False,
                attn_chunk: int = 4096):
    """One decode step.  token: (B, 1) int32; cache from ``prefill`` /
    ``_cache_struct`` (layer-stacked).  Returns (logits, cache, len+1)."""
    batch_spec = fsdp_axis(multi_pod)
    emb = params["embed"]["tok"].astype(dtype)
    x = emb[token]
    x = constrain(x, P(batch_spec, None, None))

    def body(x, xs):
        pl, cl = xs
        h = rmsnorm(x, pl["norm1"], cfg.norm_eps)
        a, new_cl = A.decode_attn(
            pl["attn"], h, cl, cache_len, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
            rope_theta=cfg.rope_theta, window=cfg.sliding_window,
            chunk=attn_chunk)
        x = x + a
        h = rmsnorm(x, pl["norm2"], cfg.norm_eps)
        if cfg.arch_type == "moe":
            f, _ = M.moe_forward(pl["moe"], h, cfg)
        else:
            f = mlp(pl["mlp"], h, cfg.act)
        return x + f, new_cl

    x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_from_hidden(params, cfg, x)
    return logits, new_cache, cache_len + 1
