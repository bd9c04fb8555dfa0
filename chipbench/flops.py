"""Operations a dense decoder needs, counted from its sizes.

Convention — required work only, so that no later change can raise a
utilization by recounting:

- a multiply-add is 2 FLOPs; a matrix product (m, k) x (k, n) is 2mkn;
- training is 3 x forward (the forward pass, and a backward pass of
  twice its products); work recomputed under remat is NOT counted;
- causal attention counts the lower triangle only: S^2 / 2 score and
  value products per head for a sequence of S, i.e. S / 2 per token,
  whatever a kernel actually computes (a kernel that skips masked
  blocks does less work, not more utilization);
- the output head counts the logical vocabulary; the embedding lookup,
  norms, activations and the optimizer are not matrix products and
  count 0.

After ``benchmarks/flops_model.py``'s dense-layer formulas, with its
remat factor (x4) and full-square attention dropped.

Bytes, for a roofline share, count the same way: what the pass must
read from and write to HBM at least, each tensor once per pass, in the
compute dtype; nothing recomputed, nothing re-read for lack of on-chip
memory.
"""
from __future__ import annotations

from typing import Dict


def layer_matmul_params(m: Dict) -> int:
    """Weights of one layer that enter matrix products."""
    d, q = m["d_model"], m["n_heads"] * m["head_dim"]
    kv = m["n_kv_heads"] * m["head_dim"]
    attn = d * q + 2 * d * kv + q * d
    mlp = (3 if m["act"] == "silu" else 2) * d * m["d_ff"]
    return attn + mlp


def param_count(m: Dict) -> int:
    """All parameters with the logical vocabulary: embedding and head,
    and each layer's products and two norm gains (the final norm is
    left out, as ``ModelConfig.param_count`` leaves it)."""
    emb = m["vocab_size"] * m["d_model"] * (1 if m["tie_embeddings"]
                                            else 2)
    return emb + m["n_layers"] * (layer_matmul_params(m)
                                  + 2 * m["d_model"])


def forward_flops_per_token(m: Dict, seq_len: int) -> float:
    q = m["n_heads"] * m["head_dim"]
    per_layer = 2 * layer_matmul_params(m) + 2 * 2 * q * seq_len / 2
    head = 2 * m["d_model"] * m["vocab_size"]
    return m["n_layers"] * per_layer + head


def train_flops_per_token(m: Dict, seq_len: int) -> float:
    return 3 * forward_flops_per_token(m, seq_len)


def causal_core_per_token(m: Dict, seq_len: int, itemsize: int
                          ) -> Dict[str, float]:
    """Required work of the causal attention core (scores, softmax and
    the value product; not the projections or rotary) per token, over
    all layers, forward and backward: ``{"flops", "bytes"}``.

    FLOPs: 3 x 2 x 2 x q_dim x S / 2 a layer (two products of the lower
    triangle, a multiply-add 2 FLOPs, training 3 x forward).  Bytes: the
    forward reads q, k, v and writes o; the backward reads q, k, v, o
    and dO and writes dq, dk, dv: 6 q_dim + 6 kv_dim values a layer
    (the per-row softmax statistics, H values, are left out)."""
    q = m["n_heads"] * m["head_dim"]
    kv = m["n_kv_heads"] * m["head_dim"]
    L = m["n_layers"]
    return {"flops": L * 3 * 2 * 2 * q * seq_len / 2,
            "bytes": L * itemsize * (6 * q + 6 * kv)}
