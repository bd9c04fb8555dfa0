"""Workload wiring for the dry-run and the real launcher: builds
(fn, arg structs, in/out shardings) per (arch × shape × mesh) without
allocating anything (jax.eval_shape for params/opt state).

The train step itself is NOT defined here: it comes from the phase
execution engine (``repro.train.engine.make_grad_step``), the single
``value_and_grad`` call site shared with ``Trainer`` — this module only
pairs it with eval-shape structs and sharding trees.  The sharding-tree
helpers (``param_structs`` / ``opt_structs`` / ``opt_state_specs`` /
``_named``) are re-exports from the engine.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig
from repro.models import registry as R
from repro.train.engine import (make_grad_step, named_shardings,
                                opt_state_specs, opt_structs,
                                param_structs)

# long_500k requires sub-quadratic decoding (DESIGN.md §6)
LONG_CONTEXT_ARCHS = {"recurrentgemma-9b", "mamba2-2.7b", "starcoder2-3b"}

_named = named_shardings        # legacy name used by dryrun and tests


def validate_feeding(plan, mesh, *, process_count: int | None = None,
                     start_tokens=None, seq_len: int | None = None):
    """Dry-run/launch check that a plan's batch ramp is feedable on
    this topology: every phase's global batch must divide across the
    host processes (per-host data feeding) and across the mesh's
    data-parallel devices, and each process must own a contiguous,
    process-ordered row block of the data axes (asserted from the
    actual ``NamedSharding``, so custom meshes are covered).

    ``start_tokens`` (a checkpoint's exact ``tokens_seen``) turns this
    into the *elastic-resume* check: only the ramp from the phase that
    token count lands in onward must be feedable — the new topology
    may differ from the saving one, and phases the checkpoint already
    consumed don't constrain it.  With ``seq_len`` the phase is looked
    up on the realized (step-quantized) boundaries the loader uses;
    without it, on the plan's ideal token boundaries.  Raises
    ``ValueError`` on the first violation; returns the plan
    otherwise."""
    from repro.data.pipeline import validate_per_host_plan
    from repro.launch.mesh import (assert_per_host_row_blocks,
                                   data_parallel_size)
    n_proc = jax.process_count() if process_count is None \
        else process_count
    if mesh is not None:
        assert_per_host_row_blocks(mesh, n_proc)
    start_phase = 0
    if start_tokens is not None:
        from repro.train.checkpoint import exact_tokens
        tok = exact_tokens(start_tokens)
        ph = (plan.realized_phase_at(tok, seq_len) if seq_len
              else plan.phase_at_tokens(tok))
        start_phase = ph.index
    return validate_per_host_plan(plan, n_proc,
                                  data_parallel_size(mesh),
                                  start_phase=start_phase)


def shape_supported(cfg: ModelConfig, shape: InputShape) -> Tuple[bool, str]:
    if shape.name == "long_500k" and cfg.name not in LONG_CONTEXT_ARCHS:
        return False, ("skipped: full-attention arch at 500k decode "
                       "(see DESIGN.md §6)")
    return True, ""


def build_workload(cfg: ModelConfig, shape: InputShape, *,
                   multi_pod: bool = False, opt_kind: str = "adamw",
                   z_loss: float = 0.0, remat: bool = True,
                   seq_shard: bool = True,
                   remat_policy: str = "", serve_resident: bool = False,
                   cache_seq_shard: bool = False,
                   dtype=jnp.bfloat16):
    """Returns (fn, args tuple of ShapeDtypeStructs, in_shardings tuple,
    out_shardings)."""
    pspec = R.param_specs(cfg, multi_pod,
                          serve_resident=(serve_resident and
                                          shape.mode != "train"))
    pstruct = param_structs(cfg)
    ispec = R.input_shardings(cfg, shape, multi_pod,
                              cache_seq_shard=cache_seq_shard)
    istruct = R.input_specs(cfg, shape)

    if shape.mode == "train":
        opt, ostruct = opt_structs(cfg, pstruct, opt_kind)
        ospec = opt_state_specs(pspec, ostruct)
        step = make_grad_step(cfg, opt, z_loss=z_loss, dtype=dtype,
                              remat=remat, multi_pod=multi_pod,
                              seq_shard=seq_shard, remat_policy=remat_policy)

        def train_step(params, opt_state, batch, lr):
            new_params, new_opt, metrics = step(params, opt_state,
                                                batch, lr)
            return new_params, new_opt, metrics["loss"]

        args = (pstruct, ostruct, istruct,
                jax.ShapeDtypeStruct((), jnp.float32))
        in_specs = (pspec, ospec, ispec, P())
        out_specs = (pspec, ospec, P())
        return train_step, args, in_specs, out_specs

    if shape.mode == "prefill":
        def prefill_step(params, batch):
            tokens = batch["tokens"]
            prefix = batch.get("prefix_emb")
            logits, _cache = R.prefill(
                params, cfg, tokens, prefix_emb=prefix,
                cache_len_cap=shape.seq_len, dtype=dtype,
                multi_pod=multi_pod)
            return logits

        args = (pstruct, istruct)
        in_specs = (pspec, ispec)
        b = ispec["tokens"]
        out_specs = P(b[0], None, "model")
        return prefill_step, args, in_specs, out_specs

    # decode: the cache is a typed KVCache pytree carrying its own
    # per-request lengths (no scalar cache_len operand anymore)
    def serve_step(params, cache, token):
        logits, new_cache = R.decode_step(
            params, cfg, cache, token, dtype=dtype, multi_pod=multi_pod)
        return logits, new_cache

    args = (pstruct, istruct["cache"], istruct["token"])
    in_specs = (pspec, ispec["cache"], ispec["token"])
    b = ispec["token"]
    out_specs = (P(b[0], None, "model"), ispec["cache"])
    return serve_step, args, in_specs, out_specs
