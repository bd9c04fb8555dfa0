"""Device milliseconds per train-step execution in the recompute: the
forward that ``jax.checkpoint`` re-runs in the backward
(``rematted_computation``) and the clones XLA's own rematerialization
adds (``.remat<n>``); leaf ops, mean over chips (device trace, classed
by ``chipbench/scopes.py``). Nothing to read where no op of the class
ran."""
from chipbench import program_trace as PT


def read(layer):
    return PT.scope_reading(layer, PT.ALL, "recompute")
