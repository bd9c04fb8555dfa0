"""Device milliseconds per train-step execution in the backward pass: ops
under ``transpose(jvp(forward))``, JAX's name for the backward of the
program's ``forward`` scope, outside the recompute; leaf ops, mean over
chips (device trace, classed by ``chipbench/scopes.py``). Nothing to
read where no op of the class ran."""
from chipbench import program_trace as PT


def read(layer):
    return PT.scope_reading(layer, PT.ALL, "backward")
