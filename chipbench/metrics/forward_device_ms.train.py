"""Device milliseconds per train-step execution in the forward pass: ops
under the program's ``forward`` scope outside its transpose and its
recompute; leaf ops, mean over chips (device trace, classed by
``chipbench/scopes.py``). Nothing to read where no op of the class ran."""
from chipbench import program_trace as PT


def read(layer):
    return PT.scope_reading(layer, PT.ALL, "forward")
