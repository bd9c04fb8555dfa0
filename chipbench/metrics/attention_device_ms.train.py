"""Device milliseconds per train-step execution under the program's
``attention`` scope (``models.attention``: projections, rotary and the
causal core, in every pass); leaf ops, mean over chips (device trace).
Nothing to read where the step has no such scope."""
from chipbench import program_trace as PT


def read(layer):
    return PT.scope_reading(layer, "attention")
