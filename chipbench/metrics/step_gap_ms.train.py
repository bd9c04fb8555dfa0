"""Median idle milliseconds on a chip between two consecutive executions
of the train step, over the chips (device trace): the step boundary's
dispatch, sync and loader.  Nothing to read where fewer than two
executions fall in the window."""
from chipbench import program_trace as PT


def read(layer):
    red = (layer.get("trace") or {}).get("program")
    return PT.step_gap_ms(red) if red else None
