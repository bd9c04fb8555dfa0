"""Share of its roofline that the blocked causal attention core (the
program's ``causal_blocks`` scope) reaches: the least time the chip
could take for the core's required work in one step execution (the
larger of FLOPs over the bf16 peak and bytes over HBM bandwidth; the
reference's ``scope_work``, by ``chipbench/flops.py``'s convention) over
the device time of its ops per execution, mean over chips (device
trace).  The time includes the core's recompute; the work does not.
Nothing to read where the step has no such scope or no work is
counted for it."""
from chipbench import program_trace as PT


def read(layer):
    ms = PT.scope_reading(layer, "causal_blocks")
    work = (layer.get("scope_work") or {}).get("causal_blocks")
    peaks = layer.get("peaks")
    if ms is None or not work or not peaks:
        return None
    tokens = layer["tokens_per_execution"] / layer["chips"]
    least_s = tokens * max(work["flops"] / peaks["bf16_flops_per_s"],
                           work["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
