"""The fused training step's share of the chips' bf16 peak: the required
FLOPs per token (``chipbench/flops.py``) times the traced window's
tokens per second, over chips times peak."""


def read(layer):
    if layer.get("kind") != "train" or not layer.get("peaks"):
        return None
    peak = layer["peaks"]["bf16_flops_per_s"] * layer["chips"]
    return 100.0 * layer["flops_per_token"] * layer["tokens_per_s"] / peak
