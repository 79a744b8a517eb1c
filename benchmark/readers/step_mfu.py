"""Model FLOP/s utilization: the benchmark's own count of a token's
operations x the window's tokens/s, over chips x peak."""
from .. import flops


def read(evidence):
    rate, peaks = evidence.get("window_tokens_per_s"), evidence.get("peaks")
    if not rate or not peaks:
        return None
    cfg = evidence["config"]
    per_token = flops.train_flops_per_token(
        evidence["params"], cfg["n_layer"], cfg["n_embd"],
        evidence["traffic"]["seq"])
    return 100.0 * per_token * rate / (
        evidence["chips"] * peaks["bf16_flops_per_s"])
