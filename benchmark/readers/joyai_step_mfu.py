"""Model FLOP/s utilization of a JoyAI-LLM-Flash training cell: the
benchmark's own count of a token's operations here
(`joyai_counts.train_flops_per_token`: 6 x the parameters a token
multiplies, the held experts by the newest step's counter of assignments
computed, attention's products at 192/128) x the window's tokens/s (host
clock), over chips x peak."""
from .. import joyai_counts as counts
from ..programs import paddle_train_stats


def read(evidence):
    rate, peaks = evidence.get("window_tokens_per_s"), evidence.get("peaks")
    stats = paddle_train_stats.newest_train_step_stats()
    if not rate or not peaks or not stats \
            or "routed_computed" not in stats:
        return None
    mix = evidence["traffic"]
    tokens = mix["batch_rows"] * mix["seq"]
    per_token = counts.train_flops_per_token(
        evidence["config"], mix["seq"], stats["routed_computed"] / tokens)
    return 100.0 * per_token * rate / (
        evidence["chips"] * peaks["bf16_flops_per_s"])
