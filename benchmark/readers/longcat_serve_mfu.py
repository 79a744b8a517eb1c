"""Share of the chip's peak the whole serving window reaches: the
benchmark's own count of the operations its tokens need here
(`longcat_counts.serve_flops`: prompt and output tokens the programs were
given, the held experts by the window's own counter of assignments
computed, identity experts 0) over the window's host-clock seconds, over
the peak."""
from .. import longcat_counts as counts


def read(evidence):
    stats, peaks = evidence.get("engine_stats"), evidence.get("peaks")
    window = evidence.get("window")
    if not stats or not peaks or not window \
            or "decode_routed_computed" not in stats:
        return None
    tokens = stats["prefill_tokens"] + stats["decode_tokens"]
    computed = stats["decode_routed_computed"] \
        + stats.get("prefill_routed_computed", 0)
    ops = counts.serve_flops(evidence["config"], tokens, computed)
    return 100.0 * ops / (window[1] - window[0]) / peaks["bf16_flops_per_s"]
