"""Share of the chip's peak the whole serving window reaches: the
benchmark's own count of the operations its tokens need here
(`solar_counts.serve_flops`: prompt and output tokens the programs were
given, at their true lengths and not their buckets'; the experts by the
window's own counter of assignments computed; softmax attention's
products over a query's context; the KDA layers' rule, chunked over the
prompt tokens and the recurrence over the decoded ones, each product
counted once) over the window's host-clock seconds, over the peak.

A decoded token's pairs are the engine's exact counter
(`attn_tokens_held`); a prompt's are taken as if every prompt had the
mean length: L (L + 1) / 2 (less than the truth by the variance of the
lengths)."""
from .. import solar_counts as counts


def prompt_facts(stats):
    """(prompts, their mean true length) of the window's prefills."""
    prompts = stats.get("prefill_counted", 0)
    return prompts, (stats["prefill_tokens"] / prompts if prompts else 0.0)


def read(evidence):
    stats, peaks = evidence.get("engine_stats"), evidence.get("peaks")
    window = evidence.get("window")
    if not stats or not peaks or not window \
            or "decode_routed_computed" not in stats \
            or "decode_state_updates" not in stats:
        return None
    computed = stats["decode_routed_computed"] \
        + stats.get("prefill_routed_computed", 0)
    prompts, mean = prompt_facts(stats)
    ops = counts.serve_flops(
        evidence["config"], stats["prefill_tokens"], stats["decode_tokens"],
        computed,
        stats["attn_tokens_held"] + prompts * mean * (mean + 1) / 2)
    return 100.0 * ops / (window[1] - window[0]) / peaks["bf16_flops_per_s"]
