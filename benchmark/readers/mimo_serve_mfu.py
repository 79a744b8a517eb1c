"""Share of the chip's peak the whole serving window reaches: the
benchmark's own count of the operations its tokens need here
(`mimo_counts.serve_flops`: prompt and output tokens the programs were
given, at their true lengths and not their buckets'; the experts by the
window's own counter of assignments computed; attention's products over
what a query may see: a full layer's context, a window layer's band) over
the window's host-clock seconds, over the peak.

A decoded token's pairs are the engine's exact counters
(`attn_tokens_held`, `window_tokens_held`); a prompt's are taken as if
every prompt had the mean length: L (L + 1) / 2 in a full layer (less
than the truth by the variance of the lengths), the band's in a window
layer (linear in L, so exact above the window)."""
from .. import mimo_counts as counts


def prompt_facts(stats):
    """(prompts, their mean true length) of the window's prefills."""
    prompts = stats.get("prefill_counted", 0)
    return prompts, (stats["prefill_tokens"] / prompts if prompts else 0.0)


def read(evidence):
    stats, peaks = evidence.get("engine_stats"), evidence.get("peaks")
    window = evidence.get("window")
    if not stats or not peaks or not window \
            or "decode_routed_computed" not in stats \
            or "window_tokens_held" not in stats:
        return None
    cfg = evidence["config"]
    tokens = stats["prefill_tokens"] + stats["decode_tokens"]
    computed = stats["decode_routed_computed"] \
        + stats.get("prefill_routed_computed", 0)
    prompts, mean = prompt_facts(stats)
    ops = counts.serve_flops(
        cfg, tokens, computed,
        stats["attn_tokens_held"] + prompts * mean * (mean + 1) / 2,
        stats["window_tokens_held"] + prompts * counts.band_pairs(cfg, mean))
    return 100.0 * ops / (window[1] - window[0]) / peaks["bf16_flops_per_s"]
