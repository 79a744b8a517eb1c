"""Share of the chip's peak the whole serving window reaches: the
benchmark's own count of the operations its tokens need here
(`lfm2_counts.serve_flops`: prompt and output tokens the programs were
given, at their true lengths and not their buckets'; the experts by the
window's own counter of assignments computed; attention's products over
the context) over the window's host-clock seconds, over the peak.

Attention's (query, context) pairs are a LOWER bound from the window's
counters: a decoded token meets the tokens its slot holds (whole pages
held, less a page's worth a slot: `attn_held_share`), a prompt of L tokens
L (L + 1) / 2, summed as if every prompt had the mean length (less than
the truth by the variance of the lengths)."""
from .. import lfm2_counts as counts


def decode_tokens_held(stats, facts):
    """Cached tokens the window's decode launches attended to, summed
    over launches and slots, at the least: the pages held hold between
    one token and a whole page in each slot's last page."""
    pages = stats["attn_held_share"] * stats["decode_launches"] \
        * facts["slots"] * facts["table_entries"]
    return max(0.0, pages * facts["block_size"]
               - stats["decode_tokens"] * (facts["block_size"] - 1))


def read(evidence):
    stats, peaks = evidence.get("engine_stats"), evidence.get("peaks")
    window, facts = evidence.get("window"), evidence.get("engine_facts")
    if not stats or not peaks or not window or not facts \
            or "decode_routed_computed" not in stats \
            or "prefill_bucket_tokens" not in stats:
        return None
    tokens = stats["prefill_tokens"] + stats["decode_tokens"]
    computed = stats["decode_routed_computed"] \
        + stats.get("prefill_routed_computed", 0)
    prompts = stats.get("prefill_counted", 0)
    mean = stats["prefill_tokens"] / prompts if prompts else 0.0
    pairs = decode_tokens_held(stats, facts) \
        + prompts * mean * (mean + 1) / 2
    ops = counts.serve_flops(evidence["config"], tokens, computed, pairs)
    return 100.0 * ops / (window[1] - window[0]) / peaks["bf16_flops_per_s"]
