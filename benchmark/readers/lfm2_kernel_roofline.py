"""An LFM2-MoE kernel's share of its roofline: the least time the chip
could take for the operations and bytes the algorithm needs
(`lfm2_counts`), over the traced time of the device operations whose HLO
instruction matches `pattern`.

The calls the slice traced are counted from the trace (`events_per_call`
matching events a call); a call's MEAN operations and bytes come from the
untraced window's counters: the traffic is stationary (a standing queue
of one fixed multiset of lengths), so the slice's calls are the window's
in the mean. Nothing matched: nothing is returned, not 0.

`kernel`: "experts" (the grouped products of an expert block: three
products a call, a call an expert layer a launch or a prefill) or
"decode_attn" (the paged decode attention: a call an attention layer a
launch)."""
from .. import flops, lfm2_counts as counts, trace as tr
from .lfm2_decode_hbm_roofline import experts_read
from .lfm2_serve_mfu import decode_tokens_held


def read(evidence, pattern, kernel, events_per_call=1):
    trace, peaks = evidence.get("trace"), evidence.get("peaks")
    stats, facts = evidence.get("engine_stats"), evidence.get("engine_facts")
    if not trace or not peaks or not stats or not facts \
            or "decode_routed_computed" not in stats:
        return None
    seconds, events = tr.seconds_matching(trace, pattern)
    if not events or not seconds:
        return None
    cfg = evidence["config"]
    _, attention_layers, _, expert_layers = counts.layers(cfg)
    if kernel == "experts":
        calls = expert_layers * (stats["decode_counted"]
                                 + stats.get("prefill_counted", 0))
        ops, moved = counts.expert_products(
            cfg, stats["decode_routed_computed"]
            + stats.get("prefill_routed_computed", 0),
            experts_read(stats, cfg, "decode")
            + experts_read(stats, cfg, "prefill"))
    else:
        calls = attention_layers * stats["decode_launches"]
        ops, moved = counts.decode_attention(
            cfg, calls, decode_tokens_held(stats, facts) * attention_layers,
            facts["slots"])
    if not calls:
        return None
    traced = events / events_per_call
    least, _ = flops.roofline_seconds(ops / calls * traced,
                                      moved / calls * traced, peaks)
    return 100.0 * least / seconds
