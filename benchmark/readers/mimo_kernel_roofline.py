"""A MiMo-V2-Flash kernel's share of its roofline: the least time the chip
could take for the operations and bytes the algorithm needs
(`mimo_counts`), over the traced time of the device operations whose HLO
instruction matches `pattern`.

The calls the slice traced are counted from the trace (`events_per_call`
matching events a call); a call's MEAN operations and bytes come from the
untraced window's counters: the traffic is stationary (a standing queue
of one fixed multiset of lengths), so the slice's calls are the window's
in the mean. Nothing matched: nothing is returned, not 0.

`kernel`: "full_attn" (the paged decode attention of a full layer: a call
a full layer a launch, over the contexts), "window_attn" (a window
layer's, over the WINDOWS: what the mathematics needs, so a kernel that
streamed the contexts would read low), "band" (a window layer's prefill
attention: a call a window layer a prompt, the band's pairs), or
"experts" (the grouped products of an expert block: three products a
call, a call an expert layer of a program call whose form is grouped; a
call that runs its sorted rows in more than one buffer is counted once a
buffer, and reads high by that: one buffer where a held expert sees its
share)."""
from .. import flops, mimo_counts as counts, trace as tr
from .mimo_decode_hbm_roofline import experts_read
from .mimo_serve_mfu import prompt_facts


def read(evidence, pattern, kernel, events_per_call=1):
    trace, peaks = evidence.get("trace"), evidence.get("peaks")
    stats, facts = evidence.get("engine_stats"), evidence.get("engine_facts")
    if not trace or not peaks or not stats or not facts \
            or "window_tokens_held" not in stats \
            or "decode_routed_computed" not in stats:
        return None
    seconds, events = tr.seconds_matching(trace, pattern)
    if not events or not seconds:
        return None
    cfg = evidence["config"]
    full, window, _, expert_layers = counts.layers(cfg)
    launches, slots = stats["decode_launches"], facts["slots"]
    if kernel == "full_attn":
        calls = full * launches
        ops, moved = counts.decode_attention(
            cfg, counts.FULL, calls, stats["attn_tokens_held"] * full, slots)
    elif kernel == "window_attn":
        calls = window * launches
        ops, moved = counts.decode_attention(
            cfg, counts.WINDOW, calls, stats["window_tokens_held"] * window,
            slots)
    elif kernel == "band":
        prompts, mean = prompt_facts(stats)
        calls = window * prompts
        ops, moved = counts.band_attention(cfg, calls, mean)
    else:
        # the phases whose calls took the grouped form (the model counts
        # the grouped products it ran)
        grouped = [phase for phase in ("decode", "prefill")
                   if stats.get(phase + "_products")]
        calls = expert_layers * sum(stats[p + "_counted"] for p in grouped)
        ops, moved = counts.expert_products(
            cfg, sum(stats[p + "_routed_computed"] for p in grouped),
            sum(experts_read(stats, cfg, p) for p in grouped))
    if not calls:
        return None
    traced = events / events_per_call
    least, _ = flops.roofline_seconds(ops / calls * traced,
                                      moved / calls * traced, peaks)
    return 100.0 * least / seconds
