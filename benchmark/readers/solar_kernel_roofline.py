"""A Solar-Open2 kernel's share of its roofline: the least time the chip
could take for the operations and bytes the algorithm needs
(`solar_counts`), over the traced time of the device operations whose HLO
instruction matches `pattern`.

The calls the slice traced are counted from the trace (`events_per_call`
matching events a call); a call's MEAN operations and bytes come from the
untraced window's counters: the traffic is stationary (a standing queue
of one fixed multiset of lengths), so the slice's calls are the window's
in the mean. Nothing matched: nothing is returned, not 0.

`kernel`: "scan" (the chunked delta rule of a KDA layer over a prompt: a
call a KDA layer a prefill, over the prompt's true tokens, so the bucket's
padding reads as time that multiplied nothing), "update" (the one-token
update of the slots' states: a call a KDA layer a launch, over the ACTIVE
slots), or "experts" (the grouped products of an expert block: three
products a call, a call a layer of a program call whose form is grouped; a
call that runs its sorted rows in more than one buffer is counted once a
buffer, and reads high by that)."""
from .. import flops, solar_counts as counts, trace as tr
from .solar_decode_hbm_roofline import experts_read


def read(evidence, pattern, kernel, events_per_call=1):
    trace, peaks = evidence.get("trace"), evidence.get("peaks")
    stats = evidence.get("engine_stats")
    if not trace or not peaks or not stats \
            or "decode_state_updates" not in stats \
            or "decode_routed_computed" not in stats:
        return None
    seconds, events = tr.seconds_matching(trace, pattern)
    if not events or not seconds:
        return None
    cfg = evidence["config"]
    _, kda = counts.layers(cfg)
    if kernel == "scan":
        prompts = stats.get("prefill_counted", 0)
        calls = kda * prompts
        ops, moved = counts.scan(cfg, kda * stats["prefill_tokens"], calls)
    elif kernel == "update":
        calls = kda * stats["decode_launches"]
        ops, moved = counts.state_update(cfg, stats["decode_state_updates"])
    else:
        # the phases whose calls took the grouped form (the model counts
        # the grouped products it ran)
        grouped = [phase for phase in ("decode", "prefill")
                   if stats.get(phase + "_products")]
        calls = len(cfg["layer_types"]) \
            * sum(stats[p + "_counted"] for p in grouped)
        ops, moved = counts.expert_products(
            cfg, sum(stats[p + "_routed_computed"] for p in grouped),
            sum(experts_read(stats, cfg, p) for p in grouped))
    if not calls:
        return None
    traced = events / events_per_call
    least, _ = flops.roofline_seconds(ops / calls * traced,
                                      moved / calls * traced, peaks)
    return 100.0 * least / seconds
