"""Share of the window that the decode launches' bytes alone would take at
the chip's memory bandwidth (`solar_counts.decode_bytes`): the weights
outside the routed experts a launch, each held expert some token chose
(the counters), the cached keys and values the slots attend to in the
softmax layers (the engine's exact counter) and every updated state read
and written once, the delta rule's matrix in float32 (the engine's
`decode_state_updates`), over the window's host-clock seconds.

Over the WHOLE window, as `lfm2.decode_hbm_roofline`: in a cell whose
prefills take a large part of the device's time the share reads low by
that part."""
from .. import solar_counts as counts


def experts_read(stats, cfg, phase):
    """(call, layer, expert) triples of `phase` in which at least one
    token chose the held expert."""
    return stats.get(phase + "_counted", 0) * len(cfg["layer_types"]) \
        * cfg["n_routed_experts"] - stats.get(phase + "_experts_idle", 0)


def read(evidence):
    stats, peaks = evidence.get("engine_stats"), evidence.get("peaks")
    window = evidence.get("window")
    if not stats or not peaks or not window \
            or "decode_experts_idle" not in stats \
            or "decode_state_updates" not in stats:
        return None
    cfg = evidence["config"]
    moved = counts.decode_bytes(
        cfg, stats["decode_launches"], experts_read(stats, cfg, "decode"),
        stats["attn_tokens_held"], stats["decode_state_updates"])
    return 100.0 * moved / peaks["hbm_bytes_per_s"] / (window[1] - window[0])
