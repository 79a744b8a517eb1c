"""Share of the window that the decode launches' bytes alone would take at
the chip's memory bandwidth (`lfm2_counts.decode_bytes`): the weights
outside the experts a launch, each expert some token chose (the
counters), the cached keys and values HELD and the slots' convolution
state, over the window's host-clock seconds.

Over the WHOLE window, as `longcat.decode_hbm_roofline`: the reduced trace
does not give a program's own seconds. In a cell whose prefills take a
large part of the device's time the share reads low by that part: it says
how much of the window the launches' bytes account for, not how close a
launch runs to the bandwidth."""
from .. import lfm2_counts as counts
from .lfm2_serve_mfu import decode_tokens_held


def experts_read(stats, cfg, phase):
    """(call, layer, expert) triples of `phase` in which at least one
    token chose the expert."""
    _, _, _, expert_layers = counts.layers(cfg)
    return stats.get(phase + "_counted", 0) * expert_layers \
        * cfg["num_experts"] - stats.get(phase + "_experts_idle", 0)


def read(evidence):
    stats, peaks = evidence.get("engine_stats"), evidence.get("peaks")
    window, facts = evidence.get("window"), evidence.get("engine_facts")
    if not stats or not peaks or not window or not facts \
            or "decode_experts_idle" not in stats:
        return None
    cfg = evidence["config"]
    held = decode_tokens_held(stats, facts) * facts["cached_sublayers"]
    moved = counts.decode_bytes(cfg, stats["decode_launches"],
                                experts_read(stats, cfg, "decode"), held,
                                facts["slots"])
    return 100.0 * moved / peaks["hbm_bytes_per_s"] / (window[1] - window[0])
