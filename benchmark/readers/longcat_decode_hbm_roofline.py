"""Share of the window that the decode launches' bytes alone would take at
the chip's memory bandwidth (`longcat_counts.decode_bytes`): the weights
outside the experts a launch, the held experts some token chose (the
counters), and the cached rows HELD (`attn_held_share` of the block
tables, whole blocks), over the window's host-clock seconds."""
from .. import longcat_counts as counts


def read(evidence):
    stats, peaks = evidence.get("engine_stats"), evidence.get("peaks")
    window, facts = evidence.get("window"), evidence.get("engine_facts")
    if not stats or not peaks or not window or not facts \
            or "decode_experts_idle" not in stats:
        return None
    cfg = evidence["config"]
    launches = stats["decode_launches"]
    experts = stats["decode_counted"] * cfg["num_layers"] \
        * cfg["n_routed_experts"] - stats["decode_experts_idle"]
    rows = stats["attn_held_share"] * launches * facts["slots"] \
        * facts["table_entries"] * facts["block_size"] \
        * facts["cached_sublayers"]
    moved = counts.decode_bytes(cfg, launches, experts, rows)
    return 100.0 * moved / peaks["hbm_bytes_per_s"] / (window[1] - window[0])
