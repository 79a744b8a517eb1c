"""A JoyAI-LLM-Flash kernel's share of its roofline: the least time the
chip could take for the operations and bytes the algorithm needs
(`joyai_counts`, from the shapes this chip holds and the newest step's
counters), over the traced time of the device operations whose HLO
instruction matches `pattern`, a traced step.

`kernel`: "flash" (every block's attention, forward and backward, at the
heads' own widths) or "experts" (the grouped products of every expert
block over the assignments the step computed)."""
from .. import flops, joyai_counts as counts, trace as tr
from ..programs import paddle_train_stats


def read(evidence, pattern, kernel):
    trace, peaks = evidence.get("trace"), evidence.get("peaks")
    steps = evidence.get("traced_steps")
    if not trace or not peaks or not steps:
        return None
    seconds, events = tr.seconds_matching(trace, pattern)
    if not events:
        return None
    cfg, mix = evidence["config"], evidence["traffic"]
    dense, expert = counts.blocks(cfg)
    if kernel == "flash":
        ops, moved = counts.flash_attention_train(
            mix["batch_rows"], cfg["num_attention_heads"], mix["seq"],
            cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])
        ops, moved = ops * (dense + expert), moved * (dense + expert)
    else:
        stats = paddle_train_stats.newest_train_step_stats()
        if not stats or "routed_computed" not in stats:
            return None
        ops, moved = counts.expert_products_train(
            cfg, stats["routed_computed"], expert)
    least, _ = flops.roofline_seconds(ops, moved, peaks)
    return 100.0 * least * steps / seconds
