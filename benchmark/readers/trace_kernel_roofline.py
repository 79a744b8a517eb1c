"""A kernel's share of its roofline: the least time the chip could take for
the operations and bytes the algorithm needs (`flops.<count>`, from the
shapes one device holds), over the kernel's traced time."""
from .. import flops, trace as tr


def read(evidence, pattern, count, calls_per_layer_step):
    trace, peaks = evidence.get("trace"), evidence.get("peaks")
    if not trace or not peaks:
        return None
    seconds, events = tr.seconds_matching(trace, pattern)
    if not events:
        return None
    cfg, mix = evidence["config"], evidence["traffic"]
    mesh = mix.get("mesh") or {"data": 1, "model": 1}
    ops, moved = getattr(flops, count)(
        mix["batch_rows"] // mesh["data"], cfg["n_head"] // mesh["model"],
        mix["seq"], cfg["n_embd"] // cfg["n_head"])
    least, _ = flops.roofline_seconds(ops, moved, peaks)
    return 100.0 * least * (events / calls_per_layer_step) / seconds
