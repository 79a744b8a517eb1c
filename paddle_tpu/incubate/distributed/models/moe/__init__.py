"""Mixture-of-Experts. Reference analog:
python/paddle/incubate/distributed/models/moe/ (MoELayer + gates)."""
from .moe_layer import MoELayer  # noqa: F401
from .gate import top1_dispatch, top2_dispatch, naive_dispatch  # noqa: F401
from .held_experts import held_expert_block, route  # noqa: F401
