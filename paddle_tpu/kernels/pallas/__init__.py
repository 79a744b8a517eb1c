"""Pallas kernel tier for the serving hot path.

Reference analog: the PHI fused-kernel layer (fluid/operators/fused/) —
here the fusions target the continuous-batching decode step instead of
training graphs: paged decode attention that consumes the block-pool KV
cache (serving/cache.py) where it lies, a Pallas kernel that copies only
the pages that hold tokens and a length-bounded pure-JAX loop with int8
dequant fused into its gathers (quantization/kv_cache.py); and the served
expert block's grouped products as a tiled matmul over rows sorted by
expert (grouped_matmul.py).

Modules import lazily from the routing layer
(nn/functional/attention.py) so a CPU-only process never pays the Pallas
import unless a kernel is actually requested.
"""
from . import grouped_matmul, paged_attention  # noqa: F401
