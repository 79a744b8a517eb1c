"""R4 golden known-bad (lax surface): named-axis collectives inside an
eagerly dispatched fn without the dispatch.mark_collective stamp — the
closure scan cannot key the axis binding, so every cycle containing the
op poisons. shard_map-only bodies never reach the funnel and are clean."""
import jax

from jax import shard_map
from paddle_tpu.ops.dispatch import call_op, mark_collective


def bad_unstamped_ppermute(tensor, perm):
    def fn(v):
        return jax.lax.ppermute(v, "pipe", perm)       # line 13: unstamped
    return call_op("p2p.ppermute", fn, (tensor,))


def bad_unstamped_alltoall(tensor):
    return call_op(
        "moe.dispatch",
        lambda v: jax.lax.all_to_all(v, "expert",      # line 20: unstamped
                                     split_axis=0, concat_axis=0),
        (tensor,))


def good_stamped_ppermute(tensor, perm, key):
    """The fixed form: the stamp keys the fn before any closure walk."""
    def fn(v):
        return jax.lax.ppermute(v, "pipe", perm)
    mark_collective(fn, key)
    return call_op("p2p.ppermute", fn, (tensor,))


def good_shard_map_body(tensor, mesh, specs):
    """A compiled SPMD program: the collective is the intended lowering
    and never touches the dispatch cache."""
    def body(v):
        return jax.lax.ppermute(v, "pipe", [(0, 1)])
    return shard_map(body, mesh=mesh, in_specs=specs,
                     out_specs=specs)(tensor)
