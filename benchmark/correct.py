"""The comparison that decides `correct`: the plain reference driven at the
timed sizes, and the numbers compared. The reference is the module that the
configuration file names (`benchmark/reference/__init__.py` has its
contract); its weights and batches are made here from the seed; nothing of
the program's is read."""
from __future__ import annotations

import functools
import importlib
import statistics

import jax
import jax.numpy as jnp
import numpy as np

from . import seeded
from .reference import common

WEIGHT_DTYPE = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def reference_of(cfg):
    """The configuration's plain reference, by the name in its file."""
    return importlib.import_module(cfg["reference"])


def weight_maker(cfg, seed):
    """`make(shardings or None)` -> the cell's weights, in the type the
    configuration serves them in. The program and the reference each call
    it; the same seed gives the same values wherever they are placed."""
    shapes = reference_of(cfg).param_shapes(cfg)
    dtype = WEIGHT_DTYPE[cfg["precision"]["params"]]
    return lambda shardings=None: seeded.make_weights(
        shapes, seed, dtype, cfg["initializer_range"], shardings)


# -- training -------------------------------------------------------------

def _spread(shape, mesh):
    """Shard a reference leaf over the 1-D mesh on its first axis that
    divides; small leaves are replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    n = mesh.devices.size
    for axis, size in enumerate(shape):
        if size % n == 0 and size >= 1024:
            return NamedSharding(mesh, P(*([None] * axis + ["x"])))
    return NamedSharding(mesh, P())


def reference_train(cfg, traffic, seed, devices, steps, precision="float32"):
    """The reference's first `steps` steps on the cell's own weights and
    batches: {"losses": [...], "grad_norms": {leaf: x}, "delta_norms":
    {leaf: x}} as floats. On several devices the leaves and the rows are
    spread over them (plain SPMD: the mathematics is unchanged)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    ref = reference_of(cfg)
    shapes = ref.param_shapes(cfg)
    rows, seq = traffic["batch_rows"], traffic["seq"]
    if len(devices) > 1:
        mesh = Mesh(np.asarray(devices), ("x",))
        shardings = {k: _spread(s, mesh) for k, s in shapes.items()}
        rows_sharding = NamedSharding(mesh, P("x", None))
    else:
        shardings = rows_sharding = None
    params = jax.tree.map(lambda w: w.astype(jnp.float32),
                          weight_maker(cfg, seed)(shardings))
    batches = seeded.make_batches(steps, rows, seq, cfg["vocab_size"], seed,
                                  rows_sharding)
    block = traffic.get("reference_rows_per_block")

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def one_step(p, m1, m2, ids, labels, t):
        loss, grads = ref.loss_and_grads(p, ids, labels, cfg, precision,
                                         block)
        new_p, m1, m2 = common.adamw_step(p, m1, m2, grads, t,
                                          cfg["optimizer"])
        return new_p, m1, m2, loss, common.leaf_norms(grads)

    start = jax.tree.map(jnp.copy, params)
    m1 = jax.tree.map(jnp.zeros_like, params)
    m2 = jax.tree.map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for t, (ids, labels) in enumerate(batches, 1):
        params, m1, m2, loss, norms = one_step(
            params, m1, m2, ids, labels, jnp.asarray(t, jnp.float32))
        losses.append(float(loss))
        grad_norms = grad_norms or _floats(norms)
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta_norms(params, start)}


def _floats(tree):
    return {k: float(v) for k, v in jax.device_get(tree).items()}


def leaf_norms(tree):
    return _floats(jax.jit(common.leaf_norms)(tree))


def delta_norms(now, start):
    return _floats(jax.jit(lambda a, b: common.leaf_norms(
        {k: a[k] - b[k].astype(jnp.float32) for k in a}))(now, start))


def worst_leaf_gap(got, want):
    """The gap between two norms of one leaf, against the reference's norm
    of that leaf or of the median leaf, whichever is larger (some
    gradients are all but zero). Returns (worst gap, its leaf)."""
    floor = statistics.median(want.values())
    gaps = {k: abs(got[k] - want[k]) / max(want[k], floor) for k in want}
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def loss_gap(got, want):
    return max(abs(g - w) / abs(w) for g, w in zip(got, want))


def train_numbers(got, want):
    """The numbers a train cell compares, from the program's readings and
    the reference's."""
    grad, grad_leaf = worst_leaf_gap(got["grad_norms"], want["grad_norms"])
    delta, delta_leaf = worst_leaf_gap(got["delta_norms"],
                                       want["delta_norms"])
    return {"loss_gap": loss_gap(got["losses"], want["losses"]),
            "grad_norm_gap": grad, "delta_norm_gap": delta,
            "leaves": {"grad_norm_gap": grad_leaf,
                       "delta_norm_gap": delta_leaf}}


# -- serving ----------------------------------------------------------------

def gap_numbers(name, gaps):
    """What a serve cell reads of the gaps at all the sampled positions:
    the widest; the mean over every served token of the sample (a sum over
    tokens, not a mean of the requests' means); and the 99th percentile,
    printed for the record and compared with nothing. The widest is set
    by one position: where the model makes no discrete choice it catches
    one wrong token. Where it makes one (top-k of a router) one rounding
    flips it, in a sound program as in one of lower precision: only the
    mean tells those two apart, and neither number is shown to catch one
    wrong token there (`PERF.md` section 4, "a model that routes")."""
    gaps = np.concatenate([np.asarray(g, np.float64) for g in gaps])
    return {name: float(np.max(gaps)), name + "_mean": float(np.mean(gaps)),
            name + "_p99": float(np.percentile(gaps, 99))}


def served_gaps(cfg, seed, streams, pad_to, control=None):
    """For each (prompt ids, served ids): run the reference once over
    prompt + served tokens and read, at every served position, how far the
    served token's logit lies below the reference's best. With `control`
    (a precision) also how far below the best lies the token that the
    reference computed in that precision puts first. Returns
    `gap_numbers` of each, over all served tokens of all the streams."""
    ref = reference_of(cfg)
    weights = weight_maker(cfg, seed)()

    @functools.partial(jax.jit, static_argnames=("precision",))
    def read(w, ids, precision="float32"):
        logits = ref.forward(w, ids, cfg, "float32")[0]
        out = {"best": jnp.max(logits, -1), "logits": logits}
        if precision != "float32":
            low = ref.forward(w, ids, cfg, precision)[0]
            first = jnp.argmax(low, -1)
            out["control_gap"] = out["best"] - jnp.take_along_axis(
                logits, first[:, None], -1)[:, 0]
        return out

    gaps, control_gaps = [], []
    for prompt, served in streams:
        total = len(prompt) + len(served)
        ids = np.zeros((1, pad_to), np.int32)
        ids[0, :total] = list(prompt) + list(served)
        out = read(weights, jnp.asarray(ids), precision=control or "float32")
        # the logits at position t choose token t+1
        at = np.arange(len(prompt) - 1, total - 1)
        tok = np.asarray(served, np.int64)
        chosen = np.asarray(out["logits"][at][np.arange(len(at)), tok])
        gaps.append(np.asarray(out["best"][at]) - chosen)
        if control:
            control_gaps.append(np.asarray(out["control_gap"][at]))
    numbers = gap_numbers("logit_gap", gaps)
    if control:
        numbers.update(gap_numbers("control_logit_gap", control_gaps))
    numbers["tokens"] = sum(len(g) for g in gaps)
    return numbers
