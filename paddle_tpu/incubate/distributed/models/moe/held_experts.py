"""The expert block of a SERVING program that holds a share of the experts.

`MoELayer` (moe_layer.py) trains: a capacity per expert, tokens past it
dropped, an auxiliary loss. Serving a model cut to one chip's share of an
expert-parallel deployment asks something else of the block:

  * it routes over ALL the experts the router was published with (real
    ones and zero-computation "identity" ones) and is TOLD which real
    experts it holds: it computes those, adds nothing for a chosen real
    expert held elsewhere, and computes the identity experts where the
    token lives (a scale of the token, no matrix product, no exchange);
  * no token is dropped at any load, inside one compiled program of static
    shapes, and an expert no token chose is not read. The products come
    in one of two forms, chosen by a fact of the call's static shape
    (`products_form`; `PERF.md` section 4 has the chip's readings):
    MASKED, each held expert under a `lax.cond` on its own load, over
    every token of the call masked by the choice (a decode launch's
    products are bound by the expert's bytes, not by its rows; but the
    operations are ``held / topk`` x the needed ones once every expert
    is held), or GROUPED, the assignments sorted by expert into a buffer
    no routing can overflow and multiplied each group against its own
    expert: by the tiled Pallas kernel
    (`kernels/pallas/grouped_matmul.py`) where `product_kernel` finds a
    TPU and a shape on its tiles, else by `jax.lax.ragged_dot`
    (`grouped_experts.py`'s products, forward only). Either way
    `computed == held` always (the counters prove it) and a launch's
    time follows its routing;
  * the router works in float32, products at "highest": top-k of its
    scores is a discrete choice and the reference's router is float32 too.

Pure `jax.numpy` over arrays: the model that owns the weights calls it
from inside the engine's programs. On one chip it runs without the
exchange that expert parallelism adds, and nothing here stands in for the
absent chips.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .....kernels.pallas import grouped_matmul as _gm
from .....profiler.events import EVENTS as _EVENTS

__all__ = ["route", "held_expert_block", "masked_products",
           "grouped_products", "products_form", "buffer_rows",
           "product_kernel", "products_run", "COUNTERS"]

# what `held_expert_block` counts of a call's choices, in this order
COUNTERS = ("routed_held", "routed_identity", "routed_elsewhere",
            "load_max", "experts_idle", "routed_computed")


def route(u, router_w, bias, topk, scaling, scoring="softmax",
          normalise=False, epsilon=1e-20):
    """The router: scores over every expert in float32, ``softmax(u W_r)``
    or, with `scoring` "sigmoid", ``sigmoid(u W_r)``; the `topk` of
    ``scores + bias`` chosen (the bias moves the choice and never the
    weight), each weighed ``scaling * score``, with `normalise` over the
    sum of the chosen scores (all `topk`, wherever their experts live)
    plus `epsilon`.
    Returns ``(chosen ids [T, topk], weights [T, topk] float32)``."""
    logits = jnp.matmul(u.astype(jnp.float32), router_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    ranked = scores if bias is None else scores + bias.astype(jnp.float32)
    _, chosen = jax.lax.top_k(ranked, topk)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalise:
        weights = weights / (jnp.sum(weights, -1, keepdims=True)
                             + epsilon)
    return chosen.astype(jnp.int32), jnp.float32(scaling) * weights


def _swiglu(x, gate_w, up_w, down_w):
    """One expert over rows x ``[C, d]`` -> ``[C, d]`` float32."""
    g = jnp.matmul(x, gate_w, preferred_element_type=jnp.float32)
    up = jnp.matmul(x, up_w, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * up).astype(x.dtype)
    return jnp.matmul(h, down_w, preferred_element_type=jnp.float32)


def _sorted_assignments(chosen, first_held, held, rows):
    """flat ``[rows]`` int32: the positions, in the flattened ``[T * k]``
    assignments of chosen ``[T, k]``, of the first `rows` rows of the order
    sorted by expert: the held ones first, grouped by expert, in token
    order inside a group."""
    local = chosen.reshape(-1) - first_held
    key = jnp.where((local >= 0) & (local < held), local, held)
    # stable: inside a group the rows stay in token order
    return jnp.argsort(key, stable=True)[:rows].astype(jnp.int32)


# a block that holds FEWER than twice the experts a token chooses takes the
# grouped form from this many tokens a call (the chip's micro-calls at 8
# held of top 8, 4096 -> 2048: masked 0.85 / 2.68 / 5.19 / 19.6 ms at 128 /
# 1,024 / 2,048 / 8,192 tokens against grouped 0.93 / 2.94 / 3.39 / 5.55;
# `PERF.md` section 4)
_GROUPED_FROM_TOKENS = 2048
# the grouped form's buffer holds at most this many sorted rows (what the
# largest accepted call, a 2,048-token bucket at top 4, already holds): a
# call whose routing could fill more runs the sorted order a buffer at a time
_ROWS_MAX = 8192


def products_form(tokens, topk, held):
    """Which form a served call's products take, from its static shape:
    GROUPED where the block holds at least twice the experts a token
    chooses (the masked form then multiplies ``held / topk`` >= 2 times
    the rows any routing holds, and unrolls `held` conditionals a layer
    into every program); where it holds fewer (a share of many experts:
    8 held of top 8 of 256, 16 held of top 12 of 768) MASKED for a call
    of under `_GROUPED_FROM_TOKENS` tokens, which is bound by the experts'
    bytes in either form, and GROUPED from there on, where the masked
    form multiplies every held expert by every token and the routing
    sends a held expert a few of a hundred. The chip's readings behind
    the rule, by `tokens`: `PERF.md` section 4."""
    if held >= 2 * topk or tokens >= _GROUPED_FROM_TOKENS:
        return "grouped"
    return "masked"


def buffer_rows(tokens, topk, held):
    """The grouped form's buffer, in sorted rows: what any routing can
    hold (a token chooses an expert at most once), at most `_ROWS_MAX`."""
    return min(tokens * min(topk, held), _ROWS_MAX)


def product_kernel(rows, k, n, dtype):
    """Which product multiplies `rows` sorted rows of `dtype` by their
    groups' ``[k, n]`` matrices, from the platform and the call's static
    shape: ``"pallas"``, the tiled kernel, on a TPU over a shape on its
    tiles (`grouped_matmul.is_eligible`), else ``"ragged_dot"``, the
    library's. A TPU's call sent back for its SHAPE is visible: one
    `kernel.fallback` flight-recorder event says why."""
    ok, why = _gm.is_eligible(rows, k, n, dtype)
    if not ok and why not in ("no_pallas", "not_on_tpu"):
        _EVENTS.emit("kernel.fallback", "ragged_expert_matmul",
                     reason="kernel_fallback",
                     detail={"requested": "pallas", "actual": "ragged_dot",
                             "why": why, "rows": rows, "k": k, "n": n,
                             "dtype": jnp.dtype(dtype).name})
    return "pallas" if ok else "ragged_dot"


def products_run(tokens, topk, held, hidden, width, dtype):
    """``(grouped products, those of them the kernel runs)`` of ONE block
    call over `tokens` tokens of `hidden` values with experts `width`
    wide, from the rules the block itself follows (`products_form`,
    `grouped_matmul.is_eligible`): constants of the traced program, for
    the model that counts them."""
    if products_form(tokens, topk, held) != "grouped":
        return 0, 0
    rows = buffer_rows(tokens, topk, held)
    shapes = ((hidden, width), (hidden, width), (width, hidden))
    return len(shapes), sum(_gm.is_eligible(rows, k, n, dtype)[0]
                            for k, n in shapes)


def masked_products(u, local, is_held, valid, weights, gate_w, up_w, down_w,
                    acc):
    """`acc` + every held expert over EVERY token, weighed by the choice
    (0 for a token that did not choose it), an expert no token chose not
    read. local ``[T, k]``: the chosen ids from the first held one;
    is_held ``[T, k]`` bool: the assignments held here; valid ``[T]``
    bool: the tokens that count. Returns ``(acc, load int32 [E],
    assignments computed)``."""
    held = gate_w.shape[0]
    # [T, E]: the weight a token gives each held expert (0: not chosen);
    # a token chooses an expert at most once
    hit = (local[:, :, None] == jnp.arange(held)[None, None, :]) \
        & (is_held & valid[:, None])[:, :, None]
    w_te = jnp.sum(jnp.where(hit, weights[:, :, None], 0.0), axis=1)
    load = jnp.sum(jnp.any(hit, axis=1), axis=0).astype(jnp.int32)   # [E]

    def loaded(e, acc):
        """Expert e over every token, weighed by the choice (0 for a
        token that did not choose it)."""
        y = _swiglu(u, gate_w[e], up_w[e], down_w[e])
        return acc + w_te[:, e:e + 1] * y, load[e]

    computed = jnp.int32(0)
    for e in range(held):
        acc, n = jax.lax.cond(load[e] > 0, functools.partial(loaded, e),
                              lambda acc: (acc, jnp.int32(0)), acc)
        computed = computed + n
    return acc, load, computed


def grouped_products(u, local, is_held, valid, weights, gate_w, up_w, down_w,
                     acc):
    """`masked_products`' contract by sorted assignments: the held, valid
    assignments gathered in expert order into a buffer of
    ``T * min(k, E)`` rows, which no routing overflows (a token chooses
    an expert at most once), each group multiplied by its own expert
    (`product_kernel`: the tiled kernel or `jax.lax.ragged_dot`),
    weighed and added back to its token. The rows past the last group
    cost their bytes, not their products, and whatever the product
    leaves there is cut off behind it. Forward only.

    A call whose routing could hold more than `_ROWS_MAX` rows (8 held of
    top 8 over 8,192 tokens: 65,536, 2.7 GB of float32 temporaries) keeps
    the buffer at `_ROWS_MAX` and runs the SORTED ORDER a buffer at a
    time, under a loop whose trip count is the routing's
    (``ceil(held assignments / _ROWS_MAX)``, one where a held expert sees
    its share of the tokens): no token is dropped at any load and the
    temporaries are bounded by the buffer, not by ``T * k``."""
    t, _ = u.shape
    held, topk = gate_w.shape[0], local.shape[1]
    most = t * min(topk, held)
    rows = buffer_rows(t, topk, held)
    # an assignment not taken lies outside every group
    mine_id = jnp.where(is_held & valid[:, None], local, -1)
    flat = _sorted_assignments(mine_id, 0, held, most)
    load = jnp.sum(mine_id.reshape(-1)[:, None] == jnp.arange(
        held, dtype=mine_id.dtype)[None], axis=0, dtype=jnp.int32)
    total = jnp.sum(load)

    def buffer(acc, flat, load, mine):
        """`acc` + one buffer of sorted rows `flat` in groups of `load`,
        of which `mine` ``[rows, 1]`` are some token's."""
        token = flat // topk

        def product(a, w):
            if product_kernel(rows, *w.shape[1:], a.dtype) == "pallas":
                y = _gm.grouped_matmul(a, w, load)
            else:
                y = jax.lax.ragged_dot(a, w, load,
                                       preferred_element_type=jnp.float32)
            return jnp.where(mine, y, 0.0)

        with jax.named_scope("grouped_experts"):
            x = jnp.take(u, token, axis=0)                   # [rows, d]
            hidden = (jax.nn.silu(product(x, gate_w))
                      * product(x, up_w)).astype(u.dtype)
            y = product(hidden, down_w)                      # [rows, d] f32
        w_row = jnp.where(mine[:, 0], jnp.take(weights.reshape(-1), flat),
                          0.0)
        return acc.at[token].add(y * w_row[:, None])

    if rows == most:
        mine = (jnp.arange(rows, dtype=jnp.int32) < total)[:, None]
        return (buffer(acc, flat, load, mine), load,
                jnp.sum(mine).astype(jnp.int32))
    # buffer i holds the sorted rows i * rows .. (i + 1) * rows - 1: of each
    # group the part that lies there
    flat = jnp.pad(flat, (0, -most % rows))
    ends = jnp.cumsum(load, dtype=jnp.int32)
    rows32 = jnp.int32(rows)

    def one(i, acc):
        lo = i * rows32
        here = jnp.clip(ends - lo, 0, rows32) \
            - jnp.clip(ends - load - lo, 0, rows32)
        mine = (lo + jnp.arange(rows, dtype=jnp.int32) < total)[:, None]
        return buffer(acc, jax.lax.dynamic_slice_in_dim(flat, lo, rows),
                      here.astype(jnp.int32), mine)

    trips = (total + (rows32 - 1)) // rows32
    return (jax.lax.fori_loop(jnp.int32(0), trips.astype(jnp.int32), one,
                              acc), load, total.astype(jnp.int32))


def held_expert_block(u, router_w, bias, gate_w, up_w, down_w, *, topk,
                      real_experts, scaling, first_held=0, valid=None,
                      scoring="softmax", normalise=False, epsilon=1e-20):
    """``sum over chosen held e of w_e E_e(u) + sum over chosen identity e
    of w_e u`` for tokens u ``[T, d]``, and the call's counters.

    router_w ``[d, real_experts + identity experts]``; the stacked
    gate/up/down weights hold experts ``first_held .. first_held + E - 1``
    of the `real_experts`; ids from `real_experts` up are identity
    experts. `scoring`, `normalise` and `epsilon` are the router's form
    (`route`). `valid` ``[T]`` bool masks padding (a prompt's bucket, an
    empty slot) out of the counts and of the products. Returns ``(m [T, d]
    float32, counters int32 [len(COUNTERS)])``."""
    t, d = u.shape
    held = gate_w.shape[0]
    if valid is None:
        valid = jnp.ones((t,), bool)
    with jax.named_scope("router"):
        chosen, weights = route(u, router_w, bias, topk, scaling, scoring,
                                normalise, epsilon)
    is_identity = chosen >= real_experts
    local = chosen - first_held
    is_held = (local >= 0) & (local < held) & ~is_identity
    # the identity experts: a scale of the token
    scale = jnp.sum(jnp.where(is_identity, weights, 0.0), axis=-1)
    out = scale[:, None] * u.astype(jnp.float32)
    products = grouped_products \
        if products_form(t, topk, held) == "grouped" else masked_products
    out, load, computed = products(u, local, is_held, valid, weights,
                                   gate_w, up_w, down_w, out)
    counted = valid[:, None]
    n_held = jnp.sum(is_held & counted)
    counters = jnp.stack([
        n_held, jnp.sum(is_identity & counted),
        jnp.sum(~is_held & ~is_identity & counted),
        jnp.max(load), jnp.sum(load == 0),
        # what the experts that ran computed: the tokens that chose them
        computed]).astype(jnp.int32)
    return out, counters
