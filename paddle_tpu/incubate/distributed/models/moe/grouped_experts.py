"""The expert block of a TRAINING program that holds a share of the experts.

`held_experts.py` serves: each held expert runs over EVERY token of the
call, masked by the choice, which a decode launch of 128 tokens can afford.
A training step of 16,384 tokens cannot (8 experts x 16,384 rows where
some 4,096 assignments are theirs: 28 ms forward and backward on a v5e
against 13 grouped, `PERF.md` section 4), and `moe_layer.py`'s capacity
drops tokens. This block

  * routes over ALL the experts the router was published with (`route`,
    float32, shared with the serving block) and is TOLD which it holds: it
    computes those and adds nothing for a chosen expert held elsewhere;
  * gathers the assignments to held experts, in expert order, into a
    buffer of STATIC size that no routing can overflow: a token chooses an
    expert at most once, so at most ``T * min(topk, held)`` assignments are
    held here, and that is the buffer. No capacity, no drop at any load:
    `routed_computed == routed_held` always, and the counters show it. The
    rows past the last group cost their bytes, not their products: the
    grouped product skips them (the chip's readings: a buffer of 8 T rows
    is as fast as one of T);
  * multiplies the ragged groups by their experts with grouped products
    (`jax.lax.ragged_dot`: one product over the sorted rows, each group
    against its own expert's matrix), weighs each row by its assignment
    and adds it back to its token. A grouped product may leave ANYTHING in
    the rows past its groups, forward and backward (a TPU leaves what the
    memory held): those rows are cut off on both sides of the products, so
    nothing of them reaches a token or a gradient;
  * is differentiable throughout by JAX's own rules (the choice is a
    constant of the step; the weights carry the router's gradient).

Pure `jax.numpy` over arrays; the model that owns the weights calls it from
inside its compiled step. On one chip it runs without the exchange that
expert parallelism adds, and nothing here stands in for the absent chips.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .held_experts import route, _sorted_assignments, COUNTERS

__all__ = ["grouped_held_expert_block", "COUNTERS"]


def grouped_held_expert_block(u, router_w, bias, gate_w, up_w, down_w, *,
                              topk, scaling, first_held=0):
    """``sum over chosen held e of w_e E_e(u)`` for tokens u ``[T, d]``,
    E_e a SwiGLU, the router's scores sigmoids and its weights normalised
    over the chosen (the one routing that trains here), and the call's
    counters.

    router_w ``[d, experts]`` (every expert the router ranks); the stacked
    gate/up ``[E, d, f]`` and down ``[E, f, d]`` hold experts ``first_held
    .. first_held + E - 1``. Returns ``(m [T, d] float32, counters int32
    [len(COUNTERS)] in `held_experts.COUNTERS`' order (no identity experts
    here: that count is 0), ranked load int32 [experts]: the tokens that
    chose each expert the router ranks, held or not, which is what a
    balance rule reads)``."""
    t, d = u.shape
    held = gate_w.shape[0]
    chosen, weights = route(u, router_w, bias, topk, scaling,
                            scoring="sigmoid", normalise=True)
    # a token chooses an expert at most once: no routing holds more
    rows = t * min(topk, held)
    flat = _sorted_assignments(chosen, first_held, held, rows)
    ranked = jnp.sum(chosen.reshape(-1)[:, None] == jnp.arange(
        router_w.shape[1], dtype=chosen.dtype)[None], axis=0, dtype=jnp.int32)
    load = ranked[first_held:first_held + held]      # each held expert's
    total = jnp.sum(load)
    mine = (jnp.arange(rows, dtype=jnp.int32) < total)[:, None]
    token = flat // topk
    def product(a, w):
        """Each group of rows against its own expert's matrix; the rows
        past the groups cut off behind it: zeros forward, and backward
        no cotangent of theirs enters the product."""
        return jnp.where(mine, jax.lax.ragged_dot(
            a, w, load, preferred_element_type=jnp.float32), 0.0)

    with jax.named_scope("expert_products"):
        # cut off in FRONT of the products too: their gradient of a row
        # past the groups is whatever they left there, and must not reach
        # that row's token
        x = jnp.where(mine, jnp.take(u, token, axis=0), 0)    # [rows, d]
        hidden = (jax.nn.silu(product(x, gate_w))
                  * product(x, up_w)).astype(u.dtype)
        y = product(hidden, down_w)                           # [rows, d] f32
    w_row = jnp.where(mine[:, 0], jnp.take(weights.reshape(-1), flat), 0.0)
    out = jnp.zeros((t, d), jnp.float32).at[token].add(y * w_row[:, None])
    counters = jnp.stack([
        total, jnp.int32(0), jnp.int32(t * topk) - total,
        jnp.max(load), jnp.sum(load == 0),
        # what the grouped products computed: the rows inside a group
        jnp.sum(mine)]).astype(jnp.int32)
    return out, counters, ranked
