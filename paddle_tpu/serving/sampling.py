"""Compiled stochastic sampling: the per-slot sampler head of the ONE decode.

Reference analog: the reference samples on the host — `paddle.tensor.search`
top-k/top-p kernels invoked per step from the python generation loop
(generation_utils.py), with a host round-trip between logits and the next
token. Every sampler-config change there recompiles nothing because nothing
is compiled; here EVERYTHING is compiled, so the sampler must be a *value*
program, not a *structure* program:

  * per-slot temperature / top-k / top-p / repetition-penalty / seed live in
    fixed ``[max_batch]`` buffers, edited like tokens/lens on join/leave —
    never reshaping, never retracing. Greedy is temperature=0 under the SAME
    executable; a batch may mix greedy and five different sampler configs
    and decode still compiles exactly once;
  * per-slot keys are ``fold_in(PRNGKey(seed), position)`` stream positions
    derived in-graph (framework/random.py::slot_sample_keys), where
    ``position`` is the count of known context tokens at sampling time.
    Replays — preemption re-prefill, kill-9 resume — restore the same
    positions, so a given (seed, prompt, sampler config) reproduces its
    token stream byte-identically;
  * the whole stochastic path sits under one ``lax.cond`` on
    ``any(temperature > 0)``: an all-greedy batch never executes a sort.

Masking order follows the de-facto contract (HF logits processors):
repetition penalty -> temperature -> top-k -> top-p, then Gumbel-max
(``jax.random.categorical``) over the surviving logits. ``top_k=0`` and
``top_p>=1`` are exact no-ops, and every per-slot config with
``temperature=0`` returns ``argmax`` of the RAW logits — bit-identical to
the greedy-only decode this module replaces.

Logprobs ride the same program: the chosen-token logprob (from the raw,
pre-masking distribution) and an optional static-K panel of top-k
alternatives are extra value outputs — zero additional compiles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework.random import slot_sample_keys

__all__ = ["SAMPLER_VERSION", "validate_sampler", "default_seed",
           "apply_repetition_penalty", "apply_temperature", "apply_top_k",
           "apply_top_p", "sample_tokens"]

# Keyed into the AOT decode digest: any change to the sampling math below
# must bump this so stale exported executables are refused, not replayed.
# v2: top-k and top-p share one descending sort (XLA CPU sorts dominate the
# head's cost; summation order inside the shared softmax shifts borderline
# nucleus ties, so old exports must not replay).
SAMPLER_VERSION = 2

_NEG_INF = -1e30


def default_seed(request_id):
    """Process-stable default seed for a request: crc32 of the request id.
    The rid serializes through crash checkpoints, so a resumed request that
    never chose a seed still replays the same stream."""
    import zlib
    return zlib.crc32(str(request_id).encode("utf-8")) & 0xFFFFFFFF


def validate_sampler(temperature, top_k, top_p, repetition_penalty):
    """Raise ValueError (engine surfaces it as a `sampler_mismatch` refusal)
    for parameter values outside the compiled program's contract."""
    t = float(temperature)
    if not (t >= 0.0) or t != t or t == float("inf"):
        raise ValueError(f"temperature must be finite and >= 0, got {temperature}")
    if int(top_k) < 0:
        raise ValueError(f"top_k must be >= 0 (0 disables), got {top_k}")
    p = float(top_p)
    if not (0.0 < p <= 1.0):
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    r = float(repetition_penalty)
    if not (r > 0.0) or r == float("inf"):
        raise ValueError(
            f"repetition_penalty must be finite and > 0, got {repetition_penalty}")


def apply_repetition_penalty(logits, history, valid, penalty):
    """Divide positive / multiply negative logits of already-seen tokens by
    ``penalty`` (the CTRL rule). ``history`` is ``[S, C]`` int32 context
    ids, ``valid`` a ``[S, C]`` bool mask of which entries are real,
    ``penalty`` ``[S]`` with 1.0 as the exact no-op."""
    s, v = logits.shape
    rows = jnp.arange(s, dtype=jnp.int32)[:, None]
    ids = jnp.clip(history, 0, v - 1)
    seen = jnp.zeros((s, v), dtype=jnp.bool_).at[rows, ids].max(valid)
    pen = penalty[:, None].astype(logits.dtype)
    penalized = jnp.where(logits > 0, logits / pen, logits * pen)
    return jnp.where(seen, penalized, logits)


def apply_temperature(logits, temperature):
    """Scale by 1/T with a safe divisor — T=0 slots are decided by the
    greedy argmax select downstream, never by this branch's values."""
    t = jnp.maximum(temperature, 1e-6)[:, None].astype(logits.dtype)
    return logits / t


def apply_top_k(logits, top_k):
    """Keep the k highest logits per slot (ties at the k-th value survive).
    ``top_k`` is ``[S]`` int32; 0 disables. One descending sort serves every
    slot — k is a *value*, the kth threshold is a gather."""
    s, v = logits.shape
    desc = -jnp.sort(-logits, axis=-1)
    kth_idx = jnp.clip(top_k - 1, 0, v - 1)[:, None]
    kth = jnp.take_along_axis(desc, kth_idx, axis=-1)
    thresh = jnp.where((top_k > 0)[:, None], kth, _NEG_INF)
    return jnp.where(logits < thresh, _NEG_INF, logits)


def apply_top_p(logits, top_p):
    """Nucleus filter: keep the smallest prefix of the descending
    distribution with cumulative mass >= p (exclusive-mass test, so the
    top-1 token always survives). ``top_p`` is ``[S]``; >= 1 is an exact
    no-op (enforced by mask, not by trusting cumsum round-off)."""
    probs = jax.nn.softmax(logits, axis=-1)
    desc = -jnp.sort(-probs, axis=-1)
    exclusive = jnp.cumsum(desc, axis=-1) - desc
    keep_sorted = exclusive < top_p[:, None]
    min_kept = jnp.min(jnp.where(keep_sorted, desc, jnp.inf), axis=-1,
                       keepdims=True)
    keep = (probs >= min_kept) | (top_p >= 1.0)[:, None]
    return jnp.where(keep, logits, _NEG_INF)


@jax.named_scope("sample_tokens")
def sample_tokens(logits, temperature, top_k, top_p, repetition_penalty,
                  seeds, positions, history, valid, logprobs_topk=0):
    """The sampler head. All inputs are per-slot value arrays over a fixed
    ``[S, V]`` logits block; returns
    ``(next_token[S] i32, chosen_logprob[S] f32,
       alt_ids[S, K] i32, alt_logprobs[S, K] f32)``
    with K = ``logprobs_topk`` (a static engine config, keyed into the AOT
    digest; K=0 yields empty panels). Fully traceable; compiles once."""
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    stochastic = temperature > 0

    def _stoch(lg):
        lg = apply_repetition_penalty(lg, history, valid, repetition_penalty)
        lg = apply_temperature(lg, temperature)
        # ONE descending sort serves both filters (XLA sorts dominate the
        # head's cost; apply_top_k/apply_top_p keep the reference one-filter
        # semantics but each pay for their own sort).
        v = lg.shape[-1]
        desc = -jnp.sort(-lg, axis=-1)
        # top-k threshold: the kth-largest logit (ties at kth survive);
        # k=0 disables via a -inf threshold.
        kth_idx = jnp.clip(top_k - 1, 0, v - 1)[:, None]
        kth = jnp.take_along_axis(desc, kth_idx, axis=-1)
        k_thresh = jnp.where((top_k > 0)[:, None], kth, _NEG_INF)
        # top-p threshold: softmax over the sorted row IS the sorted
        # distribution, so the exclusive-mass prefix maps straight back to
        # a logit threshold (the smallest kept logit; ties survive exactly
        # as in apply_top_p's prob-space test). p >= 1 is an exact no-op.
        p_desc = jax.nn.softmax(desc, axis=-1)
        exclusive = jnp.cumsum(p_desc, axis=-1) - p_desc
        keep_sorted = exclusive < top_p[:, None]
        n_keep = jnp.maximum(jnp.sum(keep_sorted, axis=-1), 1)
        pth = jnp.take_along_axis(desc, (n_keep - 1)[:, None], axis=-1)
        p_thresh = jnp.where((top_p < 1.0)[:, None], pth, _NEG_INF)
        thresh = jnp.maximum(k_thresh, p_thresh)
        lg = jnp.where(lg < thresh, _NEG_INF, lg)
        keys = slot_sample_keys(seeds, positions)
        def one(key, row):
            return jax.random.categorical(key, row)
        return jax.vmap(one)(keys, lg).astype(jnp.int32)

    sampled = jax.lax.cond(jnp.any(stochastic), _stoch,
                           lambda lg: greedy, logits)
    nxt = jnp.where(stochastic, sampled, greedy)

    logp = jax.nn.log_softmax(logits, axis=-1)
    chosen = jnp.take_along_axis(logp, nxt[:, None], axis=-1)[:, 0]
    k = int(logprobs_topk)
    if k > 0:
        alt_lps, alt_ids = jax.lax.top_k(logp, k)
        alt_ids = alt_ids.astype(jnp.int32)
    else:
        s = logits.shape[0]
        alt_ids = jnp.zeros((s, 0), jnp.int32)
        alt_lps = jnp.zeros((s, 0), jnp.float32)
    return nxt, chosen, alt_ids, alt_lps
