"""The gated delta rule with a decay a CHANNEL (Kimi Delta Attention; Kimi
Linear, arXiv:2510.26692): a linear-attention layer whose memory of the
past is a MATRIX STATE a head that every token updates and none extends.

With q, k in ``R^D`` (k of unit length), v in ``R^Dv``, a log decay
``g <= 0`` a channel of k (``a = exp(g)``) and a step ``beta`` in [0, 2]
(above 1 the transition ``I - beta k k^T`` has a NEGATIVE eigenvalue), the
state ``S`` in ``R^{D x Dv}`` of a head, zeros before a sequence, goes

    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

Two forms of it (PERF.md section 4 has the chip's readings behind each
choice), `S` and the decay in float32 THROUGHOUT and every
product that reads the state at "highest" (a float32 product at the TPU's
default rounds its operands to bfloat16: a bfloat16 state by another name):

`kda_chunk_scan`, a prompt: chunks of `CHUNK` tokens in the WY form. With
``G_r`` the decays summed from the chunk's start through row r, and
``u_r = beta_r (v_r - S_{r-1}^T Diag(a_r) k_r)`` (so that ``S_r = Diag(a_r)
S_{r-1} + k_r u_r^T``), the chunk's u solve ``(I + A) U = beta (V - (K
e^G) S_0)`` with ``A_rj = beta_r sum_d k_rd k_jd e^(G_rd - G_jd)`` below
the diagonal; then ``O = (Q e^G) S_0 + B U`` with ``B_rj = sum_d q_rd k_jd
e^(G_rd - G_jd)`` on and below it, and ``S_C = Diag(e^G_C) S_0 + (K
e^(G_C - G))^T U``. No exponent is ever positive: A and B take their
decays between sub-blocks of `_SUB` rows relative to the row block's
start (two factors, each at most 1, so the pairs are a matrix product)
and INSIDE a sub-block pair by pair (a decay near 0 underflows to 0, where
``e^-G`` would overflow). A masked position (a bucket's padding: ``beta =
0, g = 0``) leaves the state as it was, so the state handed back is the
state after the prompt's true length.

`kda_decode_step`, one token a slot: the recurrence itself over the
slots' states where they lie (``[layers, slots, H, D, Dv]``, donated),
multiplies and sums on the vector unit (a matrix product of one row
would load a ``D x Dv`` matrix a head for it); an inactive slot's state is
written back as it was. On a TPU a Pallas kernel (`_update_kernel`: a
slot's eight heads a grid step, the state read once and written once into
the buffer it came in; XLA's own fusions read it twice), elsewhere the
same lines of `jax.numpy`.

`kda_short_conv` / `kda_short_conv_step`: the depthwise causal
convolution of a few taps and the SiLU that q, k and v pass first, behind
the last ``taps - 1`` inputs a slot keeps of each.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ._common import ZERO, on_tpu

try:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    _HAS_PALLAS = True
except ImportError:                      # pragma: no cover
    _HAS_PALLAS = False

__all__ = ["kda_chunk_scan", "kda_decode_step", "kda_short_conv",
           "kda_short_conv_step", "update_form", "CHUNK"]

CHUNK = 64       # tokens a chunk of the WY form
_SUB = 16        # rows of a sub-block whose pairs are decayed one by one
_SPAN = 512      # tokens whose chunks are prepared at once (temporaries)
_HEADS = 8       # heads a grid step of the one-token update's kernel
_HIGHEST = jax.lax.Precision.HIGHEST


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_HIGHEST,
                      preferred_element_type=jnp.float32)


def _pair_products(q, k, g_sum, beta):
    """A ``[..., C, C]`` (strictly lower, times beta a row) and B (lower,
    the diagonal with it) of a chunk's rows q, k ``[..., C, D]`` under the
    summed decays g_sum ``[..., C, D]``."""
    c = q.shape[-2]
    sub = min(_SUB, c)
    nb = c // sub
    lead = q.shape[:-2]
    d = q.shape[-1]

    def blocks(x):
        return x.reshape(lead + (nb, sub, d))

    gb, qb, kb = blocks(g_sum), blocks(q), blocks(k)
    # the decays summed up to each row block's start
    start = jnp.concatenate(
        [jnp.zeros(lead + (1, d), g_sum.dtype), gb[..., :-1, -1, :]], -2)
    rows = jnp.exp(gb - start[..., :, None, :])           # <= 1
    # column j as row block I sees it (masked where j is not before I)
    cols = k[..., None, :, :] * jnp.exp(jnp.minimum(
        start[..., :, None, :] - g_sum[..., None, :, :], 0.0))
    before = (jnp.arange(c)[None, None, :] // sub
              < jnp.arange(nb)[:, None, None])             # [nb, 1, C]
    off_a = jnp.where(before, _mm("...ird,...ijd->...irj", kb * rows, cols),
                      0.0)
    off_b = jnp.where(before, _mm("...ird,...ijd->...irj", qb * rows, cols),
                      0.0)
    # inside a sub-block, pair by pair: e^(G_r - G_j), j <= r
    decay = jnp.exp(jnp.minimum(
        gb[..., :, :, None, :] - gb[..., :, None, :, :], 0.0))
    lower = jnp.arange(sub)[:, None] >= jnp.arange(sub)[None, :]
    kd = kb[..., :, None, :, :] * decay
    in_a = jnp.where(lower & ~jnp.eye(sub, dtype=bool),
                     jnp.sum(kb[..., :, :, None, :] * kd, -1), 0.0)
    in_b = jnp.where(lower, jnp.sum(qb[..., :, :, None, :] * kd, -1), 0.0)
    eye = jnp.eye(nb, dtype=q.dtype)

    def whole(off, inside):
        diag = inside[..., :, :, None, :] * eye[:, None, :, None]
        return (off.reshape(lead + (nb, sub, nb, sub)) + diag).reshape(
            lead + (c, c))

    return whole(off_a, in_a) * beta[..., :, None], whole(off_b, in_b)


def _prepare(q, k, v, g, beta):
    """What a span's chunks need of themselves, before any state: rows
    ``[..., n, C, .]`` -> (W, U~, Q e^G, K e^(G_C - G), B, e^G_C)."""
    g_sum = jnp.cumsum(g, axis=-2)
    grow = jnp.exp(g_sum)
    a, b = _pair_products(q, k, g_sum, beta)
    c = q.shape[-2]
    rhs = jnp.concatenate([k * grow, v], -1) * beta[..., None]
    solved = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(c, dtype=a.dtype), rhs, lower=True, unit_diagonal=True)
    d = q.shape[-1]
    last = g_sum[..., -1:, :]
    return (solved[..., :d], solved[..., d:], q * grow,
            k * jnp.exp(last - g_sum), b, jnp.exp(last[..., 0, :]))


@jax.named_scope("kda_chunk_scan")
def kda_chunk_scan(q, k, v, g, beta, state):
    """The delta rule over a prompt. q, k ``[B, T, H, D]`` (k of unit
    length), v ``[B, T, H, Dv]``, in the activations' type; g ``[B, T, H,
    D]`` (log decays, <= 0) and beta ``[B, T, H]`` float32; state ``[B, H,
    D, Dv]`` float32: the state before the first token. Returns (o ``[B, T, H, Dv]`` float32, the state after
    the last token). Any T: the rows behind it up to whole chunks are
    masked here as a caller masks a bucket's padding."""
    f32 = jnp.float32
    b, t, h, d = q.shape
    chunk = min(CHUNK, -(-t // _SUB) * _SUB)      # a short prompt: one chunk
    span = max(chunk, min(_SPAN, -(-t // chunk) * chunk) // chunk * chunk)
    padded = -(-t // span) * span

    def spans(x):
        x = jnp.pad(x, ((0, 0), (0, padded - t))
                    + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, padded // span, span // chunk, chunk)
                      + x.shape[2:])
        # [spans, B, H, chunks, C, .]
        return jnp.moveaxis(jnp.moveaxis(x, 4, 2), 1, 0)

    xs = (spans(q), spans(k), spans(v), spans(g),
          spans(beta[..., None])[..., 0])

    def one_span(state, x):
        # widened a span at a time: q, k and v arrive in the activations'
        # type and are held whole in nothing wider
        parts = _prepare(*(part.astype(f32) for part in x))
        # chunk by chunk, the state between them
        def one_chunk(s, c):
            w, u0, qg, kg, bm, grow = c
            u = u0 - _mm("bhcd,bhdv->bhcv", w, s)
            o = _mm("bhcd,bhdv->bhcv", qg, s) + _mm("bhcj,bhjv->bhcv", bm, u)
            s = grow[..., None] * s + _mm("bhcd,bhcv->bhdv", kg, u)
            return s, o
        state, o = jax.lax.scan(
            one_chunk, state, tuple(jnp.moveaxis(p, 2, 0) for p in parts))
        return state, o                           # [chunks, B, H, C, Dv]

    state, o = jax.lax.scan(one_span, state.astype(f32), xs)
    # [spans, chunks, B, H, C, Dv] -> [B, T, H, Dv]
    o = jnp.transpose(o, (2, 0, 1, 4, 3, 5)).reshape(b, padded, h, -1)
    return o[:, :t], state


def update_form(heads, width, value_width, interpret=False):
    """Which form the one-token update takes, from where it runs and the
    static shape: ``"pallas"``, the kernel below, on a TPU (or in the
    interpreter) over heads in whole groups of `_HEADS` and a head's
    matrix in whole (sublane, lane) tiles, else ``"jnp"`` (the CPU, a tiny
    model). What the chip chose between the two: PERF.md section 4."""
    if not (_HAS_PALLAS and (interpret or on_tpu())):
        return "jnp"
    if heads % _HEADS or width % 8 or value_width % 128:
        return "jnp"
    return "pallas"


def _update_kernel(live_ref, q_ref, k_ref, g_ref, v_ref, beta_ref, s_ref,
                   o_ref, s_out_ref):
    """`_HEADS` heads of one slot. q, k and g arrive a COLUMN a head
    (``[D, _HEADS]``: a key channel a sublane, as the state's rows lie), v
    and beta a ROW a head (``[_HEADS, Dv]``), so every product below is a
    broadcast along lanes or sublanes and a sum over sublanes: the state is
    read once and written once."""
    live = live_ref[pl.program_id(0)] != ZERO
    for i in range(_HEADS):
        s = s_ref[0, 0, i]                                   # [D, Dv]
        q, k, g = (ref[0, 0][:, i:i + 1] for ref in (q_ref, k_ref, g_ref))
        decayed = jnp.exp(g) * s
        seen_k = jnp.sum(decayed * k, axis=0, keepdims=True)  # [1, Dv]
        seen_q = jnp.sum(decayed * q, axis=0, keepdims=True)
        u = beta_ref[0, i:i + 1, :] * (v_ref[0, i:i + 1, :] - seen_k)
        o_ref[0, i:i + 1, :] = seen_q + u * jnp.sum(k * q, axis=0,
                                                    keepdims=True)
        s_out_ref[0, 0, i] = jnp.where(live, decayed + k * u, s)


def _pallas_update(q, k, v, g, beta, states, layer, active, interpret):
    slots, heads, d = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    layer = np.int32(layer)

    def columns(x):
        return x.reshape(slots, heads // _HEADS, _HEADS, d).transpose(
            0, 1, 3, 2)

    column = pl.BlockSpec((1, 1, d, _HEADS), lambda s, j, live: (s, j, ZERO,
                                                                 ZERO))
    row = pl.BlockSpec((1, _HEADS, dv), lambda s, j, live: (s, j, ZERO))
    matrix = pl.BlockSpec((1, 1, _HEADS, d, dv),
                          lambda s, j, live: (layer, s, j, ZERO, ZERO))
    return pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(slots, heads // _HEADS),
            in_specs=[column, column, column, row, row, matrix],
            out_specs=[row, matrix]),
        out_shape=[jax.ShapeDtypeStruct((slots, heads, dv), f32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        # the states go out in the buffer they came in: the other layers'
        # and, through `live`, the inactive slots' as they were
        input_output_aliases={6: 1},
        name="kda_decode_step", interpret=interpret,
    )(active.astype(jnp.int32), columns(q), columns(k), columns(g), v,
      jnp.broadcast_to(beta[..., None], (slots, heads, dv)), states)


@jax.named_scope("kda_decode_step")
def kda_decode_step(q, k, v, g, beta, states, layer, active,
                    interpret=False):
    """One token a slot. q, k, g ``[S, H, D]``, v ``[S, H, Dv]``, beta
    ``[S, H]``; states ``[layers, S, H, D, Dv]`` float32, of which `layer`
    is read and written where it lies: an ACTIVE slot's goes one token on,
    an inactive slot's stays. Returns (o ``[S, H, Dv]`` float32,
    states). The form is `update_form`'s."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    if update_form(q.shape[1], q.shape[2], v.shape[2],
                   interpret) == "pallas":
        return _pallas_update(q, k, v, g, beta, states, layer, active,
                              interpret)
    s = states[layer]
    decayed = jnp.exp(g)[..., None] * s
    # both sums in ONE pass over the state: o = S_t^T q = (Diag(a) S)^T q
    # + u (k . q)
    seen_k = jnp.sum(decayed * k[..., None], axis=-2)
    seen_q = jnp.sum(decayed * q[..., None], axis=-2)
    u = beta[..., None] * (v - seen_k)
    o = seen_q + u * jnp.sum(k * q, axis=-1, keepdims=True)
    new = jnp.where(active[:, None, None, None],
                    decayed + k[..., None] * u[..., None, :], s)
    return o, states.at[layer].set(new)


def _taps_over(window, taps):
    """silu(sum_j taps[:, j] * window[j]) over the list of the last
    ``len(window)`` inputs, oldest first."""
    return jax.nn.silu(sum(taps[:, j].astype(jnp.float32)
                           * x.astype(jnp.float32)
                           for j, x in enumerate(window)))


@jax.named_scope("kda_short_conv")
def kda_short_conv(streams, taps, state, length):
    """The depthwise causal convolution and SiLU of each of `streams`
    (``[B, T, C_i]``) under its `taps` (``[C_i, K]``, the newest input's
    tap last) behind `state` ``[B, (K - 1) * sum C_i]``: the K - 1 inputs
    before the call, oldest first, every stream's side by side at a
    position (zeros before a sequence). Returns (the streams convolved,
    float32; the state after `length` ``[B]`` of the call's positions)."""
    keep = taps[0].shape[1] - 1
    b, t = streams[0].shape[:2]
    widths = [x.shape[-1] for x in streams]
    past = state.reshape(b, keep, sum(widths))
    at = length[:, None] + jnp.arange(keep, dtype=jnp.int32)[None, :]
    outs, lasts, first = [], [], 0
    for x, w, c in zip(streams, taps, widths):
        xx = jnp.concatenate([past[..., first:first + c].astype(x.dtype), x],
                             axis=1)
        outs.append(_taps_over([xx[:, j:j + t] for j in range(keep + 1)], w))
        # positions length - keep .. length - 1 of x lie at length ..
        # length + keep - 1 of xx
        lasts.append(jnp.take_along_axis(xx, at[:, :, None], axis=1))
        first += c
    return outs, jnp.concatenate(lasts, -1).reshape(b, -1).astype(
        state.dtype)


@jax.named_scope("kda_short_conv")
def kda_short_conv_step(streams, taps, states, layer, active):
    """`kda_short_conv` for one token a slot (streams ``[S, C_i]``) behind
    the slots' states where they lie (``[layers, S, (K - 1) * sum C_i]``):
    rows of ``[S, .]`` only, slots on the sublanes and values on the lanes
    as the state itself lies (through ``[S, K - 1, C]`` the TPU's compiler
    turns the whole donated state into another layout and back). An
    inactive slot's state stays."""
    keep = taps[0].shape[1] - 1
    state = states[layer]
    total = sum(x.shape[-1] for x in streams)
    outs, first = [], 0
    for x, w in zip(streams, taps):
        c = x.shape[-1]
        window = [state[:, j * total + first:j * total + first + c]
                  for j in range(keep)] + [x]
        outs.append(_taps_over(window, w))
        first += c
    new = jnp.concatenate(
        [state[:, total:]] + [x.astype(state.dtype) for x in streams], -1)
    new = jnp.where(active[:, None], new, state)
    return outs, states.at[layer].set(new)
