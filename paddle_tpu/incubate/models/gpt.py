"""GPT model family (decoder-only transformer LM).

Reference analog: the Fleet GPT-3 training path the reference was built for
(SURVEY.md north star; mp layers fleet/layers/mpu/mp_layers.py + fused
transformer ops fluid/operators/fused/). Model configs follow the standard
GPT-2 124M / GPT-3 1.3B / 6.7B shapes from BASELINE.md.

TPU-first design:
  - attention core routes through F.scaled_dot_product_attention → Pallas
    flash kernel when eligible (bf16, block-aligned seq);
  - hybrid parallelism is expressed as NamedShardings over the global mesh
    (`shard_gpt`): embedding/vocab and qkv/ffn columns on the "model" axis,
    activations on "data" (+ sequence on "sep" when present) — XLA inserts the
    Megatron collectives. The fused qkv columns read [3, H, Dh], so their
    contiguous column shards are NOT heads: the training path moves the
    weight to a split by heads in front of the product (`_qkv_by_heads`),
    so that no activation crosses the "model" axis for it;
  - everything trains through one jitted step (paddle_tpu.jit.TrainStep or
    the sharded variant in __graft_entry__).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np
import jax
import jax.numpy as jnp

from ...nn.layer_base import Layer
from ...nn.layer.container import LayerList
from ...nn.layer.common import Linear, Embedding, Dropout
from ...nn.layer.norm import LayerNorm
from ...nn import functional as F
from ...nn import initializer as I
from ...nn.initializer_util import ParamAttr
from ...ops import manipulation as manip
from ...framework.core import Tensor

__all__ = ["GPTConfig", "GPTModel", "GPTForCausalLM", "GPTPretrainingCriterion",
           "gpt2_124m", "gpt2_355m", "gpt3_1p3b", "gpt3_6p7b", "shard_gpt",
           "GPTEmbeddingPipe", "GPTHeadPipe", "gpt_pipeline_layers",
           "GPTDecodeStep"]


@dataclass
class GPTConfig:
    vocab_size: int = 50304            # padded to a multiple of 128 for MXU
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 1024
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    initializer_range: float = 0.02
    layer_norm_epsilon: float = 1e-5
    use_flash_attention: bool = True
    tie_word_embeddings: bool = True


def gpt2_124m(**overrides):
    return GPTConfig(**{**dict(hidden_size=768, num_hidden_layers=12,
                               num_attention_heads=12, intermediate_size=3072),
                        **overrides})


def gpt2_355m(**overrides):
    return GPTConfig(**{**dict(hidden_size=1024, num_hidden_layers=24,
                               num_attention_heads=16, intermediate_size=4096),
                        **overrides})


def gpt3_1p3b(**overrides):
    return GPTConfig(**{**dict(hidden_size=2048, num_hidden_layers=24,
                               num_attention_heads=16, intermediate_size=8192,
                               max_position_embeddings=2048),
                        **overrides})


def gpt3_6p7b(**overrides):
    return GPTConfig(**{**dict(hidden_size=4096, num_hidden_layers=32,
                               num_attention_heads=32, intermediate_size=16384,
                               max_position_embeddings=2048),
                        **overrides})


def _head_split_mesh(weight, heads):
    """The key of the global mesh when the fused qkv product has to be taken
    against the weight moved to heads (`_qkv_by_heads`), else None: the
    weight is one `shard_gpt` split over a "model" axis wider than one
    (`is_distributed`, the mark the reference's mp layers leave on such a
    weight, which a trace keeps where it hides the value's sharding), the
    heads divide by that axis, and the call is not inside a manual region
    (there a value is one shard already, and nothing is constrained)."""
    from ...distributed.fleet.meta_parallel.mp_ops import in_spmd_axis
    from ...distributed.mesh import current_mesh, mesh_key
    mesh = current_mesh()
    if mesh is None or not getattr(weight, "is_distributed", False):
        return None
    width = mesh.shape.get("model", 1)
    if width <= 1 or heads % width or \
            any(in_spmd_axis(a) for a in mesh.axis_names):
        return None
    return mesh_key(mesh)


def _qkv_by_heads(x, proj, heads, mkey):
    """q, k and v of `proj(x)`, each [B, N, H, Dh] and split by HEADS over
    "model".

    `shard_gpt` stores the fused weight [D, 3D] in contiguous column shards
    of the "model" axis. The columns read [3, H, Dh], so with two shards
    the first holds all of q and half of k's heads and the second the rest
    of k and all of v: a split of the columns, not of the heads, and the
    partitioner undid it after the product by gathering the whole [B, N,
    3D] activation (and its gradient in the backward) over the axis. Here
    the WEIGHT is moved instead, in front of the products: each of its
    three [D, D] column blocks (q, k, v; columns [H, Dh]) is constrained to
    a column split of its own, which IS a split by heads (the bias the
    same), and each is multiplied on its own, so q, k and v leave as the
    attention's `shard_map` (`_FLASH_SPEC`) wants them and what crosses the
    axis a layer is weight-sized: a B-th of the activation's rows. The
    stored parameter keeps its name, shape, column order and sharding; the
    products and their float32 sums are `F.linear`'s over the same `d`, so
    is the op's name (AMP lists, FLOP accounting).

    The backward is written out (`custom_vjp`) for one reason: it moves the
    weight AGAIN from the stored parameter, behind a barrier that ties the
    move to the incoming gradient. Left to autodiff the moved blocks are
    residuals, alive from every layer's forward to its backward: a step's
    peak higher by the whole model's qkv weights. The weight's gradient
    goes back to the stored split the way the weight came (the move's own
    transpose).

    The mesh's key, not the mesh, rides in the closure (the op stays
    keyable); the mesh is read back at trace time."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ...distributed.mesh import current_mesh, mesh_key
    from ...ops._helpers import call_op_multi

    def by_heads(w, bias):
        mesh = current_mesh()
        if mesh_key(mesh) != mkey:
            raise RuntimeError("the global mesh changed between the dispatch "
                               "of the qkv projection and its trace")
        d = w.shape[0]
        columns, entries = (NamedSharding(mesh, P(None, "model")),
                            NamedSharding(mesh, P("model")))
        blocks = [slice(t * d, (t + 1) * d) for t in range(3)]
        return ([jax.lax.with_sharding_constraint(w[:, at], columns)
                 for at in blocks],
                [jax.lax.with_sharding_constraint(bias[at], entries)
                 for at in blocks])

    @jax.custom_vjp
    def fn(v, w, bias):
        return tuple((jnp.matmul(v, wt) + bt).reshape(*v.shape[:2], heads, -1)
                     for wt, bt in zip(*by_heads(w, bias)))

    def fwd(v, w, bias):
        return fn(v, w, bias), (v, w, bias)

    def bwd(kept, grads):
        v, w, bias = kept
        w, first = jax.lax.optimization_barrier((w, grads[0]))
        grads = (first,) + tuple(grads[1:])
        (moved, _), to_stored = jax.vjp(by_heads, w, bias)
        grads = [g.reshape(*g.shape[:2], -1) for g in grads]
        dw, dbias = to_stored((
            [jnp.einsum("bnd,bnc->dc", v, g) for g in grads],
            [g.sum((0, 1), dtype=jnp.float32).astype(bias.dtype)
             for g in grads]))
        dv = sum(jnp.einsum("bnc,dc->bnd", g, wt)
                 for g, wt in zip(grads, moved))
        return dv, dw, dbias
    fn.defvjp(fwd, bwd)
    return call_op_multi("linear", fn, (x, proj.weight, proj.bias), 3)


class GPTAttention(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.num_heads = config.num_attention_heads
        self.head_dim = config.hidden_size // config.num_attention_heads
        self.hidden_size = config.hidden_size
        init = I.Normal(0.0, config.initializer_range)
        self.qkv_proj = Linear(config.hidden_size, 3 * config.hidden_size,
                               weight_attr=ParamAttr(initializer=init))
        self.out_proj = Linear(config.hidden_size, config.hidden_size,
                               weight_attr=ParamAttr(initializer=init))
        self.dropout_p = config.attention_probs_dropout_prob
        self.use_flash_attention = config.use_flash_attention
        self.resid_dropout = Dropout(config.hidden_dropout_prob)

    def forward(self, x, cache=None):
        b, n = x.shape[0], x.shape[1]
        mkey = _head_split_mesh(self.qkv_proj.weight, self.num_heads) \
            if cache is None else None
        if mkey is None:
            qkv = self.qkv_proj(x)
            qkv = manip.reshape(qkv, [b, n, 3, self.num_heads, self.head_dim])
            q = manip.squeeze(manip.slice(qkv, [2], [0], [1]), 2)
            k = manip.squeeze(manip.slice(qkv, [2], [1], [2]), 2)
            v = manip.squeeze(manip.slice(qkv, [2], [2], [3]), 2)
        else:
            q, k, v = _qkv_by_heads(x, self.qkv_proj, self.num_heads, mkey)
        if cache is not None and hasattr(cache, "block_tables"):
            # paged serving cache (serving/cache.py PagedCacheView): the
            # continuous-batching engine's block-pool memory — sequences
            # of different lengths share one pool via per-slot block
            # tables, so ONE compiled decode step serves every tenant mix
            return self._paged_decode_step(q, k, v, cache, b, n)
        if cache is not None and len(cache) == 3:
            # static serving cache: preallocated [B, T, H, D] buffers + a
            # write position — one compiled decode step serves every token
            # (reference analog: the fused_multi_transformer serving cache,
            # inference/api/analysis_predictor.h:95 clientele)
            return self._decode_step(q, k, v, cache, b, n)
        if cache is not None:
            pk, pv = cache
            k = manip.concat([pk, k], axis=1)
            v = manip.concat([pv, v], axis=1)
            cache = (k, v)
        out = F.scaled_dot_product_attention(
            q, k, v, is_causal=True,
            dropout_p=self.dropout_p if self.training else 0.0,
            training=self.training,
            use_flash_attention=self.use_flash_attention)
        out = manip.reshape(out, [b, n, self.hidden_size])
        out = self.resid_dropout(self.out_proj(out))
        return (out, cache) if cache is not None else out

    def _decode_step(self, q, k, v, cache, b, n):
        """Single-token attention against a static KV buffer: write the new
        K/V at `pos`, attend over positions <= pos. All shapes static, so
        XLA compiles ONE program for the whole decode loop."""
        k_buf, v_buf, pos = cache
        head_dim = self.head_dim

        def fn(qv, kv, vv, kbv, vbv, posv):
            z = jnp.asarray(0, jnp.int32)   # match index dtypes under x64
            start = (z, posv.astype(jnp.int32), z, z)
            kbv = jax.lax.dynamic_update_slice(kbv, kv.astype(kbv.dtype),
                                               start)
            vbv = jax.lax.dynamic_update_slice(vbv, vv.astype(vbv.dtype),
                                               start)
            t = kbv.shape[1]
            # [B,H,n,D] x [B,H,D,T] -> scores [B,H,n,T]
            qh = jnp.transpose(qv, (0, 2, 1, 3))
            kh = jnp.transpose(kbv, (0, 2, 3, 1))
            scores = jnp.einsum("bhnd,bhdt->bhnt", qh, kh) \
                / jnp.sqrt(jnp.asarray(head_dim, qv.dtype))
            # row r of this chunk sits at absolute position pos+r and may
            # attend to every position <= pos+r (causal within the chunk)
            n_in = qv.shape[1]
            row_pos = posv + jnp.arange(n_in)[None, None, :, None]
            valid = jnp.arange(t)[None, None, None, :] <= row_pos
            scores = jnp.where(valid, scores, jnp.asarray(-1e9, qv.dtype))
            probs = jax.nn.softmax(scores.astype(jnp.float32),
                                   axis=-1).astype(qv.dtype)
            vh = jnp.transpose(vbv, (0, 2, 1, 3))
            out = jnp.einsum("bhnt,bhtd->bhnd", probs, vh)
            return jnp.transpose(out, (0, 2, 1, 3)), kbv, vbv

        from ...ops._helpers import call_op_multi, ensure_tensor, const_input
        # the write position rides as a dispatch input: a captured
        # per-step position array would re-key the op on every token
        out, new_k, new_v = call_op_multi(
            "gpt_decode_attention", fn,
            (ensure_tensor(q), ensure_tensor(k), ensure_tensor(v),
             k_buf, v_buf, const_input(pos)), num_outputs=3)
        out = manip.reshape(out, [b, n, self.hidden_size])
        out = self.out_proj(out)
        return out, (new_k, new_v, pos)


    def _paged_decode_step(self, q, k, v, cache, b, n):
        """Single-token attention against the paged block pool: write this
        step's K/V at each slot's write position, stream that slot's blocks
        by table (blockwise online softmax — or the dense gather oracle),
        attend over positions <= seq_len. Shapes are fixed by
        (max_batch, max_blocks, block_size), so the serving engine compiles
        ONE program for every batch composition."""
        if n != 1:
            raise ValueError(
                "paged decode is single-token; prefill goes through the "
                f"dynamic-cache path (got a {n}-token chunk)")
        from ...nn.functional.attention import (paged_decode_attention,
                                                resolve_paged_kernel)
        from ...ops._helpers import call_op_multi, ensure_tensor
        block_size = cache.block_size
        # the RESOLVED variant is captured in the op fn's closure — that
        # is what keys it into the per-op dispatch cache, so a
        # FLAGS_serve_attention_kernel flip re-keys instead of replaying
        # the previous variant's executable. An engine-owned cache view
        # pins the variant it resolved at construction.
        variant = cache.kernel
        if variant is None:
            variant = resolve_paged_kernel(
                num_heads=self.num_heads, head_dim=self.head_dim,
                block_size=block_size,
                kv_dtype=ensure_tensor(cache.k_pools)._value.dtype)

        quantized = cache.k_scales is not None
        # the view holds the STACKED pools and this layer's index: the op
        # writes and reads them at `layer` and returns them whole
        layer = cache.layer

        def fn(qv, kv, vv, kp, vp, tab, lens, act, ksc=None, vsc=None):
            return paged_decode_attention(
                qv, kv, vv, kp, vp, layer, tab, lens, act, block_size,
                k_scales=ksc, v_scales=vsc, kernel=variant)

        # int8 KV: the scale side-tables are dispatch INPUTS (never
        # closure captures) and flow back out with the pools — the
        # differing arity also keys the two modes apart in the cache
        inputs = (ensure_tensor(q), ensure_tensor(k), ensure_tensor(v),
                  ensure_tensor(cache.k_pools), ensure_tensor(cache.v_pools),
                  ensure_tensor(cache.block_tables),
                  ensure_tensor(cache.seq_lens), ensure_tensor(cache.active))
        if quantized:
            inputs += (ensure_tensor(cache.k_scales),
                       ensure_tensor(cache.v_scales))
        outs = call_op_multi("gpt_paged_decode_attention", fn, inputs,
                             num_outputs=5 if quantized else 3)
        out = manip.reshape(outs[0], [b, n, self.hidden_size])
        out = self.out_proj(out)
        new_scales = (outs[3]._value, outs[4]._value) if quantized else ()
        return out, cache.updated(outs[1]._value, outs[2]._value,
                                  *new_scales)


class GPTMLP(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        init = I.Normal(0.0, config.initializer_range)
        self.fc_in = Linear(config.hidden_size, config.intermediate_size,
                            weight_attr=ParamAttr(initializer=init))
        self.fc_out = Linear(config.intermediate_size, config.hidden_size,
                             weight_attr=ParamAttr(initializer=init))
        self.dropout = Dropout(config.hidden_dropout_prob)

    def forward(self, x):
        return self.dropout(self.fc_out(F.gelu(self.fc_in(x),
                                               approximate=True)))


class GPTBlock(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.ln_1 = LayerNorm(config.hidden_size,
                              epsilon=config.layer_norm_epsilon)
        self.attn = GPTAttention(config)
        self.ln_2 = LayerNorm(config.hidden_size,
                              epsilon=config.layer_norm_epsilon)
        self.mlp = GPTMLP(config)

    def forward(self, x, cache=None):
        if cache is not None:
            a, cache = self.attn(self.ln_1(x), cache)
        else:
            a = self.attn(self.ln_1(x))
        x = x + a
        x = x + self.mlp(self.ln_2(x))
        return (x, cache) if cache is not None else x


class GPTModel(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        init = I.Normal(0.0, config.initializer_range)
        self.wte = Embedding(config.vocab_size, config.hidden_size,
                             weight_attr=ParamAttr(initializer=init))
        self.wpe = Embedding(config.max_position_embeddings,
                             config.hidden_size,
                             weight_attr=ParamAttr(initializer=init))
        self.drop = Dropout(config.hidden_dropout_prob)
        self.h = LayerList([GPTBlock(config)
                            for _ in range(config.num_hidden_layers)])
        self.ln_f = LayerNorm(config.hidden_size,
                              epsilon=config.layer_norm_epsilon)

    def forward(self, input_ids, position_ids=None, caches=None):
        b, n = input_ids.shape[0], input_ids.shape[1]
        paged = caches is not None and hasattr(caches[0], "block_tables")
        static_cache = caches is not None and not paged \
            and len(caches[0]) == 3
        if paged:
            past_len = None
        elif static_cache:
            past = caches[0][2]._value           # current write position
            past_len = None
        else:
            past_len = caches[0][0].shape[1] if caches is not None else 0
        if position_ids is None and paged:
            # continuous batching: every slot sits at its OWN position
            # (seq_lens), unlike the dense static cache's shared scalar
            raw = caches[0].seq_lens
            lens = jnp.asarray(getattr(raw, "_value", raw)).astype(jnp.int32)
            pos = Tensor(lens[:, None]
                         + jnp.arange(n, dtype=jnp.int32)[None, :])
        elif position_ids is None and static_cache:
            pos = Tensor(past.astype(jnp.int32)
                         + jnp.arange(n, dtype=jnp.int32)[None, :])
        elif position_ids is None:
            pos = Tensor(jnp.arange(past_len, past_len + n,
                                    dtype=jnp.int32)[None, :])
        else:
            pos = position_ids
        x = self.wte(input_ids) + self.wpe(pos)
        x = self.drop(x)
        if caches is None:
            for block in self.h:
                x = block(x)
            return self.ln_f(x)
        if paged:
            # ONE view over the stacked pools is threaded through the
            # layers: each writes in place at its own index and hands the
            # pools on, so the returned view holds the step's pools
            cache = caches[0]
            for block in self.h:
                x, cache = block(x, cache)
            return self.ln_f(x), [cache]
        new_caches = []
        for block, cache in zip(self.h, caches):
            x, c = block(x, cache)
            new_caches.append(c)
        return self.ln_f(x), new_caches


class GPTForCausalLM(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  bias_attr=False)

    def gen_caches(self, batch_size, dtype=None):
        """Empty KV caches for incremental decoding. dtype defaults to the
        model's parameter dtype (so bf16 models get bf16 caches)."""
        from ...ops.creation import zeros
        cfg = self.config
        if dtype is None:
            params = self.parameters()
            dtype = params[0].dtype if params else "float32"
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        return [(zeros([batch_size, 0, cfg.num_attention_heads, head_dim],
                       dtype),
                 zeros([batch_size, 0, cfg.num_attention_heads, head_dim],
                       dtype))
                for _ in range(cfg.num_hidden_layers)]

    def cache_spec(self):
        """What a serving engine caches for a token of this model: a key
        and a value for every head of every layer."""
        from ...serving.cache import CacheSpec
        cfg = self.config
        return CacheSpec.per_head(
            cfg.num_hidden_layers, cfg.num_attention_heads,
            cfg.hidden_size // cfg.num_attention_heads)

    def forward(self, input_ids, position_ids=None, caches=None):
        if caches is None:
            hidden = self.gpt(input_ids, position_ids)
        else:
            hidden, caches = self.gpt(input_ids, position_ids, caches)
        with jax.named_scope("lm_head"):
            if self.lm_head is not None:
                logits = self.lm_head(hidden)
            else:
                # tied: logits = hidden @ wte^T
                logits = F.linear(
                    hidden, manip.transpose(self.gpt.wte.weight, [1, 0]))
        return logits if caches is None else (logits, caches)

    def num_params(self, include_embeddings=True):
        total = 0
        for _, p in self.named_parameters():
            if not include_embeddings and "wte" in _:
                continue
            total += p.size
        return total

    def flops_per_token(self, seq_len, training=True):
        """Model FLOPs per token, PaLM-appendix counting: training =
        6N + 12*L*h*s (fwd+bwd), inference = 2N + 4*L*h*s."""
        n = self.num_params()
        cfg = self.config
        attn_fwd = 4 * cfg.num_hidden_layers * cfg.hidden_size * seq_len
        if training:
            return 6 * n + 3 * attn_fwd
        return 2 * n + attn_fwd

    def gen_static_caches(self, batch_size, max_len, dtype=None):
        """Preallocated serving caches: per layer (k_buf, v_buf) of shape
        [B, max_len, H, D] plus a shared position scalar — the static-shape
        counterpart of gen_caches for the compiled decode loop."""
        cfg = self.config
        if dtype is None:
            params = self.parameters()
            dtype = params[0]._value.dtype if params else jnp.float32
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        shape = (batch_size, max_len, cfg.num_attention_heads, head_dim)
        return [(Tensor(jnp.zeros(shape, dtype)),
                 Tensor(jnp.zeros(shape, dtype)))
                for _ in range(cfg.num_hidden_layers)]

    def generate(self, input_ids, max_new_tokens=32, do_sample=False,
                 top_k=1, top_p=1.0, temperature=1.0, seed=0):
        """Batched autoregressive decoding, compiled as ONE XLA program:
        prefill on the full prompt, then a lax.scan over decode steps
        against static KV buffers (shapes fixed at [B, P + N]).

        Reference analog: the serving decode the reference drives through
        AnalysisPredictor + fused_multi_transformer
        (inference/api/analysis_predictor.h:95, incubate FusedMultiTransformer);
        greedy (do_sample=False) or top-k/top-p temperature sampling
        (top_p >= 1 disables the nucleus filter; the mask reuses the
        serving sampler's `apply_top_p`, so both paths keep one
        definition of the nucleus rule).
        Returns the generated ids, [B, max_new_tokens].
        """
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        ids = ids.astype(jnp.int32)
        b, p = ids.shape
        n_new = int(max_new_tokens)
        total = p + n_new
        params = self.parameters()
        was_training = self.training
        self.eval()

        def swap_call(pvals, *args, **kw):
            saved = [pp._value for pp in params]
            try:
                for pp, vv in zip(params, pvals):
                    pp._value = vv
                from ...framework.autograd import set_grad_enabled
                with set_grad_enabled(False):
                    return self.forward(*args, **kw)
            finally:
                for pp, vv in zip(params, saved):
                    pp._value = vv

        cfg = self.config
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        dt = params[0]._value.dtype

        def decode(pvals, prompt, key):
            # prefill: dynamic-cache forward over the prompt (static shapes
            # because the prompt length is static)
            empty = [(Tensor(jnp.zeros((b, 0, cfg.num_attention_heads,
                                        head_dim), dt)),) * 2
                     for _ in range(cfg.num_hidden_layers)]
            logits, caches = swap_call(pvals,
                                       Tensor(prompt, stop_gradient=True),
                                       caches=[tuple(c) for c in empty])
            # pack prompt KV into the static buffers
            bufs = []
            for (ck, cv) in caches:
                kb = jnp.zeros((b, total, cfg.num_attention_heads, head_dim),
                               dt).at[:, :p].set(ck._value)
                vb = jnp.zeros((b, total, cfg.num_attention_heads, head_dim),
                               dt).at[:, :p].set(cv._value)
                bufs.append((kb, vb))
            last = logits._value[:, -1, :]

            def pick(lg, k2):
                if not do_sample:
                    return jnp.argmax(lg, axis=-1).astype(jnp.int32)
                lg = lg.astype(jnp.float32) / max(temperature, 1e-6)
                if top_k and top_k > 0:
                    kth = jnp.sort(lg, axis=-1)[:, -int(top_k)][:, None]
                    lg = jnp.where(lg < kth, -jnp.inf, lg)
                if top_p is not None and float(top_p) < 1.0:
                    from ...serving.sampling import apply_top_p
                    lg = apply_top_p(lg, jnp.full((lg.shape[0],),
                                                  float(top_p),
                                                  jnp.float32))
                return jax.random.categorical(k2, lg, axis=-1) \
                    .astype(jnp.int32)

            tok0 = pick(last, jax.random.fold_in(key, 0))

            def step(carry, i):
                tok, bufs, key = carry
                pos = p + i
                static = [(Tensor(kb), Tensor(vb),
                           Tensor(jnp.asarray(pos, jnp.int32)))
                          for kb, vb in bufs]
                lg, new_caches = swap_call(
                    pvals, Tensor(tok[:, None], stop_gradient=True),
                    caches=static)
                bufs = [(nk._value, nv._value)
                        for nk, nv, _pos in new_caches]
                nxt = pick(lg._value[:, -1, :],
                           jax.random.fold_in(key, i + 1))
                return (nxt, bufs, key), tok

            (last_tok, _, _), toks = jax.lax.scan(
                step, (tok0, bufs, key), jnp.arange(n_new - 1))
            out = jnp.concatenate([jnp.transpose(toks, (1, 0)),
                                   last_tok[:, None]], axis=1)
            return out

        try:
            # cache the compiled decode per shape/flag signature — a fresh
            # jax.jit wrapper every call would retrace AND recompile
            if not hasattr(self, "_gen_cache"):
                self._gen_cache = {}
            sig = (b, p, n_new, bool(do_sample), int(top_k),
                   float(top_p if top_p is not None else 1.0),
                   float(temperature))
            jitted = self._gen_cache.get(sig)
            if jitted is None:
                jitted = jax.jit(decode)
                self._gen_cache[sig] = jitted
            out = jitted([pp._value for pp in params], ids,
                         jax.random.PRNGKey(seed))
        finally:
            if was_training:
                self.train()
        return Tensor(out, stop_gradient=True)


class GPTDecodeStep(Layer):
    """One serving decode step as a saveable artifact: (tokens [B,1],
    k_bufs [L,B,T,H,D], v_bufs, pos scalar) -> (logits [B,1,V], new_k,
    new_v). jit.save(...) of this layer yields the StableHLO program the
    inference Predictor replays per generated token — the TPU-native analog
    of running the reference's fused_multi_transformer decode through
    AnalysisPredictor (inference/api/analysis_predictor.h:95)."""

    def __init__(self, model: "GPTForCausalLM"):
        super().__init__()
        self.model = model

    def forward(self, tokens, k_bufs, v_bufs, pos):
        cfg = self.model.config
        caches = []
        for l in range(cfg.num_hidden_layers):
            kb = manip.squeeze(manip.slice(k_bufs, [0], [l], [l + 1]), 0)
            vb = manip.squeeze(manip.slice(v_bufs, [0], [l], [l + 1]), 0)
            caches.append((kb, vb, pos))
        logits, new_caches = self.model(tokens, caches=caches)
        new_k = manip.stack([c[0] for c in new_caches])
        new_v = manip.stack([c[1] for c in new_caches])
        return logits, new_k, new_v


class GPTPretrainingCriterion(Layer):
    """Language-model loss (next-token cross entropy)."""

    def __init__(self, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index

    @jax.named_scope("lm_loss")
    def forward(self, logits, labels):
        b, n, v = logits.shape
        flat = manip.reshape(logits, [b * n, v])
        flat_lab = manip.reshape(labels, [b * n])
        return F.cross_entropy(flat, flat_lab,
                               ignore_index=self.ignore_index)


# ---------------------------------------------------------------------------
# Hybrid-parallel sharding rules
# ---------------------------------------------------------------------------

def shard_gpt(model: GPTForCausalLM, mesh, dtype=None):
    """Annotate GPT parameters with NamedShardings over `mesh`.

    Megatron placement (SURVEY.md §7 row "mp layers"): qkv and fc_in are
    column-parallel (out-dim on "model"), out_proj and fc_out are row-parallel
    (in-dim on "model"), embeddings vocab-parallel. Remaining axes are left to
    the partitioner; optimizer state inherits shardings from params and is
    further sharded over "sharding" by the sharded optimizer.

    The fused qkv weight is STORED [D, 3D] in contiguous column shards, and
    its columns read [3, H, Dh]: over two shards the first holds q and half
    of k's heads, the second the rest of k and v. Those are not heads. The
    split by heads that attention needs is made where the weight is USED:
    `GPTAttention.forward` (no serving cache) multiplies by each of the
    three [D, D] blocks constrained to a column split of its own
    (`_qkv_by_heads`), which moves weight-sized tensors a layer each way
    and no activation. The stored name, shape, column order and sharding
    are a checkpoint's and the optimizer's, and stay. Every parameter split
    over a "model" axis wider than one is marked `is_distributed` (as the
    reference's mp layers mark theirs): a trace hides a value's sharding,
    the mark is what the model reads there.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    def put(p, spec):
        if p is None:
            return
        val = p._value
        if dtype is not None:
            val = val.astype(dtype)
        p._value = jax.device_put(val, NamedSharding(mesh, spec))

    rules = [
        ("wte.weight", P("model", None)),
        ("wpe.weight", P()),
        ("qkv_proj.weight", P(None, "model")),
        ("qkv_proj.bias", P("model")),
        ("out_proj.weight", P("model", None)),
        ("out_proj.bias", P()),
        ("fc_in.weight", P(None, "model")),
        ("fc_in.bias", P("model")),
        ("fc_out.weight", P("model", None)),
        ("fc_out.bias", P()),
        ("lm_head.weight", P(None, "model")),
        ("ln_", P()),
    ]
    for name, p in model.named_parameters():
        spec = None
        for pat, s in rules:
            if pat in name:
                spec = s
                break
        put(p, spec if spec is not None else P())
        p.is_distributed = spec is not None and "model" in spec \
            and mesh.shape.get("model", 1) > 1
    return model


# ---------------------------------------------------------------------------
# Pipeline-parallel decomposition
# ---------------------------------------------------------------------------

class GPTEmbeddingPipe(Layer):
    """Prologue stage: token + position embedding (shares the model's
    wte/wpe/drop sublayers). Reference analog: the embedding LayerDesc in the
    reference GPT pipeline models (fleet pp_layers SharedLayerDesc for tied
    embeddings)."""

    def __init__(self, model: "GPTForCausalLM"):
        super().__init__()
        self.wte = model.gpt.wte
        self.wpe = model.gpt.wpe
        self.drop = model.gpt.drop

    def forward(self, input_ids):
        n = input_ids.shape[1]
        pos = Tensor(jnp.arange(0, n, dtype=jnp.int32)[None, :],
                     stop_gradient=True)
        return self.drop(self.wte(input_ids) + self.wpe(pos))


class GPTHeadPipe(Layer):
    """Epilogue stage: final LayerNorm + (tied) LM head. The tied wte weight
    is the SAME Parameter object as the embedding's — PipelineTrainStep
    dedupes by identity so its gradient accumulates from both uses."""

    def __init__(self, model: "GPTForCausalLM"):
        super().__init__()
        self.ln_f = model.gpt.ln_f
        self.lm_head = model.lm_head
        self._wte = model.gpt.wte

    def forward(self, x):
        h = self.ln_f(x)
        if self.lm_head is not None:
            return self.lm_head(h)
        return F.linear(h, manip.transpose(self._wte.weight, [1, 0]))


def gpt_pipeline_layers(model: "GPTForCausalLM"):
    """Flatten a GPTForCausalLM into the sequential layer list consumed by
    PipelineTrainStep: [embedding, block*L, ln_f+head]. The transformer
    blocks form the homogeneous run that gets sharded over the "pipe" axis."""
    return ([GPTEmbeddingPipe(model)] + list(model.gpt.h)
            + [GPTHeadPipe(model)])
