"""JoyAI-LLM-Flash (the DeepSeek-V3 family's decoder), for training.

Source: `config.json` of huggingface.co/jdopensource/JoyAI-LLM-Flash
(`model_type` `joyai_llm_flash`: key for key the DeepSeek-V3 family's), the
layer equations of DeepSeek-V3 (arXiv:2412.19437 sections 2.1-2.2) and of
the family's `modeling_deepseek.py` / `deepseek_mtp.py`. What differs from
`gpt.py`, the only other model that trains here:

  * attention is multi-head LATENT attention (`mla.py`, shared with
    `longcat_flash.py`), trained EXPANDED: every head's key is its own
    `qk_nope_head_dim` values beside the one rotary key all heads share,
    192 wide, over 128-wide values, through the flash kernel;
  * `first_k_dense_replace` leading layers carry a dense SwiGLU FFN, the
    others an expert layer: sigmoid scores, the top `num_experts_per_tok`
    of ``scores + e_score_correction_bias`` (`noaux_tc`; the bias moves the
    choice, never the weight, and has no gradient), weights normalised
    over the chosen and scaled, beside `n_shared_experts` shared expert(s)
    every token passes. The routed experts are HELD IN SHARES
    (`experts_held`): `moe/grouped_experts.py` routes over all of them,
    computes the held ones over the tokens that chose them, drops none;
  * one multi-token-prediction (MTP) module (arXiv:2412.19437 eq. 21-24)
    shares the embedding and the head: position i joins the embedding of
    its NEXT token to the main model's final hidden state, passes one more
    expert layer and predicts the token after next;
  * the loss is ``CE(main) + mtp_loss_weight * CE(MTP)``, each a mean over
    its own positions, and the model computes it ITSELF (`forward(ids,
    labels)` returns the loss: the MTP module reads the labels), so it
    trains as ``TrainStep(model, None, optimizer)``;
  * RMSNorm, rotary positions (pairs interleaved), an untied head, no bias.

Each block is rematerialised in the backward pass (`jax.checkpoint`): a step
of 16,384 tokens keeps one block's activations at a time. A forward leaves
the step's counters in the buffer `train_counters` (`train_counter_names`:
the expert blocks' `COUNTERS` summed over the blocks, and the two loss
terms), which `TrainStep` hands out of the compiled step beside the loss,
and each router's load in the buffer `expert_load`, which the balance rule
(`balance_router_bias`, for a loop to call BETWEEN steps) reads.

Training only. `LLMEngine` reads `cache_spec()` of a model it serves, and
this one refuses by name: serving it (every expert held, the MTP module as
a drafter) is not written.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp

from ...nn.layer_base import Layer
from ...framework.core import Tensor, Parameter
from ...incubate.distributed.models.moe.grouped_experts import (
    grouped_held_expert_block, COUNTERS)
from . import mla

__all__ = ["JoyAIFlashConfig", "JoyAIFlashForCausalLM"]


@dataclass
class JoyAIFlashConfig:
    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 1
    num_nextn_predict_layers: int = 1
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256        # the experts the router ranks
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    rope_theta: float = 32e6
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    # the routed experts THIS program holds: (first id, how many); None is
    # all of them. The router's width never follows it
    experts_held: tuple | None = None
    # lambda of the MTP term (arXiv:2412.19437 section 4.2: 0.3, then 0.1)
    mtp_loss_weight: float = 0.3
    # gamma of the balance rule (section 4.2: 0.001)
    bias_update_speed: float = 0.001

    @property
    def held(self):
        return self.experts_held or (0, self.n_routed_experts)


def _attention_shapes(cfg, p):
    d, h = cfg.hidden_size, cfg.num_attention_heads
    a = p + "self_attn."
    return {
        p + "input_layernorm.weight": (d,),
        a + "q_a_proj.weight": (d, cfg.q_lora_rank),
        a + "q_a_layernorm.weight": (cfg.q_lora_rank,),
        a + "q_b_proj.weight": (
            cfg.q_lora_rank,
            h * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)),
        a + "kv_a_proj_with_mqa.weight": (
            d, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
        a + "kv_a_layernorm.weight": (cfg.kv_lora_rank,),
        a + "kv_b_proj.weight": (
            cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        a + "o_proj.weight": (h * cfg.v_head_dim, d),
        p + "post_attention_layernorm.weight": (d,),
    }


def _expert_layer_shapes(cfg, p):
    d, fe, held = cfg.hidden_size, cfg.moe_intermediate_size, cfg.held[1]
    fs = fe * cfg.n_shared_experts
    m = p + "mlp."
    return {
        m + "gate.weight": (d, cfg.n_routed_experts),
        m + "experts.gate_proj.weight": (held, d, fe),
        m + "experts.up_proj.weight": (held, d, fe),
        m + "experts.down_proj.weight": (held, fe, d),
        m + "shared_experts.gate_proj.weight": (d, fs),
        m + "shared_experts.up_proj.weight": (d, fs),
        m + "shared_experts.down_proj.weight": (fs, d),
    }


def param_shapes(cfg):
    """{name: shape} of the parameters, in the order the forward pass meets
    them (`benchmark/reference/joyai_llm_flash.py` states the same). The
    MTP module is layer `num_hidden_layers`, as the family's checkpoints
    number it; it owns no embedding and no head."""
    d, ff = cfg.hidden_size, cfg.intermediate_size
    shapes = {"model.embed_tokens.weight": (cfg.vocab_size, d)}
    for i in range(cfg.num_hidden_layers):
        p = f"model.layers.{i}."
        shapes.update(_attention_shapes(cfg, p))
        if i < cfg.first_k_dense_replace:
            shapes.update({p + "mlp.gate_proj.weight": (d, ff),
                           p + "mlp.up_proj.weight": (d, ff),
                           p + "mlp.down_proj.weight": (ff, d)})
        else:
            shapes.update(_expert_layer_shapes(cfg, p))
    shapes.update({"model.norm.weight": (d,),
                   "lm_head.weight": (d, cfg.vocab_size)})
    for i in range(cfg.num_hidden_layers,
                   cfg.num_hidden_layers + cfg.num_nextn_predict_layers):
        p = f"model.layers.{i}."
        shapes.update({p + "enorm.weight": (d,), p + "hnorm.weight": (d,),
                       p + "eh_proj.weight": (2 * d, d)})
        shapes.update(_attention_shapes(cfg, p))
        shapes.update(_expert_layer_shapes(cfg, p))
        shapes[p + "shared_head.norm.weight"] = (d,)
    return shapes


def router_bias_name(layer):
    """The router's `e_score_correction_bias` of a layer: a buffer, not a
    parameter: no gradient reaches it, the balance rule moves it."""
    return f"model.layers.{layer}.mlp.gate.e_score_correction_bias"


def _swiglu(x, gate_w, up_w, down_w):
    return (jax.nn.silu(x @ gate_w) * (x @ up_w)) @ down_w


def _mean_cross_entropy(logits, labels, counted):
    """Mean over the `counted` rows of the cross entropy of logits ``[R,
    V]`` against labels ``[R]``, through the vocabulary-blocked kernel
    where it runs (as `nn.functional.cross_entropy` chooses it)."""
    from ...kernels import cross_entropy as fused_ce
    if fused_ce.is_eligible(logits, labels):
        nll = fused_ce.fused_softmax_cross_entropy(logits, labels)
    else:
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(counted, nll, 0.0)) \
        / jnp.sum(counted).astype(jnp.float32)


class JoyAIFlashForCausalLM(Layer):
    """The whole model as one `Layer`: its parameters by the names of
    `param_shapes`, its forward and loss in `jax.numpy`.

    `weights` ({name: array}) are taken as the parameters' values where
    given; otherwise each is drawn N(0, `initializer_range`), norm scales
    1."""

    # what a forward with labels leaves in the buffer `train_counters`
    train_counter_names = COUNTERS + ("loss_main", "loss_mtp")

    def __init__(self, config: JoyAIFlashConfig, weights=None):
        super().__init__()
        if config.num_nextn_predict_layers != 1:
            raise ValueError("JoyAIFlashForCausalLM trains ONE multi-token-"
                             "prediction module; num_nextn_predict_layers "
                             f"is {config.num_nextn_predict_layers}")
        self.config = config
        shapes = param_shapes(config)
        if weights is not None and set(weights) != set(shapes):
            raise ValueError("weights do not name the model's parameters: "
                             f"{sorted(set(weights) ^ set(shapes))[:6]}")
        rng = np.random.default_rng(0)
        for name, shape in shapes.items():
            if weights is not None:
                value = weights[name]
                if tuple(value.shape) != tuple(shape):
                    raise ValueError(f"{name}: got {tuple(value.shape)}, "
                                     f"the model has {tuple(shape)}")
            elif len(shape) == 1:
                value = jnp.ones(shape, jnp.float32)
            else:
                value = jnp.asarray(rng.normal(
                    0.0, config.initializer_range, shape), jnp.float32)
            self._parameters[name] = Parameter(value, name=name)
        for i in self.expert_layers():
            self._buffers[router_bias_name(i)] = Tensor(jnp.zeros(
                (config.n_routed_experts,), jnp.float32))
        self._buffers["train_counters"] = Tensor(jnp.zeros(
            (len(self.train_counter_names),), jnp.float32))
        # tokens that chose each ranked expert in the newest step, a row
        # an expert layer (`expert_layers()`' order)
        self._buffers["expert_load"] = Tensor(jnp.zeros(
            (len(self.expert_layers()), config.n_routed_experts),
            jnp.int32))

    def expert_layers(self):
        """Ids of the layers that hold an expert block, the MTP module's
        among them."""
        cfg = self.config
        return list(range(cfg.first_k_dense_replace, cfg.num_hidden_layers
                          + cfg.num_nextn_predict_layers))

    def _w(self, name):
        return self._parameters[name]._value

    def router_bias(self, layer):
        return self._buffers[router_bias_name(layer)]

    def cache_spec(self):
        raise NotImplementedError(
            "JoyAIFlashForCausalLM trains only: no cache_spec(), so "
            "LLMEngine cannot serve it (every expert held and the MTP "
            "module as a drafter are not written)")

    # -- the balance rule -------------------------------------------------
    def balance_router_bias(self):
        """DeepSeek-V3's auxiliary-loss-free balance rule (section 2.1.2),
        for a training loop to call BETWEEN steps, outside the gradient:
        in every expert layer ``b_e += bias_update_speed * sign(mean load -
        load_e)``, the loads the newest step's own count of the tokens
        that chose each ranked expert (`expert_load`). An overloaded
        expert's bias falls, an idle one's rises."""
        loads = self._buffers["expert_load"]._value.astype(jnp.float32)
        for row, layer in enumerate(self.expert_layers()):
            bias = self.router_bias(layer)
            bias._value = bias._value + self.config.bias_update_speed \
                * jnp.sign(jnp.mean(loads[row]) - loads[row])

    # -- one block --------------------------------------------------------
    def _block(self, layer):
        """The pure function of block `layer`: ``(x [B, T, d], pos, leaves
        {leaf: value}, router bias or None) -> (x, counters, ranked load)``,
        rematerialised in the backward pass."""
        cfg = self.config
        dense = layer < cfg.first_k_dense_replace
        first, _ = cfg.held

        def block(x, pos, leaves, bias):
            w = lambda leaf: leaves[leaf]
            b, t, d = x.shape
            a = mla.rms(x, w("input_layernorm.weight"), cfg.rms_norm_eps)
            x = x + mla.causal_train(
                *mla.queries_and_row(a, pos, lambda leaf: w(
                    "self_attn." + leaf), cfg),
                lambda leaf: w("self_attn." + leaf), cfg)
            u = mla.rms(x, w("post_attention_layernorm.weight"),
                        cfg.rms_norm_eps)
            if dense:
                return x + _swiglu(u, w("mlp.gate_proj.weight"),
                                   w("mlp.up_proj.weight"),
                                   w("mlp.down_proj.weight")), None, None
            with jax.named_scope("held_experts"):
                m, counted, load = grouped_held_expert_block(
                    u.reshape(b * t, d), w("mlp.gate.weight"), bias,
                    w("mlp.experts.gate_proj.weight"),
                    w("mlp.experts.up_proj.weight"),
                    w("mlp.experts.down_proj.weight"),
                    topk=cfg.num_experts_per_tok,
                    scaling=cfg.routed_scaling_factor, first_held=first)
            shared = _swiglu(u, w("mlp.shared_experts.gate_proj.weight"),
                             w("mlp.shared_experts.up_proj.weight"),
                             w("mlp.shared_experts.down_proj.weight"))
            return x + shared + m.reshape(b, t, d).astype(x.dtype), \
                counted, load

        return jax.checkpoint(block)

    def _run_block(self, layer, x, pos):
        p = f"model.layers.{layer}."
        leaves = {name[len(p):]: param._value
                  for name, param in self._parameters.items()
                  if name.startswith(p)}
        bias = None if layer < self.config.first_k_dense_replace \
            else self.router_bias(layer)._value
        return self._block(layer)(x, pos, leaves, bias)

    def _head_loss(self, h, labels, counted):
        """Mean cross entropy of ``h W_head`` against the labels over the
        counted positions; the logits are made again in the backward pass,
        not kept."""
        @jax.checkpoint
        def head(h, head_w):
            logits = h.reshape(-1, h.shape[-1]) @ head_w
            return _mean_cross_entropy(logits, labels.reshape(-1),
                                       counted.reshape(-1))
        return head(h, self._w("lm_head.weight"))

    # -- the model --------------------------------------------------------
    def forward(self, input_ids, labels=None):
        """Without `labels`: the main model's logits ``[B, T,
        vocabulary]`` of ids ``[B, T]``. With `labels` ``[B, T]``
        (labels[i] the token after ids[i]): the training loss, main term
        plus `mtp_loss_weight` x the MTP module's, which predicts
        labels[i + 1] at every position but a row's last."""
        cfg = self.config
        ids = jnp.asarray(getattr(input_ids, "_value", input_ids))
        b, t = ids.shape
        pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
        embed = self._w("model.embed_tokens.weight")
        x = embed[ids]
        counters, loads = jnp.zeros((len(COUNTERS),), jnp.int32), []
        for i in range(cfg.num_hidden_layers):
            x, counted, load = self._run_block(i, x, pos)
            if counted is not None:
                counters = counters + counted
                loads.append(load)
        # h_main: AFTER the final norm, for the head and (as
        # `deepseek_mtp.py` hands it on) for the MTP module alike
        h = mla.rms(x, self._w("model.norm.weight"), cfg.rms_norm_eps)
        if labels is None:
            with jax.named_scope("lm_head"):
                return Tensor(h @ self._w("lm_head.weight"))
        labels = jnp.asarray(getattr(labels, "_value", labels))
        with jax.named_scope("main_head_and_loss"):
            loss_main = self._head_loss(h, labels, jnp.ones((b, t), bool))
        # the MTP module (layer `num_hidden_layers`): position i joins the
        # embedding of its next token to h_i and predicts the token after
        # next; a row's last position has none
        p = f"model.layers.{cfg.num_hidden_layers}."
        z = jnp.concatenate([
            mla.rms(embed[labels], self._w(p + "enorm.weight"),
                    cfg.rms_norm_eps),
            mla.rms(h, self._w(p + "hnorm.weight"), cfg.rms_norm_eps)],
            axis=-1) @ self._w(p + "eh_proj.weight")
        y, counted, load = self._run_block(cfg.num_hidden_layers, z, pos)
        counters = counters + counted
        self._buffers["expert_load"]._value = jnp.stack(loads + [load])
        with jax.named_scope("mtp_head_and_loss"):
            loss_mtp = self._head_loss(
                mla.rms(y, self._w(p + "shared_head.norm.weight"),
                        cfg.rms_norm_eps),
                jnp.roll(labels, -1, axis=1),
                jnp.broadcast_to(jnp.arange(t)[None] < t - 1, (b, t)))
        loss = loss_main + cfg.mtp_loss_weight * loss_mtp
        self._buffers["train_counters"]._value = jnp.concatenate([
            counters.astype(jnp.float32),
            jnp.stack([loss_main, loss_mtp]).astype(jnp.float32)])
        return Tensor(loss)
