"""The system under test for JoyAI-LLM-Flash (the DeepSeek-V3 family):
paddle_tpu's `JoyAIFlashForCausalLM` through `jit.TrainStep` and
`AdamW(multi_precision=True)`, as `paddle_gpt.Trainer` drives GPT. Training
only, one chip. Sizes and constructor arguments come from the configuration
and traffic files; the parameter names are the reference's own; the model
computes its two-term loss itself, so the step has no loss function."""
from __future__ import annotations

import contextlib

# imported HERE, not where it is used: a checkout whose program lacks the
# model fails when the loop imports this module, before the reference's
# minutes, and not after them
from paddle_tpu.incubate.models.joyai_llm_flash import (
    JoyAIFlashConfig, JoyAIFlashForCausalLM)

from . import paddle_gpt
from .paddle_gpt import enable_compile_cache  # noqa: F401

# what the program does with the source's switches: any other value is
# another model, not a setting of this one
_SWITCHES = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
             "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
             "rope_interleave": True, "rope_scaling": None,
             "tie_word_embeddings": False, "attention_bias": False,
             "hidden_act": "silu", "moe_layer_freq": 1}


def _model_config(cfg):
    for key, want in _SWITCHES.items():
        if cfg[key] != want:
            raise ValueError(f"{key} is {cfg[key]!r}: JoyAIFlashForCausalLM "
                             f"computes {want!r}")
    published = cfg.get("published", {})
    return JoyAIFlashConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        num_nextn_predict_layers=cfg["num_nextn_predict_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        # the router ranks the PUBLISHED experts; the file's own count is
        # what this chip holds of them
        n_routed_experts=published.get("n_routed_experts",
                                       cfg["n_routed_experts"]),
        experts_held=(cfg.get("experts_held_from", 0),
                      cfg["n_routed_experts"]),
        n_shared_experts=cfg["n_shared_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        rope_theta=cfg["rope_theta"], rms_norm_eps=cfg["rms_norm_eps"],
        initializer_range=cfg["initializer_range"],
        mtp_loss_weight=cfg["mtp_loss_weight"],
        bias_update_speed=cfg["bias_update_speed"])


class Trainer(paddle_gpt.Trainer):
    """`TrainStep` over the model built AROUND the seeded weights (made
    once, where they live), with AdamW from the file's `optimizer`; the
    rest of the loops' contract is `paddle_gpt.Trainer`'s."""

    def __init__(self, cfg, traffic, make_weights, devices):
        import paddle_tpu as paddle
        from paddle_tpu.jit import TrainStep
        if traffic.get("mesh") or len(devices) != 1:
            raise ValueError("JoyAIFlashForCausalLM trains on one chip: its "
                             "experts' exchange over a mesh is not written")
        self._paddle = paddle
        self.mesh = None
        self._exit = contextlib.ExitStack()
        self.model = JoyAIFlashForCausalLM(_model_config(cfg),
                                           weights=make_weights())
        self._make_weights = make_weights
        opt = cfg["optimizer"]
        self.opt = paddle.optimizer.AdamW(
            learning_rate=opt["learning_rate"],
            weight_decay=opt["weight_decay"], beta1=opt["beta1"],
            beta2=opt["beta2"], epsilon=opt["epsilon"],
            parameters=self.model.parameters(), multi_precision=True)
        self.train_step = TrainStep(self.model, None, self.opt,
                                    donate=traffic["donate"])
        self._params = [p for p in self.model.parameters()
                        if not p.stop_gradient]

    def _slot(self, name):
        self.opt._create_accumulators(self._params)
        slots = self.opt._accumulators[name]
        return {k: slots[p.name] for k, p in self.model.named_parameters()}

    def initial_params(self):
        return self._make_weights()


def build_trainer(cfg, traffic, make_weights, devices):
    return Trainer(cfg, traffic, make_weights, devices)
