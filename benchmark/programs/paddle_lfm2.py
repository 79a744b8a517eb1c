"""The system under test for the LFM2-MoE family: paddle_tpu's
`Lfm2MoeForCausalLM` through `serving.LLMEngine`. Serving only. Sizes and
constructor arguments come from the configuration and traffic files; the
parameter names are the reference's own."""
from __future__ import annotations

from .paddle_gpt import (decode_seconds, enable_compile_cache,  # noqa: F401
                         pool_blocks_held)


def _model_config(cfg):
    from paddle_tpu.incubate.models.lfm2_moe import Lfm2MoeConfig
    return Lfm2MoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        layer_types=tuple(cfg["layer_types"]),
        num_dense_layers=cfg["num_dense_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        conv_L_cache=cfg["conv_L_cache"], num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        use_expert_bias=cfg["use_expert_bias"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["norm_eps"],
        max_position_embeddings=cfg["max_position_embeddings"],
        initializer_range=cfg["initializer_range"])


def build_engine(cfg, traffic, make_weights):
    """`LLMEngine` over the model built AROUND the seeded weights (made
    once, where they live: a chip-filling set is never held twice), with
    the constructor arguments of the traffic file's `engine` group."""
    from paddle_tpu.incubate.models.lfm2_moe import Lfm2MoeForCausalLM
    from paddle_tpu.serving import LLMEngine
    model = Lfm2MoeForCausalLM(_model_config(cfg), weights=make_weights())
    return LLMEngine(model, **traffic["engine"])


def engine_facts(engine):
    """Shapes a reader needs: the pools' and the block table's."""
    cache = engine.cache
    return {"pool_shape": list(cache.k_pools.shape),
            "slots": engine.max_batch_size,
            "pool_blocks": cache.allocator.capacity,
            "cached_sublayers": cache.spec.num_layers,
            "block_size": engine.block_size,
            "table_entries": engine.max_blocks_per_seq}
