"""The system under test for the MiMo-V2-Flash family: paddle_tpu's
`MiMoV2FlashForCausalLM` through `serving.LLMEngine`. Serving only. Sizes
and constructor arguments come from the configuration and traffic files;
the parameter names are the reference's own."""
from __future__ import annotations

from .paddle_gpt import (decode_seconds, enable_compile_cache,  # noqa: F401
                         pool_blocks_held)


def _model_config(cfg):
    from paddle_tpu.incubate.models.mimo_v2_flash import MiMoV2FlashConfig
    published = cfg.get("published", {})
    return MiMoV2FlashConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        layer_types=tuple(cfg["layer_types"]),
        first_k_dense_replace=cfg["first_k_dense_replace"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        swa_num_key_value_heads=cfg["swa_num_key_value_heads"],
        head_dim=cfg["head_dim"], v_head_dim=cfg["v_head_dim"],
        partial_rotary_factor=cfg["partial_rotary_factor"],
        rope_theta=cfg["rope_theta"], swa_rope_theta=cfg["swa_rope_theta"],
        sliding_window=cfg["sliding_window"],
        add_swa_attention_sink_bias=cfg["add_swa_attention_sink_bias"],
        add_full_attention_sink_bias=cfg["add_full_attention_sink_bias"],
        attention_value_scale=cfg["attention_value_scale"],
        # the router ranks the PUBLISHED experts; the file's own count is
        # what this chip holds of them
        n_routed_experts=published.get("n_routed_experts",
                                       cfg["n_routed_experts"]),
        experts_held=(cfg.get("experts_held_from", 0),
                      cfg["n_routed_experts"]),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        layernorm_epsilon=cfg["layernorm_epsilon"],
        max_position_embeddings=cfg["max_position_embeddings"],
        initializer_range=cfg["initializer_range"])


def build_engine(cfg, traffic, make_weights):
    """`LLMEngine` over the model built AROUND the seeded weights (made
    once, where they live: a chip-filling set is never held twice), with
    the constructor arguments of the traffic file's `engine` group."""
    from paddle_tpu.incubate.models.mimo_v2_flash import \
        MiMoV2FlashForCausalLM
    from paddle_tpu.serving import LLMEngine
    model = MiMoV2FlashForCausalLM(_model_config(cfg),
                                   weights=make_weights())
    return LLMEngine(model, **traffic["engine"])


def engine_facts(engine):
    """Shapes a reader needs: the paged pools', the block table's, and the
    window layers' rings."""
    cache = engine.cache
    spec = cache.spec
    return {"pool_shape": list(cache.k_pools.shape),
            "slots": engine.max_batch_size,
            "pool_blocks": cache.allocator.capacity,
            "cached_sublayers": spec.num_layers,
            "block_size": engine.block_size,
            "table_entries": engine.max_blocks_per_seq,
            "window_layers": spec.window_layers, "window": spec.window,
            "ring_blocks": spec.ring_blocks(engine.block_size),
            "ring_bytes": int(sum(p.nbytes for p in cache.window_pools))}
