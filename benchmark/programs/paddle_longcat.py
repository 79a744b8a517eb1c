"""The system under test for the LongCat-Flash family: paddle_tpu's
`LongCatFlashForCausalLM` through `serving.LLMEngine`. Serving only. Sizes
and constructor arguments come from the configuration and traffic files;
the parameter names are the reference's own."""
from __future__ import annotations

from .paddle_gpt import (decode_seconds, enable_compile_cache,  # noqa: F401
                         pool_blocks_held)


def _model_config(cfg):
    from paddle_tpu.incubate.models.longcat_flash import LongCatFlashConfig
    published = cfg.get("published", {})
    return LongCatFlashConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        ffn_hidden_size=cfg["ffn_hidden_size"],
        expert_ffn_hidden_size=cfg["expert_ffn_hidden_size"],
        num_layers=cfg["num_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        mla_scale_q_lora=cfg["mla_scale_q_lora"],
        mla_scale_kv_lora=cfg["mla_scale_kv_lora"],
        # the router ranks the PUBLISHED experts; the file's own count is
        # what this chip holds of them
        n_routed_experts=published.get("n_routed_experts",
                                       cfg["n_routed_experts"]),
        experts_held=(cfg.get("experts_held_from", 0),
                      cfg["n_routed_experts"]),
        zero_expert_num=cfg["zero_expert_num"], moe_topk=cfg["moe_topk"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        rope_theta=cfg["rope_theta"], rms_norm_eps=cfg["rms_norm_eps"],
        max_position_embeddings=cfg["max_position_embeddings"],
        initializer_range=cfg["initializer_range"])


def build_engine(cfg, traffic, make_weights):
    """`LLMEngine` over the model built AROUND the seeded weights (made
    once, where they live: a chip-filling set is never held twice), with
    the constructor arguments of the traffic file's `engine` group."""
    from paddle_tpu.incubate.models.longcat_flash import \
        LongCatFlashForCausalLM
    from paddle_tpu.serving import LLMEngine
    model = LongCatFlashForCausalLM(_model_config(cfg),
                                    weights=make_weights())
    return LLMEngine(model, **traffic["engine"])


def engine_facts(engine):
    """Shapes a reader needs: the latent pool's, and what a row holds."""
    cache = engine.cache
    pool = cache.k_pools
    return {"pool_shape": list(pool.shape), "slots": engine.max_batch_size,
            "pool_blocks": cache.allocator.capacity,
            "cache_kind": cache.spec.kind,
            "cached_sublayers": cache.spec.num_layers,
            "row_values": sum(part[0] for part in cache.spec.parts),
            "row_width": pool.shape[-1], "block_size": engine.block_size,
            "table_entries": engine.max_blocks_per_seq,
            "pool_bytes": int(pool.nbytes + cache.v_pools.nbytes)}
