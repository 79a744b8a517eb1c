"""The system under test for the Solar-Open2 family: paddle_tpu's
`SolarOpen2ForCausalLM` through `serving.LLMEngine`. Serving only. Sizes
and constructor arguments come from the configuration and traffic files;
the parameter names are the reference's own."""
from __future__ import annotations

# imported HERE, not where it is used: a checkout without the model (the
# parent of the PR that brought it) fails at the import of this module,
# before a device is claimed or a weight is made
from paddle_tpu.incubate.models.solar_open2 import (SolarOpen2Config,
                                                    SolarOpen2ForCausalLM)

from .paddle_gpt import (decode_seconds, enable_compile_cache,  # noqa: F401
                         pool_blocks_held)


def _model_config(cfg):
    published = cfg.get("published", {})
    linear = cfg["linear_attn_config"]
    return SolarOpen2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        layer_types=tuple(cfg["layer_types"]),
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], use_gqa_gate=cfg["use_gqa_gate"],
        linear_num_heads=linear["num_heads"],
        linear_head_dim=linear["head_dim"],
        short_conv_kernel_size=linear["short_conv_kernel_size"],
        kda_allow_neg_eigval=cfg["kda_allow_neg_eigval"],
        # the router ranks the PUBLISHED experts; the file's own count is
        # what this chip holds of them
        n_routed_experts=published.get("n_routed_experts",
                                       cfg["n_routed_experts"]),
        experts_held=(cfg.get("experts_held_from", 0),
                      cfg["n_routed_experts"]),
        n_shared_experts=cfg["n_shared_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        rms_norm_eps=cfg["rms_norm_eps"],
        max_position_embeddings=cfg["max_position_embeddings"],
        initializer_range=cfg["initializer_range"])


def build_engine(cfg, traffic, make_weights):
    """`LLMEngine` over the model built AROUND the seeded weights (made
    once, where they live: a chip-filling set is never held twice), with
    the constructor arguments of the traffic file's `engine` group."""
    from paddle_tpu.serving import LLMEngine
    model = SolarOpen2ForCausalLM(_model_config(cfg), weights=make_weights())
    return LLMEngine(model, **traffic["engine"])


def engine_facts(engine):
    """Shapes a reader needs: the pools' and the block table's (the
    state's bytes and its layers' work are `engine.stats()`')."""
    cache = engine.cache
    return {"pool_shape": list(cache.k_pools.shape),
            "slots": engine.max_batch_size,
            "pool_blocks": cache.allocator.capacity,
            "cached_sublayers": cache.spec.num_layers,
            "block_size": engine.block_size,
            "table_entries": engine.max_blocks_per_seq}
