"""A MiMo-V2-Flash configuration small enough for the CPU that keeps every
ratio's KIND: window and full layers in one stack (the published pattern's
beginning: full, window x 4, full, window), one leading dense layer, more
key/value heads in a window layer (2, four queries each) than in a full one
(1, eight queries), a key wider than its value, a rotary part of a head
(round(0.334 x 12) = 4 of 12 values), a window (6) shorter than the
contexts served and not a whole number of blocks, a sink a head, a share
of the experts held (4 of 16, top 4). The file's keys are the real
configuration's."""

TINY_MIMO = {
    "attention_value_scale": 0.707, "hidden_size": 64,
    "intermediate_size": 96, "max_position_embeddings": 64,
    "num_attention_heads": 8, "head_dim": 12, "num_hidden_layers": 7,
    "num_key_value_heads": 1, "layernorm_epsilon": 1e-05,
    "rope_theta": 5000000, "vocab_size": 128,
    "partial_rotary_factor": 0.334, "sliding_window": 6,
    "swa_rope_theta": 10000, "attention_bias": False, "v_head_dim": 8,
    "layer_types": ["full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention",
                    "sliding_attention"],
    "first_k_dense_replace": 1,
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False,
    "moe_intermediate_size": 32, "n_routed_experts": 4,
    "experts_held_from": 4, "published": {"n_routed_experts": 16},
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "routed_scaling_factor": None,
    "swa_num_key_value_heads": 2, "initializer_range": 0.1,
    "precision": {"params": "bfloat16", "activations": "bfloat16",
                  "control": "fp8"},
    "program": "benchmark.programs.paddle_mimo",
    "reference": "benchmark.reference.mimo_v2_flash",
}
