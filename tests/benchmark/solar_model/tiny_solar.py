"""A Solar-Open2 configuration small enough for the CPU that keeps every
ratio's KIND: one softmax layer in four beside three delta-rule layers
(the published pattern's beginning, two periods of it), four query heads a
key/value head in the softmax layers, no positions, the gate; delta-rule
heads with a matrix state a head (4 heads of 16 x 16), convolutions of 4
taps, steps up to 2 (negative eigenvalues); a share of the experts held
(8 of 64 from id 8, top 4: one of eight shares) beside one shared expert; an untied head. The
file's keys are the real configuration's."""

TINY_SOLAR = {
    "model_type": "solar_open2", "hidden_size": 64,
    "intermediate_size": 160, "moe_intermediate_size": 32,
    "num_hidden_layers": 8, "num_attention_heads": 8, "head_dim": 8,
    "num_key_value_heads": 2, "vocab_size": 128,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                           "num_heads": 4, "num_kv_heads": None},
    "layer_types": ["full_attention", "linear_attention",
                    "linear_attention", "linear_attention"] * 2,
    "rms_norm_eps": 1e-05, "tie_word_embeddings": False,
    "max_position_embeddings": 64, "first_k_dense_replace": 0,
    "use_rope": False, "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True,
    "n_routed_experts": 8, "experts_held_from": 8,
    "published": {"n_routed_experts": 64},
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 4,
    "initializer_range": 0.1,
    "precision": {"params": "bfloat16", "activations": "bfloat16",
                  "state": "float32", "control": "fp8"},
    "program": "benchmark.programs.paddle_solar",
    "reference": "benchmark.reference.solar_open2",
}
