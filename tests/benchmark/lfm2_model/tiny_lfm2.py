"""An LFM2-MoE configuration small enough for the CPU that keeps every
ratio's KIND: two kinds of layer in one stack (the published pattern's
beginning: conv, conv, attention, conv, conv, conv, attention), two leading
dense layers, four query heads a key/value head, a convolution of kernel 3,
top 4 of more experts than a token chooses, every expert held, a tied
head. The file's keys are the real configuration's."""

TINY_LFM2 = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 64,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                    "conv", "full_attention"],
    "max_position_embeddings": 64, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 8,
    "num_dense_layers": 2, "num_experts": 16, "num_experts_per_tok": 4,
    "num_hidden_layers": 7, "num_key_value_heads": 2,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 128, "initializer_range": 0.1,
    "precision": {"params": "bfloat16", "activations": "bfloat16",
                  "control": "fp8"},
    "program": "benchmark.programs.paddle_lfm2",
    "reference": "benchmark.reference.lfm2_moe",
}
