"""A LongCat-Flash configuration small enough for the CPU that keeps every
ratio's KIND: two attention sublayers and two dense FFNs a layer around one
expert block, a router over real + identity experts that is wider than
what is held, more choices a token than experts held, rotary and
compressed parts of a latent row, a vocabulary slice. The file's keys are
the real configuration's."""

TINY_LONGCAT = {
    "vocab_size": 128, "hidden_size": 64, "ffn_hidden_size": 128,
    "expert_ffn_hidden_size": 32, "num_layers": 2,
    "num_attention_heads": 4, "kv_lora_rank": 16, "q_lora_rank": 32,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "qk_nope_head_dim": 16,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "routed_scaling_factor": 6, "n_routed_experts": 4,
    "max_position_embeddings": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 10000000, "zero_expert_num": 8, "moe_topk": 6,
    "initializer_range": 0.1, "experts_held_from": 0,
    "published": {"n_routed_experts": 16},
    "precision": {"params": "bfloat16", "activations": "bfloat16",
                  "control": "fp8"},
    "program": "benchmark.programs.paddle_longcat",
    "reference": "benchmark.reference.longcat_flash",
}
