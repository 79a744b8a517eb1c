"""A JoyAI-LLM-Flash configuration small enough for the CPU that keeps
EVERY mechanism: a leading dense layer and two expert layers, the MTP
module, a router over 16 experts of which 4 are held (top 4, sigmoid
scores, weights normalised over all the chosen), a shared expert, latent
attention whose q/k heads (8 + 4) are wider than its v heads (6), a
vocabulary slice. The file's keys are the real configuration's."""

TINY_JOYAI = {
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 32, "intermediate_size": 64, "kv_lora_rank": 8,
    "moe_intermediate_size": 16, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 4, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts_per_tok": 4,
    "num_hidden_layers": 3, "num_nextn_predict_layers": 1,
    "q_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 32000000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 6,
    "vocab_size": 96, "experts_held_from": 4, "initializer_range": 0.1,
    "mtp_loss_weight": 0.3, "bias_update_speed": 0.001,
    "published": {"n_routed_experts": 16},
    "precision": {"params": "bfloat16", "activations": "bfloat16",
                  "optimizer_state": "float32", "control": "fp8"},
    "optimizer": {"name": "AdamW", "learning_rate": 1e-4,
                  "weight_decay": 0.01, "beta1": 0.9, "beta2": 0.999,
                  "epsilon": 1e-8},
    "program": "benchmark.programs.paddle_joyai",
    "reference": "benchmark.reference.joyai_llm_flash",
}
