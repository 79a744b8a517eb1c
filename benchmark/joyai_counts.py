"""Operations and bytes a JoyAI-LLM-Flash (DeepSeek-V3 family)
configuration's TRAINING needs, computed from its file's shapes. Kept with
the benchmark so that no later PR can move the yardstick; `cfg` is the
configuration file as loaded, so the counts are of what THIS chip holds
(its experts, its vocabulary slice, its layers and the MTP module).

A trained token costs 6 x the parameters it multiplies (forward 2, backward
4), the held routed experts by the step's own counter of assignments
COMPUTED, and attention's products over the causal half of the context at
the heads' own widths (192-wide q and k, 128-wide v). Operations a
rematerialising backward pass makes again are not counted."""
from __future__ import annotations


def attention_params(cfg):
    """One latent-attention block's five projections."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (d * ql + ql * h * (nope + rope) + d * (kl + rope)
            + kl * h * (nope + vd) + h * vd * d)


def dense_ffn_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg):
    """One routed expert (a shared expert is `n_shared_experts` of them)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg):
    ranked = cfg.get("published", {}).get("n_routed_experts",
                                          cfg["n_routed_experts"])
    return cfg["hidden_size"] * ranked


def blocks(cfg):
    """(dense blocks, expert blocks) the step runs: the layers and the MTP
    module's one more expert block."""
    dense = cfg["first_k_dense_replace"]
    return dense, cfg["num_hidden_layers"] - dense \
        + cfg["num_nextn_predict_layers"]


def token_params(cfg):
    """Parameters EVERY token multiplies here: attention in every block,
    the dense FFN, an expert block's router and shared expert, the MTP
    module's projection, and the head's slice once for the main model and
    once for each MTP module. The embedding is a row read."""
    d = cfg["hidden_size"]
    dense, expert = blocks(cfg)
    mtp = cfg["num_nextn_predict_layers"]
    return ((dense + expert) * attention_params(cfg)
            + dense * dense_ffn_params(cfg)
            + expert * (router_params(cfg)
                        + cfg["n_shared_experts"] * expert_params(cfg))
            + mtp * 2 * d * d
            + (1 + mtp) * d * cfg["vocab_size"])


def attention_flops_per_token(cfg, seq):
    """Forward and backward of every block's causal attention, a token:
    QK^T and dQ, dK at the q/k width, PV and dV, dP at the v width, each 2
    x width x heads over the causal half of the context."""
    dense, expert = blocks(cfg)
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (dense + expert) * 3 * 2 * cfg["num_attention_heads"] \
        * (qk + cfg["v_head_dim"]) * seq / 2


def train_flops_per_token(cfg, seq, computed_per_token):
    """`computed_per_token`: assignments the held experts computed a token
    a step (all expert blocks together), from the step's counter."""
    return 6 * token_params(cfg) \
        + 6 * expert_params(cfg) * computed_per_token \
        + attention_flops_per_token(cfg, seq)


def flash_attention_train(batch, heads, seq, qk_dim, v_dim,
                          bytes_per_value=2):
    """(operations, bytes) of one block's causal attention, forward and
    backward, at unequal head widths.

    Operations: forward QK^T (q/k width) and PV (v width), backward dV and
    dP (v width), dQ and dK (q/k width): 2 * seq * seq * width each for a
    head, halved by the causal mask. The backward pass's recomputation of
    QK^T is not needed by the algorithm and is not counted.
    Bytes: forward reads q, k, v and writes o; backward reads q, k, v, o
    and do and writes dq, dk, dv: six tensors at each width."""
    ops = 3 * 2 * batch * heads * seq * seq * (qk_dim + v_dim) / 2
    moved = 6 * batch * seq * heads * (qk_dim + v_dim) * bytes_per_value
    return ops, moved


def expert_products_train(cfg, rows, blocks_run, bytes_per_value=2):
    """(operations, bytes) of the grouped products of `blocks_run` expert
    blocks over `rows` computed assignments in all (the step's counter).

    Operations: gate, up and down products forward, and for each the
    gradient of its input and of its weight: 3 x 3 x 2 * d * f a row.
    Bytes: every held expert's three matrices read forward, read backward
    and their gradient written; a row's input, two hidden rows and output
    forward, and as many gradients backward."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    ops = 3 * 3 * 2 * d * f * rows
    weights = 3 * blocks_run * cfg["n_routed_experts"] * expert_params(cfg)
    moved = bytes_per_value * (weights + 2 * rows * (2 * d + 2 * f))
    return ops, moved
