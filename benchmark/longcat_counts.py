"""Operations and bytes a LongCat-Flash configuration's serving needs,
computed from its file's shapes. Kept with the benchmark so that no later
PR can move the yardstick; `cfg` is the configuration file as loaded, so
the counts are of what THIS chip holds (its experts, its vocabulary slice).

Only matrix products against parameters are counted: attention's products
over the context are left out of the operations (an under-count, so a share
of a peak computed from them cannot pass 100%), and its cached rows are in
the bytes."""
from __future__ import annotations


def attention_params(cfg):
    """One latent-attention sublayer's five projections."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (d * ql + ql * h * (nope + rope) + d * (kl + rope)
            + kl * h * (nope + vd) + h * vd * d)


def dense_ffn_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["ffn_hidden_size"]


def expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"]


def router_params(cfg):
    ranked = cfg.get("published", {}).get(
        "n_routed_experts", cfg["n_routed_experts"]) + cfg["zero_expert_num"]
    return cfg["hidden_size"] * ranked


def token_params(cfg):
    """Parameters EVERY token multiplies here: a layer's two attention
    sublayers, two dense FFNs and router, and the head's slice. The
    embedding is a row read, the identity experts a scale: neither is a
    matrix product."""
    layer = 2 * attention_params(cfg) + 2 * dense_ffn_params(cfg) \
        + router_params(cfg)
    return cfg["num_layers"] * layer \
        + cfg["hidden_size"] * cfg["vocab_size"]


def serve_flops(cfg, tokens, expert_assignments):
    """2 x parameters multiplied: every token's, and one held expert's for
    each assignment the program computed (the window's own counter)."""
    return 2 * (token_params(cfg) * tokens
                + expert_params(cfg) * expert_assignments)


def decode_bytes(cfg, launches, experts_read, rows_held, bytes_per_value=2):
    """Bytes `launches` decode launches must read: all the weights outside
    the experts once a launch, a held expert's once for each (launch,
    layer) in which at least one token chose it (`experts_read`, from the
    counters), and the cached rows that held a token some slot attends to
    (`rows_held`: rows x cached sublayers, at the row's own values: the
    same work whatever layout a pool pads them to)."""
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return bytes_per_value * (token_params(cfg) * launches
                              + expert_params(cfg) * experts_read
                              + row * rows_held)
