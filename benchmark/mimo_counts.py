"""Operations and bytes a MiMo-V2-Flash configuration's serving needs,
computed from its file's shapes. Kept with the benchmark so that no later
PR can move the yardstick; `cfg` is the configuration file as loaded, so
the counts are of the layers, the experts and the vocabulary THIS chip
holds.

Matrix products against parameters, and attention's products over what a
query may see: a full layer's whole context, a window layer's band of
`sliding_window` (the MATHEMATICS' count, whatever implements it: a kernel
that multiplies a band as a triangle, or streams a context for a window,
reads low, never over 100%)."""
from __future__ import annotations

FULL, WINDOW = "full_attention", "sliding_attention"
HEAD_SLOTS = 2          # a head's two products a (query, key) pair


def kv_heads(cfg, kind):
    return cfg["swa_num_key_value_heads"] if kind == WINDOW \
        else cfg["num_key_value_heads"]


def attention_params(cfg, kind):
    """q, k, v and o projections of a layer of `kind`."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dk, dv, kh = cfg["head_dim"], cfg["v_head_dim"], kv_heads(cfg, kind)
    return d * (h * dk + kh * dk + kh * dv) + h * dv * d


def dense_ffn_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg):
    """The router ranks the PUBLISHED experts, whatever is held."""
    return cfg["hidden_size"] * cfg.get("published", {}).get(
        "n_routed_experts", cfg["n_routed_experts"])


def layers(cfg):
    """(full layers, window layers, dense layers, expert layers)."""
    kinds = cfg["layer_types"]
    dense = cfg["first_k_dense_replace"]
    return (kinds.count(FULL), kinds.count(WINDOW), dense,
            len(kinds) - dense)


def token_params(cfg):
    """Parameters EVERY token multiplies here: each layer's attention
    projections, the dense FFN, a router an expert layer, the head's
    slice. (The embedding is a row read.) Also every parameter a decode
    launch reads whatever its routing."""
    full, window, dense, expert = layers(cfg)
    return full * attention_params(cfg, FULL) \
        + window * attention_params(cfg, WINDOW) \
        + dense * dense_ffn_params(cfg) + expert * router_params(cfg) \
        + cfg["hidden_size"] * cfg["vocab_size"]


def pair_flops(cfg):
    """A layer's operations for ONE (query token, key token) pair: q . k
    over `head_dim` and p . v over `v_head_dim`, every query head."""
    return HEAD_SLOTS * (cfg["head_dim"] + cfg["v_head_dim"]) \
        * cfg["num_attention_heads"]


def band_pairs(cfg, length):
    """(query, key) pairs of a window layer over a prompt of `length`:
    query i sees min(i + 1, window) keys."""
    w = min(cfg["sliding_window"], length)
    return length * w - w * (w - 1) / 2


def serve_flops(cfg, tokens, expert_assignments, full_pairs=0,
                window_pairs=0):
    """2 x parameters multiplied: every token's, one expert's for each
    assignment the program computed (the window's own counter), and
    attention's products: `full_pairs` and `window_pairs` are (query, key)
    pairs of ONE layer of the kind."""
    full, window, _, _ = layers(cfg)
    return 2 * (token_params(cfg) * tokens
                + expert_params(cfg) * expert_assignments) \
        + pair_flops(cfg) * (full * full_pairs + window * window_pairs)


def cached_row(cfg, kind):
    """Values a cached token holds in a layer of `kind`: K and V."""
    return kv_heads(cfg, kind) * (cfg["head_dim"] + cfg["v_head_dim"])


def decode_bytes(cfg, launches, experts_read, full_tokens, window_tokens,
                 bytes_per_value=2):
    """Bytes `launches` decode launches must move: all the weights outside
    the experts once a launch, an expert's once for each (launch, layer)
    in which at least one token chose it (`experts_read`, from the
    counters), and the cached keys and values some slot attends to:
    `full_tokens` a full layer (the contexts), `window_tokens` a window
    layer (the windows), summed over launches and slots."""
    full, window, _, _ = layers(cfg)
    return bytes_per_value * (
        token_params(cfg) * launches + expert_params(cfg) * experts_read
        + full * cached_row(cfg, FULL) * full_tokens
        + window * cached_row(cfg, WINDOW) * window_tokens)


def expert_products(cfg, assignments, experts_read, bytes_per_value=2):
    """(operations, bytes) of the grouped products for `assignments` rows
    routed to `experts_read` (call, layer, expert) triples: three products
    a row; each expert read once, each row gathered and its result written
    in the model's width."""
    d = cfg["hidden_size"]
    ops = 2 * expert_params(cfg) * assignments
    moved = bytes_per_value * (expert_params(cfg) * experts_read
                               + 2 * d * assignments)
    return ops, moved


def decode_attention(cfg, kind, calls, tokens, slots, bytes_per_value=2):
    """(operations, bytes) of `calls` paged decode attention calls of a
    layer of `kind` over `tokens` cached tokens in all (summed over calls
    and slots: a full layer's contexts, a window layer's windows): K and V
    rows read once, the queries in and the output out."""
    h = cfg["num_attention_heads"]
    ops = pair_flops(cfg) * tokens
    moved = bytes_per_value * (
        cached_row(cfg, kind) * tokens
        + h * (cfg["head_dim"] + cfg["v_head_dim"]) * slots * calls)
    return ops, moved


def band_attention(cfg, calls, length, bytes_per_value=2):
    """(operations, bytes) of `calls` prefill attentions of a window layer
    over prompts of mean `length` (linear in it from the window up): the
    band's pairs; q, k, v read and the output written once."""
    h = cfg["num_attention_heads"]
    ops = pair_flops(cfg) * band_pairs(cfg, length) * calls
    moved = bytes_per_value * calls * length * (
        h * (cfg["head_dim"] + cfg["v_head_dim"]) + cached_row(cfg, WINDOW))
    return ops, moved
