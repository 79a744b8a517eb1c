"""Operations and bytes an LFM2-MoE configuration's serving needs, computed
from its file's shapes. Kept with the benchmark so that no later PR can
move the yardstick; `cfg` is the configuration file as loaded, so the
counts are of the layers THIS chip holds (every expert, the whole
vocabulary).

Matrix products against parameters, and attention's products over the
context (counted here: a prompt token of a causal prefill meets half the
prompt, a decoded token its whole context). The convolution's three
elementwise products a token are left out (6 d operations a layer against
2 x 16.8 M: under a thousandth)."""
from __future__ import annotations


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def conv_params(cfg):
    """A convolution layer's two projections (its depthwise taps multiply
    nothing by a matrix)."""
    d = cfg["hidden_size"]
    return d * 3 * d + d * d


def attention_params(cfg):
    d, hd = cfg["hidden_size"], head_dim(cfg)
    h, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * h * hd + 2 * d * kh * hd + h * hd * d


def dense_ffn_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg):
    return cfg["hidden_size"] * cfg["num_experts"]


def layers(cfg):
    """(convolution layers, attention layers, dense layers, expert
    layers) of the file's `layer_types`."""
    kinds = cfg["layer_types"]
    dense = cfg["num_dense_layers"]
    return (kinds.count("conv"), kinds.count("full_attention"), dense,
            len(kinds) - dense)


def token_params(cfg):
    """Parameters EVERY token multiplies here: each layer's `op`, the dense
    FFNs, a router an expert layer, and the tied head. The embedding is a
    row read of the head's own matrix, so these are also every parameter
    outside the experts: what a decode launch reads whatever its
    routing."""
    conv, attn, dense, expert = layers(cfg)
    return conv * conv_params(cfg) + attn * attention_params(cfg) \
        + dense * dense_ffn_params(cfg) + expert * router_params(cfg) \
        + cfg["hidden_size"] * cfg["vocab_size"]


def attention_flops(cfg, query_context_pairs):
    """Attention's own products: 2 x (q . k and p . v) x head width x
    query heads x attention layers, for each (query token, context token)
    pair."""
    _, attn, _, _ = layers(cfg)
    return 4 * head_dim(cfg) * cfg["num_attention_heads"] * attn \
        * query_context_pairs


def serve_flops(cfg, tokens, expert_assignments, query_context_pairs=0):
    """2 x parameters multiplied: every token's, one expert's for each
    assignment the program computed (the window's own counter), and
    attention's products over the context."""
    return 2 * (token_params(cfg) * tokens
                + expert_params(cfg) * expert_assignments) \
        + attention_flops(cfg, query_context_pairs)


def state_values(cfg, slots):
    """The values of the convolutions' state a launch reads and writes for
    `slots` slots."""
    conv, _, _, _ = layers(cfg)
    return conv * slots * (cfg["conv_L_cache"] - 1) * cfg["hidden_size"]


def decode_bytes(cfg, launches, experts_read, tokens_held, slots,
                 bytes_per_value=2):
    """Bytes `launches` decode launches must move: all the weights outside
    the experts once a launch, an expert's once for each (launch, layer)
    in which at least one token chose it (`experts_read`, from the
    counters), the cached keys and values of the tokens some slot attends
    to (`tokens_held`: tokens x attention layers, K and V at the key/value
    heads' width), and the slots' convolution state read and written."""
    row = 2 * cfg["num_key_value_heads"] * head_dim(cfg)
    return bytes_per_value * (token_params(cfg) * launches
                              + expert_params(cfg) * experts_read
                              + row * tokens_held
                              + 2 * state_values(cfg, slots) * launches)


def expert_products(cfg, assignments, experts_read, bytes_per_value=2):
    """(operations, bytes) of the grouped products for `assignments` rows
    routed to `experts_read` (launch or prefill, layer, expert) triples:
    three products a row; each expert read once, each row gathered and
    its result written in the model's width."""
    d = cfg["hidden_size"]
    ops = 2 * expert_params(cfg) * assignments
    moved = bytes_per_value * (expert_params(cfg) * experts_read
                               + 2 * d * assignments)
    return ops, moved


def decode_attention(cfg, calls, tokens_held, slots, bytes_per_value=2):
    """(operations, bytes) of `calls` paged decode attention calls (a call
    an attention layer a launch) over `tokens_held` cached tokens in all
    (tokens x calls, summed): a query head's two products a cached token;
    K and V rows read once, the queries and the output."""
    hd = head_dim(cfg)
    h, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ops = 4 * hd * h * tokens_held
    moved = bytes_per_value * (2 * kh * hd * tokens_held
                               + 2 * h * hd * slots * calls)
    return ops, moved
