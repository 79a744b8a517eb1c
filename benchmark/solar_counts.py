"""Operations and bytes a Solar-Open2 configuration's serving needs,
computed from its file's shapes. Kept with the benchmark so that no later
PR can move the yardstick; `cfg` is the configuration file as loaded, so
the counts are of the layers, the experts and the vocabulary THIS chip
holds.

Matrix products against parameters; softmax attention's products over
what a query may see (its context); and the delta rule of the KDA layers
in the two forms a chip runs it in:

  * a PROMPT, in the chunked (WY) form at `CHUNK` rows, which is what the
    mathematics costs once it is matrix products: a chunk of C rows of a
    head of width D needs the lower triangles of K K^T and Q K^T (C^2 2D
    operations together), the triangular solve for W and U (C^2 2D), B U
    (C^2 D) and the three products with the D x D state (6 C D^2): 5 C D
    + 6 D^2 a row a head, each product counted ONCE (the program
    multiplies in float32 at "highest", six bfloat16 passes, against a
    peak that is bfloat16's). Its bytes: q, k, v in and o out in the
    activations' type, the float32 decays and beta, a prompt's state out.
    That is 90 operations a byte, under the chip's 240: by this count the
    scan's roofline is its BYTES, and a share of a few percent says the
    program moves or recomputes far more than q, k, v, g and o once (the
    sub-block pairs, the triangular inverse, transposes): what a later PR
    may find, holding to the comparison that decides `correct`;
  * a DECODE launch, the recurrence itself: 7 D^2 operations a head a
    token (the decay, S^T k, the rank-one update, S^T q) over a state that
    is read once and written once in float32: bound by the bytes."""
from __future__ import annotations

KDA, GQA = "linear_attention", "full_attention"
CHUNK = 64
HEAD_SLOTS = 2          # a head's two products a (query, key) pair
STATE_BYTES = 4         # the delta rule's matrix is float32


def layers(cfg):
    """(softmax layers, KDA layers)."""
    kinds = cfg["layer_types"]
    return kinds.count(GQA), kinds.count(KDA)


def _linear(cfg):
    lin = cfg["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]


def gqa_params(cfg):
    """q, k, v, the gate and o of a softmax layer."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hd, kh = cfg["head_dim"], cfg["num_key_value_heads"]
    gate = h * hd if cfg["use_gqa_gate"] else 0
    return d * (h * hd + 2 * kh * hd + gate) + h * hd * d


def kda_params(cfg):
    """q, k, v and o, the two low-rank pairs (decay, gate) and beta's
    projection of a KDA layer. (Taps, `A_log`, `dt_bias` and the norm are
    no matrix products.)"""
    d = cfg["hidden_size"]
    h, hd, _ = _linear(cfg)
    return 4 * d * h * hd + 2 * (d * hd + hd * h * hd) + d * h


def expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg):
    """The router ranks the PUBLISHED experts, whatever is held."""
    return cfg["hidden_size"] * cfg.get("published", {}).get(
        "n_routed_experts", cfg["n_routed_experts"])


def token_params(cfg):
    """Parameters EVERY token multiplies here: each layer's projections,
    its router and its shared experts, the head's slice. (The embedding is
    a row read.) Also every parameter a decode launch reads whatever its
    routing."""
    gqa, kda = layers(cfg)
    return gqa * gqa_params(cfg) + kda * kda_params(cfg) \
        + (gqa + kda) * (router_params(cfg)
                         + cfg["n_shared_experts"] * expert_params(cfg)) \
        + cfg["hidden_size"] * cfg["vocab_size"]


def pair_flops(cfg):
    """A softmax layer's operations for ONE (query token, key token)
    pair: q . k and p . v over `head_dim`, every query head."""
    return HEAD_SLOTS * 2 * cfg["head_dim"] * cfg["num_attention_heads"]


def cached_row(cfg):
    """Values a cached token holds in a softmax layer: K and V."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"]


def scan_flops_per_token(cfg, chunk=CHUNK):
    """The chunked delta rule's operations a prompt token, ONE KDA
    layer (the module's docstring)."""
    h, hd, _ = _linear(cfg)
    return h * (5 * chunk * hd + 6 * hd * hd)


def update_flops(cfg):
    """The recurrence's operations a decoded token, ONE KDA layer."""
    h, hd, _ = _linear(cfg)
    return h * 7 * hd * hd


def state_values(cfg):
    """(float32 values of the matrix state, values of the convolutions'
    kept inputs) a slot, ONE KDA layer."""
    h, hd, taps = _linear(cfg)
    return h * hd * hd, (taps - 1) * 3 * h * hd


def scan(cfg, tokens, prompts, bytes_per_value=2):
    """(operations, bytes) of the chunked scan over `tokens` prompt tokens
    of `prompts` prompts in ONE KDA layer: q, k, v read and o written in
    the activations' type, g in float32 and beta; a prompt's state written
    once (it starts at zeros)."""
    h, hd, _ = _linear(cfg)
    matrix, _ = state_values(cfg)
    moved = tokens * h * (4 * hd * bytes_per_value + (hd + 1) * 4) \
        + prompts * matrix * STATE_BYTES
    return scan_flops_per_token(cfg) * tokens, moved


def state_update(cfg, updates, bytes_per_value=2):
    """(operations, bytes) of `updates` one-token updates (an active slot
    of ONE KDA layer of one launch each): the matrix state read and
    written, q, k, v in and o out in the activations' type, g in float32
    and beta."""
    h, hd, _ = _linear(cfg)
    matrix, _ = state_values(cfg)
    moved = updates * (2 * matrix * STATE_BYTES
                       + h * (4 * hd * bytes_per_value + (hd + 1) * 4))
    return update_flops(cfg) * updates, moved


def serve_flops(cfg, prompt_tokens, decoded_tokens, expert_assignments,
                attention_pairs):
    """2 x parameters multiplied: every token's, one expert's for each
    assignment the program computed (the window's own counter); softmax
    attention's products (`attention_pairs`: (query, key) pairs of ONE
    softmax layer); the KDA layers' rule, chunked over prompt tokens and
    the recurrence over decoded ones."""
    gqa, kda = layers(cfg)
    return 2 * (token_params(cfg) * (prompt_tokens + decoded_tokens)
                + expert_params(cfg) * expert_assignments) \
        + pair_flops(cfg) * gqa * attention_pairs \
        + kda * (scan_flops_per_token(cfg) * prompt_tokens
                 + update_flops(cfg) * decoded_tokens)


def prefill_flops(cfg, bucket, expert_assignments):
    """The operations of ONE prefill at `bucket`, padding and all (what
    the program multiplies): `serve_flops` of a prompt that fills it,
    causal pairs."""
    return serve_flops(cfg, bucket, 0, expert_assignments,
                       bucket * (bucket + 1) / 2)


def decode_bytes(cfg, launches, experts_read, attention_tokens,
                 state_updates, bytes_per_value=2):
    """Bytes `launches` decode launches must move: all the weights outside
    the routed experts once a launch, an expert's once for each (launch,
    layer) in which at least one token chose it (`experts_read`, from the
    counters), the cached keys and values some slot attends to
    (`attention_tokens` a softmax layer, summed over launches and slots),
    and each updated state read and written, the matrix in float32 and the
    convolutions' inputs (`state_updates`: active slots x KDA layers,
    summed over the launches)."""
    gqa, _ = layers(cfg)
    matrix, kept = state_values(cfg)
    return bytes_per_value * (
        token_params(cfg) * launches + expert_params(cfg) * experts_read
        + gqa * cached_row(cfg) * attention_tokens) \
        + state_updates * 2 * (matrix * STATE_BYTES
                               + kept * bytes_per_value)


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
