"""What a configuration is held to, whatever its model: the keys it may
cut are those the `model-configs` guide's section 4 lets a cell cut, never
a width; its file says where it comes from and where it departs; and
where it states the source's own count beside a reduced one (the file's
`published` group), the floors hold: an eighth of the vocabulary, 8 routed
experts, four layers after the leading dense ones, and of a layer pattern
its beginning, a whole period of it, every kind of layer it has and each
within one layer of its published share. `problems(entry, cfg)` lists what is
wrong with one entry of BENCHMARK.json's `configs` and its file."""
import re

# depth and the layer pattern; experts, heads and vocabulary rows HELD;
# and what `gpt2_124m` changed to be held to a reference (dropout off)
DEPTH = re.compile(r"^(n_layer|num_hidden_layers|num_layers)$")
# how many leading layers are dense: one count under either family's key
LEADING_DENSE = re.compile(r"^(first_k_dense_replace|num_dense_layers)$")
PATTERN = re.compile(r"^(layer_types|mlp_only_layers|"
                     r"num_nextn_predict_layers|full_attention_interval)$")
EXPERTS_HELD = re.compile(r"^(n_routed_experts|num_experts|"
                          r"num_local_experts)$")
HEADS_HELD = re.compile(r"^(num_attention_heads|num_key_value_heads)$")
VOCABULARY = re.compile(r"^vocab_size$")
DROPOUT = re.compile(r"(_pdrop|dropout(_prob)?)$")
MAY_CUT = (DEPTH, LEADING_DENSE, PATTERN, EXPERTS_HELD, HEADS_HELD, VOCABULARY,
           DROPOUT)
WIDTH = re.compile(
    r"(^hidden_size$|^n_embd$|^d_model$|intermediate_size$|^n_inner$|"
    r"head_dim$|_rank$|_dim$|^num_experts_per_tok$|^top_k$|window|"
    r"state_size$|^d_state$|^d_conv$|^expand$|^n_head$)")


def period(pattern):
    """The period as the pattern's publisher counts it: the shortest p with
    `pattern[i] == pattern[i + p]` at EVERY place from the start through
    three quarters or more of the places where it can be asked. A tail
    that departs (a last attention layer that comes one layer early)
    leaves the period what it is; a pattern that never repeats has its own
    length."""
    n = len(pattern)
    for p in range(1, n + 1):
        agree = next((i for i in range(n - p)
                      if pattern[i] != pattern[i + p]), n - p)
        if 4 * agree >= 3 * (n - p):
            return p


def leading_dense(cfg):
    return next((v for k, v in cfg.items() if LEADING_DENSE.search(k)), 0)


def pattern_problems(kept, source):
    """What section 4 of the `model-configs` guide means by a whole period:
    `kept`, the layers after the dense ones, against the `source`'s."""
    found = []
    if len(kept) < period(source):
        found.append("layer_types keeps no whole period of the published "
                     "pattern")
    if kept != source[:len(kept)]:
        found.append("layer_types is not the beginning of the published "
                     "pattern")
    for kind in sorted(set(source)):
        share = source.count(kind) / len(source)
        if kind not in kept:
            found.append(f"layer_types keeps no {kind} layer")
        elif abs(kept.count(kind) - share * len(kept)) > 1:
            found.append(f"layer_types keeps {kept.count(kind)} {kind} "
                         f"layers of {len(kept)}: over one layer from the "
                         f"published share, {share:.0%}")
    return found


def problems(entry, cfg):
    found = []
    for key in entry["reduced"]:
        if WIDTH.search(key) or not any(r.search(key) for r in MAY_CUT):
            found.append(f"`reduced` names {key}: a width, or no kind of "
                         "key that a cell may cut")
        if key not in cfg.get("changed", {}) \
                and key not in cfg.get("assumed", {}):
            found.append(f"{key} is reduced and the file says neither under "
                         "`changed` nor `assumed` how")
    for key in ("source", "deployment", "program", "reference"):
        if not cfg.get(key):
            found.append(f"the file states no `{key}`")
    dense = leading_dense(cfg)
    for key, source in cfg.get("published", {}).items():
        if key not in entry["reduced"]:
            found.append(f"{key} has a published count and is not in "
                         "`reduced`")
        if VOCABULARY.search(key) and cfg[key] * 8 < source:
            found.append(f"{key} {cfg[key]}: under an eighth of {source}")
        if EXPERTS_HELD.search(key) and cfg[key] < 8:
            found.append(f"{key} {cfg[key]}: under 8 routed experts")
        if DEPTH.search(key) and cfg[key] < source \
                and cfg[key] - dense < 4:
            found.append(f"{key} {cfg[key]}: under four layers after the "
                         f"{dense} leading dense")
        if key == "layer_types":
            found += pattern_problems(cfg[key][dense:], source[dense:])
    return found
