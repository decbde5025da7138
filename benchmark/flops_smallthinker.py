"""Operations and bytes the SmallThinker (``smallthinker``) block requires,
from the configuration file's own keys: what ``flops.py`` is to the llama
block.  Needed work only: a window layer reads at most
``sliding_window_size`` tokens of a row's context, a global layer all of
them, both at ONE count of query heads; an expert's weights count once for
each pass that touches it, and every expert of a layer is held here.
"""

from __future__ import annotations


def layer_kinds(c: dict) -> list:
    """The kind of each layer that is here (the published lists are
    whole; ``sliding_window_layout`` 1 = a window layer)."""
    return ["window" if w else "full"
            for w in c["sliding_window_layout"][:c["num_hidden_layers"]]]


def layers_of_kind(c: dict, kind: str) -> int:
    return layer_kinds(c).count(kind)


def attention_params(c: dict) -> int:
    """Wq, Wk, Wv and Wo of one layer (either kind: one head count)."""
    e, d = c["hidden_size"], c["head_dim"]
    return 2 * e * d * (c["num_attention_heads"] + c["num_key_value_heads"])


def expert_params(c: dict) -> int:
    """Gate, up and down projections of one expert."""
    return 3 * c["hidden_size"] * c["moe_ffn_hidden_size"]


def layer_params(c: dict) -> int:
    """One layer as held here: attention, the router over every expert it
    scores, the experts held (all of them) and the two norms' gains."""
    return (attention_params(c)
            + c["hidden_size"] * c["routed_experts_scored"]
            + expert_params(c) * c["moe_num_primary_experts"]
            + 2 * c["hidden_size"])


def total_params(c: dict) -> int:
    """Every parameter held: the layers, the embedding, the untied head
    and the final norm's gain."""
    return (c["num_hidden_layers"] * layer_params(c)
            + 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def expert_bytes_per_step(c: dict, w_bytes: int = 2) -> int:
    """Bytes of expert weights a step streams when its tokens touch every
    expert of every layer."""
    return (c["num_hidden_layers"] * c["moe_num_primary_experts"]
            * expert_params(c) * w_bytes)


def kv_bytes_per_token(c: dict, kv_bytes: int = 2) -> int:
    """K and V of one token in one layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * kv_bytes


def window_tokens(c: dict, contexts) -> int:
    """Tokens the window layers attend for decode rows of these contexts."""
    return sum(min(int(n), c["sliding_window_size"]) for n in contexts)


def attention_bytes(c: dict, full_tokens: int, in_window_tokens: int,
                    kv_bytes: int = 2) -> int:
    """Bytes paged attention must read for decode rows whose contexts sum
    to ``full_tokens`` and, cut to the window, to ``in_window_tokens``:
    each attended token's K and V once a layer of its kind (one fetch
    serves the 7 query heads of a KV head; queries and outputs are
    negligible)."""
    return kv_bytes_per_token(c, kv_bytes) * (
        layers_of_kind(c, "full") * full_tokens
        + layers_of_kind(c, "window") * in_window_tokens)


def attention_flops(c: dict, full_tokens: int, in_window_tokens: int) -> int:
    """Score and value matmuls, one query row of every head against each
    attended token, every layer of the kind."""
    return 4 * c["head_dim"] * c["num_attention_heads"] * (
        layers_of_kind(c, "full") * full_tokens
        + layers_of_kind(c, "window") * in_window_tokens)


def grouped_expert_bytes(c: dict, experts_touched: int, pairs: int,
                         w_bytes: int = 2) -> int:
    """Bytes of the grouped expert matmul: the weights of every expert a
    pass touches, once, and each pair's row in and out."""
    return (experts_touched * expert_params(c) * w_bytes
            + pairs * 2 * c["hidden_size"] * w_bytes)


def grouped_expert_flops(c: dict, pairs: int) -> int:
    """Gate, up and down projections of every token-expert pair."""
    return 2 * pairs * expert_params(c)
