"""Operations and bytes the Laguna (``laguna``) block requires, from the
configuration file's own keys: what ``flops.py`` is to the llama block.
Needed work only: a window layer reads at most ``sliding_window`` tokens of
a row's context, a full layer all of them; each kind has its own count of
query heads.  The held experts' counts are ``flops_pangu_moe``'s
(``expert_params`` reads ``hidden_size`` and ``moe_intermediate_size``,
which this family names alike).
"""

from __future__ import annotations

from .flops_pangu_moe import expert_params

KINDS = {"full_attention": "full", "sliding_attention": "window"}


def layer_kinds(c: dict) -> list:
    """The kind of each layer that is here (the published list is whole)."""
    return [KINDS[t] for t in c["layer_types"][:c["num_hidden_layers"]]]


def layers_of_kind(c: dict, kind: str) -> int:
    return layer_kinds(c).count(kind)


def heads_of_kind(c: dict, kind: str) -> int:
    at = layer_kinds(c).index(kind)
    return c["num_attention_heads_per_layer"][at]


def attention_params(c: dict, kind: str) -> int:
    """Wq, Wk, Wv, Wo and the per-head gate of one layer of ``kind``."""
    e, d, k = c["hidden_size"], c["head_dim"], c["num_key_value_heads"]
    h = heads_of_kind(c, kind)
    return 2 * e * h * d + 2 * e * k * d + e * h


def routed_layer_extra(c: dict) -> int:
    """A routed layer less its attention, as held here: the router over
    every expert it scores, the shared expert and the experts held."""
    shared = c["shared_expert_intermediate_size"] // c["moe_intermediate_size"]
    return (c["hidden_size"] * c["routed_experts_scored"]
            + expert_params(c) * (shared + c["num_experts"]))


def total_params(c: dict) -> int:
    dense = c["leading_dense_layers"]
    return (sum(attention_params(c, kind) for kind in layer_kinds(c))
            + dense * 3 * c["hidden_size"] * c["intermediate_size"]
            + (c["num_hidden_layers"] - dense) * routed_layer_extra(c)
            + 2 * c["vocab_size"] * c["hidden_size"])


def kv_bytes_per_token(c: dict, kv_bytes: int = 2) -> int:
    """K and V of one token in one layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * kv_bytes


def window_tokens(c: dict, contexts) -> int:
    """Tokens the window layers attend for decode rows of these contexts."""
    return sum(min(int(n), c["sliding_window"]) for n in contexts)


def attention_bytes(c: dict, full_tokens: int, in_window_tokens: int,
                    kv_bytes: int = 2) -> int:
    """Bytes paged attention must read for decode rows whose contexts sum
    to ``full_tokens`` and, cut to the window, to ``in_window_tokens``:
    each attended token's K and V once a layer of its kind (one fetch
    serves every query head of a KV head; queries and outputs are
    negligible)."""
    return kv_bytes_per_token(c, kv_bytes) * (
        layers_of_kind(c, "full") * full_tokens
        + layers_of_kind(c, "window") * in_window_tokens)


def attention_flops(c: dict, full_tokens: int, in_window_tokens: int) -> int:
    """Score and value matmuls, one query row of every head of the kind
    against each attended token, every layer of the kind."""
    per = 4 * c["head_dim"]
    return per * (
        layers_of_kind(c, "full") * heads_of_kind(c, "full") * full_tokens
        + layers_of_kind(c, "window") * heads_of_kind(c, "window")
        * in_window_tokens)
