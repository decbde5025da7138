"""Operations and bytes the openPangu-Ultra-MoE (``pangu_ultra_moe``) block
requires, from the configuration file's own keys: what ``flops.py`` is to
the llama block.  Needed work only: the latent plane counts its 576
values (not the 640 the pool pads it to), an expert's weights count once
for each pass that touches it.
"""

from __future__ import annotations


def attention_params(c: dict) -> int:
    e, h = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    return (e * rq + rq * h * (dn + dr) + e * (rkv + dr)
            + rkv * h * (dn + dv) + h * dv * e)


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def dense_layer_params(c: dict) -> int:
    return attention_params(c) + 3 * c["hidden_size"] * c["intermediate_size"]


def routed_layer_params(c: dict) -> int:
    """A routed layer as held here: attention, the router over every
    expert it scores, the shared experts and the experts held."""
    return (attention_params(c)
            + c["hidden_size"] * c["routed_experts_scored"]
            + expert_params(c) * (c["n_shared_experts"]
                                  + c["n_routed_experts"]))


def routed_layers(c: dict) -> int:
    return c["num_hidden_layers"] - c["first_k_dense_replace"]


def total_params(c: dict) -> int:
    return (c["first_k_dense_replace"] * dense_layer_params(c)
            + routed_layers(c) * routed_layer_params(c)
            + 2 * c["vocab_size"] * c["hidden_size"])


def latent_plane(c: dict) -> int:
    """Values one token holds in one layer's cache."""
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def mla_decode_bytes(c: dict, context_tokens: int, kv_bytes: int = 2) -> int:
    """Bytes the absorbed decode must read for rows whose contexts sum to
    ``context_tokens``, all layers: each context token's plane once (one
    fetch serves every head; queries and outputs are negligible)."""
    return (context_tokens * latent_plane(c) * kv_bytes
            * c["num_hidden_layers"])


def mla_decode_flops(c: dict, context_tokens: int) -> int:
    """The absorbed score (every head against the whole plane) and the
    probabilities' sum of the plane's ``kv_lora_rank`` values, one query
    row against every context token, all layers."""
    return (2 * c["num_attention_heads"]
            * (latent_plane(c) + c["kv_lora_rank"]) * context_tokens
            * c["num_hidden_layers"])


def grouped_expert_bytes(c: dict, experts_touched: int, pairs: int,
                         w_bytes: int = 2) -> int:
    """Bytes of the grouped expert matmul: the weights of every expert a
    pass touches, once, and each pair's row in and out."""
    return (experts_touched * expert_params(c) * w_bytes
            + pairs * 2 * c["hidden_size"] * w_bytes)


def grouped_expert_flops(c: dict, pairs: int) -> int:
    """Gate, up and down projections of every token-expert pair."""
    return 2 * pairs * expert_params(c)
