"""Operations and bytes a configuration's shapes require, from the
configuration file's own keys (the published names).  Recomputed operations
are never counted: these are what the algorithm needs, not what a program
happens to execute.
"""

from __future__ import annotations


def layer_params(c: dict) -> int:
    """Matmul parameters of one block: q, k, v, o and the gated MLP."""
    e, f = c["hidden_size"], c["intermediate_size"]
    h, k, d = (c["num_attention_heads"], c["num_key_value_heads"],
               c["head_dim"])
    return e * h * d + 2 * e * k * d + h * d * e + 3 * e * f


def head_params(c: dict) -> int:
    return c["vocab_size"] * c["hidden_size"]


def matmul_params(c: dict) -> int:
    """Parameters every token is multiplied by (the embedding lookup is
    not a matmul)."""
    return c["num_hidden_layers"] * layer_params(c) + head_params(c)


def total_params(c: dict) -> int:
    tied = 1 if c.get("tie_word_embeddings") else 2
    return (c["num_hidden_layers"] * layer_params(c)
            + tied * head_params(c))


def causal_attention_flops(c: dict, seq: int, matmuls: int) -> int:
    """FLOPs of ``matmuls`` score-sized matmuls over one causal sequence
    in one layer: each is 2*seq*seq*head_dim*heads, halved by the mask
    (a window at least as long as the sequence changes nothing)."""
    window = c.get("sliding_window") or seq
    assert window >= seq, "banded attention: count the band, not half"
    return matmuls * c["num_attention_heads"] * c["head_dim"] * seq * seq


def train_flops_per_token(c: dict, seq: int) -> float:
    """Forward + backward: 6 per matmul parameter, plus causal attention
    (2 matmuls forward, 4 backward)."""
    attention = c["num_hidden_layers"] * causal_attention_flops(c, seq, 6)
    return 6.0 * matmul_params(c) + attention / seq


def flash_train_flops(c: dict, seq: int, rows: int) -> int:
    """What flash attention needs for ``rows`` sequences, forward and
    backward, all layers: 2 matmuls forward, and backward the scores once
    more plus dV, dP, dQ, dK = 7.  (The program's two backward kernels
    compute the scores twice, and recomputation runs the forward twice;
    neither is needed work.)"""
    return rows * c["num_hidden_layers"] * causal_attention_flops(c, seq, 7)


def kv_bytes_per_token_layer(c: dict, kv_bytes: int = 2) -> int:
    """K and V of one token in one layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * kv_bytes


def paged_bytes(c: dict, context_tokens: int, kv_bytes: int = 2) -> int:
    """Bytes paged attention must read for rows whose contexts sum to
    ``context_tokens``, all layers (queries and outputs are negligible)."""
    return (context_tokens * kv_bytes_per_token_layer(c, kv_bytes)
            * c["num_hidden_layers"])


def paged_decode_flops(c: dict, context_tokens: int) -> int:
    """QK^T and PV of one query row against every context token, all
    layers."""
    return (4 * c["num_attention_heads"] * c["head_dim"] * context_tokens
            * c["num_hidden_layers"])


def paged_prefill_flops(c: dict, prompt_sq: int) -> int:
    """QK^T and PV of whole prompts under the causal mask, all layers;
    ``prompt_sq`` is the sum of the prompts' squared lengths."""
    return (2 * c["num_attention_heads"] * c["head_dim"] * prompt_sq
            * c["num_hidden_layers"])
