"""Operations and bytes the Olmo-Hybrid (``olmo_hybrid``) block requires,
from the configuration file's own keys: what ``flops.py`` is to the llama
block.

Needed work only, counted from the shapes and never from what a kernel
chose to move: a linear (gated delta-rule) layer reads and writes a row's
matrix state ``[linear_key_head_dim, heads x linear_value_head_dim]`` once
a step of a decode row (once a ROW of a prefill, whatever its tokens), in
``delta_state_dtype``; the convolution's tail ``[linear_conv_kernel_dim -
1, heads x (2 dk + dv)]`` likewise, in bfloat16; a full layer reads the K
and V of every token a decode row attends, 30 KV heads of them.
"""

from __future__ import annotations

_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}

#: tokens of one chunk of the matrix form (``ops/delta_rule.py::MAX_CHUNK``,
#: repeated here so that the count does not follow the program)
CHUNK = 64


def layer_kinds(c: dict) -> list:
    """"linear_attention" or "full_attention", a layer that is run."""
    return list(c["layer_types"][:c["num_hidden_layers"]])


def linear_layers(c: dict) -> int:
    return layer_kinds(c).count("linear_attention")


def full_layers(c: dict) -> int:
    return layer_kinds(c).count("full_attention")


def heads(c: dict) -> int:
    return c["linear_num_value_heads"]


def key_width(c: dict) -> int:
    return c["linear_num_key_heads"] * c["linear_key_head_dim"]


def value_width(c: dict) -> int:
    return heads(c) * c["linear_value_head_dim"]


def conv_channels(c: dict) -> int:
    """The convolution runs over q, k AND v."""
    return 2 * key_width(c) + value_width(c)


def mixer_params(c: dict) -> int:
    """One linear mixer: q, k, v and gate projections, the two gates a
    head, the output projection, the convolution, A_log, dt_bias and the
    output norm's gain."""
    e = c["hidden_size"]
    return (e * (conv_channels(c) + value_width(c) + 2 * heads(c))
            + value_width(c) * e
            + c["linear_conv_kernel_dim"] * conv_channels(c)
            + 2 * heads(c) + c["linear_value_head_dim"])


def attention_params(c: dict) -> int:
    """One full mixer: q, k, v, o and the two whole-width norms' gains."""
    e, d = c["hidden_size"], c["head_dim"]
    h, k = c["num_attention_heads"], c["num_key_value_heads"]
    return 2 * e * h * d + 2 * e * k * d + h * d + k * d


def mlp_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def total_params(c: dict) -> int:
    """Every matrix of the layers that are run, the embedding and the
    untied head (norm gains of the residual stream left out, as
    ``flops.py`` leaves them)."""
    return (linear_layers(c) * mixer_params(c)
            + full_layers(c) * attention_params(c)
            + c["num_hidden_layers"] * mlp_params(c)
            + c["vocab_size"] * c["hidden_size"]
            * (1 if c["tie_word_embeddings"] else 2))


def state_bytes(c: dict) -> int:
    """The matrix state of one sequence in one linear layer: 2,211,840 B
    as published."""
    return c["linear_key_head_dim"] * value_width(c) \
        * _ITEMSIZE[c["delta_state_dtype"]]


def conv_tail_bytes(c: dict) -> int:
    """The convolution's tail of one sequence in one linear layer."""
    return (c["linear_conv_kernel_dim"] - 1) * conv_channels(c) \
        * _ITEMSIZE["bfloat16"]


def slot_layer_bytes(c: dict) -> int:
    """What a sequence holds of one linear layer: 2.28 MB as published."""
    return state_bytes(c) + conv_tail_bytes(c)


def slot_bytes(c: dict) -> int:
    return linear_layers(c) * slot_layer_bytes(c)


def kv_bytes_per_token(c: dict, kv_bytes: int = 2) -> int:
    """K and V of one token in every full layer that is run: 15,360 B at
    one full layer of 30 KV heads."""
    return full_layers(c) * 2 * c["num_key_value_heads"] * c["head_dim"] \
        * kv_bytes


def token_operand_bytes(c: dict) -> int:
    """The recurrence's operands and result for ONE token in one layer, in
    float32: q and k (``heads x dk`` each), v in and o out (``heads x dv``
    each), alpha and beta (one a head each)."""
    return (2 * key_width(c) + 2 * value_width(c) + 2 * heads(c)) * 4


def update_decode_bytes(c: dict, rows: int) -> int:
    """Bytes the update of ``rows`` one-token rows must move in every
    linear layer: each row's state read and written once, its operands and
    read-out."""
    return linear_layers(c) * rows * (2 * state_bytes(c)
                                      + token_operand_bytes(c))


def chunk_prefill_bytes(c: dict, rows: int, tokens: int) -> int:
    """The same for prompt rows: the state once a ROW, the operands and
    the read-out a true token."""
    return linear_layers(c) * (rows * 2 * state_bytes(c)
                               + tokens * token_operand_bytes(c))


def chunk_prefill_ops(c: dict, tokens: int, chunk: int = CHUNK) -> int:
    """Multiply-adds x 2 of the chunked matrix form for ``tokens`` tokens
    in every linear layer, a head and token of a chunk of ``chunk``: the
    lower triangles of ``K K^T`` and ``Q K^T`` (``chunk x dk`` each), the
    unit-lower-triangular solve by substitution and the masked product
    with its result (``chunk x dv`` each), and the three ``[chunk, dk] x
    [dk, dv]`` products into and out of the state (``2 dk dv`` each)."""
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    per_token_head = 2 * chunk * dk + 2 * chunk * dv + 6 * dk * dv
    return linear_layers(c) * tokens * heads(c) * per_token_head


def attention_decode_bytes(c: dict, attended_tokens: int,
                           kv_bytes: int = 2) -> int:
    """K and V bytes the decode rows' attention must read in the full
    layers: every attended token once."""
    return attended_tokens * kv_bytes_per_token(c, kv_bytes)
