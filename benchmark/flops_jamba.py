"""Operations and bytes the Jamba (``jamba``) block requires, from the
configuration file's own keys: what ``flops.py`` is to the llama block.

Needed work only, counted from the shapes and never from what a kernel
chose to move: a Mamba layer's recurrence reads and writes a row's state
``[mamba_d_state, d_inner]`` once a step of a decode row (once a ROW of a
prefill, whatever its tokens), in ``ssm_state_dtype``; the convolution's
tail ``[mamba_d_conv - 1, d_inner]`` likewise, in bfloat16.
"""

from __future__ import annotations

_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}


def d_inner(c: dict) -> int:
    return c["mamba_expand"] * c["hidden_size"]


def layer_kinds(c: dict) -> list:
    """"attention" or "mamba", a layer (the family's rule)."""
    return ["attention" if i % c["attn_layer_period"]
            == c["attn_layer_offset"] else "mamba"
            for i in range(c["num_hidden_layers"])]


def mamba_layers(c: dict) -> int:
    return layer_kinds(c).count("mamba")


def mixer_params(c: dict) -> int:
    """One Mamba mixer: in_proj, x_proj, dt_proj (with bias), out_proj,
    the convolution (with bias), A_log, D and the three norms' gains."""
    e, d = c["hidden_size"], d_inner(c)
    n, r, k = c["mamba_d_state"], c["mamba_dt_rank"], c["mamba_d_conv"]
    return (e * 2 * d + d * (r + 2 * n) + r * d + d + d * e
            + d * k + d + d * n + d + r + 2 * n)


def attention_params(c: dict) -> int:
    e, d = c["hidden_size"], c["head_dim"]
    h, k = c["num_attention_heads"], c["num_key_value_heads"]
    return 2 * e * h * d + 2 * e * k * d


def mlp_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def total_params(c: dict) -> int:
    """Every matrix, the embedding once where the head is tied (norm gains
    of the residual stream left out, as ``flops.py`` leaves them)."""
    mamba = mamba_layers(c)
    return (mamba * mixer_params(c)
            + (c["num_hidden_layers"] - mamba) * attention_params(c)
            + c["num_hidden_layers"] * mlp_params(c)
            + c["vocab_size"] * c["hidden_size"]
            * (1 if c["tie_word_embeddings"] else 2))


def state_bytes(c: dict) -> int:
    """The recurrent state of one sequence in one Mamba layer."""
    return c["mamba_d_state"] * d_inner(c) * _ITEMSIZE[c["ssm_state_dtype"]]


def conv_tail_bytes(c: dict) -> int:
    """The convolution's tail of one sequence in one Mamba layer."""
    return (c["mamba_d_conv"] - 1) * d_inner(c) * _ITEMSIZE["bfloat16"]


def slot_layer_bytes(c: dict) -> int:
    """What a sequence holds of one Mamba layer: 358 KB as published."""
    return state_bytes(c) + conv_tail_bytes(c)


def slot_bytes(c: dict) -> int:
    return mamba_layers(c) * slot_layer_bytes(c)


def kv_bytes_per_token(c: dict, kv_bytes: int = 2) -> int:
    """K and V of one token in every attention layer."""
    return (c["num_hidden_layers"] - mamba_layers(c)) * 2 \
        * c["num_key_value_heads"] * c["head_dim"] * kv_bytes


def token_operand_bytes(c: dict) -> int:
    """The recurrence's operands and result for ONE token in one layer, as
    the program hands them over in float32: dt and x in, y out
    (``d_inner`` each), B and C (``mamba_d_state`` each)."""
    return (3 * d_inner(c) + 2 * c["mamba_d_state"]) * 4


def layer_constant_bytes(c: dict) -> int:
    """A and D of one layer, read once a call."""
    return (c["mamba_d_state"] + 1) * d_inner(c) * 4


def recurrence_decode_bytes(c: dict, rows: int, steps: int = 1) -> int:
    """Bytes the recurrence of ``rows`` one-token rows must move, summed
    over ``steps`` steps' worth of them, in every Mamba layer: each row's
    state read and written once, its operands and ``y``, and A and D once
    a layer and step."""
    return mamba_layers(c) * (
        rows * (2 * state_bytes(c) + token_operand_bytes(c))
        + steps * layer_constant_bytes(c))


def recurrence_prefill_bytes(c: dict, rows: int, tokens: int,
                             steps: int = 1) -> int:
    """The same for prompt rows: the state once a ROW, the operands and
    ``y`` a true token."""
    return mamba_layers(c) * (
        rows * 2 * state_bytes(c) + tokens * token_operand_bytes(c)
        + steps * layer_constant_bytes(c))


def conv_decode_bytes(c: dict, rows: int) -> int:
    """The convolution's tail read and written once a row and layer."""
    return mamba_layers(c) * rows * 2 * conv_tail_bytes(c)


def recurrence_ops(c: dict, tokens: int) -> int:
    """Elementwise operations of the recurrence: a state element takes an
    exponential, three products and two sums a token (decay, input,
    update, the product with C and its sum)."""
    return mamba_layers(c) * tokens * 6 * c["mamba_d_state"] * d_inner(c)
