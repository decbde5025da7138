"""Operations and bytes the Nemotron-H (``nemotron_h``) block requires, from
the configuration file's own keys: what ``flops.py`` is to the llama block.

Needed work only, counted from the shapes and never from what a kernel chose
to move: a Mamba-2 layer reads and writes a row's state ``[ssm_state_size,
mamba_num_heads x mamba_head_dim]`` once a step of a decode row (once a ROW
of a prefill, whatever its tokens), in ``ssm_state_dtype``; an attention
layer reads each context token's K and V at the KV heads' count, in the TWO
attention layers only; an expert is TWO matrices, and its weights count once
for each pass that touches it.
"""

from __future__ import annotations

_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}

#: tokens of one chunk of the matrix form (``ops/ssm.py::SSD_CHUNK``, the
#: published ``chunk_size``; repeated here so that the count does not
#: follow the program)
CHUNK = 128


def letters(c: dict) -> str:
    """The letters of the layers that are run: ``num_hidden_layers`` of
    ``hybrid_override_pattern`` from the published index ``first_layer``."""
    first = c["first_layer"]
    return c["hybrid_override_pattern"][first:first + c["num_hidden_layers"]]


def ssd_layers(c: dict) -> int:
    return letters(c).count("M")


def routed_layers(c: dict) -> int:
    return letters(c).count("E")


def attention_layers(c: dict) -> int:
    return letters(c).count("*")


def inner(c: dict) -> int:
    """Channels of a Mamba-2 mixer: heads x head dim, NOT expand x hidden."""
    return c["mamba_num_heads"] * c["mamba_head_dim"]


def conv_channels(c: dict) -> int:
    """x, B and C: what the convolution runs over."""
    return inner(c) + 2 * c["n_groups"] * c["ssm_state_size"]


def ssd_params(c: dict) -> int:
    """One ``M`` layer: the in projection (z, xBC, dt), the out projection,
    the convolution and its bias, A_log, D and dt_bias a head, the gated
    norm's gain and the layer's norm."""
    e, d, H = c["hidden_size"], inner(c), c["mamba_num_heads"]
    return (e * (d + conv_channels(c) + H) + d * e
            + conv_channels(c) * (c["conv_kernel"] + 1) + 3 * H + d + e)


def attention_params(c: dict) -> int:
    """One ``*`` layer: q and o at the query heads, k and v at the KV
    heads, the layer's norm."""
    e, dh = c["hidden_size"], c["head_dim"]
    return (2 * e * c["num_attention_heads"] * dh
            + 2 * e * c["num_key_value_heads"] * dh + e)


def expert_params(c: dict) -> int:
    """TWO matrices: up and down."""
    return 2 * c["hidden_size"] * c["moe_intermediate_size"]


def routed_params(c: dict) -> int:
    """One ``E`` layer as held here: the experts held, the shared expert,
    the router over every expert it scores with its bias, the norm."""
    e = c["hidden_size"]
    return (c["n_routed_experts"] * expert_params(c)
            + c["n_shared_experts"] * 2 * e
            * c["moe_shared_expert_intermediate_size"]
            + e * c["routed_experts_scored"] + c["routed_experts_scored"]
            + e)


def total_params(c: dict) -> int:
    """Every parameter of the layers that are run, the embedding, the
    untied head and the final norm."""
    e = c["hidden_size"]
    return (ssd_layers(c) * ssd_params(c)
            + attention_layers(c) * attention_params(c)
            + routed_layers(c) * routed_params(c)
            + 2 * c["vocab_size"] * e + e)


def state_bytes(c: dict) -> int:
    """The state of one sequence in one Mamba-2 layer: 2,097,152 B as
    published."""
    return c["ssm_state_size"] * inner(c) * _ITEMSIZE[c["ssm_state_dtype"]]


def conv_tail_bytes(c: dict) -> int:
    """The convolution's tail (over x, B AND C) of one sequence in one
    Mamba-2 layer."""
    return (c["conv_kernel"] - 1) * conv_channels(c) * _ITEMSIZE["bfloat16"]


def slot_bytes(c: dict) -> int:
    return ssd_layers(c) * (state_bytes(c) + conv_tail_bytes(c))


def token_operand_bytes(c: dict) -> int:
    """The recurrence's operands and result for ONE token in one Mamba-2
    layer, in float32: ``dt x`` in and ``y`` out (``d`` each), the decay
    spread over the channels as the kernel takes it (``d``), B and C of
    every group."""
    return (3 * inner(c) + 2 * c["n_groups"] * c["ssm_state_size"]) * 4


def update_decode_bytes(c: dict, rows: int) -> int:
    """Bytes the update of ``rows`` one-token rows must move in every
    Mamba-2 layer: each row's state read and written once, its operands
    and read-out."""
    return ssd_layers(c) * rows * (2 * state_bytes(c)
                                   + token_operand_bytes(c))


def chunk_prefill_bytes(c: dict, rows: int, tokens: int) -> int:
    """The same for prompt rows: the state once a ROW, the operands and
    the read-out a true token."""
    return ssd_layers(c) * (rows * 2 * state_bytes(c)
                            + tokens * token_operand_bytes(c))


def chunk_prefill_ops(c: dict, tokens: int, chunk: int = CHUNK) -> int:
    """Multiply-adds x 2 of the chunked matrix form for ``tokens`` tokens in
    every Mamba-2 layer, a token of a chunk of ``chunk``: ``C B^T`` once a
    GROUP (``chunk x N``), and a head the masked product ``(L o C B^T) (dt
    X)`` (``chunk x P``), the read-out of the carried state ``C S`` and the
    state's update ``B^T (dt X)`` (``N x P`` each)."""
    N, P = c["ssm_state_size"], c["mamba_head_dim"]
    per_token = c["n_groups"] * 2 * chunk * N \
        + c["mamba_num_heads"] * (2 * chunk * P + 4 * N * P)
    return ssd_layers(c) * tokens * per_token


def attention_bytes(c: dict, context_tokens: int, kv_bytes: int = 2) -> int:
    """Bytes paged attention must read for decode rows whose contexts sum
    to ``context_tokens``, in the attention layers that are run: K and V of
    every context token at the KV heads' count (one fetch serves the 16
    query heads of a group)."""
    return (context_tokens * 2 * c["num_key_value_heads"] * c["head_dim"]
            * kv_bytes * attention_layers(c))


def attention_flops(c: dict, context_tokens: int) -> int:
    """Scores and the probabilities' sum of V, one query row of every
    query head against every context token, in the attention layers."""
    return (4 * c["num_attention_heads"] * c["head_dim"] * context_tokens
            * attention_layers(c))


def grouped_expert_bytes(c: dict, experts_touched: int, pairs: int,
                         w_bytes: int = 2) -> int:
    """Bytes of the grouped expert matmul: the TWO matrices of every expert
    a pass touches, once, and each pair's row in and out."""
    return (experts_touched * expert_params(c) * w_bytes
            + pairs * 2 * c["hidden_size"] * w_bytes)


def grouped_expert_flops(c: dict, pairs: int) -> int:
    """Up and down projections of every token-expert pair."""
    return 2 * pairs * expert_params(c)
