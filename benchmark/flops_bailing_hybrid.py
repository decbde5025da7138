"""Operations and bytes the Ling-3.0 (``bailing_hybrid``) block requires,
from the configuration file's own keys: what ``flops.py`` is to the llama
block.

Needed work only, counted from the shapes and never from what a kernel
chose to move: a KDA layer reads and writes a row's matrix state
``[head_dim, heads x head_dim]`` once a step of a decode row (once a ROW of
a prefill, whatever its tokens), in ``kda_state_dtype``; the latent layer
reads each context token's 576-value plane once (not the 640 the pool pads
it to); an expert's weights count once for each pass that touches it.
"""

from __future__ import annotations

_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}

#: tokens of one chunk of the matrix form under a decay a key channel
#: (``ops/delta_rule.py::MAX_CHANNEL_CHUNK``, repeated here so that the
#: count does not follow the program)
CHUNK = 16


def layer_kinds(c: dict) -> list:
    """"kda" or "latent", a layer that is run: published layer ``i`` is
    latent where ``(i + 1) % layer_group_size == 0``, and the layers run
    start at the published index ``first_layer``."""
    return ["latent" if (c["first_layer"] + i + 1) % c["layer_group_size"]
            == 0 else "kda" for i in range(c["num_hidden_layers"])]


def kda_layers(c: dict) -> int:
    return layer_kinds(c).count("kda")


def latent_layers(c: dict) -> int:
    return layer_kinds(c).count("latent")


def routed_layers(c: dict) -> int:
    return c["num_hidden_layers"] - c["first_k_dense_replace"]


def heads(c: dict) -> int:
    return c["num_attention_heads"]


def head_width(c: dict) -> int:
    """q, k or v of all heads of a KDA layer."""
    return heads(c) * c["head_dim"]


def kda_params(c: dict) -> int:
    """One KDA mixer: q, k, v, the decay gate (full rank) and the output
    projection, beta and the output gate a head, the convolution, A_log,
    dt_bias and the output norm's gain."""
    e, w = c["hidden_size"], head_width(c)
    return (e * 4 * w + w * e + 2 * e * heads(c)
            + c["short_conv_kernel_size"] * 3 * w + heads(c) + w
            + c["head_dim"])


def latent_params(c: dict) -> int:
    """One latent mixer with a direct query (``q_lora_rank`` null)."""
    e, h = c["hidden_size"], heads(c)
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    rkv = c["kv_lora_rank"]
    return (e * h * (dn + dr) + e * (rkv + dr) + rkv * h * (dn + dv)
            + h * dv * e)


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def feed_forward_params(c: dict, routed: bool) -> int:
    """A dense layer's SwiGLU, or a routed layer as held here: the router
    over every expert it scores with its bias, the shared experts and the
    experts held."""
    if not routed:
        return 3 * c["hidden_size"] * c["intermediate_size"]
    return (c["hidden_size"] * c["routed_experts_scored"]
            + c["routed_experts_scored"]
            + 3 * c["hidden_size"] * c["moe_shared_expert_intermediate_size"]
            * c["num_shared_experts"] + expert_params(c) * c["num_experts"])


def total_params(c: dict) -> int:
    """Every matrix of the layers that are run, the embedding and the
    untied head (norm gains of the residual stream left out, as
    ``flops.py`` leaves them)."""
    mixers = kda_layers(c) * kda_params(c) \
        + latent_layers(c) * latent_params(c)
    dense = c["first_k_dense_replace"]
    return (mixers + dense * feed_forward_params(c, False)
            + routed_layers(c) * feed_forward_params(c, True)
            + 2 * c["vocab_size"] * c["hidden_size"])


def state_bytes(c: dict) -> int:
    """The matrix state of one sequence in one KDA layer: 2,097,152 B as
    published."""
    return c["head_dim"] * head_width(c) * _ITEMSIZE[c["kda_state_dtype"]]


def conv_tail_bytes(c: dict) -> int:
    """The convolution's tail (over q, k AND v) of one sequence in one KDA
    layer."""
    return (c["short_conv_kernel_size"] - 1) * 3 * head_width(c) \
        * _ITEMSIZE["bfloat16"]


def slot_bytes(c: dict) -> int:
    return kda_layers(c) * (state_bytes(c) + conv_tail_bytes(c))


def latent_plane(c: dict) -> int:
    """Values one token holds in the latent layer's cache."""
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def token_operand_bytes(c: dict) -> int:
    """The recurrence's operands and result for ONE token in one KDA layer,
    in float32: q, k and the decay (``heads x head_dim`` each), v in and o
    out (the same each), beta (one a head)."""
    return (5 * head_width(c) + heads(c)) * 4


def update_decode_bytes(c: dict, rows: int) -> int:
    """Bytes the update of ``rows`` one-token rows must move in every KDA
    layer: each row's state read and written once, its operands and
    read-out."""
    return kda_layers(c) * rows * (2 * state_bytes(c)
                                   + token_operand_bytes(c))


def chunk_prefill_bytes(c: dict, rows: int, tokens: int) -> int:
    """The same for prompt rows: the state once a ROW, the operands and
    the read-out a true token."""
    return kda_layers(c) * (rows * 2 * state_bytes(c)
                            + tokens * token_operand_bytes(c))


def chunk_prefill_ops(c: dict, tokens: int, chunk: int = CHUNK) -> int:
    """Multiply-adds x 2 of the chunked matrix form for ``tokens`` tokens
    in every KDA layer, a head and token of a chunk of ``chunk``: the lower
    triangles of ``K+ K-^T`` and ``Q+ K-^T`` (``chunk x d`` each), the
    unit-lower-triangular solve by substitution and the masked product with
    its result (``chunk x d`` each), and the three ``[chunk, d] x [d, d]``
    products into and out of the state (``2 d d`` each)."""
    d = c["head_dim"]
    per_token_head = 4 * chunk * d + 6 * d * d
    return kda_layers(c) * tokens * heads(c) * per_token_head


def mla_decode_bytes(c: dict, context_tokens: int, kv_bytes: int = 2) -> int:
    """Bytes the absorbed decode must read for rows whose contexts sum to
    ``context_tokens``, in the latent layers that are run: each context
    token's plane once (one fetch serves every head)."""
    return context_tokens * latent_plane(c) * kv_bytes * latent_layers(c)


def mla_decode_flops(c: dict, context_tokens: int) -> int:
    """The absorbed score (every head against the whole plane) and the
    probabilities' sum of the plane's ``kv_lora_rank`` values, one query
    row against every context token, in the latent layers that are run."""
    return (2 * heads(c) * (latent_plane(c) + c["kv_lora_rank"])
            * context_tokens * latent_layers(c))


def grouped_expert_bytes(c: dict, experts_touched: int, pairs: int,
                         w_bytes: int = 2) -> int:
    """Bytes of the grouped expert matmul: the weights of every expert a
    pass touches, once, and each pair's row in and out."""
    return (experts_touched * expert_params(c) * w_bytes
            + pairs * 2 * c["hidden_size"] * w_bytes)


def grouped_expert_flops(c: dict, pairs: int) -> int:
    """Gate, up and down projections of every token-expert pair."""
    return 2 * pairs * expert_params(c)
