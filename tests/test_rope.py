"""The rotary embedding (``models/transformer.py::apply_rope``: the pair
swap as a product with a 0/1 permutation, fused with the rotation) against
the strided-pair formula, which is kept HERE as the plain reference: the
same values bit for bit, forward and backward, at every shape a caller
hands it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.transformer import (TransformerConfig, apply_rope,
                                              rope_table)


def strided_pairs(x, sin, cos):
    """The interleaved-pair rotation written with stride-2 slices and a
    stack (``apply_rope`` as it was until PR 49)."""
    rot = 2 * sin.shape[-1]
    head = x[..., :rot].astype(jnp.float32)
    x1, x2 = head[..., 0::2], head[..., 1::2]
    sin, cos = sin[:, :, None, :], cos[:, :, None, :]
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    out = jnp.stack([r1, r2], axis=-1).reshape(head.shape).astype(x.dtype)
    if rot == x.shape[-1]:
        return out
    return jnp.concatenate([out, x[..., rot:]], axis=-1)


def bits(a):
    return np.asarray(a.astype(jnp.float32))


#: name -> (x's shape [rows, tokens, heads, head_dim], rope_pct)
SHAPES = {
    # training and ``CausalLM.logits``: [B, S, H, D], q and k of a GQA layer
    "train-q": ((2, 48, 8, 128), 1.0),
    "train-k": ((2, 48, 2, 128), 1.0),
    # partial rotary (GPT-NeoX, Phi, GPT-J): the tail passes through
    "partial-quarter": ((2, 16, 4, 128), 0.25),
    "partial-half": ((1, 16, 4, 64), 0.5),
    # a serving step: 64 decode rows of one token, a 4 x 128 prefill piece
    "serve-decode": ((64, 1, 32, 128), 1.0),
    "serve-prefill": ((4, 128, 8, 128), 1.0),
    # the latent block's 64-wide rotary slices: 128 query heads, one key
    "latent-q": ((16, 1, 128, 64), 1.0),
    "latent-k": ((16, 1, 1, 64), 1.0),
}


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("name", sorted(SHAPES))
def test_apply_rope_is_the_strided_pair_rotation(name, dtype, jit):
    shape, pct = SHAPES[name]
    rows, tokens, heads, dim = shape
    cfg = TransformerConfig(vocab_size=8, hidden_size=heads * dim,
                            intermediate_size=8, num_layers=1,
                            num_heads=heads, rope_pct=pct)
    kx, kg, kp = jax.random.split(jax.random.key(sum(shape)), 3)
    x = jax.random.normal(kx, shape, jnp.float32).astype(dtype)
    g = jax.random.normal(kg, shape, jnp.float32).astype(dtype)
    # positions as a serving step has them: every row somewhere else
    pos = jax.random.randint(kp, (rows, 1), 0, 4000) + jnp.arange(tokens)
    sin, cos = rope_table(cfg, pos)
    assert 2 * sin.shape[-1] == int(dim * pct)

    def both(rope):
        def run(x, g):
            out, vjp = jax.vjp(lambda x: rope(x, sin, cos), x)
            return out, vjp(g)[0]
        return (jax.jit(run) if jit else run)(x, g)

    out, dx = both(apply_rope)
    want, want_dx = both(strided_pairs)
    assert out.dtype == dtype and dx.dtype == dtype
    # the values term for term: one input times 1.0 through the product
    np.testing.assert_array_equal(bits(out), bits(want))
    if not jit:
        # the bfloat16 cotangent itself goes through the permutation (a
        # transposed one-pass product of float32 ``g * sin2`` would round
        # it, in every element)
        np.testing.assert_array_equal(bits(dx), bits(want_dx))
    else:
        # compiled, the CPU contracts ``a*b + c*d`` into one multiply-add
        # around whichever product autodiff wrote first: a last float32
        # bit, which a bfloat16 rounding shows in one element of 100,000
        if dtype == jnp.bfloat16:
            assert np.mean(bits(dx) != bits(want_dx)) < 1e-3
        np.testing.assert_allclose(bits(dx), bits(want_dx), atol=2e-6,
                                   rtol=float(jnp.finfo(dtype).eps))
    # a rotation and the opposite one: x again, to the dtype's rounding
    back = apply_rope(out, -sin, cos)
    assert float(jnp.max(jnp.abs(back.astype(jnp.float32)
                                 - x.astype(jnp.float32)))) \
        <= 8 * float(jnp.finfo(dtype).eps)
    # and the backward IS that rotation of the cotangent (operation by
    # operation: compiled, the CPU contracts multiply-adds by the fusion)
    if not jit:
        np.testing.assert_array_equal(bits(dx),
                                      bits(apply_rope(g, -sin, cos)))


def test_the_hessian_vector_product_still_differentiates_the_rope():
    """``runtime/eigenvalue.py`` takes ``jvp(grad(loss))``: forward mode
    over the rope's backward, which a ``custom_vjp`` called from its own
    rules would refuse."""
    x = jax.random.normal(jax.random.key(0), (1, 8, 2, 16), jnp.float32)
    cfg = TransformerConfig(vocab_size=8, hidden_size=32, intermediate_size=8,
                            num_layers=1, num_heads=2)
    sin, cos = rope_table(cfg, jnp.arange(8)[None])

    def loss(rope, x):
        return jnp.sum(rope(x, sin, cos) ** 3)

    got = jax.jvp(jax.grad(lambda x: loss(apply_rope, x)), (x,), (x,))[1]
    want = jax.jvp(jax.grad(lambda x: loss(strided_pairs, x)), (x,), (x,))[1]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
