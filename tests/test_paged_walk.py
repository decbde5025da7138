"""The decode step of the paged K/V kernel as a walk over each row's own
pages (``ops/paged_attention.py::paged_walk_attention``, PR 45): a grid
over rows, a ring of page tiles copied from the pool in HBM.

Held here, in interpret mode at tiny shapes: the walk against
``attention_reference`` over every kind of row a step program hands it
(one call a case, its rows the contexts), and which form a call of
``paged_decode_attention`` lowers to.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import paged_attention as pa

PAGE, D, PAGES = 8, 16, 96

#: (id, KV heads, query heads a KV head, table width, tile (group, sub) or
#: None for the rule's own, window, how a window's table is laid out).
#: The tiles divide no table width here but where they say so
WALKS = [
    ("K1-P8", 1, 4, 8, (3, 1), None, None),
    ("K8-P16", 8, 2, 16, (6, 2), None, None),
    ("K30-P40", 30, 1, 40, (6, 3), None, None),
    # the rule's own tile: wider than the table, one tile a row
    ("K8-P8-rule", 8, 3, 8, None, None, None),
    # a window group's short table, ``start_pos`` counted from its first
    # page (``model.py::_forward_hidden``): the live pages in its first slots
    ("window-rebased", 8, 2, 16, (3, 1), 40, "rebased"),
    # a full table under a sliding window: nulls below the window's page
    ("window-nulls", 1, 4, 40, (4, 2), 40, "nulls"),
    ("window-divides", 8, 1, 16, (4, 2), 40, "nulls"),
]


def contexts(group: int, slots: int, window, layout) -> np.ndarray:
    """Tokens of the rows of one call: one token, a tile's edge, one page
    past it, the whole table, a one-token row and the row after it (the
    ring turns on across rows; that one is the last row too)."""
    edge = min(group, slots - 1) * PAGE
    ctx = np.array([1, edge, edge + 1, slots * PAGE, 1, edge + PAGE + 3])
    if layout == "rebased":     # what a window group's table can hold
        ctx = np.minimum(ctx, (slots - 1) * PAGE)
    return np.minimum(ctx, slots * PAGE)


@pytest.mark.parametrize("K,G,slots,tile,window,layout",
                         [c[1:] for c in WALKS], ids=[c[0] for c in WALKS])
def test_the_walk_is_the_reference(K, G, slots, tile, window, layout):
    rng = np.random.default_rng(K * slots)
    pool = jnp.asarray(rng.standard_normal(
        (2, PAGES + 1, 2, K, PAGE, D)), jnp.float32)
    group, sub = tile or pa.walk_blocks(G, K, D, PAGE, slots, 4, 4)
    if tile is None:
        assert group >= slots and group % sub == 0
    ctx = contexts(group, slots, window, layout)
    S = len(ctx)
    live = -(-ctx // PAGE)
    first = np.zeros_like(live)
    if layout == "rebased":
        # the table starts at the window's first page: fewer tokens held
        ctx = ctx - np.maximum(ctx - window, 0) // PAGE * PAGE
        live = -(-ctx // PAGE)
    elif layout == "nulls":
        first = np.maximum(ctx - window, 0) // PAGE
    at = np.arange(slots)[None]
    pages = 1 + rng.permutation(S * slots).reshape(S, slots) % PAGES
    table = jnp.asarray(np.where(
        (at >= first[:, None]) & (at < live[:, None]), pages, 0), jnp.int32)
    start = jnp.asarray(ctx - 1, jnp.int32)
    q = jnp.asarray(rng.standard_normal((S, 1, K * G, D)), jnp.float32)
    if tile is None:            # as the step programs call it
        got = pa.paged_attention(q, pool, 1, table, start,
                                 jnp.ones((S,), jnp.int32), window=window,
                                 use_kernel=True, interpret=True)
    else:
        got = pa.paged_walk_attention(
            q, pool, 1, table, start, group=group, sub=sub,
            sm_scale=float(D) ** -0.5, window=window, interpret=True)
    k, v = pa.paged_context(pool, 1, table)
    want = pa.attention_reference(q, k, v, start, jnp.ones((S,), jnp.int32),
                                  window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("Q,int8,alibi,walks", [
    (1, False, False, True), (8, False, False, False),
    (1, True, False, False), (1, False, True, False)],
    ids=["decode", "prompt-chunk", "int8", "alibi"])
def test_which_form_a_call_lowers_to(Q, int8, alibi, walks):
    """A decode step over plain pages with no bias is the walk (the jitted
    ``paged_walk_attention`` in the jaxpr, a grid over rows alone); prompt
    chunks, int8 pages and ALiBi keep the grid over the bucket, under the
    same kernel name."""
    K, G, S, slots = 2, 2, 3, 16
    pool = jnp.zeros((2, PAGES + 1, 2, K, PAGE, D), jnp.float32)
    if int8:
        pool = pa.KVPages(*pa.quantize_kv_blocks(pool))
    slopes = 2.0 ** -np.arange(1, K * G + 1, dtype=np.float32) \
        if alibi else None
    jaxpr = str(jax.make_jaxpr(
        lambda q, kv, table, start: pa.paged_decode_attention(
            q, kv, 1, table, start, alibi_slopes=slopes, interpret=True,
            name="paged_attention_window"))(
        jnp.zeros((S, Q, K * G, D)), pool, jnp.zeros((S, slots), jnp.int32),
        jnp.zeros((S,), jnp.int32)))
    assert ("name=paged_walk_attention" in jaxpr) == walks
    assert jaxpr.count("pallas_call") == 1
    assert ("name=paged_attention_window_decode" if Q == 1
            else "name=paged_attention_window_prefill") in jaxpr
    assert ("grid=(3,)" if walks else "grid=(3, 1, 2)") in jaxpr
