"""The table the paged K/V kernel's index maps read
(``ops/paged_attention.py::fetch_table``): a page slot that holds nothing
for its row names the block its pipeline buffer already holds, so the
pipeline fetches nothing for it.

Held here: the rule itself against a plain left-to-right loop and against
the timing tool's ``repeat`` table; that nothing but the kernel's index
maps reads it (the cache write, the dense gather and the host keep the
engine's null-padded table); and that the kernel's outputs under it are
the outputs under the engine's table to the last bit, in interpret mode,
for every kind of call the step programs make.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import paged_attention as pa

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))

P_SLOTS = 16


def cascade(table: np.ndarray, group: int) -> np.ndarray:
    """The rule as a loop: left to right, a dead slot takes what the slot
    ``group`` places back holds by then."""
    out = table.copy()
    for p in range(group, table.shape[1]):
        out[:, p] = np.where(table[:, p] == 0, out[:, p - group], out[:, p])
    return out


def null_padded(live, first=None, slots=P_SLOTS, seed=0, pool=None):
    """An engine's table: row ``i`` holds pages (distinct, or of a pool of
    ``pool`` pages) in slots ``first[i] .. live[i] - 1`` and the null page
    everywhere else."""
    live = np.asarray(live)
    first = np.zeros_like(live) if first is None else np.asarray(first)
    rng = np.random.default_rng(seed)
    pages = rng.permutation(len(live) * slots).reshape(len(live), slots)
    pages = 1 + pages % (pool or pages.size)
    at = np.arange(slots)[None]
    return np.where((at >= first[:, None]) & (at < live[:, None]), pages,
                    0).astype(np.int32)


@pytest.mark.parametrize("live", ["0", "1", "group-1", "group", "group+1",
                                  "P", "window"])
@pytest.mark.parametrize("group", [1, 2, 4, 8])
def test_fetch_table_is_the_repeat_rule(group, live):
    """Rows of 0, 1, ``group - 1``, ``group``, ``group + 1`` and ``P`` live
    pages, and a window table (10 live of 16): the program's table equals
    the loop's; live slots are never altered; a dead slot with no live one
    before it in its column keeps page 0."""
    n = {"0": 0, "1": 1, "group-1": group - 1, "group": group,
         "group+1": group + 1, "P": P_SLOTS, "window": 10}[live]
    # the row beside rows of other lengths, one of them under a window
    # that gave its leading pages back (``evict_pages_below``)
    table = null_padded([n, 3, P_SLOTS, 13], first=[0, 0, 0, 5], seed=group)
    got = np.asarray(pa.fetch_table(jnp.asarray(table), group))
    assert got.shape == table.shape and got.dtype == table.dtype
    np.testing.assert_array_equal(got, cascade(table, group))
    np.testing.assert_array_equal(got[table != 0], table[table != 0])
    columns = table.reshape(len(table), -1, group)
    seen = np.logical_or.accumulate(columns != 0, axis=1)
    assert (got.reshape(columns.shape)[~seen] == 0).all()
    # a borrowed page is the row's own, ``group`` slots back or a
    # multiple of it
    for s, p in zip(*np.nonzero(got != table)):
        assert got[s, p] in table[s, p % group:p:group]
    held, alive = pa.slots_held(table, group)
    assert alive == int((table != 0).sum())
    live_groups = (columns != 0).any(axis=2, keepdims=True)
    assert held == int(((got != table).reshape(columns.shape)
                        & live_groups).sum())


@pytest.mark.parametrize("group", [1, 2, 4, 8])
def test_the_timing_tool_builds_its_repeat_table_from_the_program(group):
    """``tools/time_paged_blocks.py::tables``: ``repeat`` is the program's
    rule of its ``null`` table (and the loop's), so the tool times what
    the step programs do."""
    import time_paged_blocks as tool
    ctxs = np.array([1, 63, 64, 65, 500, 640, 1024, 300])
    kinds = tool.tables(ctxs, 64, P_SLOTS, group, pages=512)
    live = -(-ctxs // 64)
    assert ((kinds["null"] != 0).sum(axis=1) == live).all()
    np.testing.assert_array_equal(kinds["repeat"],
                                  cascade(kinds["null"], group))
    np.testing.assert_array_equal(
        kinds["repeat"],
        np.asarray(pa.fetch_table(jnp.asarray(kinds["null"], jnp.int32),
                                  group)))
    assert (kinds["real"] != 0).all()
    # a window table: what a row holds counts from the first page its
    # window reaches, at most 10 pages at a window of 512
    held = tool.held_from(np.array([100, 513, 576, 2100]), 64, 512, 1)
    assert held.tolist() == [100, 513, 512, 564]
    assert (-(-tool.held_from(np.arange(1, 4000), 64, 512, 1) // 64)
            ).max() == 9


def test_a_table_of_one_group_is_the_engines_table():
    """Nothing comes before a row's only group: the function returns its
    argument, so a program whose page bucket is one group (the short
    cell's 8 slots) holds no operation of the rule."""
    table = jnp.asarray(null_padded([3, 8, 0, 5], slots=8))
    assert pa.fetch_table(table, 8) is table
    jaxpr = jax.make_jaxpr(lambda t: pa.fetch_table(t, 8))(table)
    assert jaxpr.eqns == []


def _pool(key, pages, K, page, D, int8=False, layers=2):
    kv = jax.random.normal(key, (layers, pages + 1, 2, K, page, D),
                           jnp.float32)
    if not int8:
        return kv
    return pa.KVPages(*pa.quantize_kv_blocks(kv))


def test_only_the_kernels_index_maps_read_the_fetch_table(monkeypatch):
    """The cache write (scatter and kernel), the dense gather and
    ``paged_context`` never ask for the fetch table, nor does a decode
    step's walk; the grid form asks once, with the group its blocks have;
    and what the write leaves in the pool is what the engine's table says
    (a dead slot's borrowed page is not written)."""
    asked = []
    rule = pa.fetch_table
    monkeypatch.setattr(pa, "fetch_table",
                        lambda table, group: asked.append(group)
                        or rule(table, group))
    K, G, page, D, S = 2, 2, 16, 32, 3
    key = jax.random.PRNGKey(0)
    pool = _pool(key, 64, K, page, D)
    table = jnp.asarray(null_padded([9, 2, 16], seed=3, pool=64))
    start = jnp.asarray([9 * page - 1, 20, 16 * page - 5], jnp.int32)
    lens = jnp.ones((S,), jnp.int32)
    k_new = jax.random.normal(jax.random.fold_in(key, 1), (S, 1, K, D))
    before = np.asarray(pool)
    for interpret in (False, True):     # the scatter, then the kernel
        out = np.asarray(pa.write_kv(pool, 1, k_new, -k_new, table, start,
                                     lens, interpret=interpret))
        changed = np.nonzero((out != before).any(axis=(0, 2, 3, 4, 5)))[0]
        own = [int(table[s, int(start[s]) // page]) for s in range(S)]
        assert sorted(changed.tolist()) == sorted(own)
    q = jax.random.normal(jax.random.fold_in(key, 2), (S, 1, K * G, D))
    pa.paged_context(pool, 1, table)
    dense = pa.paged_attention(q, pool, 1, table, start, lens,
                               use_kernel=False)
    walk = pa.paged_attention(q, pool, 1, table, start, lens,
                              use_kernel=True, interpret=True)
    assert asked == []
    kernel = pa.paged_grid_attention(q, pool, 1, table, start,
                                     interpret=True)
    assert asked == [8]
    for got in (walk, kernel):
        np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                                   rtol=2e-5, atol=2e-5)


#: (id, Q, query heads a KV head, what else the call has): every kind of
#: call a step program makes of the kernel
PARITY = [(f"Q{Q}-G{G}", Q, G, {}) for Q in (1, 128) for G in (1, 4, 6, 9)]
PARITY += [
    # a window layer whose table gave its leading pages back
    # (``evict_pages_below``: null slots UNDER the window too)
    ("window-evicted-Q1", 1, 4, {"window": 40, "evicted": True}),
    ("window-evicted-Q8", 8, 4, {"window": 40, "evicted": True}),
    # a window group's short table (``evict_window_pages`` keeps the live
    # pages in its first slots; ``start_pos`` counts from its first page)
    ("window-table-Q1", 1, 9, {"window": 40, "rebased": True}),
    ("window-table-Q8", 8, 9, {"window": 40, "rebased": True}),
    ("int8-Q1", 1, 4, {"int8": True}),
    ("int8-Q8", 8, 4, {"int8": True}),
    ("alibi-Q1", 1, 4, {"alibi": True}),
    ("alibi-Q8", 8, 4, {"alibi": True}),
]


@pytest.mark.parametrize("Q,G,extra", [c[1:] for c in PARITY],
                         ids=[c[0] for c in PARITY])
def test_kernel_outputs_under_the_fetch_table_are_bit_identical(
        monkeypatch, Q, G, extra):
    """The kernel in interpret mode under the fetch table against the
    same call under the engine's null-padded table (the rule switched
    off): ``array_equal``.  What a row sees is decided by position, so a
    borrowed page's columns are masked as the null page's are."""
    K, page, D, slots = 2, 16, 32, 24
    window = extra.get("window")
    key = jax.random.PRNGKey(Q * 16 + G)
    pool = _pool(key, 96, K, page, D, int8=extra.get("int8", False))
    # contexts (tokens a row's table holds once its new ones are written):
    # one page, a group less a page, a group, a group and a page, two
    # groups and a token, the whole table, and a padding row
    ctx = np.array([Q, 7 * page, 8 * page, 9 * page - 3, 16 * page + 1,
                    slots * page, Q])
    ctx = np.maximum(ctx, Q)
    live = -(-ctx // page)
    first = np.zeros_like(live)
    if extra.get("evicted"):        # pages wholly under the window: null
        first = np.maximum(ctx - Q - window + 1, 0) // page
    elif extra.get("rebased"):      # a short table: at most 5 live pages
        ctx = np.minimum(ctx, window + page + Q - 1)
        live = -(-ctx // page)
    table = null_padded(live, first=first, slots=slots, seed=G, pool=96)
    start = jnp.asarray(ctx - Q, jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, 1),
                          (len(ctx), Q, K * G, D), jnp.float32)
    slopes = None
    if extra.get("alibi"):
        slopes = 2.0 ** -np.arange(1, K * G + 1, dtype=np.float32)

    def run():
        return np.asarray(pa.paged_grid_attention(
            q, pool, 1, jnp.asarray(table), start, window=window,
            alibi_slopes=slopes, interpret=True))

    group = pa.kernel_blocks(Q * G, K, D, page, slots, 4,
                             1 if extra.get("int8") else 4,
                             extra.get("int8", False), slopes is not None)[1]
    fetch = np.asarray(pa.fetch_table(jnp.asarray(table), group))
    assert group > 1 and (fetch != table).any()    # the rule has work
    under_fetch = run()
    monkeypatch.setattr(pa, "fetch_table", lambda table, group: table)
    under_null = run()
    assert np.isfinite(under_fetch).all()
    np.testing.assert_array_equal(under_fetch, under_null)


def test_the_step_span_counts_the_decode_rows_page_slots():
    """``fastgen.step`` carries ``kv_slots_live`` / ``kv_slots_held`` /
    ``kv_slots_bucket`` of its decode rows over both page groups of a
    model that has two: the slots that hold a page, and none held while a
    row's table is one page group (nothing comes before it), some once the
    full group's table is two groups wide and the second is part dead;
    and the slots of the rows' tables, of which the decode kernel's walk
    visits the live ones alone."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.inference.v2 import FastGenScheduler, SamplingParams
    from deepspeed_tpu.telemetry import get_tracer
    from test_laguna import engine_of, family, sequences_of
    cfg, params = family()
    engine = engine_of(cfg, params)
    sched = FastGenScheduler(engine)
    telemetry.set_enabled(True)
    try:
        # this run's records alone: the ring is the process's, and a model
        # of one page group that served before leaves steps without the
        # window group's counts
        mark = len(get_tracer().records())
        for uid, p in enumerate(sequences_of((21, 30), seed=2)):
            sched.submit(uid, p.tolist(), SamplingParams(max_new_tokens=40))
        sched.run_to_completion()
        steps = [r[5] for r in get_tracer().records()[mark:]
                 if r[0] == "fastgen.step" and r[5]]
    finally:
        telemetry.set_enabled(False)
    assert engine.model.decode_page_group(16, "full") == 8
    decode = [s for s in steps if s["rows"] > s["prefill_rows"]]
    assert decode and all(s["kv_slots_live"] > 0 for s in decode)
    for s in decode:
        # no row's full table passes one group of 8 slots before the two
        # rows hold 9 pages between them
        assert s["kv_slots_held"] == 0 or s["kv_pages_reserved"] > 8
        assert s["kv_slots_live"] <= (s["kv_pages_reserved"]
                                      + s["kv_pages_reserved_window"] + 2)
        # rows x (the full group's table width + the window group's)
        rows = s["rows"] - s["prefill_rows"]
        assert s["kv_slots_bucket"] % rows == 0
        assert s["kv_slots_live"] < s["kv_slots_bucket"]
    # a row's 9th page: one live slot of its second group, seven held
    assert any(s["kv_slots_held"] == 7 for s in decode)
    assert all(s["kv_slots_held"] == s["kv_slots_live"]
               == s["kv_slots_bucket"] == 0
               for s in steps if s["rows"] == s["prefill_rows"])
