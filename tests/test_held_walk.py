"""The held experts' kernel (``moe/held.py::grouped_expert_ffn``) walks the
row tiles IN USE (PR 48): in interpret mode against the ``jnp`` form on the
rows of the tiles in use, and the whole layer against the dense form with
the rows of every unused tile poisoned, at every count of tiles in use from
none to all of the bound."""
import functools
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import held

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
from time_expert_tiles import routing_of, tiles_in_use  # noqa: E402

#: rows a tile by tokens: ``row_tile``'s at 256 and 384, and a tile small
#: enough for an expert of three tiles at 16 tokens (a plan brings its tile)
TILES = {16: 4, 256: 32, 384: 64}
USED = ("none", "one", "each", "three", "bound")
F, E, LAYERS, LAYER = 128, 32, 3, 2


def routed(used: str, tokens: int, first: int = 8):
    """(experts [T, k], held experts, tile, tiles in use, tiles of the
    bound) of a routing under which ``used`` of the bound's tiles are in
    use: none, one, one an expert, an expert with three, every one."""
    tm = TILES[tokens]
    if used == "bound":
        # every pair here and every expert one pair past whole tiles
        n_held, k = tm, 1
        full = (tokens - n_held) // tm
        counts = np.array([tm + 1] * full + [1] * (n_held - full))
    else:
        n_held, k = 4, 2
        counts = np.full(n_held, max(tokens * k // 16, 1))
        if used == "none":
            counts[:] = 0
        elif used == "one":
            counts[:] = 0
            counts[1] = min(tm, tokens)
        elif used == "three":
            counts[2] = 2 * tm + 1
    # every other pair at an expert held elsewhere (``first + n_held``)
    experts = first + routing_of(counts, tokens, k, n_held)
    in_use = tiles_in_use(counts, tm)
    bound = held._rows_bound(tokens * k, n_held, tm) // tm
    want = {"none": 0, "one": 1, "each": n_held, "three": n_held + 2,
            "bound": bound}[used]
    assert in_use == want, (used, tokens, in_use, want)
    return jnp.asarray(experts), n_held, tm, in_use, bound


def stack_of(rng, n_held):
    """The layers' stack with NaN in every layer but ``LAYER``."""
    def one():
        w = np.full((LAYERS, n_held, F, E), np.nan, np.float32)
        w[LAYER] = rng.normal(size=(n_held, F, E)) / 8
        return jnp.asarray(w)
    return {n: one() for n in ("wg", "wu", "wd")}


def act_of(used: str, tokens: int, flip: int) -> str:
    """Every activation over the cases (the two gates' and the ungated
    expert's own), one a case (a case is seconds of the interpreter), the
    two tests a step apart."""
    acts = sorted(held.ACTS)
    return acts[(USED.index(used) + sorted(TILES).index(tokens) + flip)
                % len(acts)]


@pytest.mark.parametrize("tokens", sorted(TILES))
@pytest.mark.parametrize("used", USED)
def test_the_kernel_matches_the_jnp_form_on_the_tiles_in_use(used, tokens):
    experts, n_held, tm, in_use, bound = routed(used, tokens)
    act = act_of(used, tokens, 0)
    rng = np.random.default_rng(tokens + len(used))
    x = jnp.asarray(rng.normal(size=(tokens, E)), jnp.float32)
    stack = stack_of(rng, n_held)
    row_token, _, tile_expert, n_used, _ = held._plan(
        experts, jnp.ones(tokens, bool), 8, n_held, tm)
    assert int(n_used[0]) == in_use and tile_expert.shape[0] == bound
    operands = (x[row_token], tile_expert, n_used, jnp.int32(LAYER),
                None if act in held.UNGATED else stack["wg"], stack["wu"],
                stack["wd"])
    got = held.grouped_expert_ffn(*operands, tm=tm, act=act, interpret=True)
    want = held._grouped_reference(*operands, tm=tm, act=act)
    live = in_use * tm
    assert np.isfinite(np.asarray(got[:live])).all()
    np.testing.assert_allclose(np.asarray(got[:live]),
                               np.asarray(want[:live]), atol=2e-5)


@pytest.mark.parametrize("tokens", sorted(TILES))
@pytest.mark.parametrize("used", USED)
def test_the_rows_of_unused_tiles_are_never_used(monkeypatch, used, tokens):
    """The layer against the dense form, the kernel's output rows past the
    tiles in use overwritten with NaN: the kernel leaves them unwritten,
    and ``held_experts_ffn`` reads them only to drop them."""
    experts, n_held, tm, in_use, _ = routed(used, tokens)
    act = act_of(used, tokens, 1)
    rng = np.random.default_rng(tokens + len(used))
    x = jnp.asarray(rng.normal(size=(tokens, E)), jnp.float32)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, experts.shape), jnp.float32)
    stack = stack_of(rng, n_held)
    plan = held._plan(experts, jnp.ones(tokens, bool), 8, n_held, tm)
    kernel = held.grouped_expert_ffn

    @functools.wraps(kernel)
    def poisoned(x_rows, tile_expert, n_used, *rest, **kw):
        y = kernel(x_rows, tile_expert, n_used, *rest, **kw)
        return jnp.where((jnp.arange(y.shape[0]) < n_used[0] * tm)[:, None],
                         y, jnp.nan)

    layer = functools.partial(
        held.held_experts_ffn, x, experts, weights, stack, 8,
        layer=jnp.int32(LAYER), plan=plan, act=act, interpret=True)
    plain, counts = layer()
    monkeypatch.setattr(held, "grouped_expert_ffn", poisoned)
    got, _ = layer()
    want = held.dense_held_reference(
        x, experts, weights, {n: w[LAYER] for n, w in stack.items()}, 8,
        act=act)
    assert int(counts.sum()) == int(np.sum(
        (np.asarray(experts) >= 8) & (np.asarray(experts) < 8 + n_held)))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(plain))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5)


def scratch_bytes(tm: int, tf: int, e: int, itemsize: int) -> int:
    """VMEM the kernel's scratch takes: two tiles of rows in, one out, the
    float32 sum and the ring."""
    return (3 * itemsize + 4) * tm * e \
        + held.ring_sets(tf, e, itemsize) * 3 * tf * e * itemsize


def test_the_ring_is_sized_by_bytes():
    """Sets of (gate, up, down) slices in VMEM at the served families'
    widths in bfloat16, and the kernel's scratch under the default scoped
    VMEM at the widest, under tiles of 64 rows (a kernel that asked for
    more hung the chip inside a mixed step program: PERF.md, PR 27)."""
    assert held.ring_sets(64, 7680, 2) == 2            # Pangu: 983 KB a slice
    assert held.ring_sets(64, 3072, 2) == 3            # Laguna: 393 KB
    assert held.ring_sets(64, 2560, 2) == 3            # SmallThinker: 328 KB
    assert held.ring_sets(64, 64, 4) == held.RING_SETS
    assert held.ring_sets(128, 16384, 2) == 2          # never under two
    for e in (7680, 3072, 2560):
        assert scratch_bytes(64, 64, e, 2) < 12 * 10 ** 6
    assert scratch_bytes(64, 64, 7680, 2) == 10_813_440
