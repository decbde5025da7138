"""Ragged batch — host-side builder producing static-shape device arrays.

Reference: ``inference/v2/ragged/ragged_wrapper.py`` (``RaggedBatchWrapper``
packs token ids + per-token/per-seq metadata into pinned host buffers
mirrored on device).  Under XLA there is no pinned-buffer mirroring;
instead the batch is padded into one of a small set of **static shape
buckets** so every distinct shape compiles exactly once:

    token_ids   : [S, Q] int32   (null-padded)
    q_lens      : [S]    int32   new tokens per slot (0 = empty slot)
    start_pos   : [S]    int32   committed history length per slot
    page_table  : [S, P] int32   KV page indices (0 = null page)

For a model of more than one cache (a second page group,
``StateManager.window_cache``; a state pool, ``StateManager.state_pool``)
the table is WIDE (``cache_kinds.TableLayout``): the full group's table,
then the window group's short table (``W`` slots, slot j = the page of
absolute index ``base + j``) and ``base`` itself, then the row's slot of
the state pool: they ride the operand the programs already take, and
``RaggedInferenceModel`` takes it apart (``_forward_hidden``).

``S`` (sequence slots), ``Q`` (max new tokens per sequence) and ``P``
(max pages per sequence) are bucketed by the engine's lattice (powers of
two by default); a pure-decode batch compiles with Q=1, a prefill chunk
with Q=chunk.  Padding slots write
their KV into the null page and are masked out of attention and logits.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .cache_kinds import TableLayout
from .sequence import SequenceDescriptor


#: the floors of the slot and page buckets (``lattice.BucketLattice``)
MIN_SLOTS = 1
MIN_PAGES = 8


def _bucket(n: int, floor: int = 1) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


@dataclasses.dataclass
class RaggedBatch:
    token_ids: np.ndarray    # [S, Q] int32
    q_lens: np.ndarray       # [S] int32
    start_pos: np.ndarray    # [S] int32
    page_table: np.ndarray   # [S, P] int32
    uids: List[int]          # live uids, in slot order (len <= S)
    #: every slot starts at position 0 (pure fresh prefill) — a STATIC
    #: property of the bucket, so the compiled step may use the flash
    #: kernel over the new tokens instead of the paged gather
    fresh: bool = False

    @property
    def num_slots(self) -> int:
        return self.token_ids.shape[0]

    @property
    def max_q(self) -> int:
        return self.token_ids.shape[1]

    @property
    def current_sequences(self) -> int:
        return len(self.uids)

    #: pages a row of the FULL group's table (the key's ``P``); the
    #: table's width where it is the only one
    pages: int = 0

    @property
    def shape_key(self) -> Tuple[int, int, int, bool]:
        return (self.token_ids.shape[0], self.token_ids.shape[1],
                self.pages or self.page_table.shape[1], self.fresh)


def build_batch(seqs: Sequence[SequenceDescriptor],
                tokens: Sequence[np.ndarray],
                page_size: int,
                lattice,
                fresh_supported: bool = True,
                min_q: int = 1,
                start_pos: Optional[Sequence[int]] = None,
                table: TableLayout = TableLayout(),
                scratch_slot: int = 0) -> RaggedBatch:
    """Pack (descriptor, new-token) pairs into a bucketed RaggedBatch.

    Callers must already have reserved KV pages on each descriptor
    (engine's ``maybe_allocate_kv``) and called ``pre_forward``.

    ``lattice``: the engine's :class:`..lattice.BucketLattice`, whose
    ``shape`` is the bucket rule (``min_q`` floors its Q bucket).

    ``fresh_supported``: whether the model has a dedicated fresh-prefill
    attention path.  Models without one (ALiBi) ignore the flag, so it
    must be coerced False here — otherwise a fresh prefill forms a
    ``(S, Q, P, True)`` step-cache key the precompiled lattice never
    contains (``precompile`` only lowers the True variant when the model
    has ``_fresh_attention``), spuriously raising under ``strict_shapes``
    or recompiling on the request path.

    ``start_pos``: each row's start position where it is not the
    descriptor's committed length (the draft catch-up re-feeds committed
    history from where the draft pool stopped).

    ``table``: the model's :class:`..cache_kinds.TableLayout`; where it
    has more than one cache the table is the wide one of the module
    docstring.  ``scratch_slot``: the state pool's scratch slot, which the
    padding rows name.
    """
    n = len(seqs)
    assert n == len(tokens) and n >= 1
    if start_pos is None:
        start_pos = [s.seen_tokens for s in seqs]
    S, Q, P = lattice.shape(n, max(len(t) for t in tokens),
                            max(s.allocated_capacity for s in seqs), min_q)

    token_ids = np.zeros((S, Q), dtype=np.int32)
    q_lens = np.zeros(S, dtype=np.int32)
    starts = np.zeros(S, dtype=np.int32)
    W = table.window_slots(Q)
    page_table = np.zeros((S, P + table.extra(Q)), dtype=np.int32)
    if table.state:
        page_table[:, -1] = scratch_slot
    uids = []
    for i, (sd, toks) in enumerate(zip(seqs, tokens)):
        toks = np.asarray(toks, dtype=np.int32).reshape(-1)
        token_ids[i, :len(toks)] = toks
        q_lens[i] = len(toks)
        starts[i] = start_pos[i]
        page_table[i, :P] = sd.page_table(P)
        if W:
            live = sd.window_pages
            if len(live) > W:
                raise ValueError(
                    f"sequence {sd.uid} holds {len(live)} window-group "
                    f"pages > the table's {W} slots (eviction has not "
                    "kept up with the context)")
            page_table[i, P:P + len(live)] = live
            page_table[i, P + W] = sd.window_base
        if table.state:
            page_table[i, -1] = sd.state_slot
        uids.append(sd.uid)
    fresh = fresh_supported and Q > 1 and not any(start_pos)
    return RaggedBatch(token_ids, q_lens, starts, page_table, uids,
                       fresh=fresh, pages=P)
