"""Per-sequence host state.

Equivalent of the reference ``DSSequenceDescriptor`` /
``PlaceholderSequenceDescriptor``
(``inference/v2/ragged/sequence_descriptor.py``), minus the mirrored
pinned-tensor bookkeeping: on TPU the block table is materialized into
the batch's device arrays at ``finalize()`` time, so the descriptor is a
plain Python object.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class SequenceDescriptor:
    uid: int
    #: tokens whose KV is already committed to the cache
    seen_tokens: int = 0
    #: KV pages in this sequence's block table, in order — full prefix
    #: pages may be SHARED with other sequences (allocator refcounts)
    pages: List[int] = dataclasses.field(default_factory=list)
    #: tokens in flight in the current forward (pre_forward..post_forward)
    in_flight_tokens: int = 0
    #: host KV blob while preempted (offload_sequence), else None
    host_blob: object = None
    #: table slots the blob's pages belonged to (window-evicted slots
    #: stay null through an offload/restore cycle)
    live_slots: List[int] = dataclasses.field(default_factory=list)
    #: the second block table of a model with two page groups (its
    #: window layers' pool): only the LIVE pages, in order, table slot j
    #: holding the page of absolute index ``window_base + j``.  Pages
    #: wholly under the window are released and leave the table
    #: (``evict_window_pages``), so the table stays short
    window_pages: List[int] = dataclasses.field(default_factory=list)
    #: absolute page index of ``window_pages[0]``
    window_base: int = 0
    #: host blob of the window group's pages while preempted
    window_blob: object = None
    #: the sequence's slot of the state pool (a model with state-space
    #: layers: its recurrent state and convolution tails, every such
    #: layer's, live at this index); -1: none held
    state_slot: int = -1
    #: host copy of the slot's rows while preempted
    state_blob: object = None
    #: full prompt token ids, registered at admission when prefix
    #: caching is on — the indexer hashes full prompt pages from these
    #: (generated tokens are never indexed: their values are only
    #: host-known at drain time under async scheduling)
    prompt_tokens: Optional[np.ndarray] = None
    #: leading full pages already walked by the prefix indexer
    indexed_pages: int = 0
    #: cumulative page-hash chain cursor at ``indexed_pages``
    last_digest: bytes = b""
    #: warm-prefix provenance (ISSUE 16): tokens attached at admission
    #: from each tier — keys "device"/"host"/"disk"/"remote" — feeding
    #: the workload ledger's per-request tier-hit fields; None until
    #: match_prefix runs
    tier_hits: Optional[dict] = None

    @property
    def allocated_capacity(self) -> int:
        return len(self.pages)

    def pre_forward(self, n_tokens: int) -> None:
        self.in_flight_tokens = n_tokens

    def post_forward(self) -> None:
        self.seen_tokens += self.in_flight_tokens
        self.in_flight_tokens = 0

    def commit_tokens(self, n: int) -> None:
        """Variable-advance commit (speculative verification, ISSUE 10):
        only ``n`` of the in-flight tokens join the sequence — the rest
        were rejected drafts whose KV slots the next step overwrites
        before anything reads them (write-before-read, the chained
        step's optimistic-token discipline).  ``0 <= n <= in_flight``."""
        self.seen_tokens += min(max(n, 0), self.in_flight_tokens)
        self.in_flight_tokens = 0

    def extend_pages(self, pages: np.ndarray) -> None:
        self.pages.extend(int(p) for p in pages)

    def evict_pages_below(self, first_live_page: int) -> List[int]:
        """Sliding-window eviction: pages wholly below the attention
        window are dead for every FUTURE query (positions only grow).
        Their table slots become the null page — masked/skipped by the
        windowed attention paths — and the page ids are returned for the
        allocator.  Live KV becomes O(window) while the table stays
        positional (absolute page index = position // page_size)."""
        freed = []
        for i in range(min(first_live_page, len(self.pages))):
            if self.pages[i] != 0:
                freed.append(self.pages[i])
                self.pages[i] = 0
        return freed

    def evict_window_pages(self, first_live_page: int) -> List[int]:
        """The window group's form of :meth:`evict_pages_below`: the
        pages wholly below the window leave the short table, which then
        starts at ``first_live_page``; their ids are returned for the
        allocator."""
        drop = min(first_live_page - self.window_base,
                   len(self.window_pages))
        if drop <= 0:
            return []
        freed = self.window_pages[:drop]
        del self.window_pages[:drop]
        self.window_base += drop
        return freed

    def page_table(self, max_pages: int) -> np.ndarray:
        """Block table row padded with the null page to ``max_pages``."""
        if len(self.pages) > max_pages:
            raise ValueError(
                f"sequence {self.uid} has {len(self.pages)} pages "
                f"> bucket max {max_pages}")
        row = np.zeros(max_pages, dtype=np.int32)
        row[:len(self.pages)] = self.pages
        return row


def placeholder() -> SequenceDescriptor:
    """A throwaway descriptor for schedulability queries on unknown uids
    (reference ``PlaceholderSequenceDescriptor``)."""
    return SequenceDescriptor(uid=-1)
