"""Persistent state manager: tracked sequences + blocked KV cache.

Reference: ``inference/v2/ragged/ragged_manager.py:19`` (``DSStateManager``).

Prefix caching (ISSUE 3): the manager owns the :class:`PrefixCache` and
is the single choke point for page lifetime, so every release path
(flush, preemption offload, sliding-window eviction) is shared-page
aware — a page leaves the device pool only when its last sharer drops
it AND the prefix cache no longer retains it.  ``free_pages`` reports
free-list pages plus cache-parked pages: the cache is exactly the
otherwise-idle pool, reclaimed LRU on allocator pressure, so admission
accounting and steady-state capacity are unchanged.

Page groups: a model whose layers are of two attention kinds (full and
window) keeps each kind's K/V in a pool of its own, with its own
allocator (``window_cache``) and a second block table a sequence
(``SequenceDescriptor.window_pages``).  The unit is the group and not the
layer: layers of one kind need the same pages at the same positions, so
they share one table.  The full group's table grows with the context;
the window group's holds the pages the window still reaches and gives
the others back (``evict_window``).  Every codec below that walks a
sequence's pages (flush, preempt offload / restore, snapshot, the
handoff) walks both lists.  The prefix cache is OFF with a window group:
a matched prefix would skip the prefill of tokens whose window-group K/V
nobody holds, so nothing is indexed.  A model with one window or none
has ONE group and this module's paths are what they were.

Sequence state that is not pages: a model with state-space or delta-rule
layers (``ops/ssm.py``, ``ops/delta_rule.py``; the slot's shape is the
kind's, ``cache_kinds.py``) keeps, a sequence, ONE SLOT of a fixed state pool
(``kv_cache.StatePool``; ``SequenceDescriptor.state_slot``).  A slot is
reserved with the sequence's first pages or not at all, released at
flush, moved to a host copy and back by preempt offload / restore, and
written beside the pages by ``export_state`` / ``import_state`` (the
snapshot and the handoff), which map it onto a fresh slot.  A reserved
slot is not cleared by the host: the program starts a row at position 0
from zeros.  The prefix cache is OFF with a state pool too (a matched
prefix would skip the prefill of tokens whose recurrent state nobody
holds), and there is no tier.  What a layer kind caches is declared in
``cache_kinds.py``.
"""

from __future__ import annotations

import time
from collections import Counter
from typing import Dict, List, Optional, Set, Tuple

import numpy as np

from ....runtime.fault_injection import get_fault_injector
from ....telemetry import metrics as tm
from ....telemetry import trace_span
from ....telemetry.flight_recorder import get_flight_recorder
from ....utils.comms_logging import serving_counters
from .blocked_allocator import KVAllocationError, NULL_PAGE
from .kv_cache import (BlockedKVCache, KVCacheConfig, PageBlob, StatePool,
                       StatePoolConfig, StateSlotBlob, blob_columns,
                       concat_blobs)
from .kv_tiers import TieredPageStore
from .prefix_cache import PrefixCache
from .sequence import SequenceDescriptor


class StateManager:
    def __init__(self, kv_config: KVCacheConfig,
                 max_tracked_sequences: int = 2048,
                 kv_sharding=None,
                 prefix_caching: bool = True,
                 tier_host_pages: int = 0,
                 tier_disk_pages: int = 0,
                 tier_dir: Optional[str] = None,
                 window_kv_config: Optional[KVCacheConfig] = None,
                 window: int = 0,
                 state_config: Optional[StatePoolConfig] = None):
        self.kv_config = kv_config
        self.max_tracked_sequences = max_tracked_sequences
        self.kv_cache = BlockedKVCache(kv_config, sharding=kv_sharding)
        #: the window group's pool and allocator (module docstring), and
        #: the window its layers attend; None for a model of one group
        self.window_cache: Optional[BlockedKVCache] = None
        self.window = int(window)
        #: pages the window group's eviction has given back, ever
        self.window_pages_released = 0
        if window_kv_config is not None:
            assert window > 0 \
                and window_kv_config.page_size == kv_config.page_size
            self.window_cache = BlockedKVCache(window_kv_config,
                                               sharding=kv_sharding)
            prefix_caching = False      # module docstring
        #: the state pool and its slots (module docstring); None for a
        #: model whose every layer kind caches pages
        self.state_pool: Optional[StatePool] = None
        if state_config is not None:
            self.state_pool = StatePool(state_config)
            prefix_caching = False      # module docstring
        self.prefix_cache: Optional[PrefixCache] = (
            PrefixCache(kv_config.page_size) if prefix_caching else None)
        # host/disk prefix tier (ISSUE 16): only meaningful under the
        # device prefix index — the tier is keyed by its chain digests
        self.tiers: Optional[TieredPageStore] = None
        if tier_host_pages > 0 and self.prefix_cache is not None:
            self.tiers = TieredPageStore(
                tier_host_pages,
                disk_pages=tier_disk_pages,
                disk_dir=tier_dir or None,
                # the disk tier's BYTE bound (ISSUE 20): disk_pages ×
                # the true quantized per-page footprint, so file sizes
                # are audited, not just entry counts
                bytes_per_page=kv_config.bytes_per_page)
        #: chain digests whose device pages were imported from a peer
        #: replica (cross-replica page fetch) — attributes their FIRST
        #: local match to the "remote" tier in the workload ledger
        self._remote_digests: Set[bytes] = set()
        self._seqs: Dict[int, SequenceDescriptor] = {}
        # offloaded-host-blob accounting (ISSUE 8): preempted sequences
        # hold KV in host blobs that device-page accounting can't see —
        # tracked here so expiry/flush of a preempted request provably
        # releases its blob (check_invariants audits the counters)
        self._offload_blobs = 0
        self._offload_bytes = 0

    def close(self) -> None:
        """Release tier resources (AIO handle, owned disk dir)."""
        if self.tiers is not None:
            self.tiers.close()

    # -- sequence tracking --------------------------------------------------
    @property
    def n_tracked_sequences(self) -> int:
        return len(self._seqs)

    @property
    def free_pages(self) -> int:
        """Schedulable pages: the free list plus cache-parked pages
        (reclaimed on demand by ``ensure_free``)."""
        free = self.kv_cache.free_pages
        if self.prefix_cache is not None:
            free += self.kv_cache.allocator.parked_pages
        return free

    @property
    def offloaded_blobs(self) -> int:
        """Sequences currently holding host-offloaded KV blobs."""
        return self._offload_blobs

    @property
    def offloaded_blob_bytes(self) -> int:
        """Host bytes held by offloaded (preempted) sequences' blobs."""
        return self._offload_bytes

    def kv_occupancy(self) -> Tuple[int, int]:
        """(pages in the live sequences' block tables, tokens written to
        them): what is reserved against what is used.  A page shared by
        several sequences counts once per table; a page the sliding
        window gave back counts in neither.  Walks every sequence, so it
        is taken only for a live span."""
        page = self.kv_config.page_size
        pages = tokens = 0
        for sd in self._seqs.values():
            live = sum(1 for p in sd.pages if p != NULL_PAGE)
            pages += live
            tokens += max(sd.seen_tokens
                          - (len(sd.pages) - live) * page, 0)
        return pages, tokens

    @property
    def free_window_pages(self) -> int:
        """Free pages of the window group (0 for a model of one group)."""
        return (self.window_cache.free_pages
                if self.window_cache is not None else 0)

    @property
    def free_state_slots(self) -> int:
        """Free slots of the state pool (0 for a model without one)."""
        return (self.state_pool.free_slots
                if self.state_pool is not None else 0)

    def state_slots_needed(self, sd: SequenceDescriptor) -> int:
        """Slots a step of ``sd`` has to reserve: 1 for a sequence of a
        model with a state pool that holds none yet."""
        return int(self.state_pool is not None and sd.state_slot < 0)

    def window_occupancy(self) -> Tuple[int, int]:
        """:meth:`kv_occupancy` of the window group: (pages in the live
        sequences' window tables, tokens those pages hold)."""
        page = self.kv_config.page_size
        pages = tokens = 0
        for sd in self._seqs.values():
            pages += len(sd.window_pages)
            if sd.window_pages:
                tokens += max(sd.seen_tokens - sd.window_base * page, 0)
        return pages, tokens

    def get_sequence(self, uid: int) -> Optional[SequenceDescriptor]:
        return self._seqs.get(uid)

    def get_or_create_sequence(self, uid: int) -> SequenceDescriptor:
        sd = self._seqs.get(uid)
        if sd is None:
            if len(self._seqs) >= self.max_tracked_sequences:
                raise RuntimeError(
                    f"tracked-sequence limit {self.max_tracked_sequences} hit")
            sd = SequenceDescriptor(uid=uid)
            self._seqs[uid] = sd
        return sd

    # -- shared-page-aware release ------------------------------------------
    def _release_pages(self, pages: List[int]) -> None:
        """Drop one table reference from each page.  Pages whose last
        sharer left are PARKED when the prefix cache still indexes them
        (retention: refcount 0, allocated, reclaimable LRU) and returned
        to the free list otherwise."""
        if not pages:
            return
        alloc = self.kv_cache.allocator
        zeroed = alloc.decref(pages)
        if not zeroed:
            return
        if self.prefix_cache is None:
            alloc.reclaim(zeroed)
            return
        reclaim = []
        for p in zeroed:
            if self.prefix_cache.contains_page(p):
                # retained: was in use until this very release
                self.prefix_cache.touch_page(p)
            else:
                reclaim.append(p)
        if reclaim:
            alloc.reclaim(reclaim)

    def ensure_free(self, num_pages: int) -> None:
        """Make the free list hold ``num_pages`` by LRU-evicting parked
        prefix-cache pages if needed (no-op when already satisfied)."""
        alloc = self.kv_cache.allocator
        deficit = num_pages - alloc.free_pages
        if deficit <= 0 or self.prefix_cache is None:
            return
        with trace_span("kv.evict"):
            entries = self.prefix_cache.evict_entries(deficit,
                                                      alloc.is_parked)
            if not entries:
                return
            if self.tiers is not None:
                # demote BEFORE reclaim: page contents are read while
                # the pages are still allocated.  ensure_free only runs
                # from admission paths (never the scheduler's dispatch
                # hot loop — the dslint hot-path pass is the guard), so
                # the d2h gather + tier write stay off the hot path
                self._demote(entries)
            evicted = [p for _, p in entries]
            alloc.reclaim(evicted)
            serving_counters.record_prefix_evicted(len(evicted))
            get_flight_recorder().record("kv.evict", pages=len(evicted))

    def _demote(self, entries: List[tuple]) -> None:
        """Store evicted parked pages' contents in the host/disk tier
        under their cumulative chain digests.  A refused put (tier I/O
        error, duplicate digest) just loses that page's warmth — the
        eviction itself proceeds regardless."""
        with trace_span("kv.demote"):
            blob = self.kv_cache.read_pages([p for _, p in entries])
            stored = 0
            for i, (digest, _page) in enumerate(entries):
                if self.tiers.put(digest, blob_columns(blob, [i])):
                    stored += 1
            if stored:
                get_flight_recorder().record("kv.demote", pages=stored)

    # -- prefix cache -------------------------------------------------------
    def match_prefix(self, sd: SequenceDescriptor,
                     prompt: np.ndarray) -> int:
        """Attach the longest cached prefix of ``prompt`` to a FRESH
        sequence: full pages only (the trailing partial page is never
        shared), and at least one suffix token is always left to prefill
        (the step needs last-token logits).  Registers the prompt for
        indexing either way.  Returns the tokens attached."""
        if self.prefix_cache is None or sd.seen_tokens or sd.pages \
                or sd.host_blob is not None:
            return 0  # started sequences keep their original registration
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        sd.prompt_tokens = prompt
        page = self.kv_config.page_size
        max_pages = (len(prompt) - 1) // page
        if max_pages <= 0:
            return 0
        with trace_span("kv.match_prefix"):
            pages, digest = self.prefix_cache.match(prompt, max_pages)
            hits = {"device": 0, "host": 0, "disk": 0, "remote": 0}
            if pages:
                # attach the device hits FIRST: live references make
                # the matched pages un-evictable while the promotion
                # below runs ensure_free for its landing pages
                self.kv_cache.allocator.add_ref(pages)
                self._attribute_device_hits(prompt, len(pages), hits)
            promoted: List[int] = []
            if self.tiers is not None and len(pages) < max_pages:
                promoted, digest = self._promote_chain(
                    prompt, len(pages), digest, max_pages, hits)
            pages = [int(p) for p in pages] + promoted
            if not pages:
                return 0
            sd.pages = pages
            sd.seen_tokens = len(pages) * page
            sd.indexed_pages = len(pages)
            sd.last_digest = digest
            sd.tier_hits = hits
            return sd.seen_tokens

    def _attribute_device_hits(self, prompt: np.ndarray, n_pages: int,
                               hits: dict) -> None:
        """Split a device prefix match into device-born vs remote-born
        tokens: pages imported by a cross-replica fetch count as
        "remote" on their FIRST match (then the digest demotes to plain
        device provenance)."""
        page = self.kv_config.page_size
        if not self._remote_digests:
            hits["device"] = n_pages * page
            return
        d = b""
        for i in range(n_pages):
            d = self.prefix_cache.chain(d, prompt[i * page:(i + 1) * page])
            if d in self._remote_digests:
                self._remote_digests.discard(d)
                hits["remote"] += page
            else:
                hits["device"] += page

    def _promote_chain(self, prompt: np.ndarray, n_matched: int,
                       digest: bytes, max_pages: int,
                       hits: dict) -> tuple:
        """Extend a device prefix match past its first miss by walking
        the SAME digest chain into the host/disk tier (ISSUE 16).
        Promoted blobs are scattered onto fresh device pages and
        re-indexed, so the next same-prefix request hits on device.
        Returns ``(promoted page ids, new chain cursor)``; any tier
        miss/failure just stops the walk — a shorter warm prefix, never
        an admission error."""
        page = self.kv_config.page_size
        chain: List[bytes] = []
        d = digest
        for i in range(n_matched, max_pages):
            d = self.prefix_cache.chain(d, prompt[i * page:(i + 1) * page])
            if self.tiers.contains(d) is None:
                break
            chain.append(d)
        if not chain:
            return [], digest
        t0 = time.perf_counter()
        with trace_span("kv.promote"):
            blobs, hit_tiers = self.tiers.take_many(chain)
            if not blobs:
                return [], digest
            try:
                self.ensure_free(len(blobs))
                new_pages = self.kv_cache.restore_pages(
                    concat_blobs(blobs))
            except KVAllocationError:
                # pool full of live pages: the promotion loses (the
                # blobs already left the tier) — a clean miss, never an
                # error on the admission path
                self.tiers.landed(len(blobs))
                return [], digest
            self.tiers.landed(len(blobs))
            # refcount 1 from restore_pages = this sequence's reference
            # (device-matched pages got theirs from add_ref above)
            for cd, p in zip(chain, new_pages):
                self.prefix_cache.insert(cd, int(p))
            for t in hit_tiers:
                hits[t] += page
            tm.KV_TIER_PROMOTE_MS.observe(
                (time.perf_counter() - t0) * 1000.0)
            get_flight_recorder().record(
                "kv.promote", pages=len(blobs),
                host=hit_tiers.count("host"),
                disk=hit_tiers.count("disk"))
        return [int(p) for p in new_pages], chain[len(blobs) - 1]

    def index_prefix(self, sd: SequenceDescriptor) -> None:
        """Index newly-committed FULL prompt pages (called after each
        commit).  Generated-token pages (positions past the prompt) are
        never indexed, so the page a chained decode step optimistically
        writes is never a cache page."""
        if self.prefix_cache is None or sd.prompt_tokens is None:
            return
        page = self.kv_config.page_size
        full = min(sd.seen_tokens, len(sd.prompt_tokens)) // page
        if full <= sd.indexed_pages:
            return
        with trace_span("kv.index_prefix"):
            for i in range(sd.indexed_pages, full):
                digest = self.prefix_cache.chain(
                    sd.last_digest,
                    sd.prompt_tokens[i * page:(i + 1) * page])
                p = sd.pages[i] if i < len(sd.pages) else NULL_PAGE
                if p != NULL_PAGE:  # window-evicted slots can't be indexed
                    if self.prefix_cache.insert(digest, int(p)) \
                            and self.tiers is not None:
                        # a re-prefilled prefix supersedes any demoted
                        # copy: a digest is never device-indexed and
                        # tier-resident at once
                        self.tiers.discard(digest)
                sd.last_digest = digest
                sd.indexed_pages = i + 1

    def export_digests(self, top_k: int = 64) -> List[str]:
        """The prefix cache's bounded affinity hint (ISSUE 12): up to
        ``top_k`` most-recently-used cumulative digests as hex, most
        recent first; empty when caching is off.  No page ids or KV
        contents — safe to publish to a pool router."""
        if self.prefix_cache is None:
            return []
        return self.prefix_cache.export_digests(top_k)

    def reset_prefix_cache(self) -> None:
        """Drop the whole index and reclaim its parked pages (bench
        cold-start; live sequences' pages free normally at flush)."""
        if self.prefix_cache is None:
            return
        alloc = self.kv_cache.allocator
        parked = [p for p in self.prefix_cache.clear()
                  if alloc.is_parked(p)]
        if parked:
            alloc.reclaim(parked)
        if self.tiers is not None:
            self.tiers.clear()      # cold start means cold everywhere

    # -- lifecycle ----------------------------------------------------------
    def offloadable_slots(self, sd: SequenceDescriptor) -> List[int]:
        """Table slots an offload would actually move to host: non-null
        and privately held (refcount 1).  Shared pages stay resident —
        the scheduler's preemption-victim ranking uses this same
        predicate so a fully-shared victim can't be picked for a no-op
        offload."""
        alloc = self.kv_cache.allocator
        return [i for i, p in enumerate(sd.pages)
                if p != NULL_PAGE and alloc.ref_count(p) == 1]

    def _release_blob(self, sd: SequenceDescriptor) -> None:
        """Drop a sequence's offloaded host blob and its accounting."""
        self._offload_blobs -= 1
        self._offload_bytes -= sd.host_blob.nbytes
        sd.host_blob = None
        sd.live_slots = []

    def _hold_side_blob(self, sd: SequenceDescriptor, field: str,
                        blob) -> None:
        """Account a host blob held beside the full group's: the window
        group's pages (``window_blob``) or the state slot's rows
        (``state_blob``), each counted as a blob of its own."""
        setattr(sd, field, blob)
        self._offload_blobs += 1
        self._offload_bytes += blob.nbytes

    def _release_side_blob(self, sd: SequenceDescriptor,
                           field: str) -> None:
        self._offload_blobs -= 1
        self._offload_bytes -= getattr(sd, field).nbytes
        setattr(sd, field, None)

    def flush_sequence(self, uid: int) -> None:
        sd = self._seqs.pop(uid, None)
        if sd is not None:
            with trace_span("kv.flush"):
                # window eviction leaves null-page placeholders — not
                # ours
                self._release_pages(
                    [p for p in sd.pages if p != NULL_PAGE])
                if sd.host_blob is not None:
                    # a request expired/cancelled WHILE PREEMPTED must
                    # release its offloaded host blob too, not just its
                    # device pages (the blob accounting audit would
                    # otherwise report the leak forever)
                    self._release_blob(sd)
                if sd.window_pages:
                    self.window_cache.release(sd.window_pages)
                if sd.window_blob is not None:
                    self._release_side_blob(sd, "window_blob")
                if sd.state_slot >= 0:
                    self.state_pool.release(sd.state_slot)
                    sd.state_slot = -1
                if sd.state_blob is not None:
                    self._release_side_blob(sd, "state_blob")

    def offload_sequence(self, uid: int) -> None:
        """Preempt: move a sequence's PRIVATE live KV pages to host
        memory and free them (reference kv_cache offload hook).  Shared
        pages (another sequence's table also holds them) stay resident —
        freeing them would yank KV from under the sharers; privately-
        held pages the cache indexes are unindexed and offloaded (the
        point of preemption is reclaiming memory).  The sequence stays
        tracked; it cannot be scheduled until restore_sequence."""
        sd = self._seqs.get(uid)
        if sd is None or sd.host_blob is not None \
                or sd.window_blob is not None or sd.state_blob is not None:
            return  # unknown/flushed uids tolerated like flush_sequence
        with trace_span("kv.offload"):
            self._offload_impl(sd)
            if sd.window_pages:
                # the window group's pages are all private (nothing of
                # it is shared or indexed): every one moves, and comes
                # back in order under the same window_base
                self._hold_side_blob(
                    sd, "window_blob",
                    self.window_cache.offload_pages(sd.window_pages))
                sd.window_pages = []
            if sd.state_slot >= 0:
                # the slot's rows move with the pages: both or neither
                self._hold_side_blob(
                    sd, "state_blob",
                    self.state_pool.read_slot(sd.state_slot))
                self.state_pool.release(sd.state_slot)
                sd.state_slot = -1

    def _offload_impl(self, sd: SequenceDescriptor) -> None:
        sd.live_slots = self.offloadable_slots(sd)
        live = [sd.pages[i] for i in sd.live_slots]
        if not live:
            sd.host_blob = None
            return
        if self.prefix_cache is not None:
            dropped = [p for p in live if self.prefix_cache.contains_page(p)]
            if dropped:
                self.prefix_cache.drop_pages(dropped)
                # the sequence's digest chain now passes through
                # unindexed pages: any page indexed past the break could
                # never be matched (match() walks from the root), so
                # stop indexing this sequence rather than fill the cache
                # with unmatchable entries that flush would then park
                sd.prompt_tokens = None
        sd.host_blob = self.kv_cache.offload_pages(live)
        self._offload_blobs += 1
        self._offload_bytes += sd.host_blob.nbytes
        for i in sd.live_slots:
            sd.pages[i] = NULL_PAGE

    def restore_sequence(self, uid: int) -> None:
        """Bring a preempted sequence's KV back onto device (reference
        restore hook).  Raises if the pool lacks free pages."""
        sd = self._seqs.get(uid)
        if sd is None or (sd.host_blob is None and sd.window_blob is None
                          and sd.state_blob is None):
            return
        with trace_span("kv.restore"):
            if sd.state_blob is not None and not self.free_state_slots:
                # before any page is touched: a restore fails whole
                raise KVAllocationError(
                    "restore needs a state slot, none free")
            need_w = (int(sd.window_blob.shape[1])
                      if sd.window_blob is not None else 0)
            if need_w > self.free_window_pages:
                # before the full group is touched: a restore fails whole
                raise KVAllocationError(
                    f"restore needs {need_w} window-group pages, "
                    f"{self.free_window_pages} free")
            if sd.host_blob is not None:
                self.ensure_free(int(sd.host_blob.shape[1]))
                pages = self.kv_cache.restore_pages(sd.host_blob)
                for slot, p in zip(sd.live_slots, pages):
                    sd.pages[slot] = int(p)
                self._release_blob(sd)
            if sd.window_blob is not None:
                sd.window_pages = [int(p) for p in
                                   self.window_cache.restore_pages(
                                       sd.window_blob)]
                self._release_side_blob(sd, "window_blob")
            if sd.state_blob is not None:
                sd.state_slot = self.state_pool.reserve()
                self.state_pool.write_slot(sd.state_slot, sd.state_blob)
                self._release_side_blob(sd, "state_blob")
        # restored pages are private again; if offload unindexed any of
        # them it also disabled this sequence's indexing (broken chain),
        # otherwise the digest chain is intact and indexing continues

    def evict_window(self, sd: SequenceDescriptor, window: int) -> int:
        """Release every page wholly below ``seen_tokens - window + 1``
        (the earliest position any future query can attend).  Shared
        pages just lose this sequence's reference — the sharers (and the
        prefix cache's retention) keep them alive.  Returns the number
        of table slots cleared."""
        min_attended = sd.seen_tokens - window + 1
        if min_attended <= 0:
            return 0
        first_live = min_attended // self.kv_config.page_size
        if self.window_cache is not None:
            # two groups: the window group's table alone gives pages
            # back; the full group's layers need theirs for good
            freed = sd.evict_window_pages(first_live)
            if freed:
                self.window_cache.release(freed)
                self.window_pages_released += len(freed)
            return len(freed)
        freed = sd.evict_pages_below(first_live)
        if freed:
            self._release_pages(freed)
        return len(freed)

    # -- snapshot export/import (ISSUE 8) -----------------------------------
    # The export/import pair is deliberately the page-transfer seam
    # ROADMAP item 4's prefill/decode disaggregation and multi-replica
    # migration will ride: everything crosses as (JSON-able meta, named
    # numpy arrays), with page ids remapped on import so the receiving
    # pool's layout is free to differ.

    def export_state(self, seq_ids: Optional[List[int]] = None) -> tuple:
        """Serialize every tracked sequence, the prefix-cache index, and
        the referenced KV page CONTENTS (each distinct device page
        written once — sharing and refcounts are reconstructed from the
        block tables on import).  Requires drained state (no in-flight
        tokens).  Returns ``(meta, arrays)``.

        With ``seq_ids`` (ISSUE 13, the disaggregation handoff) the
        export is SELECTIVE: only the listed sequences, only the pages
        their block tables reference (full committed prefix pages plus
        the private partial tail page), and only the prefix-index
        entries bound to those pages — the digest chain is what lets
        the importing pool dedup already-held shared prefixes instead
        of streaming them again.  Parked cache pages outside the listed
        sequences do NOT ride along, and the resulting bundle is marked
        ``selective`` so ``import_state`` takes the merge path."""
        from ..snapshot import SnapshotError
        if seq_ids is not None:
            missing = [u for u in seq_ids if int(u) not in self._seqs]
            if missing:
                raise SnapshotError(
                    f"selective export of untracked sequences {missing}")
            export_seqs = {int(u): self._seqs[int(u)] for u in seq_ids}
        else:
            export_seqs = self._seqs
        page_order: List[int] = []
        seen = set()
        for sd in export_seqs.values():
            if sd.in_flight_tokens:
                raise SnapshotError(
                    f"sequence {sd.uid} has {sd.in_flight_tokens} "
                    "in-flight tokens — drain the step before export")
            for p in sd.pages:
                if p != NULL_PAGE and p not in seen:
                    seen.add(p)
                    page_order.append(int(p))
        prefix_entries = []
        if self.prefix_cache is not None and seq_ids is None:
            prefix_entries = self.prefix_cache.export_entries()
            for _, p in prefix_entries:
                if p not in seen:       # parked (cache-retained) page
                    seen.add(p)
                    page_order.append(int(p))
        elif self.prefix_cache is not None:
            # selective: only entries whose page the bundle carries —
            # the importer's dedup and re-indexing hooks
            prefix_entries = [(d, p) for d, p
                              in self.prefix_cache.export_entries()
                              if p in seen]
        arrays: Dict[str, np.ndarray] = {}
        if page_order:
            # quantized caches export as (payload, scale) array pairs —
            # snapshot/handoff codecs carry named numpy arrays only, so
            # a PageBlob travels split and is reassembled on import
            self._pack_blob(arrays, "page_blob",
                            self.kv_cache.read_pages(page_order))
        seqs = []
        for uid, sd in export_seqs.items():
            m = {"uid": int(uid), "seen_tokens": int(sd.seen_tokens),
                 "pages": [int(p) for p in sd.pages],
                 "live_slots": [int(i) for i in sd.live_slots],
                 "indexed_pages": int(sd.indexed_pages),
                 "last_digest": sd.last_digest.hex(),
                 "has_prompt": sd.prompt_tokens is not None,
                 "has_blob": sd.host_blob is not None}
            if sd.prompt_tokens is not None:
                arrays[f"prompt_{uid}"] = np.asarray(sd.prompt_tokens,
                                                     np.int32)
            if sd.host_blob is not None:
                self._pack_blob(arrays, f"hostblob_{uid}", sd.host_blob)
            if self.window_cache is not None:
                m.update(window_pages=[int(p) for p in sd.window_pages],
                         window_base=int(sd.window_base),
                         has_window_blob=sd.window_blob is not None)
                if sd.window_blob is not None:
                    self._pack_blob(arrays, f"windowblob_{uid}",
                                    sd.window_blob)
            if self.state_pool is not None:
                # the slot's rows travel beside the pages, read from the
                # pool or from the host copy a preempted sequence holds
                blob = (self.state_pool.read_slot(sd.state_slot)
                        if sd.state_slot >= 0 else sd.state_blob)
                m["state"] = ("slot" if sd.state_slot >= 0 else
                              "blob" if blob is not None else "none")
                if blob is not None:
                    arrays[f"state_h_{uid}"] = blob.h
                    arrays[f"state_conv_{uid}"] = blob.conv
            seqs.append(m)
        window_ids = [int(p) for sd in export_seqs.values()
                      for p in sd.window_pages]
        if window_ids:
            self._pack_blob(arrays, "window_page_blob",
                            self.window_cache.read_pages(window_ids))
        meta = {
            "kv": self._kv_meta(),
            "prefix_caching": self.prefix_cache is not None,
            "page_ids": page_order,
            "sequences": seqs,
            "prefix": [[d.hex(), int(p)] for d, p in prefix_entries],
        }
        if self.window_cache is not None:
            meta["window_page_ids"] = window_ids
        if seq_ids is not None:
            meta["selective"] = True
        return meta, arrays

    def _kv_meta(self) -> dict:
        cfg = self.kv_config
        meta = {"num_layers": cfg.num_layers, "kv_heads": cfg.kv_heads,
                "head_dim": cfg.head_dim, "page_size": cfg.page_size,
                "dtype": np.dtype(cfg.dtype).name,
                "quantization": cfg.quantization, "planes": cfg.planes}
        if self.window_cache is not None:
            meta["window_layers"] = self.window_cache.cfg.num_layers
        if self.state_pool is not None:
            sc = self.state_pool.cfg
            meta["state"] = [sc.kind, sc.num_layers, *sc.state, *sc.tail,
                             np.dtype(sc.state_dtype).name,
                             np.dtype(sc.conv_dtype).name]
        return meta

    def _check_state_room(self, meta: dict) -> None:
        """Import half of the state pool, before any mutation: the
        bundle's live slots need as many free ones here (the retryable
        :class:`KVAllocationError` otherwise)."""
        need = sum(1 for m in meta["sequences"]
                   if m.get("state") == "slot")
        if need > self.free_state_slots:
            raise KVAllocationError(
                f"bundle needs {need} state slots, pool has "
                f"{self.free_state_slots} free")

    def _import_state_slot(self, sd: SequenceDescriptor, m: dict,
                           arrays: Dict[str, np.ndarray]) -> None:
        """One imported sequence's state: onto a fresh slot, or held as
        the host copy it was."""
        how = m.get("state", "none")
        if self.state_pool is None or how == "none":
            return
        blob = StateSlotBlob(arrays[f"state_h_{sd.uid}"],
                             arrays[f"state_conv_{sd.uid}"])
        if how == "slot":
            sd.state_slot = self.state_pool.reserve()
            self.state_pool.write_slot(sd.state_slot, blob)
        else:
            self._hold_side_blob(sd, "state_blob", blob)

    def _window_mapping(self, meta: dict,
                        arrays: Dict[str, np.ndarray]) -> Dict[int, int]:
        """Import half of the window group: the bundle's window pages on
        fresh pages of this manager's window pool -> {old id: new id}.
        Raises the retryable :class:`KVAllocationError` before anything
        is written where the pool lacks room."""
        from ..snapshot import SnapshotError
        old_ids = [int(p) for p in meta.get("window_page_ids", [])]
        if not old_ids:
            return {}
        blob = self._unpack_blob(arrays, "window_page_blob")
        if blob is None or blob.shape[1] != len(old_ids):
            raise SnapshotError(
                "window page blob missing or inconsistent with "
                "window_page_ids")
        if len(old_ids) > self.free_window_pages:
            raise KVAllocationError(
                f"bundle needs {len(old_ids)} window-group pages, pool "
                f"has {self.free_window_pages} free")
        new = self.window_cache.restore_pages(blob)
        return {o: int(n) for o, n in zip(old_ids, new)}

    def _import_window(self, sd: SequenceDescriptor, m: dict,
                       mapping: Dict[int, int],
                       arrays: Dict[str, np.ndarray]) -> None:
        """One imported sequence's window table and window blob."""
        if self.window_cache is None:
            return
        sd.window_pages = [mapping[int(p)] for p in m["window_pages"]]
        sd.window_base = int(m["window_base"])
        if m.get("has_window_blob"):
            self._hold_side_blob(
                sd, "window_blob",
                self._unpack_blob(arrays, f"windowblob_{sd.uid}"))

    def _check_kv_meta(self, meta: dict) -> None:
        from ..snapshot import SnapshotError
        # pre-quantization bundles carry no "quantization" key — they
        # are fp by construction, so normalize instead of refusing
        kv = dict(meta["kv"])
        kv.setdefault("quantization", "none")
        kv.setdefault("planes", 2)      # bundles older than latent pools
        ours = self._kv_meta()
        if kv != ours:
            raise SnapshotError(
                f"KV geometry mismatch: bundle {kv} vs engine {ours}")

    @staticmethod
    def _pack_blob(arrays: Dict[str, np.ndarray], key: str,
                   blob) -> None:
        """Store a page blob under ``key`` as named numpy arrays: a
        quantized :class:`PageBlob` splits into payload + ``_scale``."""
        if isinstance(blob, PageBlob):
            arrays[key] = blob.payload
            arrays[key + "_scale"] = blob.scale
        else:
            arrays[key] = np.asarray(blob)

    @staticmethod
    def _unpack_blob(arrays: Dict[str, np.ndarray], key: str):
        """Inverse of ``_pack_blob``; None when ``key`` is absent."""
        payload = arrays.get(key)
        if payload is None:
            return None
        scale = arrays.get(key + "_scale")
        if scale is not None:
            return PageBlob(payload, scale)
        return payload

    def import_state(self, meta: dict, arrays: Dict[str, np.ndarray]
                     ) -> Optional[dict]:
        """Reconstruct exported state into THIS (empty) manager: fresh
        device pages are allocated and scattered from the blob, block
        tables are remapped onto them with the original refcounts
        (shared prefix pages shared again, cache-retained pages parked
        again), and the prefix index is rebuilt in its original LRU
        order.  Raises :class:`SnapshotError` on geometry mismatch,
        non-empty state, or a pool too small for the bundle.

        A ``selective`` bundle (``export_state(seq_ids=...)``) instead
        MERGES into this possibly-busy manager — the disaggregation
        handoff path — and returns ``{"pages_streamed",
        "pages_shared"}`` (pages whose chain digest this manager's
        prefix cache already held attach by reference instead of being
        scattered from the blob: prefix sharing survives the pool
        boundary)."""
        from ..snapshot import SnapshotError
        if meta.get("selective"):
            return self._import_selective(meta, arrays)
        alloc = self.kv_cache.allocator
        if self._seqs or alloc.live_pages or alloc.parked_pages:
            raise SnapshotError(
                "import_state requires an empty state manager "
                f"({len(self._seqs)} tracked sequences, "
                f"{alloc.live_pages} live / {alloc.parked_pages} parked "
                "pages)")
        self._check_kv_meta(meta)
        if bool(meta.get("prefix_caching")) != \
                (self.prefix_cache is not None):
            raise SnapshotError(
                "prefix_caching mismatch between bundle and engine — "
                "restore with the same serving config for a "
                "deterministic resume")
        old_ids = [int(p) for p in meta["page_ids"]]
        if len(old_ids) > alloc.free_pages:
            raise SnapshotError(
                f"bundle needs {len(old_ids)} KV pages, pool has "
                f"{alloc.free_pages} free")
        try:
            self._check_state_room(meta)
            window_mapping = self._window_mapping(meta, arrays)
        except KVAllocationError as e:
            raise SnapshotError(str(e)) from None
        mapping = {NULL_PAGE: NULL_PAGE}
        if old_ids:
            blob = self._unpack_blob(arrays, "page_blob")
            if blob is None or blob.shape[1] != len(old_ids):
                raise SnapshotError(
                    "page blob missing or inconsistent with page_ids")
            new = self.kv_cache.restore_pages(blob)     # refcount 1 each
            mapping.update((o, int(n)) for o, n in zip(old_ids, new))
        # reconstruct refcounts: allocate gave each page one reference;
        # the block tables define the true count (0 = parked)
        refs = Counter()
        for m in meta["sequences"]:
            for p in m["pages"]:
                if p != NULL_PAGE:
                    refs[int(p)] += 1
        for old in old_ids:
            n, newp = refs.get(old, 0), mapping[old]
            if n == 0:
                alloc.decref([newp])    # parked; indexed again below
            elif n > 1:
                alloc.add_ref([newp] * (n - 1))
        for m in meta["sequences"]:
            uid = int(m["uid"])
            try:
                pages = [mapping[int(p)] for p in m["pages"]]
            except KeyError as e:
                raise SnapshotError(
                    f"sequence {uid} references unexported page {e}")
            sd = SequenceDescriptor(
                uid=uid, seen_tokens=int(m["seen_tokens"]), pages=pages,
                live_slots=[int(i) for i in m["live_slots"]],
                indexed_pages=int(m["indexed_pages"]),
                last_digest=bytes.fromhex(m["last_digest"]))
            if m["has_prompt"]:
                sd.prompt_tokens = np.asarray(arrays[f"prompt_{uid}"],
                                              np.int32)
            if m["has_blob"]:
                sd.host_blob = self._unpack_blob(arrays,
                                                 f"hostblob_{uid}")
                self._offload_blobs += 1
                self._offload_bytes += sd.host_blob.nbytes
            self._import_window(sd, m, window_mapping, arrays)
            self._import_state_slot(sd, m, arrays)
            self._seqs[uid] = sd
        if self.prefix_cache is not None:
            for d_hex, p in meta["prefix"]:
                newp = mapping.get(int(p))
                if newp is None:
                    raise SnapshotError(
                        f"prefix index references unexported page {p}")
                self.prefix_cache.insert(bytes.fromhex(d_hex), newp)
        return None

    def _import_selective(self, meta: dict,
                          arrays: Dict[str, np.ndarray]) -> dict:
        """Merge one selective (handoff) bundle into this possibly-busy
        manager (ISSUE 13).  Phases are ordered so a refused import
        leaves no mutation behind: (1) validate uids/geometry and
        compute the digest-dedup mapping, (2) budget-check the pages
        that must actually stream, (3) attach dedup pages by reference
        (they leave the eviction pool BEFORE ensure_free runs), evict
        for and scatter the streamed subset, (4) rebuild descriptors /
        host blobs and re-index the digest chain so the NEXT handoff
        sharing this prefix dedups too."""
        from ..snapshot import SnapshotError
        self._check_kv_meta(meta)
        alloc = self.kv_cache.allocator
        for m in meta["sequences"]:
            if int(m["uid"]) in self._seqs:
                raise SnapshotError(
                    f"selective import: uid {m['uid']} already tracked")
        if (len(self._seqs) + len(meta["sequences"])
                > self.max_tracked_sequences):
            # retryable backpressure, like the page-budget refusal
            # below: the importing pool frees tracked slots as its
            # requests finish
            raise KVAllocationError(
                f"handoff import would track "
                f"{len(self._seqs) + len(meta['sequences'])} sequences "
                f"(limit {self.max_tracked_sequences}) — retry after "
                "the pool drains")
        old_ids = [int(p) for p in meta["page_ids"]]
        blob = self._unpack_blob(arrays, "page_blob")
        if old_ids and (blob is None or blob.shape[1] != len(old_ids)):
            raise SnapshotError(
                "page blob missing or inconsistent with page_ids")
        # digest-keyed dedup: a full prefix page whose cumulative chain
        # digest this manager's cache already indexes holds exactly the
        # same KV (same tokens, same weights across the disagg pools,
        # 128-bit chained blake2b) — attach the local page instead of
        # streaming the exported copy
        digest_of = {int(p): bytes.fromhex(d) for d, p in meta["prefix"]}
        mapping = {NULL_PAGE: NULL_PAGE}
        dedup: Dict[int, int] = {}
        stream: List[int] = []
        for old in old_ids:
            local = None
            d = digest_of.get(old)
            if d is not None and self.prefix_cache is not None:
                local = self.prefix_cache.lookup(d)
                if local is not None and not alloc.is_allocated(local):
                    local = None    # defensive: never attach a freed page
            if local is not None:
                dedup[old] = int(local)
                mapping[old] = int(local)
            else:
                stream.append(old)
        # budget check BEFORE any mutation (the refusal must stay
        # retryable): parked pages that are about to be attached as
        # dedup targets become LIVE below, so they cannot also be
        # evicted to make room for the streamed pages — subtract them
        # from the schedulable count or a refused allocation would
        # land after the add_ref and leak phantom references
        parked_dedup = sum(1 for local in dedup.values()
                           if alloc.is_parked(local))
        available = alloc.free_pages + alloc.parked_pages - parked_dedup
        if len(stream) > available:
            raise KVAllocationError(
                f"handoff import needs {len(stream)} streamed pages, "
                f"pool has {available} schedulable — retry after "
                "the decode pool drains")
        # the window group's pages stream whole (nothing of it is shared);
        # a refusal here is still before any mutation
        self._check_state_room(meta)
        window_mapping = self._window_mapping(meta, arrays)
        # true refcounts per exported page = appearances in the
        # imported block tables (selective bundles carry no parked
        # pages, so every exported page is referenced at least once)
        refs = Counter()
        for m in meta["sequences"]:
            for p in m["pages"]:
                if p != NULL_PAGE:
                    refs[int(p)] += 1
        for old, local in dedup.items():
            n = refs.get(old, 0)
            if n:
                alloc.add_ref([local] * n)
        if stream:
            self.ensure_free(len(stream))
            col = {p: i for i, p in enumerate(old_ids)}
            sub = blob_columns(blob, [col[p] for p in stream])
            new = self.kv_cache.restore_pages(sub)   # refcount 1 each
            for old, newp in zip(stream, new):
                mapping[old] = int(newp)
                n = refs.get(old, 0)
                if n < 1:
                    raise SnapshotError(
                        f"selective bundle streams unreferenced page "
                        f"{old}")
                if n > 1:
                    alloc.add_ref([int(newp)] * (n - 1))
        for m in meta["sequences"]:
            uid = int(m["uid"])
            try:
                pages = [mapping[int(p)] for p in m["pages"]]
            except KeyError as e:
                raise SnapshotError(
                    f"sequence {uid} references unexported page {e}")
            sd = SequenceDescriptor(
                uid=uid, seen_tokens=int(m["seen_tokens"]), pages=pages,
                live_slots=[int(i) for i in m["live_slots"]],
                indexed_pages=int(m["indexed_pages"]),
                last_digest=bytes.fromhex(m["last_digest"]))
            if m["has_prompt"]:
                sd.prompt_tokens = np.asarray(arrays[f"prompt_{uid}"],
                                              np.int32)
            if m["has_blob"]:
                sd.host_blob = self._unpack_blob(arrays,
                                                 f"hostblob_{uid}")
                self._offload_blobs += 1
                self._offload_bytes += sd.host_blob.nbytes
            self._import_window(sd, m, window_mapping, arrays)
            self._import_state_slot(sd, m, arrays)
            self._seqs[uid] = sd
        if self.prefix_cache is not None:
            for d_hex, p in meta["prefix"]:
                newp = mapping.get(int(p))
                if newp is not None:
                    d = bytes.fromhex(d_hex)
                    if self.prefix_cache.insert(d, int(newp)) \
                            and self.tiers is not None:
                        self.tiers.discard(d)
        return {"pages_streamed": len(stream),
                "pages_shared": len(dedup)}

    # -- cross-replica page fetch (ISSUE 16 tentpole c) ---------------------
    # A pool-level sibling of the disagg handoff: when the router's
    # least-backlog placement loses the affinity match, the chosen
    # replica imports the matched committed prefix pages from the
    # replica that holds them instead of recomputing the prefill.  Only
    # (digest, page contents) cross — no sequences, no block tables —
    # and the imported pages land PARKED + indexed, so the request's
    # normal admission immediately match_prefix-hits them.

    def export_prefix(self, digests_hex: List[str],
                      max_pages: int = 64) -> Optional[tuple]:
        """Export the KV contents for the leading run of ``digests_hex``
        (a request's cumulative chain, root first) that this manager's
        prefix index holds.  Returns ``(meta, arrays)`` riding the same
        named-numpy-array convention as the handoff codec (quantized
        payloads travel quantized), or None on a cold index."""
        if self.prefix_cache is None or not digests_hex:
            return None
        alloc = self.kv_cache.allocator
        chain: List[tuple] = []
        for h in digests_hex[:max_pages]:
            try:
                d = bytes.fromhex(h)
            except ValueError:
                break
            p = self.prefix_cache.lookup(d)
            if p is None or not alloc.is_allocated(int(p)):
                break       # the chain is only usable contiguously
            chain.append((d, int(p)))
        if not chain:
            return None
        with trace_span("kv.export_prefix"):
            blob = self.kv_cache.read_pages([p for _, p in chain])
            arrays: Dict[str, np.ndarray] = {}
            self._pack_blob(arrays, "page_blob", blob)
            meta = {"kv": self._kv_meta(), "page_fetch": True,
                    "digests": [d.hex() for d, _ in chain]}
            return meta, arrays

    def import_prefix(self, meta: dict,
                      arrays: Dict[str, np.ndarray]) -> dict:
        """Merge a peer's exported prefix pages into this manager's
        cache as parked indexed pages.  Digests already held locally
        (device index or tier) are skipped; a pool without room raises
        the retryable :class:`KVAllocationError` BEFORE any mutation.
        Returns ``{"pages_imported", "pages_skipped"}``."""
        if self.prefix_cache is None:
            return {"pages_imported": 0, "pages_skipped": 0}
        self._check_kv_meta(meta)
        alloc = self.kv_cache.allocator
        blob = self._unpack_blob(arrays, "page_blob")
        digests = [bytes.fromhex(h) for h in meta.get("digests", [])]
        from ..snapshot import SnapshotError
        if digests and (blob is None or blob.shape[1] != len(digests)):
            raise SnapshotError(
                "page-fetch blob missing or inconsistent with digests")
        keep = []
        for i, d in enumerate(digests):
            if self.prefix_cache.lookup(d) is not None:
                continue    # already warm on device
            if self.tiers is not None and self.tiers.contains(d):
                continue    # already warm in the tier
            keep.append(i)
        if not keep:
            return {"pages_imported": 0, "pages_skipped": len(digests)}
        if len(keep) > alloc.free_pages + alloc.parked_pages:
            raise KVAllocationError(
                f"page fetch needs {len(keep)} pages, pool has "
                f"{alloc.free_pages + alloc.parked_pages} schedulable "
                "— retry after the pool drains")
        with trace_span("kv.import_prefix"):
            self.ensure_free(len(keep))
            new = self.kv_cache.restore_pages(blob_columns(blob, keep))
            imported = 0
            for i, p in zip(keep, new):
                if self.prefix_cache.insert(digests[i], int(p)):
                    self._remote_digests.add(digests[i])
                    imported += 1
                # park on success (indexed, refcount 0) / reclaim on a
                # refused insert — one shared-release path does both
                self._release_pages([int(p)])
        return {"pages_imported": imported,
                "pages_skipped": len(digests) - imported}

    # -- KV accounting ------------------------------------------------------
    def pages_needed(self, sd: SequenceDescriptor, n_new_tokens: int) -> int:
        """Extra pages required to hold ``n_new_tokens`` more tokens."""
        page = self.kv_config.page_size
        total = sd.seen_tokens + n_new_tokens
        need = -(-total // page)  # ceil
        return max(0, need - sd.allocated_capacity)

    def window_pages_needed(self, sd: SequenceDescriptor,
                            n_new_tokens: int) -> int:
        """:meth:`pages_needed` of the window group (0 without one): its
        short table has to reach the page of the last new token."""
        if self.window_cache is None:
            return 0
        last = (sd.seen_tokens + n_new_tokens - 1) \
            // self.kv_config.page_size
        return max(0, last + 1 - sd.window_base - len(sd.window_pages))

    def allocate_for(self, sd: SequenceDescriptor, n_new_tokens: int) -> None:
        extra = self.pages_needed(sd, n_new_tokens)
        extra_w = self.window_pages_needed(sd, n_new_tokens)
        if extra_w > self.free_window_pages:
            # admission reserves in both groups or in neither
            raise KVAllocationError(
                f"window group: {extra_w} pages requested, "
                f"{self.free_window_pages} free")
        need_slot = self.state_slots_needed(sd)
        if need_slot > self.free_state_slots:
            # pages and a slot, or neither
            raise KVAllocationError("state pool: no free slot")
        if extra:
            get_fault_injector().maybe_raise(
                "kv.alloc_oom", KVAllocationError,
                f"injected KV allocator OOM ({extra} pages requested)")
            self.ensure_free(extra)
            sd.extend_pages(self.kv_cache.reserve(extra))
        if extra_w:
            sd.window_pages.extend(
                int(p) for p in self.window_cache.reserve(extra_w))
        if need_slot:
            sd.state_slot = self.state_pool.reserve()

    # -- invariants (DS_KV_DEBUG) -------------------------------------------
    def check_invariants(self) -> None:
        """O(live pages) page-accounting audit:
        ``free + live + parked == total``, every block-table reference
        is backed by exactly one allocator ref, every parked page is
        still prefix-cache indexed, and the offloaded-host-blob
        counters match the tracked descriptors (a preempted request's
        expiry must release its blob, ISSUE 8).  Raises RuntimeError on
        violation — wired into FastGenScheduler.step under
        ``DS_KV_DEBUG=1`` so scheduler changes can't silently leak or
        double-use pages."""
        alloc = self.kv_cache.allocator
        refs = Counter()
        for sd in self._seqs.values():
            for p in sd.pages:
                if p != NULL_PAGE:
                    refs[p] += 1
        for p, n in refs.items():
            if not alloc.is_allocated(p):
                raise RuntimeError(
                    f"KV invariant: page {p} is in a block table but on "
                    "the free list")
            if alloc.ref_count(p) != n:
                raise RuntimeError(
                    f"KV invariant: page {p} has allocator refcount "
                    f"{alloc.ref_count(p)} but appears in {n} block "
                    "tables")
        live, parked = alloc.live_pages, alloc.parked_pages
        if live != len(refs):
            raise RuntimeError(
                f"KV invariant: allocator sees {live} live pages, block "
                f"tables reference {len(refs)}")
        if alloc.free_pages + live + parked != alloc.total_pages:
            raise RuntimeError(
                f"KV invariant: free({alloc.free_pages}) + live({live}) "
                f"+ cached({parked}) != total({alloc.total_pages})")
        if self.window_cache is not None:
            # the window group: every page of a window table is held
            # once (nothing of it is shared, parked or indexed), a table
            # never ends short of the sequence's committed tokens, and
            # it holds no page wholly under the window
            walloc = self.window_cache.allocator
            page = self.kv_config.page_size
            held = Counter(p for sd in self._seqs.values()
                           for p in sd.window_pages)
            for p, n in held.items():
                if n != 1 or p == NULL_PAGE or not walloc.is_allocated(p) \
                        or walloc.ref_count(p) != 1:
                    raise RuntimeError(
                        f"KV invariant: window-group page {p} is in {n} "
                        "window tables or not held once by the allocator")
            if walloc.live_pages != len(held) or walloc.parked_pages \
                    or walloc.free_pages + len(held) != walloc.total_pages:
                raise RuntimeError(
                    f"KV invariant: window group free"
                    f"({walloc.free_pages}) + tables({len(held)}) != "
                    f"total({walloc.total_pages}), or parked pages")
            for sd in self._seqs.values():
                if sd.window_blob is not None or not sd.seen_tokens:
                    continue
                end = (sd.window_base + len(sd.window_pages)) * page
                if end < sd.seen_tokens or sd.window_base * page \
                        > max(sd.seen_tokens - self.window + 1, 0):
                    raise RuntimeError(
                        f"KV invariant: sequence {sd.uid}'s window table "
                        f"covers [{sd.window_base * page}, {end}) with "
                        f"{sd.seen_tokens} tokens committed")
        if self.state_pool is not None:
            # the state pool: no slot twice, none lost, none held beside
            # its own host copy, and every started sequence on the device
            # has one
            pool = self.state_pool
            held = Counter(sd.state_slot for sd in self._seqs.values()
                           if sd.state_slot >= 0)
            for slot, n in held.items():
                if n != 1 or not pool.is_held(slot):
                    raise RuntimeError(
                        f"KV invariant: state slot {slot} is held by {n} "
                        "sequences or not reserved in the pool")
            if pool.held_slots != len(held) \
                    or pool.free_slots + len(held) != pool.cfg.num_slots:
                raise RuntimeError(
                    f"KV invariant: state pool free({pool.free_slots}) + "
                    f"sequences' slots({len(held)}) != "
                    f"total({pool.cfg.num_slots}) (a slot lost?)")
            for sd in self._seqs.values():
                if sd.state_blob is not None and sd.state_slot >= 0:
                    raise RuntimeError(
                        f"KV invariant: sequence {sd.uid} holds a state "
                        "slot and its host copy at once")
                if sd.seen_tokens and sd.state_slot < 0 \
                        and sd.state_blob is None:
                    raise RuntimeError(
                        f"KV invariant: sequence {sd.uid} has "
                        f"{sd.seen_tokens} tokens committed and no state")
        blobs = [sd.host_blob for sd in self._seqs.values()
                 if sd.host_blob is not None] + [
                     sd.window_blob for sd in self._seqs.values()
                     if sd.window_blob is not None] + [
                     sd.state_blob for sd in self._seqs.values()
                     if sd.state_blob is not None]
        blob_bytes = sum(b.nbytes for b in blobs)
        if (len(blobs) != self._offload_blobs
                or blob_bytes != self._offload_bytes):
            raise RuntimeError(
                f"KV invariant: offloaded-blob accounting drift — "
                f"counters say {self._offload_blobs} blobs / "
                f"{self._offload_bytes} bytes, descriptors hold "
                f"{len(blobs)} / {blob_bytes} (a flushed preempted "
                "sequence leaked its host blob?)")
        if parked:
            if self.prefix_cache is None:
                raise RuntimeError(
                    f"KV invariant: {parked} parked pages with prefix "
                    "caching off")
            indexed = set(self.prefix_cache.pages())
            for p in alloc.parked_page_ids():
                if int(p) not in indexed:
                    raise RuntimeError(
                        f"KV invariant: parked page {int(p)} is not "
                        "prefix-cache indexed (leaked)")
        if self.tiers is not None:
            # tier accounting (ISSUE 16): host + disk + inflight ==
            # indexed, caps respected, disk entries' files present —
            # and nothing can be both device-indexed and tier-resident
            # (a digest demotes only on eviction, promotes only on a
            # device miss)
            self.tiers.check_invariants()
            if self.prefix_cache is not None:
                for d, _ in self.prefix_cache.export_entries():
                    if self.tiers.contains(d) is not None:
                        raise RuntimeError(
                            "KV invariant: digest indexed on device AND "
                            "tier-resident (double-held prefix "
                            f"{d.hex()})")
