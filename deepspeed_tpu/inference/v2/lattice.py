"""Mined bucket lattices (ISSUE 14 tentpole 2).

``analyze_trace`` (ISSUE 9) mines a workload trace's step-key occupancy
and recommends quantile-fitted bucket boundaries; this module closes the
loop it left open.  A :class:`BucketLattice` carries **non-power-of-two
bucket tops** for the S (slots), Q (tokens/row) and P (pages/row)
dimensions plus the precompile key set enumerated over them, so an
engine built with ``serving_optimization.lattice = "auto:<path>"``
buckets live batches to the tops traffic actually needs — tokenwise
identical to the power-of-two default (padding never changes tokens),
with fewer wasted pad rows and a smaller compiled program set.

The on-disk **lattice artifact** (``analyze_trace --emit-lattice``) is a
versioned JSON document::

    {"kind": "ds_lattice", "version": 1,
     "config_digest": "<blake2b over (page_size, vocab_size)>",
     "page_size": ..., "vocab_size": ..., "has_fresh": ...,
     "s_buckets": [...], "q_buckets": [...], "p_buckets": [...],
     "keys": [[S, Q, P, fresh, ...], ...],
     "source": "<trace path>", "requests": N, "dispatches": N}

``resolve_lattice`` validates the digest against the consuming engine's
own geometry and refuses a mismatch with a structured
:class:`LatticeError` — never a silent cold lattice.  ``auto:<path>``
accepts either an artifact (JSON, mined once and checked in) or a raw
workload-trace JSONL ledger (mined on the fly at engine build).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from typing import Any, Dict, List, Sequence, Tuple

from .ragged.batch import MIN_PAGES, MIN_SLOTS, _bucket
from .step_key import StepKey

LATTICE_ARTIFACT_VERSION = 1
LATTICE_ARTIFACT_KIND = "ds_lattice"


class LatticeError(ValueError):
    """A lattice artifact could not be loaded or does not match the
    consuming engine (wrong kind/version, undecodable file, or a
    config-digest mismatch).  Engine build fails loudly — serving on a
    silently-wrong lattice would re-pay every compile on the request
    path, exactly the cold start the artifact exists to prevent."""


def lattice_config_digest(page_size: int, vocab_size: int) -> str:
    """Digest of the geometry facts a lattice is only valid under —
    computed identically at mine time (from the trace meta) and at load
    time (from the engine), so a mismatch is mechanical to detect.
    Page size changes every P bucket's meaning; vocab size changes the
    compiled programs themselves."""
    facts = json.dumps({"page_size": int(page_size),
                        "vocab_size": int(vocab_size)}, sort_keys=True)
    return hashlib.blake2b(facts.encode("utf-8"),
                           digest_size=8).hexdigest()


def lattice_content_digest(doc: Dict[str, Any]) -> str:
    """Identity digest of one PARTICULAR lattice — geometry digest plus
    the bucket tops and key set.  This (not the geometry digest) is
    what a snapshot bundle records and ``restore()`` compares: two
    lattices mined from different traces on the SAME geometry share a
    config digest but are differently bucketed, and precompiling one's
    manifest on the other's engine would compile programs the live
    bucketing never dispatches.  It also namespaces the persistent
    compile cache per lattice content."""
    facts = json.dumps({
        "config": str(doc.get("config_digest", "")),
        "s": list(doc.get("s_buckets", [])),
        "q": list(doc.get("q_buckets", [])),
        "p": list(doc.get("p_buckets", [])),
        "keys": sorted(map(repr, doc.get("keys", []))),
    }, sort_keys=True)
    return hashlib.blake2b(facts.encode("utf-8"),
                           digest_size=8).hexdigest()


def fit_buckets(lengths: Sequence[int], ratio: float = 1.3,
                max_buckets: int = 12, floor: int = 1) -> List[int]:
    """Quantile-style bucket tops fit to an observed length
    distribution: greedily group sorted distinct lengths so every
    length maps to a top within ``ratio``x of itself (each bucket's
    top is the LARGEST observed length it covers — zero overshoot at
    the top, bounded overshoot at the bottom).  When that needs more
    than ``max_buckets`` buckets, the ratio widens until it fits.  A
    bimodal distribution gets tops at the modes, not at the enclosing
    powers of two."""
    # a ratio <= 1 can never merge (and the widening step below can't
    # grow a non-positive one) — floor it instead of hanging
    ratio = max(float(ratio), 1.001)
    vals = sorted({max(int(v), floor) for v in lengths})
    if not vals:
        return []
    while True:
        buckets: List[int] = []
        i = 0
        while i < len(vals):
            lo = vals[i]
            j = i
            while j + 1 < len(vals) and vals[j + 1] <= lo * ratio:
                j += 1
            buckets.append(vals[j])
            i = j + 1
        if len(buckets) <= max_buckets:
            return buckets
        ratio *= 1.25


def _pick(n: int, tops: Tuple[int, ...], floor: int) -> int:
    """Smallest lattice top >= n; traffic past the largest top falls
    back to power-of-two growth — still correct (padding is padding),
    just an off-lattice key the watchdog will name."""
    n = max(int(n), 1)
    for t in tops:
        if t >= n:
            return t
    return _bucket(n, floor)


def enumerate_lattice_keys(s_vals: Sequence[int], q_vals: Sequence[int],
                           p_vals: Sequence[int], *, page_size: int,
                           max_ragged_batch_size: int, has_fresh: bool,
                           sampling: bool, spec_q: int = 0,
                           draft: bool = False) -> List[Tuple]:
    """Every (S, Q, P[, fresh[, kind, ...]]) step-cache key the bucket
    lattice over the given dimension tops contains — the ONE
    enumeration behind both the power-of-two default
    (``engine.lattice_keys`` builds power lists and delegates here) and
    a mined :class:`BucketLattice` (arbitrary tops), so the two can
    never drift on the key-family rules (fresh variants, chain
    cross-products, the spec bucket).  ``spec_q`` is the
    ALREADY-BUCKETED speculative Q width (0 = no spec keys).
    ``draft`` adds the model-drafted families (ISSUE 17): a
    "draft_spec" twin of every spec key (the device-resident draft
    loop + verify program) and a "draft_fill" twin of every plain
    logits key (the draft-KV catch-up forward — it chunk-buckets
    exactly like prefill, so it rides the same (S, Q, P) grid)."""
    s_vals = sorted({int(s) for s in s_vals})
    q_vals = sorted({int(q) for q in q_vals} | {1})
    p_vals = sorted({int(p) for p in p_vals})
    keys: List[Tuple] = []
    for S in s_vals:
        for Q in q_vals:
            if S * Q > max_ragged_batch_size:
                continue
            for P in p_vals:
                if P * page_size < Q:  # bucket can't hold its own tokens
                    continue
                # Q>1 buckets exist in both variants: fresh prefill
                # (flash path) and continued prefill (paged path) — but
                # only when the model HAS a fresh implementation (ALiBi
                # models ignore the flag; compiling the True variant
                # would duplicate every prefill executable)
                for fresh in ((False, True) if Q > 1 and has_fresh
                              else (False,)):
                    shape = (S, Q, P, fresh)
                    keys.append(StepKey.logits(shape))
                    if draft and not fresh:
                        # catch-up writes paged draft KV — never fresh
                        keys.append(StepKey.draft_fill(shape))
                    if not sampling:
                        continue
                    for greedy in (True, False):
                        keys.append(StepKey.sample(shape, greedy))
                        if Q == 1 and not fresh:
                            # double-buffer chain: the previous step's
                            # slot bucket can only be >= this one's
                            # (chained rows are a subset of the
                            # previous step's rows)
                            for prev_s in s_vals:
                                if prev_s < S:
                                    continue
                                keys.append(StepKey.chain(shape, prev_s,
                                                          greedy))
    if sampling and spec_q > 0:
        for S in s_vals:
            if S * spec_q > max_ragged_batch_size:
                continue
            for P in p_vals:
                if P * page_size < spec_q:
                    continue
                shape = (S, spec_q, P, False)
                for greedy in (True, False):
                    keys.append(StepKey.spec(shape, greedy))
                    if draft:
                        keys.append(StepKey.draft_spec(shape, greedy))
    return keys


@dataclasses.dataclass(frozen=True)
class BucketLattice:
    """Bucket tops + precompile key set an engine serves under: THE
    bucket rule.  :meth:`shape` is what ``build_batch`` and
    ``predict_step_key`` bucket a batch with and :meth:`bucket_s` what
    the mixed step pads its token vector with — keeping bucketing and
    the precompiled key set derived from the SAME tops is what makes
    ``compile_on_path == 0`` hold by construction.  A lattice with no
    tops (:data:`POWER_LATTICE`, the default) buckets every dimension
    to a power of two over its floor, which is also where a mined
    lattice sends traffic past its largest top."""
    s_tops: Tuple[int, ...]
    q_tops: Tuple[int, ...]
    p_tops: Tuple[int, ...]
    keys: Tuple[Tuple, ...] = ()
    digest: str = ""
    source: str = ""
    has_fresh: bool = True

    def __post_init__(self):
        object.__setattr__(self, "s_tops", tuple(sorted(
            {max(int(s), MIN_SLOTS) for s in self.s_tops})))
        object.__setattr__(self, "q_tops", tuple(sorted(
            {int(q) for q in self.q_tops} | {1})))
        object.__setattr__(self, "p_tops", tuple(sorted(
            {max(int(p), MIN_PAGES) for p in self.p_tops})))

    @property
    def mined(self) -> bool:
        """Whether the tops (and ``keys``, the precompile target) come
        from observed traffic; False: the power-of-two default."""
        return bool(self.s_tops)

    def bucket_s(self, n: int) -> int:
        return _pick(n, self.s_tops, MIN_SLOTS)

    def bucket_q(self, n: int) -> int:
        return _pick(n, self.q_tops, 1)

    def bucket_p(self, n: int) -> int:
        return _pick(n, self.p_tops, MIN_PAGES)

    def shape(self, rows: int, max_q: int, max_pages: int,
              min_q: int = 1) -> Tuple[int, int, int]:
        """The bucketed ``(S, Q, P)`` of a batch of ``rows`` sequences,
        the longest bringing ``max_q`` new tokens and the largest
        holding ``max_pages`` pages.  ``min_q`` floors the Q bucket:
        speculative steps pad every dispatch to the ONE ``1 +
        spec_max_draft`` bucket, so that a short-draft step cannot form
        a smaller off-lattice key."""
        return (self.bucket_s(rows), self.bucket_q(max(max_q, min_q)),
                self.bucket_p(max_pages))


#: the default: no mined tops, every dimension a power of two
POWER_LATTICE = BucketLattice(s_tops=(), q_tops=(), p_tops=())


def _mined_lattice(s_tops, q_tops, p_tops, **facts) -> BucketLattice:
    """A lattice from an artifact's or a trace's tops, which must name
    at least one S and one P bucket."""
    lat = BucketLattice(s_tops=tuple(s_tops), q_tops=tuple(q_tops),
                        p_tops=tuple(p_tops), **facts)
    if not (lat.s_tops and lat.p_tops):
        raise LatticeError(
            "lattice needs at least one S and one P bucket top "
            f"(got s={lat.s_tops}, p={lat.p_tops})")
    return lat


def _prune_q_tops(tops: List[int], ratio: float, s_tops: List[int],
                  p_tops: List[int], page_size: int,
                  batch: int) -> List[int]:
    """Drop Q tops the next kept top already covers within ``ratio``,
    PROVIDED every (S, P) combination feasible for the dropped top
    stays feasible for its successor (S*Q <= batch and P*page >= Q are
    the enumeration's inclusion rules — a drop that pushed a formable
    key across either boundary would turn a covered chunk length into
    an on-path compile).  Q=1 (decode) is never dropped."""
    ratio = max(float(ratio), 1.0)
    kept: List[int] = []
    for t in sorted(tops, reverse=True):
        if t == 1 or not kept:
            kept.append(t)
            continue
        u = kept[-1]            # smallest top kept so far above t
        safe = (u <= t * ratio
                and all(s * u <= batch for s in s_tops
                        if s * t <= batch)
                and all(p * page_size >= u for p in p_tops
                        if p * page_size >= t))
        if not safe:
            kept.append(t)
    return sorted(kept)


def mine_lattice(trace: Dict[str, Any], ratio: float = 1.3,
                 max_buckets: int = 12,
                 max_ragged_batch_size: int = 768,
                 source: str = "") -> Dict[str, Any]:
    """Build a lattice artifact from a loaded workload trace
    (``{"meta", "requests", "compiles", "key_counts"}`` — the
    ``replay_trace.load_trace`` / :func:`load_trace_facts` shape).

    Dimension tops: S and P keep the OBSERVED bucket values exactly
    (they are powers of two from capture, and picking the smallest
    observed top >= n reproduces capture-time bucketing bit-for-bit —
    the tokenwise-identity half of the claim), while Q gets the
    quantile-fitted tops over the recorded prompt lengths (the
    fewer-wasted-pad-rows half: a 17-token prompt pads to the 17 top,
    not to 32).  The key set is the full enumeration over those tops
    plus the observed mixed-step keys expanded across the fitted Q tops
    (mixed keys are never cross-product-enumerated — two geometries —
    so the observed combinations seed them)."""
    meta = trace.get("meta", {})
    requests = trace.get("requests", [])
    page = int(meta.get("page_size", 16) or 16)
    vocab = int(meta.get("vocab_size", 0) or 0)

    try:
        occ: Dict[StepKey, int] = {
            StepKey.parse(k): int(n)
            for k, n in trace.get("key_counts", {}).items()}
        for k in trace.get("compiles", []):
            occ.setdefault(StepKey.parse(k), 1)
    except ValueError as e:
        raise LatticeError(f"trace {source or '<in memory>'}: {e}")
    if not occ and not requests:
        raise LatticeError(
            "trace has no step-key occupancy and no requests — nothing "
            "to mine a lattice from")

    s_set, p_set, q_obs, spec_draft = set(), set(), set(), 0
    mixed_combos = set()
    fresh_seen = False
    draft_seen = False
    for k in occ:
        s_set.add(k.S)
        p_set.add(k.P)
        fresh_seen = fresh_seen or k.fresh
        if k.kind == "chain":
            s_set.add(k.prev_len)
        elif k.kind in ("spec", "draft_spec"):
            spec_draft = max(spec_draft, k.Q - 1)
            draft_seen = draft_seen or k.kind == "draft_spec"
        elif k.kind == "draft_fill":
            q_obs.add(k.Q)
            draft_seen = True
        elif k.kind == "mixed":
            S_p, Q_p, P_p, fresh_p = k.prefill
            s_set.add(S_p)
            p_set.add(P_p)
            q_obs.add(Q_p)
            mixed_combos.add((k.S, k.P, S_p, P_p, fresh_p, k.greedy))
        else:
            q_obs.add(k.Q)

    prompt_lens = [int(r["prompt_len"]) for r in requests]
    if not s_set:
        # occupancy-free trace (requests only): no observed bucketing
        # to reproduce — power tops up to the request count (capped)
        s = _bucket(1, MIN_SLOTS)
        top = min(_bucket(max(len(requests), 1), MIN_SLOTS), 512)
        while s <= top:
            s_set.add(s)
            s *= 2
    if not p_set:
        total = max((int(r["prompt_len"]) + int(r.get("gen_len", 0))
                     for r in requests), default=page)
        p_set = {_bucket(-(-total // page), MIN_PAGES)}
    # Q tops: the quantile fit over full prompt lengths UNION the
    # observed Q bucket values, then ratio-pruned.  The fit alone is a
    # trap: a budget-limited prompt chunks to <= max_ragged_batch_size
    # tokens, and if the only covering fitted top is the (huge)
    # full-prompt length, the formed S*Q key is excluded by the
    # batch-size rule and compiles on path — the observed (power)
    # values guarantee every intermediate chunk length a covered top.
    # The union then carries near-duplicates (a fitted 66 next to an
    # observed 64), so a top is pruned when the next kept top covers
    # it within ``ratio`` AND stays feasible for every mined (S, P) —
    # coverage is exact by construction, padding overshoot stays
    # ratio-bounded, and the enumerated set shrinks back below the
    # power lattice's
    q_union = sorted(set(fit_buckets(prompt_lens, ratio=ratio,
                                     max_buckets=max_buckets))
                     | q_obs | {1})
    q_tops = _prune_q_tops(q_union, ratio, sorted(s_set), sorted(p_set),
                           page, max_ragged_batch_size)

    lat = _mined_lattice(s_set, q_tops, p_set, has_fresh=fresh_seen)
    spec_q = lat.bucket_q(1 + spec_draft) if spec_draft else 0
    keys = enumerate_lattice_keys(
        lat.s_tops, lat.q_tops, lat.p_tops, page_size=page,
        max_ragged_batch_size=max_ragged_batch_size,
        has_fresh=fresh_seen, sampling=True, spec_q=spec_q,
        draft=draft_seen)
    # mixed expansion: fitted Q tops re-bucket prompt chunks, so each
    # observed mixed combination fans out across every fitted Q_p the
    # replayed chunking could now form
    for (sd, pd, sp, pp, fresh_p, greedy) in sorted(mixed_combos):
        for q in lat.q_tops:
            if q <= 1 or sd + sp * q > max_ragged_batch_size * 2:
                continue
            keys.append(StepKey.mixed((sd, 1, pd, False),
                                      (sp, q, pp, fresh_p), greedy))

    return {
        "kind": LATTICE_ARTIFACT_KIND,
        "version": LATTICE_ARTIFACT_VERSION,
        "config_digest": lattice_config_digest(page, vocab),
        "page_size": page,
        "vocab_size": vocab,
        # the budget the enumeration's S*Q skip rule ran under: an
        # engine with a LARGER budget can form keys this artifact
        # excluded at mine time, so resolve_lattice refuses that
        # pairing (keys excluded here are invisible to the engine-side
        # filters — they only ever remove)
        "max_ragged_batch_size": int(max_ragged_batch_size),
        "has_fresh": fresh_seen,
        "s_buckets": list(lat.s_tops),
        "q_buckets": list(lat.q_tops),
        "p_buckets": list(lat.p_tops),
        "keys": [list(k) for k in keys],
        "source": source,
        "requests": len(requests),
        "dispatches": sum(occ.values()),
    }


def load_trace_facts(path: str) -> Dict[str, Any]:
    """The ONE workload-trace JSONL parser: engine-side
    ``auto:<trace.jsonl>`` mining reads through it, and
    ``tools/replay_trace.load_trace`` delegates here (the engine can't
    import ``tools/``; tools import this package — one parser, one
    place to learn a new record kind)."""
    meta: Dict[str, Any] = {}
    requests: List[Dict[str, Any]] = []
    compiles: List[list] = []
    key_counts: Dict[tuple, int] = {}
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                rec = json.loads(line)
                kind = rec.get("kind")
                if kind == "meta" and not meta:
                    meta = rec
                elif kind == "request":
                    requests.append(rec)
                elif kind == "compile":
                    compiles.append(rec["key"])
                elif kind == "keys":
                    for key, n in rec["counts"]:
                        key_counts[tuple(key)] = (
                            key_counts.get(tuple(key), 0) + int(n))
    except OSError as e:
        raise LatticeError(f"cannot read workload trace {path}: {e}")
    except ValueError as e:
        raise LatticeError(f"{path} is not a workload-trace JSONL "
                           f"ledger: {e}")
    return {"meta": meta, "requests": requests, "compiles": compiles,
            "key_counts": key_counts}


def write_artifact(artifact: Dict[str, Any], path: str) -> str:
    with open(path, "w") as f:
        json.dump(artifact, f, indent=1)
        f.write("\n")
    return path


def _validate_artifact(doc: Any, path: str) -> Dict[str, Any]:
    if not isinstance(doc, dict) or doc.get("kind") != LATTICE_ARTIFACT_KIND:
        raise LatticeError(
            f"{path} is not a lattice artifact (kind="
            f"{doc.get('kind') if isinstance(doc, dict) else type(doc)!r})")
    if doc.get("version") != LATTICE_ARTIFACT_VERSION:
        raise LatticeError(
            f"unsupported lattice artifact version {doc.get('version')!r} "
            f"in {path} (this build reads {LATTICE_ARTIFACT_VERSION})")
    for field in ("config_digest", "page_size", "vocab_size",
                  "max_ragged_batch_size", "s_buckets", "q_buckets",
                  "p_buckets", "keys"):
        if field not in doc:
            raise LatticeError(
                f"lattice artifact {path} is missing {field!r}")
    # a truncated/hand-edited key would otherwise surface as a raw
    # IndexError deep inside engine precompile
    for i, key in enumerate(doc["keys"]):
        try:
            StepKey.parse(key)
        except ValueError as e:
            raise LatticeError(
                f"lattice artifact {path}: keys[{i}] = {key!r} is not "
                f"a valid (S, Q, P, fresh[, kind, ...]) step-cache key "
                f"({e})")
    return doc


def load_artifact(path: str) -> Dict[str, Any]:
    """Read + validate a lattice artifact; :class:`LatticeError` on
    anything less than a complete, version-matched document."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except OSError as e:
        raise LatticeError(f"cannot read lattice artifact {path}: {e}")
    except ValueError as e:
        raise LatticeError(f"{path} is not a JSON lattice artifact: {e}")
    return _validate_artifact(doc, path)


def _lattice_from_artifact(doc: Dict[str, Any],
                           source: str) -> BucketLattice:
    return _mined_lattice(
        doc["s_buckets"], doc["q_buckets"], doc["p_buckets"],
        keys=tuple(StepKey.parse(k) for k in doc["keys"]),
        # identity, not just geometry: two lattices mined on the same
        # (page, vocab) from different traces must NOT compare equal
        digest=lattice_content_digest(doc),
        source=source,
        has_fresh=bool(doc.get("has_fresh", True)))


def resolve_lattice(spec: str, *, page_size: int, vocab_size: int,
                    max_ragged_batch_size: int = 768
                    ) -> BucketLattice:
    """Resolve a ``serving_optimization.lattice`` spec at engine build.

    ``""`` -> :data:`POWER_LATTICE` (the default).  ``"auto:<path>"`` loads
    a lattice artifact (JSON) or mines one on the fly from a raw
    workload-trace ledger (JSONL), then validates the artifact's config
    digest against THIS engine's (page_size, vocab_size) — a mismatch
    raises :class:`LatticeError` naming both sides, never a silent
    cold lattice."""
    spec = (spec or "").strip()
    if not spec:
        return POWER_LATTICE
    if not spec.startswith("auto:"):
        raise LatticeError(
            f"unknown lattice spec {spec!r} (expected \"\" for the "
            "power-of-two default or \"auto:<artifact-or-trace-path>\")")
    path = spec[len("auto:"):]
    if not path or not os.path.exists(path):
        raise LatticeError(
            f"lattice spec {spec!r}: no such file {path!r}")
    # an artifact is ONE JSON object with our kind marker; anything
    # else (a JSONL ledger parses line-wise, not as one document) is
    # treated as a raw trace and mined on the fly
    is_artifact = False
    try:
        with open(path) as f:
            doc = json.load(f)
        is_artifact = (isinstance(doc, dict)
                       and doc.get("kind") == LATTICE_ARTIFACT_KIND)
    except OSError as e:
        raise LatticeError(f"cannot read {path}: {e}")
    except ValueError:
        pass        # not a single JSON document -> try the ledger path
    if is_artifact:
        doc = _validate_artifact(doc, path)   # already parsed once
    else:
        doc = mine_lattice(load_trace_facts(path),
                           max_ragged_batch_size=max_ragged_batch_size,
                           source=path)
    want = lattice_config_digest(page_size, vocab_size)
    have = str(doc["config_digest"])
    if have != want:
        raise LatticeError(
            f"lattice artifact {path} was mined under config digest "
            f"{have} (page_size={doc.get('page_size')}, "
            f"vocab_size={doc.get('vocab_size')}) but this engine's "
            f"digest is {want} (page_size={page_size}, "
            f"vocab_size={vocab_size}) — re-mine with "
            "tools/analyze_trace.py --emit-lattice from a trace "
            "captured on this geometry (refusing a silent cold lattice)")
    mined_batch = int(doc.get("max_ragged_batch_size", 0) or 0)
    if mined_batch and mined_batch < max_ragged_batch_size:
        raise LatticeError(
            f"lattice artifact {path} was mined under "
            f"max_ragged_batch_size={mined_batch} but this engine runs "
            f"{max_ragged_batch_size} — keys the larger budget can "
            "form were excluded at mine time and would compile on the "
            "request path; re-mine with analyze_trace --emit-lattice "
            f"--batch-size {max_ragged_batch_size} (or larger)")
    return _lattice_from_artifact(doc, source=path)
