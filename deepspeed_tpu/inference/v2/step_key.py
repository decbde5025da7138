"""The step program's identity: the key's layout and the table of step kinds.

A served step is ONE compiled program, named by a :class:`StepKey`: the
bucketed shape ``(S, Q, P, fresh)`` of its batch, then for every kind but
the plain forward the kind's name and the kind's own fields.  This module
is the only place that knows that layout and what a kind's program is
(:data:`STEP_KINDS`); everything else forms a key through a constructor,
reads one through the named readers, and asks the table for the rest.
``docs/DESIGN.md`` ("The step program's key") has the table in prose.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

#: one segment's bucketed shape, ``RaggedBatch.shape_key``: slots, tokens
#: a row, pages a row, and whether every row starts at position 0
Shape = Tuple[int, int, int, bool]

#: key classes a role-shrunk lattice filters on (ISSUE 13): "prefill"
#: = Q>1 logits/sample buckets (incl. fresh variants), "decode" = Q==1
#: logits/sample buckets, "chain" = the double-buffer continuation
#: family, "spec" = the speculative families (verification buckets
#: plus the ISSUE 17 model-drafted draft_spec/draft_fill programs —
#: speculation is a decode-pool activity, so they class together)
LATTICE_KINDS = ("prefill", "decode", "chain", "spec")

_BOOL_FIELDS = frozenset({"fresh", "fresh_p", "greedy"})


def window_slots(window: int, page_size: int, Q: int) -> int:
    """Slots of the window group's table (a model with two page groups)
    in a segment of ``Q`` tokens a row: the pages a row can hold live,
    from the page of the first position its first new token attends
    (``seen - window + 1``) to the page of its last new token, in whole
    groups of 8 slots (the attention kernel's step).  It follows from the
    key's ``Q`` alone, so the second table's bucket is no field of the
    key: 16 slots for a decode row and for a 128-token chunk at a window
    of 512 and pages of 64."""
    return -(-((window + Q - 2) // page_size + 2) // 8) * 8


class StepKey(tuple):
    """A step-cache key.  Its VALUE is the bare tuple it always was —
    ``StepKey.chain((64, 1, 8, False), 64, True) == (64, 1, 8, False,
    "chain", 64, True)``, hashes alike, ``json.dumps`` gives the same
    list and ``repr`` is ``tuple``'s (compiled-key manifests, lattice
    artifacts, the benchmark's hints and ``compiled_keys()``'s order all
    hold that form) — so a bare tuple finds a ``StepKey`` in a dict and
    the other way round."""

    __slots__ = ()

    # -- one constructor a kind ---------------------------------------------
    @classmethod
    def logits(cls, shape: Shape) -> "StepKey":
        return cls(shape)

    @classmethod
    def sample(cls, shape: Shape, greedy: bool) -> "StepKey":
        return cls((*shape, "sample", bool(greedy)))

    @classmethod
    def chain(cls, shape: Shape, prev_len: int, greedy: bool) -> "StepKey":
        """``prev_len``: the previous step's token vector without its
        ``step_tail``, i.e. the slot bucket that step sampled into."""
        S, Q, P, _ = shape
        assert Q == 1, "chained steps are decode-only"
        return cls((S, 1, P, False, "chain", int(prev_len), bool(greedy)))

    @classmethod
    def spec(cls, shape: Shape, greedy: bool) -> "StepKey":
        # spec rows always have history: never the fresh variant
        return cls((*shape[:3], False, "spec", bool(greedy)))

    @classmethod
    def draft_spec(cls, shape: Shape, greedy: bool) -> "StepKey":
        return cls((*shape[:3], False, "draft_spec", bool(greedy)))

    @classmethod
    def draft_fill(cls, shape: Shape) -> "StepKey":
        # the catch-up writes paged draft KV: never the fresh variant
        return cls((*shape[:3], False, "draft_fill"))

    @classmethod
    def mixed(cls, decode: Shape, prefill: Shape, greedy: bool) -> "StepKey":
        assert decode[1] == 1, "segment A of a mixed step is decode-only"
        return cls((*decode, "mixed", *prefill, bool(greedy)))

    @classmethod
    def form(cls, kind: str, shapes: Sequence[Shape], greedy: bool = False,
             prev_len: int = 0) -> "StepKey":
        """The key of ``kind`` over its segments' shapes (two of a mixed
        key, else one) — the one call behind the live dispatch and
        ``predict_step_key``.  ``greedy`` / ``prev_len`` are read only by
        the kinds that carry them."""
        row = STEP_KINDS[kind]
        args: List[Any] = list(shapes)
        if row.chained:
            args.append(prev_len)
        if row.samples:
            args.append(greedy)
        return getattr(cls, kind)(*args)

    @classmethod
    def parse(cls, seq: Sequence) -> "StepKey":
        """A key from outside the program (a JSON manifest, a lattice
        artifact, a trace, a test's tuple): ``ValueError`` unless it
        names a kind and has that kind's fields, each of its type."""
        if type(seq) is cls:
            return seq
        try:
            key = cls(seq)
        except TypeError:
            raise ValueError(f"{seq!r} is not a step-cache key") from None
        n = len(key)
        kind = key[4] if n > 4 else "logits"
        row = STEP_KINDS.get(kind) if isinstance(kind, str) else None
        # the plain forward's key is its shape alone, with no name
        if row is None or n != (4 if kind == "logits"
                                else 5 + len(row.fields)):
            raise ValueError(
                f"{tuple(key)!r} is not a valid (S, Q, P, fresh[, kind, "
                f"...]) step-cache key (kinds: {sorted(STEP_KINDS)})")
        names = ("S", "Q", "P", "fresh") + row.fields
        for name, v in zip(names, key[:4] + key[5:]):
            ok = (type(v) is bool if name in _BOOL_FIELDS
                  else isinstance(v, int) and type(v) is not bool and v >= 1)
            if not ok:
                raise ValueError(
                    f"step-cache key {tuple(key)!r}: {name}={v!r} is not "
                    f"a {'bool' if name in _BOOL_FIELDS else 'count'}")
        return key

    # -- readers ------------------------------------------------------------
    @property
    def S(self) -> int:
        return self[0]

    @property
    def Q(self) -> int:
        return self[1]

    @property
    def P(self) -> int:
        return self[2]

    @property
    def kind(self) -> str:
        return self[4] if len(self) > 4 else "logits"

    def _field(self, name: str):
        fields = STEP_KINDS[self.kind].fields
        return self[5 + fields.index(name)] if name in fields else None

    @property
    def fresh(self) -> bool:
        """The fresh-prefill flag of the segment that can have one: the
        prefill segment's of a mixed key, else the batch's."""
        return self[3] if self.kind != "mixed" else self._field("fresh_p")

    @property
    def greedy(self) -> Optional[bool]:
        return self._field("greedy")

    @property
    def prev_len(self) -> Optional[int]:
        return self._field("prev_len")

    @property
    def decode(self) -> Shape:
        """A mixed key's first segment."""
        return self[:4]

    @property
    def prefill(self) -> Shape:
        """A mixed key's second segment."""
        assert self.kind == "mixed"
        return self[5:9]

    @property
    def padded_tokens(self) -> int:
        """Token positions the program computes, padding included (what
        the enumeration holds against the batch budget)."""
        if self.kind != "mixed":
            return self.S * self.Q
        S_p, Q_p = self.prefill[:2]
        return self.S + S_p * Q_p

    def with_fresh(self, fresh: bool) -> "StepKey":
        """The same key with :attr:`fresh` set."""
        at = (3 if self.kind != "mixed" else
              5 + STEP_KINDS["mixed"].fields.index("fresh_p"))
        return StepKey(self[:at] + (bool(fresh),) + self[at + 1:])


@dataclasses.dataclass(frozen=True)
class StepKind:
    """One row of :data:`STEP_KINDS`: what the program of a kind is,
    beside its shapes."""
    #: the key's elements after the kind's name.  Their names decide the
    #: program's operands after ``(params, kv)``: always one segment's
    #: ``token_ids, q_lens, start_pos, page_table``; with ``prev_len``
    #: the previous step's tokens and a gather index take ``token_ids``'
    #: place; with ``S_p`` a second segment follows; with ``greedy`` the
    #: sampling operands close the list (:func:`step_avals`)
    fields: Tuple[str, ...]
    #: the model's traced function ...
    impl: str
    #: ... and its static keyword arguments: (argument, key reader)
    statics: Tuple[Tuple[str, str], ...] = ()
    #: whose weights and whose pool the program takes as ``(params, kv)``,
    #: the pool donated and returned: "target", "draft", or "pair" (a
    #: ``{"target", "draft"}`` dict and a ``(target, draft)`` tuple)
    trunk: str = "target"
    #: the program returns ``(output, pool)``; False: the pool alone
    output: bool = True
    #: class among :data:`LATTICE_KINDS`; None: by the batch, "prefill"
    #: where Q > 1 and "decode" where not
    lattice: Optional[str] = None
    #: logits rows one dispatch assembles across tp shards (the [N, V]
    #: arrays behind the in-program all-gather)
    logits_rows: Callable[[StepKey], int] = lambda key: key.S

    @property
    def chained(self) -> bool:
        return "prev_len" in self.fields

    @property
    def samples(self) -> bool:
        return "greedy" in self.fields


STEP_KINDS: Dict[str, StepKind] = {
    # the plain forward: last-token logits [S, V]
    "logits": StepKind((), "_step_impl", (("fresh", "fresh"),)),
    # forward + on-device sampling: tokens [S] (+ step_tail)
    "sample": StepKind(("greedy",), "_sample_step_impl",
                       (("fresh", "fresh"), ("greedy_only", "greedy"))),
    # decode whose token ids are gathered on device from the previous
    # step's tokens [prev_len + step_tail]
    "chain": StepKind(("prev_len", "greedy"), "_chained_step_impl",
                      (("greedy_only", "greedy"),), lattice="chain"),
    # speculative verify: every position unembeds, [S, 2] comes back
    "spec": StepKind(("greedy",), "_spec_step_impl",
                     (("greedy_only", "greedy"),), lattice="spec",
                     logits_rows=lambda key: key.S * key.Q),
    # draft loop + verify in one program over both pools: one [S] draft
    # gather a scan iteration on top of the verify's
    "draft_spec": StepKind(("greedy",), "_draft_spec_step_impl",
                           (("greedy_only", "greedy"),), trunk="pair",
                           lattice="spec",
                           logits_rows=lambda key: 2 * key.S * key.Q),
    # draft-trunk forward that only writes the draft pool: no unembed
    "draft_fill": StepKind((), "_draft_fill_step_impl", trunk="draft",
                           output=False, lattice="spec",
                           logits_rows=lambda key: 0),
    # decode segment [S, 1] then prefill segment [S_p, Q_p], sampled
    # once; only a role that prefills can form one
    "mixed": StepKind(("S_p", "Q_p", "P_p", "fresh_p", "greedy"),
                      "_mixed_sample_step_impl",
                      (("fresh_p", "fresh"), ("greedy_only", "greedy")),
                      lattice="prefill",
                      logits_rows=lambda key: key.S + key.prefill[0]),
}


def lattice_kind_of(key: Sequence) -> str:
    """Which :data:`LATTICE_KINDS` class a key belongs to — the
    classifier behind ``lattice_keys(kinds=...)``."""
    key = StepKey.parse(key)
    return (STEP_KINDS[key.kind].lattice
            or ("prefill" if key.Q > 1 else "decode"))


def trunk_params(model, trunk: str):
    """The ``params`` operand of a program over ``trunk``."""
    if trunk == "target":
        return model.params
    if trunk == "draft":
        return model.draft_params
    return {"target": model.params, "draft": model.draft_params}


def step_program(model, key: StepKey) -> Callable:
    """The python callable ``key`` compiles to: the kind's traced
    function of ``model`` with the key's static fields bound."""
    row = STEP_KINDS[key.kind]
    fn = getattr(model, row.impl)
    if not row.statics:
        return fn
    return functools.partial(fn, **{arg: getattr(key, reader)
                                    for arg, reader in row.statics})


def step_avals(model, key: StepKey, kv_aval) -> list:
    """Abstract argument list for AOT-lowering ``key`` on ``model``;
    ``kv_aval`` is the pool (or pair of pools) of the kind's trunk."""
    import jax
    import jax.numpy as jnp
    i32, f32 = jnp.int32, jnp.float32
    sds = jax.ShapeDtypeStruct
    row = STEP_KINDS[key.kind]

    def segment(S, Q, P, _fresh=None):
        # a model of more than one cache takes the wide table
        # (ragged/cache_kinds.py): the window group's slots and base,
        # the state slot follow
        return [sds((S, Q), i32), sds((S,), i32), sds((S,), i32),
                sds((S, P + model.table.extra(Q)), i32)]

    S = rows = key.S
    avals = segment(*key[:3])
    if row.chained:
        # the previous step's token vector carries the model's counts
        # past its rows: the one place the tail joins a chain key
        avals[:1] = [sds((key.prev_len + model.step_tail,), i32),
                     sds((S,), i32)]
    if key.kind == "mixed":
        avals += segment(*key.prefill)
        rows += key.prefill[0]
    if row.samples:
        avals += [jax.eval_shape(lambda: jax.random.key(0)),
                  sds((rows,), f32), sds((rows,), i32), sds((rows,), f32)]
        if model.keyed_sampling:
            # keyed sampling (ISSUE 13): row uid + generation position
            # feed the on-device per-row key derivation
            avals += [sds((rows,), i32), sds((rows,), i32)]
    return [trunk_params(model, row.trunk), kv_aval] + avals
