"""What a layer kind caches, declared in one place.

A model names a kind for every layer (``TransformerConfig.layer_kinds``;
none: every layer is "full").  A kind keeps, a sequence, either a PAGE
PLANE of a page group (its K/V at every position the kind still attends),
or a SLOT of the state pool (a recurrent state that does not grow with the
context), or NOTHING (a layer that is a feed-forward alone: no group, no
slot, no column of the table, no reservation).  The three places that have to agree on that read it here: the
model when it takes a segment's table apart (``model.py::_forward_hidden``), the
host when it builds the table (``batch.py::build_batch``) and the state
manager when it decides which resources a sequence reserves
(``manager.py::StateManager``).

The wide table of a segment, for a model of more than one cache::

    [S, P | W | 1 | 1]   full group's pages | window group's live pages
                         | the window table's base page | the state slot

each part present only where the model has a kind that needs it, so a
model of one page group takes the ``[S, P]`` table it always took.  The
full group's pages are K/V by head or, under latent attention, the latent
plane's: a row may carry a latent plane's pages AND a state slot.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Sequence, Tuple

from ..step_key import window_slots


@dataclasses.dataclass(frozen=True)
class CacheKind:
    """What one layer kind caches."""
    #: the page group whose pool holds the kind's K/V; "" = none
    group: str = ""
    #: the group's tables give back the pages the window has passed
    windowed: bool = False
    #: the kind holds a slot of the state pool instead of pages: what a
    #: slot holds of ONE such layer, from the model's configuration, as
    #: (the recurrent state ``[rows, width]``, the convolution's tail
    #: ``[positions, channels]``).  None: the kind caches pages
    slot_shape: Optional[Callable[[object], Tuple[Tuple[int, int],
                                                  Tuple[int, int]]]] = None

    @property
    def slot(self) -> bool:
        return self.slot_shape is not None


def _matrix_slot(cfg):
    """A matrix ``[dk, dv]`` a head, the heads side by side in the minor
    dim; the convolution's tail of q, k AND v."""
    return ((cfg.delta_key_dim, cfg.delta_heads * cfg.delta_value_dim),
            (cfg.delta_conv - 1, cfg.delta_heads
             * (2 * cfg.delta_key_dim + cfg.delta_value_dim)))


CACHE_KINDS: Dict[str, CacheKind] = {
    "full": CacheKind(group="full"),
    # latent attention (ops/mla_attention.py): one plane a token in the
    # one page group's pool, in place of K and V by head
    "latent": CacheKind(group="full"),
    "window": CacheKind(group="window", windowed=True),
    # Mamba-1 (ops/ssm.py): a diagonal state a channel, the tail of the
    # mixer's own channels
    "ssm": CacheKind(slot_shape=lambda cfg: (
        (cfg.ssm_state_dim, cfg.ssm_inner),
        (cfg.ssm_conv - 1, cfg.ssm_inner))),
    # gated delta rule (ops/delta_rule.py): one decay a head
    "delta": CacheKind(slot_shape=_matrix_slot),
    # Kimi-delta (ops/delta_rule.py): the same slot, stepped by another
    # update rule (one decay a key channel)
    "kda": CacheKind(slot_shape=_matrix_slot),
    # Mamba-2 (ops/ssm.py::ssd_scan): a head's [P, N] state at its lanes of
    # [N, H P], the tail of x, B and C
    "ssd": CacheKind(slot_shape=lambda cfg: (
        (cfg.ssm_state_dim, cfg.ssm_inner),
        (cfg.ssm_conv - 1,
         cfg.ssm_inner + 2 * cfg.ssm_groups * cfg.ssm_state_dim))),
    # a feed-forward alone (a model of ``half_blocks``): caches nothing
    "ffn": CacheKind(),
}


def slot_kind(kinds: Sequence[str]) -> Optional[str]:
    """The one kind of ``kinds`` that holds a slot of the state pool (a
    pool has one slot shape, so a model has at most one such kind)."""
    found = [k for k in dict.fromkeys(kinds) if CACHE_KINDS[k].slot]
    assert len(found) <= 1, f"one state pool, one slot kind: {found}"
    return found[0] if found else None


@dataclasses.dataclass(frozen=True)
class TableLayout:
    """The columns of a model's segment table (module docstring)."""
    #: the window the windowed group's layers attend (0: no such group)
    window: int = 0
    page_size: int = 0
    #: the model has a state pool: the last column is the row's slot
    state: bool = False

    @classmethod
    def of(cls, kinds: Sequence[str], window: Optional[int],
           page_size: int) -> "TableLayout":
        caches = [CACHE_KINDS[k] for k in dict.fromkeys(kinds)]
        return cls(window=int(window or 0)
                   if any(c.windowed for c in caches) else 0,
                   page_size=page_size,
                   state=any(c.slot for c in caches))

    def window_slots(self, Q: int) -> int:
        """Slots of the window group's table in a segment of ``Q`` tokens
        a row (``step_key.window_slots``); 0 without such a group."""
        return window_slots(self.window, self.page_size, Q) \
            if self.window else 0

    def extra(self, Q: int) -> int:
        """Columns past the full group's ``P``."""
        W = self.window_slots(Q)
        return (W + 1 if W else 0) + int(self.state)

    def split(self, table, Q: int) -> dict:
        """A table's parts by name: ``full`` ``[S, P]``, and where the
        model has them ``window`` ``[S, W]``, ``base`` ``[S]``, ``slot``
        ``[S]``."""
        if not self.extra(Q):           # one page group: the table as it is
            return {"full": table}
        W = self.window_slots(Q)
        P = table.shape[1] - self.extra(Q)
        parts = {"full": table[:, :P]}
        if W:
            parts["window"] = table[:, P:P + W]
            parts["base"] = table[:, P + W]
        if self.state:
            parts["slot"] = table[:, -1]
        return parts
