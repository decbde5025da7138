"""The few JAX spellings this code base funnels through one place.

Written for the one installation the repo runs on (jax 0.9.0): plain
``jax.shard_map`` (``check_vma`` / ``axis_names``) and
``jax.lax.axis_size``.  No version probing — when the installation
moves, this file is the one to edit.
"""

from __future__ import annotations

from typing import Any, Optional

import jax


def shard_map(f, *, mesh, in_specs, out_specs,
              check_vma: Optional[bool] = None,
              auto: Any = None):
    """``jax.shard_map`` with the partial-manual mode spelled by the
    axes left to the compiler: ``auto`` is an iterable of mesh axis
    names GSPMD keeps inside the region (``jax.shard_map`` takes the
    complement, the MANUAL subset, as ``axis_names``).  Partial-manual
    regions require jit."""
    kwargs = {}
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    if auto:
        kwargs["axis_names"] = frozenset(mesh.axis_names) - frozenset(auto)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def axis_size(axis_name) -> int:
    """Static size of (a tuple of) named mesh axes bound in the current
    trace."""
    names = (axis_name if isinstance(axis_name, (tuple, list))
             else (axis_name,))
    size = 1
    for a in names:
        size *= int(jax.lax.axis_size(a))
    return size


def manual_axis_names() -> frozenset:
    """Mesh axis names bound manually in the CURRENT trace (inside a
    shard_map region); empty outside one.  Sharding constraints must
    not mention manual axes — callers prune their specs with this."""
    from jax._src.core import get_axis_env
    return frozenset(get_axis_env().axis_sizes)
