"""DSModule registry + heuristics seam for inference v2 op classes.

Reference: ``deepspeed/inference/v2/modules/module_registry.py:22``
(``DSModuleRegistryBase`` — per-interface registries of named
implementations, each with a ``supports_config`` gate) and
``modules/heuristics.py:36-195`` (``instantiate_attention`` etc. —
the central place where an implementation is CHOSEN for a config).

TPU-native formulation: op-class implementations are pure callables
(there is no module state under jit), so the registry maps
``op_class -> [(name, priority, supports, factory)]`` and heuristics
resolve to the highest-priority implementation whose ``supports``
predicate accepts the config.  An explicit ``name`` (the reference's
``ConfigBundle.name``) bypasses the heuristic.

The registered set below is the live one — ``RaggedInferenceModel``
resolves its attention implementation here, so registering a new kernel
(e.g. a future splash-attention decode) changes engine behavior without
touching the model."""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from ...accelerator import on_tpu


@dataclasses.dataclass
class _Impl:
    name: str
    priority: int
    supports: Callable[..., bool]
    factory: Callable[..., Callable]


_REGISTRY: Dict[str, List[_Impl]] = {}


def register(op_class: str, name: str, priority: int = 0,
             supports: Optional[Callable[..., bool]] = None):
    """Decorator: register ``factory(config) -> callable`` under an op
    class (reference ``DSModuleRegistryBase.register_module``)."""
    def deco(factory):
        impls = _REGISTRY.setdefault(op_class, [])
        if any(i.name == name for i in impls):
            raise ValueError(f"duplicate implementation {op_class}/{name}")
        impls.append(_Impl(name, priority, supports or (lambda *_: True),
                           factory))
        impls.sort(key=lambda i: -i.priority)
        return factory
    return deco


def implementations(op_class: str) -> Tuple[str, ...]:
    return tuple(i.name for i in _REGISTRY.get(op_class, ()))


def instantiate(op_class: str, config: Any = None,
                name: Optional[str] = None) -> Callable:
    """Resolve an op-class implementation (reference
    ``heuristics.instantiate_*`` + ``instantiate_config``).

    With ``name``: that implementation, erroring (reference KeyError /
    unsupported ValueError) if absent or unsupporting.  Without: the
    highest-priority implementation whose ``supports(config)`` holds.
    """
    impls = _REGISTRY.get(op_class)
    if not impls:
        raise KeyError(f"unknown op class: {op_class!r}")
    if name is not None:
        for i in impls:
            if i.name == name:
                if not i.supports(config):
                    raise ValueError(
                        f"{op_class}/{name} does not support config {config}")
                return i.factory(config)
        raise KeyError(
            f"unknown implementation {op_class}/{name}; "
            f"registered: {implementations(op_class)}")
    for i in impls:
        if i.supports(config):
            return i.factory(config)
    raise ValueError(f"no {op_class} implementation supports {config}")


# ---------------------------------------------------------------------------
# registered implementations (the live set)
# ---------------------------------------------------------------------------

def _on_tpu(_cfg) -> bool:
    return on_tpu()


@register("ragged_attention", "pallas_paged_decode", priority=10,
          supports=_on_tpu)
def _pallas_decode(cfg):
    """Q=1 decode via the Pallas paged kernel; prefill via the jnp path
    (paged_attention auto-splits on Q)."""
    from ...ops.paged_attention import paged_attention
    slopes = _alibi_for(cfg)
    window = getattr(cfg, "sliding_window", None)
    name = _kernel_name(cfg)

    def attn(q, kv, layer, page_table, start_pos, q_lens):
        return paged_attention(q, kv, layer, page_table, start_pos, q_lens,
                               use_kernel=None, alibi_slopes=slopes,
                               window=window, name=name)
    return attn


@register("ragged_attention", "dense_gather", priority=0)
def _dense_gather(cfg):
    """Pure-jnp paged attention (CPU / ground truth)."""
    from ...ops.paged_attention import paged_attention
    slopes = _alibi_for(cfg)
    window = getattr(cfg, "sliding_window", None)

    def attn(q, kv, layer, page_table, start_pos, q_lens):
        return paged_attention(q, kv, layer, page_table, start_pos, q_lens,
                               use_kernel=False, alibi_slopes=slopes,
                               window=window)
    return attn


def _kernel_name(cfg) -> str:
    """The paged kernel's name in a trace: the window kind of a model
    with two attention kinds (its own page group) runs under a name of
    its own, which still starts ``paged_attention``."""
    return ("paged_attention_window"
            if getattr(cfg, "layer_kinds", ()) and cfg.sliding_window
            else "paged_attention")


def _alibi_for(cfg):
    if getattr(cfg, "pos_emb", None) != "alibi":
        return None
    from ...models.transformer import alibi_slopes
    return alibi_slopes(cfg.num_heads)


def _no_alibi(cfg) -> bool:
    # the flash kernel has no additive-bias input; ALiBi prefill stays on
    # the paged dense path
    return getattr(cfg, "pos_emb", None) != "alibi"


@register("fresh_prefill_attention", "flash", priority=10,
          supports=_no_alibi)
def _fresh_flash(cfg):
    """Pure-prefill bucket (every slot at position 0): context IS the new
    tokens, so attention runs the flash kernel over [S(batch), H, Q, D]
    with causal (+ sliding window) blocking — no paged gather, no
    [Q, C] score materialization (reference blocked_flash prefill atoms,
    inference/v2/kernels/ragged_ops/).  Off-TPU the kernel falls back to
    the dense reference with identical semantics."""
    from ...ops.flash_attention import flash_attention
    window = getattr(cfg, "sliding_window", None)
    block_q = getattr(cfg, "flash_block_q", 512)
    block_k = getattr(cfg, "flash_block_k", 512)

    def attn(q, k_rot, v):
        qf = q.transpose(0, 2, 1, 3)        # [S, H, Q, D]
        kf = k_rot.transpose(0, 2, 1, 3)    # [S, K, Q, D]
        vf = v.transpose(0, 2, 1, 3)
        out = flash_attention(qf, kf, vf, causal=True, window=window,
                              block_q=block_q, block_k=block_k)
        return out.transpose(0, 2, 1, 3)
    return attn


# norm implementations share the (params, x) -> y calling convention
@register("norm", "pallas_fused", priority=10, supports=_on_tpu)
def _pallas_norm(cfg):
    from ...ops.normalization import layernorm, rmsnorm
    eps = getattr(cfg, "norm_eps", 1e-6)
    if getattr(cfg, "norm", "rmsnorm") == "rmsnorm":
        return lambda p, x: rmsnorm(x, p["scale"], eps)
    return lambda p, x: layernorm(x, p["scale"], p["bias"], eps)


@register("norm", "xla", priority=0)
def _xla_norm(cfg):
    from ...models import transformer as T
    return lambda p, x: T._norm_apply(cfg, p, x)


@register("embedding", "ragged_embedding", priority=0)
def _embedding(cfg):
    def embed(table, token_ids):
        return table[token_ids]
    return embed
