"""Device-mesh topology: the TPU-native replacement for process groups.

The reference builds parallel "grids" out of torch.distributed process
groups (``deepspeed/utils/groups.py``, ``runtime/pipe/topology.py``:
``ProcessTopology`` / ``PipeModelDataParallelTopology``).  On TPU the same
roles are played by named axes of a single ``jax.sharding.Mesh``; XLA then
lowers per-axis collectives onto ICI/DCN.  This module owns the canonical
axis names and the arithmetic that maps a DeepSpeed-style parallel config
(dp/tp/pp/sp/ep sizes) onto a mesh.

Axis roles (ordered outermost -> innermost; innermost axes get
ICI-adjacent devices, so the most communication-hungry axes go last):

  pipe    pipeline-parallel stages           (reference: PP axis 'pipe')
  data    pure data parallelism (replicas)   (reference: DP axis 'data')
  expert  expert parallelism for MoE         (reference: EP groups)
  fsdp    ZeRO parameter/optimizer sharding  (reference: ZeRO partitioning
                                              inside the DP group)
  seq     sequence (Ulysses) parallelism     (reference: SP groups)
  tensor  tensor (Megatron) parallelism      (reference: MP/'model' axis)

DeepSpeed equivalences:
  * dp_world (grad-reduction group)  == data x expert x fsdp x seq
    (sequence ranks see different tokens, so they are also gradient
    replicas, matching reference engine.py:320-326 SP grad allreduce)
  * ZeRO stage 1/2/3 partition_count == size of 'fsdp'
  * MoE expert-data-parallel group   == 'data' (+ 'fsdp' when ep covers it)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils.logging import logger

# Canonical axis order, outermost first.
MESH_AXES: Tuple[str, ...] = ("pipe", "data", "expert", "fsdp", "hpz", "seq", "tensor")

# Composite axis groups used for common shardings.
BATCH_AXES = ("data", "expert", "fsdp", "hpz")  # batch dim of inputs
GRAD_REDUCE_AXES = ("data", "expert", "fsdp", "hpz", "seq")  # dp_world for grad psum


@dataclasses.dataclass(frozen=True)
class TopologyConfig:
    """Sizes of each mesh axis.  -1 means "absorb remaining devices"."""
    pipe: int = 1
    data: int = -1
    expert: int = 1
    fsdp: int = 1
    # ZeRO++ hpZ secondary partition: an INNER shard axis placed on
    # ICI-adjacent devices; stage-3 per-layer gathers ride only this axis
    # while optimizer state shards over fsdp x hpz (see zero/partitioner).
    hpz: int = 1
    seq: int = 1
    tensor: int = 1

    def resolve(self, n_devices: int) -> "TopologyConfig":
        sizes = {a: getattr(self, a) for a in MESH_AXES}
        free = [a for a, s in sizes.items() if s == -1]
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if n_devices % fixed != 0:
            raise ValueError(
                f"mesh axes {sizes} do not divide device count {n_devices}")
        rem = n_devices // fixed
        if not free:
            if fixed != n_devices:
                raise ValueError(
                    f"mesh axes {sizes} (product {fixed}) != device count {n_devices}")
        elif len(free) == 1:
            sizes[free[0]] = rem
        else:
            # First free axis absorbs everything, the rest get 1.
            sizes[free[0]] = rem
            for a in free[1:]:
                sizes[a] = 1
        return TopologyConfig(**sizes)


class MeshTopology:
    """A resolved device mesh plus DeepSpeed-style group arithmetic."""

    def __init__(self,
                 config: Optional[TopologyConfig] = None,
                 devices: Optional[Sequence[jax.Device]] = None):
        self.devices = list(devices) if devices is not None else jax.devices()
        cfg = (config or TopologyConfig()).resolve(len(self.devices))
        self.config = cfg
        shape = tuple(getattr(cfg, a) for a in MESH_AXES)
        dev_array = np.asarray(self.devices).reshape(shape)
        self.mesh = Mesh(dev_array, MESH_AXES)
        logger.info("MeshTopology: %s over %d devices",
                    {a: s for a, s in zip(MESH_AXES, shape) if s > 1} or "{single}",
                    len(self.devices))

    # -- DeepSpeed-compatible size accessors ------------------------------
    @property
    def world_size(self) -> int:
        return len(self.devices)

    def axis_size(self, axis: str) -> int:
        return getattr(self.config, axis)

    @property
    def pp_world_size(self) -> int:
        return self.config.pipe

    @property
    def tp_world_size(self) -> int:
        return self.config.tensor

    @property
    def sp_world_size(self) -> int:
        return self.config.seq

    @property
    def ep_world_size(self) -> int:
        return self.config.expert

    @property
    def fsdp_world_size(self) -> int:
        return self.config.fsdp

    @property
    def hpz_world_size(self) -> int:
        return self.config.hpz

    @property
    def dp_world_size(self) -> int:
        """Gradient-reduction world size (reference dp group size)."""
        return math.prod(self.axis_size(a) for a in GRAD_REDUCE_AXES)

    @property
    def batch_shard_size(self) -> int:
        """Number of distinct micro-batch shards along the batch dim."""
        return math.prod(self.axis_size(a) for a in BATCH_AXES)

    # -- sharding helpers -------------------------------------------------
    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def batch_spec(self, seq_sharded: bool = True) -> P:
        """PartitionSpec for [batch, seq, ...] input arrays."""
        if seq_sharded and self.config.seq > 1:
            return P(BATCH_AXES, "seq")
        return P(BATCH_AXES)

    def batch_sharding(self, seq_sharded: bool = True) -> NamedSharding:
        return NamedSharding(self.mesh, self.batch_spec(seq_sharded))

    def __repr__(self) -> str:
        sizes = {a: self.axis_size(a) for a in MESH_AXES}
        return f"MeshTopology({sizes})"


def single_device_topology() -> MeshTopology:
    return MeshTopology(TopologyConfig(data=1), devices=jax.devices()[:1])


def ambient_mesh():
    """The physical Mesh active at trace time, or None.

    Single lookup point for trace-time mesh discovery (used by the
    transformer's sharding constraints and comm.get_world_group) — when
    JAX moves ``thread_resources``, this one site needs the update."""
    from jax._src.mesh import thread_resources
    m = thread_resources.env.physical_mesh
    return None if m.empty else m
