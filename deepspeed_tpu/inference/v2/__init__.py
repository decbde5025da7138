from .config import (FaultInjectionConfig, KVCacheUserConfig,
                     RaggedInferenceEngineConfig,
                     ServingOptimizationConfig, StateManagerConfig)
from ...utils.compile_cache import (disable_compile_cache,
                                    ensure_compile_cache)
from .engine import InferenceEngineV2, SchedulingError, SchedulingResult
from .factory import build_hf_engine
from .lattice import (BucketLattice, LatticeError, fit_buckets,
                      mine_lattice, resolve_lattice)
from .model import RaggedInferenceModel
from .model_implementations import (implementation_for,
                                    supported_model_types)
from .ragged import (BlockedAllocator, BlockedKVCache, KVCacheConfig,
                     RaggedBatch, StateManager, build_batch)
from .ragged.blocked_allocator import KVAllocationError
from .sampling import SamplingParams, sample, sample_dynamic
from .scheduler import FastGenScheduler, Request, RequestError, generate
from .snapshot import (SNAPSHOT_VERSION, SnapshotError,
                       install_drain_handler, maybe_install_drain_handler,
                       read_bundle, write_bundle)
from .spec import NgramDrafter

__all__ = [
    "KVCacheUserConfig", "RaggedInferenceEngineConfig",
    "ServingOptimizationConfig", "StateManagerConfig",
    "InferenceEngineV2", "SchedulingError", "SchedulingResult",
    "build_hf_engine",
    "RaggedInferenceModel", "implementation_for", "supported_model_types",
    "BlockedAllocator", "BlockedKVCache",
    "KVCacheConfig", "RaggedBatch", "StateManager", "build_batch",
    "SamplingParams", "sample", "sample_dynamic",
    "FastGenScheduler", "Request", "RequestError", "generate",
    "FaultInjectionConfig", "KVAllocationError",
    "SNAPSHOT_VERSION", "SnapshotError", "install_drain_handler",
    "maybe_install_drain_handler", "read_bundle", "write_bundle",
    "NgramDrafter",
    "BucketLattice", "LatticeError", "fit_buckets", "mine_lattice",
    "resolve_lattice",
    "disable_compile_cache", "ensure_compile_cache",
]
