"""deepspeed_tpu — a TPU-native large-scale training & inference framework
with the capabilities of DeepSpeed (reference: HabanaAI/DeepSpeed v0.14.4).

Public API mirrors ``deepspeed/__init__.py``: ``initialize()`` (:69),
``init_inference()`` (:273), ``init_distributed()`` (comm.py:604) — built
on JAX/XLA: SPMD sharding over a device mesh instead of process groups,
jitted fused train steps instead of stream-scheduled CUDA kernels.
"""

from .version import __version__  # noqa: F401

import os as _os

import jax as _jax

# Sharding-invariant RNG: with the legacy (non-partitionable) threefry,
# the SAME key produces DIFFERENT values under different out_shardings —
# so a model initialized on a {fsdp:8} mesh differs from the identical
# model on {data:2, fsdp:4}, breaking cross-topology reproducibility
# (and the MiCS == plain-stage3 parity the reference guarantees).
# Set at IMPORT so every draw in the process agrees (flipping it at
# engine construction would make a script's jax.random values depend on
# whether an engine was built yet).  This changes jax.random streams vs
# the legacy impl; opt out with DS_TPU_PARTITIONABLE_RNG=0 if bitwise
# continuity with pre-existing seeds matters more than cross-topology
# init reproducibility.
if _os.environ.get("DS_TPU_PARTITIONABLE_RNG", "1") != "0":
    _jax.config.update("jax_threefry_partitionable", True)

from . import comm  # noqa: F401
from .accelerator import get_accelerator  # noqa: F401
from .parallel.topology import MeshTopology, TopologyConfig  # noqa: F401
from .runtime.config import DeepSpeedTPUConfig, load_config  # noqa: F401
from .runtime.engine import DeepSpeedEngine, TrainState  # noqa: F401
from .runtime import zero  # noqa: F401  (zero.Init / GatheredParameters)
from .runtime import pipe  # noqa: F401  (PipelineModule / LayerSpec / PipelineEngine)
from . import moe  # noqa: F401
from . import checkpoint  # noqa: F401
from . import monitor  # noqa: F401
from . import ops  # noqa: F401
from . import module_inject  # noqa: F401
from . import utils  # noqa: F401
from .runtime.pipe.engine import PipelineEngine  # noqa: F401
from .runtime.hybrid_engine import DeepSpeedHybridEngine  # noqa: F401
from .runtime.lr_schedules import add_tuning_arguments  # noqa: F401
from .inference.engine import InferenceEngine  # noqa: F401
from .inference.engine import InferenceConfig as DeepSpeedInferenceConfig  # noqa: F401
from .utils.logging import log_dist, logger  # noqa: F401


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               mpu=None,
               dist_init_required=None,
               collate_fn=None,
               config=None,
               config_params=None,
               **kwargs):
    """Build a training engine (reference ``deepspeed.initialize``,
    __init__.py:69).  Returns ``(engine, optimizer, dataloader, lr_scheduler)``.

    ``model`` follows the models/base.py protocol (``init_params``/``loss``)
    or is a :class:`~deepspeed_tpu.runtime.pipe.module.PipelineModule`, which
    selects the pipeline engine (reference engine-selection, __init__.py:166).
    """
    config = config if config is not None else config_params
    if args is not None and config is None:
        config = getattr(args, "deepspeed_config", None)

    from .runtime.config import load_config
    from .runtime.pipe.module import PipelineModule
    from .utils.compile_cache import ensure_compile_cache
    # the train step is the longest compile of a cold run: same
    # persistent cache, same placement rule as the serving engine
    ensure_compile_cache()
    if isinstance(model, PipelineModule):
        try:
            from .runtime.pipe.engine import PipelineEngine
        except ImportError as e:
            raise NotImplementedError(
                "pipeline engine not available in this build") from e
        engine = PipelineEngine(model=model, config=config,
                                training_data=training_data,
                                lr_scheduler=lr_scheduler,
                                collate_fn=collate_fn,
                                params=model_parameters, **kwargs)
    elif load_config(config).hybrid_engine.enabled:
        # reference engine selection (__init__.py:166): HybridEngine first
        from .runtime.hybrid_engine import DeepSpeedHybridEngine
        engine = DeepSpeedHybridEngine(model=model, config=config,
                                       training_data=training_data,
                                       lr_scheduler=lr_scheduler,
                                       collate_fn=collate_fn,
                                       params=model_parameters, **kwargs)
    else:
        engine = DeepSpeedEngine(model=model, config=config,
                                 training_data=training_data,
                                 lr_scheduler=lr_scheduler,
                                 collate_fn=collate_fn,
                                 params=model_parameters, **kwargs)
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


def init_distributed(dist_backend="xla", **kwargs):
    return comm.init_distributed(dist_backend=dist_backend, **kwargs)


def init_inference(model=None, config=None, **kwargs):
    """Build an inference engine (reference ``deepspeed.init_inference``,
    __init__.py:273).  See inference/ for the ragged continuous-batching
    (FastGen) engine."""
    try:
        from .inference.engine import InferenceEngine
    except ImportError as e:
        raise NotImplementedError(
            "inference engine not available in this build") from e
    return InferenceEngine(model=model, config=config, **kwargs)


def add_config_arguments(parser):
    """Update an argparse parser with the DeepSpeed argument group
    (reference deepspeed/__init__.py:250): ``--deepspeed`` enable flag
    and ``--deepspeed_config <json path>``."""
    group = parser.add_argument_group(
        "DeepSpeed", "DeepSpeed-TPU configurations")
    group.add_argument(
        "--deepspeed", default=False, action="store_true",
        help="Enable DeepSpeed (helper flag for user code)")
    group.add_argument(
        "--deepspeed_config", default=None, type=str,
        help="DeepSpeed json configuration file.")
    return parser


def default_inference_config():
    """Default FastGen/v2 engine config as a plain dict (reference
    deepspeed/__init__.py default_inference_config)."""
    import dataclasses
    from .inference.v2 import RaggedInferenceEngineConfig
    return dataclasses.asdict(RaggedInferenceEngineConfig())
