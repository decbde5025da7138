"""Test harness (reference ``tests/unit/common.py`` DistributedTest).

The reference spawns N real processes with torch.multiprocessing and real
NCCL/Gloo collectives.  TPU-native equivalent: a single process with an
N-device virtual CPU platform (``--xla_force_host_platform_device_count``)
— every test exercises *real* XLA collectives over a real
``jax.sharding.Mesh``, which is exactly what runs on a TPU slice, minus
the ICI wires.  Multi-chip sharding correctness (ZeRO/TP/PP/MoE/SP) is
therefore tested with the same code path that runs on hardware.
"""

import os

# Must be set before jax initializes its backends.  Force-override: the
# tests run on the virtual CPU mesh whatever the environment asks for.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# The library turns JAX's persistent compile cache on for every engine
# (utils/compile_cache.py); the suite runs without it — thousands of
# small CPU programs are not worth a disk entry, and compile-count tests
# must see true compiles.  test_coldstart.py switches it on for itself.
jax.config.update("jax_enable_compilation_cache", False)
import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture()
def rng():
    return jax.random.key(0)


@pytest.fixture(autouse=True)
def _reset_accelerator():
    # Each test sees a fresh accelerator selection.
    from deepspeed_tpu.accelerator import real_accelerator
    real_accelerator._accelerator = None
    yield


def pytest_collection_modifyitems(config, items):
    """Apply the central heavy-marker table (reference
    tests/unit/ci_promote_marker.py pattern: per-tier markers maintained
    centrally, test bodies untouched)."""
    from heavy_marker import CHAOS_TESTS, HEAVY_TESTS, SLOW_TESTS
    for item in items:
        if item.nodeid in HEAVY_TESTS:
            item.add_marker(pytest.mark.heavy)
        if item.nodeid in SLOW_TESTS:
            item.add_marker(pytest.mark.slow)
        if item.nodeid in CHAOS_TESTS or \
                item.nodeid.startswith("tests/test_chaos.py::"):
            item.add_marker(pytest.mark.chaos)
