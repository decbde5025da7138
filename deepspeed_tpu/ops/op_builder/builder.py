"""JIT build system for native host ops.

TPU-native analogue of ``op_builder/builder.py`` (``OpBuilder`` :108,
``load``/``jit_load`` :491-574).  Differences by design:

* Device compute compiles through XLA/Pallas, so native ops here are *host*
  ops only (offload optimizers, async NVMe I/O) — there is no nvcc stage.
* No pybind11/torch extension machinery: sources compile with ``g++ -shared
  -fPIC`` into a cached ``.so`` keyed by a content hash, loaded via
  :mod:`ctypes` with explicit prototypes.

Builders are named classes resolved through the accelerator
(``op_builder_dir``/``get_op_builder`` seam, reference
``abstract_accelerator.py:271-281``).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
from pathlib import Path
from typing import Dict, List, Optional, Type

from ...utils.logging import logger

_REPO_ROOT = Path(__file__).resolve().parents[3]
CSRC_DIR = _REPO_ROOT / "csrc"


def _cache_dir() -> Path:
    # built beside the compile cache, inside the checkout (gitignored):
    # a chip run gets the working tree and nothing around it
    root = os.environ.get("DS_TPU_OPS_CACHE",
                          str(_REPO_ROOT / ".ds_ops_cache"))
    path = Path(root)
    path.mkdir(parents=True, exist_ok=True)
    return path


class OpBuilderError(RuntimeError):
    pass


class OpBuilder:
    """Compile-and-load for one named native op."""

    NAME: str = "base"
    # subclasses list .cpp sources relative to csrc/
    SOURCES: List[str] = []

    _loaded: Dict[str, ctypes.CDLL] = {}

    def absolute_sources(self) -> List[Path]:
        return [CSRC_DIR / s for s in self.SOURCES]

    def include_dirs(self) -> List[Path]:
        return [CSRC_DIR / "includes"]

    def cxx_args(self) -> List[str]:
        args = ["-O3", "-std=c++17", "-shared", "-fPIC", "-pthread"]
        if not os.environ.get("DS_TPU_DISABLE_NATIVE_SIMD"):
            args.append("-march=native")
        return args

    def is_compatible(self) -> bool:
        from shutil import which
        return which(self.compiler()) is not None and \
            all(p.is_file() for p in self.absolute_sources())

    def compiler(self) -> str:
        return os.environ.get("CXX", "g++")

    # ---------------------------------------------------------------- load
    _compiler_id_cache: Dict[str, str] = {}

    def _compiler_id(self) -> str:
        """Compiler version + host CPU: -march=native binaries are host-
        specific, so a shared cache dir must never serve a mismatched .so
        (SIGILL on an older CPU)."""
        cxx = self.compiler()
        cached = OpBuilder._compiler_id_cache.get(cxx)
        if cached is None:
            try:
                ver = subprocess.run([cxx, "--version"], capture_output=True,
                                     text=True).stdout.splitlines()[0]
            except Exception:
                ver = "unknown"
            cached = ver + "|" + platform.processor() + platform.machine()
            OpBuilder._compiler_id_cache[cxx] = cached
        return cached

    def _hash(self) -> str:
        h = hashlib.sha256()
        for src in self.absolute_sources():
            h.update(src.read_bytes())
        for inc_dir in self.include_dirs():
            for header in sorted(inc_dir.glob("*.h")):
                h.update(header.read_bytes())
        h.update(" ".join(self.cxx_args()).encode())
        h.update(self._compiler_id().encode())
        return h.hexdigest()[:16]

    def so_path(self) -> Path:
        return _cache_dir() / f"{self.NAME}_{self._hash()}.so"

    def build(self) -> Path:
        out = self.so_path()
        if out.is_file():
            return out
        cmd = [self.compiler(), *self.cxx_args()]
        for inc in self.include_dirs():
            cmd.append(f"-I{inc}")
        cmd += [str(s) for s in self.absolute_sources()]
        tmp_out = out.with_suffix(f".tmp{os.getpid()}.so")
        cmd += ["-o", str(tmp_out)]
        logger.info("building native op %s: %s", self.NAME, " ".join(cmd))
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode != 0:
            raise OpBuilderError(
                f"native build of {self.NAME} failed:\n{proc.stderr}")
        os.replace(tmp_out, out)  # atomic under concurrent builders
        return out

    def load(self) -> ctypes.CDLL:
        if self.NAME in OpBuilder._loaded:
            return OpBuilder._loaded[self.NAME]
        if not self.is_compatible():
            raise OpBuilderError(
                f"op {self.NAME} is not buildable here (missing compiler "
                f"or sources)")
        lib = ctypes.CDLL(str(self.build()))
        self._annotate(lib)
        OpBuilder._loaded[self.NAME] = lib
        return lib

    def _annotate(self, lib: ctypes.CDLL) -> None:
        """Attach argtypes/restype prototypes. Subclasses override."""


_f32p = ctypes.POINTER(ctypes.c_float)


class CPUAdamBuilder(OpBuilder):
    """Reference ``op_builder/cpu_adam.py`` / ``csrc/adam/cpu_adam.cpp``."""
    NAME = "cpu_adam"
    SOURCES = ["adam/cpu_adam.cpp"]

    def _annotate(self, lib):
        lib.ds_cpu_adam_step.argtypes = [
            _f32p, _f32p, _f32p, _f32p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_float, ctypes.c_float, ctypes.c_float, ctypes.c_float,
            ctypes.c_float, ctypes.c_int, ctypes.c_int,
        ]
        lib.ds_cpu_adam_step.restype = None
        lib.ds_simd_width.restype = ctypes.c_int


class CPUAdagradBuilder(OpBuilder):
    NAME = "cpu_adagrad"
    SOURCES = ["adagrad/cpu_adagrad.cpp"]

    def _annotate(self, lib):
        lib.ds_cpu_adagrad_step.argtypes = [
            _f32p, _f32p, _f32p, ctypes.c_int64,
            ctypes.c_float, ctypes.c_float, ctypes.c_float,
        ]
        lib.ds_cpu_adagrad_step.restype = None


class CPULionBuilder(OpBuilder):
    NAME = "cpu_lion"
    SOURCES = ["lion/cpu_lion.cpp"]

    def _annotate(self, lib):
        lib.ds_cpu_lion_step.argtypes = [
            _f32p, _f32p, _f32p, ctypes.c_int64,
            ctypes.c_float, ctypes.c_float, ctypes.c_float, ctypes.c_float,
        ]
        lib.ds_cpu_lion_step.restype = None


class AsyncIOBuilder(OpBuilder):
    """Reference ``op_builder/async_io.py`` / ``csrc/aio/``."""
    NAME = "async_io"
    SOURCES = ["aio/ds_aio.cpp"]

    def _annotate(self, lib):
        lib.ds_aio_create.argtypes = [ctypes.c_int, ctypes.c_int]
        lib.ds_aio_create.restype = ctypes.c_void_p
        lib.ds_aio_create2.argtypes = [ctypes.c_int, ctypes.c_int,
                                       ctypes.c_int, ctypes.c_int]
        lib.ds_aio_create2.restype = ctypes.c_void_p
        lib.ds_aio_destroy.argtypes = [ctypes.c_void_p]
        lib.ds_aio_destroy.restype = None
        for fn in (lib.ds_aio_pread, lib.ds_aio_pwrite):
            fn.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
                           ctypes.c_int64, ctypes.c_int64]
            fn.restype = ctypes.c_int64
        lib.ds_aio_wait.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.ds_aio_wait.restype = ctypes.c_int64
        lib.ds_aio_wait_all.argtypes = [ctypes.c_void_p]
        lib.ds_aio_wait_all.restype = ctypes.c_int64


class NotImplementedBuilder(OpBuilder):
    """Stub for ops that are intentionally absent on TPU (reference
    ``op_builder/hpu/no_impl.py`` — the registry stays honest about what
    is out of scope instead of failing with a missing-name KeyError)."""
    NAME = "no_impl"
    SOURCES: List[str] = []
    REASON = "not implemented on TPU"

    def is_compatible(self) -> bool:
        return False

    def build(self):  # pragma: no cover - trivial
        raise OpBuilderError(f"op {self.NAME!r}: {self.REASON}")

    def load(self):
        raise OpBuilderError(f"op {self.NAME!r}: {self.REASON}")


class EvoformerAttnBuilder(NotImplementedBuilder):
    """reference csrc/deepspeed4science/evoformer_attn (CUTLASS): out of
    scope (SURVEY §2.5); AlphaFold-style workloads should use the flash
    attention kernel over fused pair activations."""
    NAME = "evoformer_attn"
    REASON = ("DS4Science evoformer CUTLASS kernels are out of scope on "
              "TPU; use ops.flash_attention over pair activations")


class SparseAttnBuilder(NotImplementedBuilder):
    """reference csrc/sparse_attention (triton-era remnant)."""
    NAME = "sparse_attn"
    REASON = ("legacy triton sparse attention is not ported; "
              "sliding-window / ring attention cover the use cases")


class SpatialInferenceBuilder(NotImplementedBuilder):
    """reference csrc/spatial (diffusers bias-add helpers)."""
    NAME = "spatial_inference"
    REASON = "diffusers spatial kernels are not ported; XLA fuses bias-adds"


ALL_OPS: Dict[str, Type[OpBuilder]] = {
    cls.NAME: cls
    for cls in (CPUAdamBuilder, CPUAdagradBuilder, CPULionBuilder,
                AsyncIOBuilder, EvoformerAttnBuilder, SparseAttnBuilder,
                SpatialInferenceBuilder)
}


def get_op_builder(name: str) -> Type[OpBuilder]:
    try:
        return ALL_OPS[name]
    except KeyError:
        raise OpBuilderError(
            f"unknown op builder {name!r}; available: {sorted(ALL_OPS)}")


def create_op_builder(name: str) -> OpBuilder:
    return get_op_builder(name)()
