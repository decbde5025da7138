"""Persistent XLA compile cache — ONE helper for training and serving.

Every compiled program is process-local: a fresh trainer, a restored
replica, a pool ``scale_up`` spawn or a cold chip run re-pays the whole
compile.  :func:`ensure_compile_cache` wires JAX's persistent
compilation cache so a second process compiling the same programs LOADS
executables from disk.  ``dst.initialize`` and ``InferenceEngineV2``
both call it; nothing else in the library touches the cache options.

Placement rule (the path is part of the cache key's neighbourhood — a
directory that moves never hits):

1. ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this
   module sets NO directory — whoever runs the program places the cache.
2. else ``compile_cache_dir`` (``serving_optimization`` config field):
   an explicit operator choice, used as given.
3. else a fixed path inside the checkout, ``<repo>/.jax_cache/``
   (gitignored) — never a temp name, pid or time.

JAX's own cache key covers the program, its shapes, the compile options
and the backend, so entries of unrelated configurations coexist in one
directory: a configuration change reads as a miss, never as a wrong
executable.  JAX's master switch ``jax_enable_compilation_cache`` is
respected, never flipped (the CPU test suite switches it off).

Loads vs true compiles are counted in
``ds_fastgen_compile_cache_{hit,miss}_total`` from JAX's own monitoring
events — every compile of the process is covered without touching the
compile path.

Degradation: an uncreatable/unwritable directory logs a warning and
the program proceeds with plain compiles; corrupt entries are
re-compiled (``jax_raise_persistent_cache_errors`` stays False).
"""

from __future__ import annotations

import os
import threading
from typing import Optional

from .logging import logger

#: the fixed in-checkout default (rule 3)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_listener_installed = False
#: the same events counted per thread: JAX calls its listeners on the
#: thread that compiles, so one formation's hit or miss can be told from
#: those of the programs forming beside it on other threads
_thread_counts = threading.local()
#: the active cache path (None = disabled)
_active_dir: Optional[str] = None


def _install_listener() -> None:
    """Count JAX's persistent-cache monitoring events into the
    ds_fastgen_compile_cache_* counters (once per process)."""
    global _listener_installed
    if _listener_installed:
        return
    from jax import monitoring

    from ..telemetry import metrics as tm

    def _on_event(event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            tm.FASTGEN_COMPILE_CACHE_HIT.inc()
            _thread_counts.hits = getattr(_thread_counts, "hits", 0) + 1
        elif event == "/jax/compilation_cache/cache_misses":
            tm.FASTGEN_COMPILE_CACHE_MISS.inc()
            _thread_counts.misses = getattr(_thread_counts, "misses", 0) + 1

    monitoring.register_event_listener(_on_event)
    _listener_installed = True


def ensure_compile_cache(config_dir: str = "") -> Optional[str]:
    """Activate the persistent cache by the placement rule above and
    install the hit/miss listener.  Returns the directory in use, or
    None when JAX's master switch is off or the directory cannot be
    written (a warning; the caller proceeds with plain compiles)."""
    global _active_dir
    import jax
    if not jax.config.jax_enable_compilation_cache:
        return None
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    path = env_dir or config_dir or DEFAULT_CACHE_DIR
    if path == _active_dir:
        return path
    try:
        os.makedirs(path, exist_ok=True)
        probe = os.path.join(path, f".ds_probe_{os.getpid()}")
        with open(probe, "w") as f:
            f.write("ok")
        os.unlink(probe)
    except OSError as e:
        logger.warning(
            "compile cache disabled: %s is not a writable directory "
            "(%s: %s) — continuing with plain XLA compiles",
            path, type(e).__name__, e)
        return None
    if _active_dir is not None:
        # the jax cache dir is PROCESS-GLOBAL: last caller wins
        logger.warning("compile cache retargeted %s -> %s (the cache "
                       "dir is process-global)", _active_dir, path)
    if not env_dir:
        jax.config.update("jax_compilation_cache_dir", path)
        _reset_jax_cache()
    # persist every program (the default 1 s floor would skip the small
    # decode buckets); corrupt entries degrade to a recompile + warning
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_raise_persistent_cache_errors", False)
    _install_listener()
    _active_dir = path
    logger.info("persistent compile cache active at %s", path)
    return path


def disable_compile_cache() -> None:
    """Detach the persistent cache (test control for measuring true
    cold compiles in-process)."""
    global _active_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", None)
    _reset_jax_cache()
    _active_dir = None


def _reset_jax_cache() -> None:
    """Drop jax's in-process handle on the previous cache directory so
    a retarget actually takes effect."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    cc.reset_cache()


def active_cache_dir() -> Optional[str]:
    return _active_dir


def thread_cache_counts() -> dict:
    """Hit/miss counts of the calling thread's own compiles so far."""
    return {"hits": getattr(_thread_counts, "hits", 0),
            "misses": getattr(_thread_counts, "misses", 0)}


def cache_counts() -> dict:
    """Hit/miss counts of this process so far."""
    from ..telemetry import metrics as tm
    return {"hits": int(tm.FASTGEN_COMPILE_CACHE_HIT.value),
            "misses": int(tm.FASTGEN_COMPILE_CACHE_MISS.value)}
