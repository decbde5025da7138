"""What the tools that time ONE kernel alone on the chip share
(``time_paged_blocks.py``, ``time_expert_tiles.py``): calls back to back on
the host's clock, under a watchdog."""
import os
import threading
import time


def start_watchdog(limit: float = 100.0) -> list:
    """``beat``: a one-item list the caller stamps with ``time.monotonic()``
    whenever a call has come back.  A call that hangs must not hold the
    chip (PERF.md, PR 27): ``limit`` seconds without a stamp and the
    process exits with 3."""
    beat = [time.monotonic()]

    def watchdog():
        while True:
            time.sleep(5)
            if time.monotonic() - beat[0] > limit:
                print(f"watchdog: {limit:.0f} s in one call", flush=True)
                os._exit(3)
    threading.Thread(target=watchdog, daemon=True).start()
    return beat


def ms_a_call(run, *operands, calls: int, beat: list):
    """(ms a call over ``calls`` calls of ``run(*operands)`` back to back
    after one warm call, the last result: an array or a tree of them)."""
    import jax
    out = jax.block_until_ready(run(*operands))     # warm
    beat[0] = time.monotonic()
    t0 = time.perf_counter()
    for _ in range(calls):
        out = run(*operands)
    jax.block_until_ready(out)
    ms = (time.perf_counter() - t0) * 1e3 / calls
    beat[0] = time.monotonic()
    return ms, out
