"""pytest plugin: a digest of every program a test lowers and compiles,
source locations dropped.

    HASH_OUT=/tmp/change.txt PYTHONPATH=tools python -m pytest \
        tests/test_chip_compile.py -p program_digests -k step_program

writes one line a ``Lowered.compile()``: the test's id, the first 16 hex
digits of the SHA-256 of the lowered text, its length.  The Mosaic bodies of
the Pallas calls are parsed and printed without their locations first (a
caller's moved line is no change of a kernel:
``tests/test_chip_compile.py::mosaic_texts``).  Run it on two checkouts and
compare the files: "the step programs of the families that were here lower
to the parent's text" (PERF.md section 6, PR 54: 26 of 26).
"""
import base64
import hashlib
import os
import re

import jax

OUT = os.environ.get("HASH_OUT", "program_digests.txt")
_compile = jax.stages.Lowered.compile
_BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def digest(text: str) -> str:
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def body(match):
        context = mlir.make_ir_context()
        context.allow_unregistered_dialects = True
        with context:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            asm = module.operation.get_asm(enable_debug_info=False)
        return hashlib.sha256(asm.encode()).hexdigest()

    return hashlib.sha256(_BODY.sub(body, text).encode()).hexdigest()[:16]


def _recording_compile(self, *args, **kwargs):
    text = self.as_text()
    test = os.environ.get("PYTEST_CURRENT_TEST", "?").split(" ")[0]
    with open(OUT, "a") as f:
        f.write(f"{test} {digest(text)} {len(text)}\n")
    return _compile(self, *args, **kwargs)


def pytest_configure(config):
    jax.stages.Lowered.compile = _recording_compile
