"""Settings for every test process in the checkout (``tests/`` and
``chipbench/tests/``), applied before JAX creates its CPU backend.

LLVM's loop-idiom pass, run by XLA's CPU backend on each kernel, spends
more than ten minutes on a CORDIC activation fused into a reduction
(gemma2's ``tanh`` softcap at the q8_8 draft rung); without it that
program compiles in seconds.  The pass only turns loops into
``memset``/``memcpy`` calls and bit-count intrinsics, so the arithmetic,
and every test result, stays that of XLA's default level.  The setting
is appended to any ``XLA_FLAGS`` already set; subprocesses inherit it.
"""

import os

_NO_LOOP_IDIOM = "--xla_backend_extra_options=-disable-loop-idiom-all"

if _NO_LOOP_IDIOM not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + _NO_LOOP_IDIOM
    ).strip()
