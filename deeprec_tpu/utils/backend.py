"""What the process runs on, and where its compiled programs are kept.

One definition of each so that no layer can answer differently: the packed
table layout (embedding/table.py), the Pallas row kernels
(ops/fused_lookup.py) and flash attention (ops/flash_attention.py) all
dispatch on `on_tpu()`, and every entry point that reaches the device
(chip_smoke.py, modelzoo/common.py, bench.py, launch.py, the serving
backend CLI) calls `enable_compile_cache()` before its first jit.
"""
from __future__ import annotations

import os

import jax

from deeprec_tpu.obs import compile_log

# <repo>/.jax_cache, resolved from this file so that every entry point of
# one checkout agrees on it. The directory is part of the cache key's
# lookup, so it must not move between runs: never a tempdir, a pid or a
# timestamp.
_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)
# The in-checkout cache rides every copy of the tree, so it is capped
# (least recently used entries go first).
_CACHE_MAX_BYTES = 128 << 20


def on_tpu() -> bool:
    """Whether jax resolves to a TPU backend. Deliberately unguarded: a
    backend that fails to initialize must raise here, not read as "no TPU"
    and quietly select the unpacked layout and the XLA paths."""
    return jax.default_backend() == "tpu"


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache and return its directory.

    `JAX_COMPILATION_CACHE_DIR`, when set, is the caller's placement and is
    left entirely to jax (which reads the variable itself); otherwise the
    cache goes to the fixed `<repo>/.jax_cache`. Installs the recorder of
    set-up (obs/compile_log.py) and tells it what the directory holds.
    """
    compile_log.install()
    # A Pallas kernel is serialized into its program with the Python call
    # stack of the trace as location info, and so into the cache key: the
    # same train step traced from two callers (or after an edit that moves
    # a caller's line) would never hit. One frame is enough for an error.
    # (Not `jax_include_full_tracebacks_in_locations=False`: that also cuts
    # every instruction's `op_name` down to its primitive. The scopes of
    # utils/scopes.py, which a device trace is read by, are then left only
    # in the function names of the stack frames, where the bodies of loops
    # hold none: the compiler rebuilds the loops round the vmapped row
    # kernels without their metadata, and half a train step reads as
    # another stage's (PERF.md section 6, PR 26). With the scopes in
    # `op_name` the TPU compiler names a Pallas call after the innermost of
    # them, `closed_call.N` where it was `tpu_custom_call.N`: a reader
    # knows a kernel by its custom-call target, not by that name.)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        compile_log.note_cache_dir(placed, -1)
        return placed
    jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", _CACHE_MAX_BYTES)
    compile_log.note_cache_dir(_CACHE_DIR, _CACHE_MAX_BYTES)
    return _CACHE_DIR
