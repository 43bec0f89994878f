"""Test harness: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's strategy of testing distributed behavior with
in-process fake clusters (SURVEY.md §4): jax's host-platform device-count
flag gives us 8 fake devices so sharding/collective paths compile and run
without TPU hardware. The CPU is the platform unless JAX_PLATFORMS names
another one: the chip-only kernel census runs through the chip tool as
`JAX_PLATFORMS=tpu python -m pytest tests/test_chip_kernels.py`.

Speed: the default run excludes tests marked ``slow`` (multi-process
launches, the largest compile grids) so `pytest -q` gives a quick green;
``DEEPREC_FULL_TESTS=1`` runs everything (any explicit ``-m`` expression
also takes over, e.g. ``-m 'slow or not slow'``).

Compilation cache: off unless the caller places one with
JAX_COMPILATION_CACHE_DIR. The abort on reloading a cached CPU executable
that an older jax showed does not reproduce on jax 0.9.0 (15 reload runs
of the checkpoint, checkpoint-corruption and launch modules against one
cache with every executable cached, 0 failures), so a placed cache is
honoured; without one the in-memory jit cache is all the suite needs, and
the flag below also keeps the subprocess workers' entry points (which call
utils.backend.enable_compile_cache) from filling <repo>/.jax_cache.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import pytest  # noqa: E402

import jax  # noqa: E402  (import after env setup)

jax.config.update("jax_threefry_partitionable", True)


def pytest_collection_modifyitems(config, items):
    """Skip slow-marked tests by default; DEEPREC_FULL_TESTS=1 (or an
    explicit -m) runs the full grid."""
    if os.environ.get("DEEPREC_FULL_TESTS") == "1" or config.option.markexpr:
        return
    skip = pytest.mark.skip(
        reason="slow; set DEEPREC_FULL_TESTS=1 (or -m slow) to run"
    )
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)
