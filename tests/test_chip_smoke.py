"""chip_smoke.py between chip runs: its phase functions at tiny size on the
CPU, running the program the chip runs — the backend predicate answers
"TPU" (packed layout, Pallas dispatch under the table vmap and the K-scan)
and every pallas_call is interpreted — plus the script's refusal to start
without a TPU and the compile-cache helper's placement rules."""
import json
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

from deeprec_tpu.utils import backend  # noqa: E402

TINY = chip_smoke.Size(
    capacity=1 << 12, batch=64, vocab=300, steps=120, k=2,
    steady_dispatches=2, max_batch=16, requests=(1, 5, 12), sharded_steps=3,
)


_CACHE_CONFIG = ("jax_compilation_cache_dir", "jax_compilation_cache_max_size",
                 "jax_traceback_in_locations_limit")


@pytest.fixture
def cache_config():
    """enable_compile_cache() edits process-wide jax config; put it back."""
    before = {name: getattr(jax.config, name) for name in _CACHE_CONFIG}
    yield before
    for name, value in before.items():
        jax.config.update(name, value)
    jax.clear_caches()  # a rehearsal leaves ~250 executables behind


@pytest.fixture
def chip_program(monkeypatch, cache_config):
    from jax.experimental import pallas as pl

    compiled = pl.pallas_call
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    monkeypatch.setattr(
        pl, "pallas_call",
        lambda *a, **kw: compiled(*a, **{**kw, "interpret": True}),
    )


def test_phases_at_tiny_size(chip_program, tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "OUT", str(tmp_path / "out"))
    device = chip_smoke.device_report()
    facts = chip_smoke.run(TINY)

    k = facts["kernels"]
    assert k["auto_equals_xla"]
    assert k["pallas_calls"] == k["interpreted_pallas_calls"] > 0
    assert k["pallas_kernels"] == ["apply_rows_sr", "gather_rows"]
    assert k["layout"] == {"group0": [26, TINY.capacity // 8, 128]}
    assert facts["resume"]["health"]["rows"] > facts["resume"]["restored_rows"]
    assert facts["serve"]["buckets_warmed"] == 2
    assert set(facts["sharded"]) >= set(chip_smoke.SHARDED_COMMS)
    assert not os.path.exists(tmp_path / "out" / "ckpt")
    # What a CPU cannot fake, and nothing else: the platform, Mosaic calls
    # in the lowered step, compiled (not interpreted) kernels, and the
    # per-device memory statistics of the two sharded arms.
    bad = chip_smoke.chip_violations(device, facts)
    assert len(bad) == 5, bad
    assert chip_smoke.chip_violations(
        {**device, "platform": "tpu"},
        {"kernels": {**k, "mosaic_custom_calls": 6,
                     "interpreted_pallas_calls": 0}},
    ) == []
    # The driver parses the last stdout line and takes these keys and no
    # other; everything else rides on the line before it.
    capsys.readouterr()
    chip_smoke.report(device, facts)
    summary, last = capsys.readouterr().out.splitlines()
    assert json.loads(last) == {
        "ok": True,
        "device": {"platform": device["platform"], "kind": device["kind"],
                   "count": device["count"]},
    }
    assert summary.startswith("chip_smoke summary: {")
    assert summary.endswith('"claim": null}')


def test_refuses_to_start_without_a_tpu():
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=240,
    )
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stderr
    assert r.stdout == ""


def test_compile_cache_placement(monkeypatch, tmp_path, cache_config):
    before = cache_config["jax_compilation_cache_dir"]
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert backend.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    first = backend.enable_compile_cache()
    assert first == os.path.join(ROOT, ".jax_cache")
    assert backend.enable_compile_cache() == first
    assert jax.config.jax_compilation_cache_dir == first
