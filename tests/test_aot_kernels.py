"""The row kernels of the training path, compiled for a v5e that is
described and not attached, at the benchmark cells' shape.

Interpret mode cannot see what Mosaic and the TPU compiler refuse (SMEM,
tiling) nor what XLA builds round a Pallas call; the chip can, at chip time
(tests/test_chip_kernels.py). This file asks the compiler installed here, at
no chip time: nothing runs, so it says nothing about results or speed.

Every compile is in a test or a fixture of THIS file, never at import: one
process at a time may load the TPU's library, each xdist worker imports
every test file, and only the worker handed this file may load it.
"""
import jax
import jax.numpy as jnp
import pytest

from deeprec_tpu.ops import fused_lookup as fl
from deeprec_tpu.ops import packed
from deeprec_tpu.utils import backend

T, C, D = 26, 1 << 18, 128   # dlrmdcn-fullrank-d128's stacked bundle


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _round(vals, ix, rows):
    """One gather and one scatter of the bundle through the funnels the
    engine calls, under the table vmap."""
    got = jax.vmap(lambda v, i: packed.gather_rows_any(
        v, i, C, use_pallas=True))(vals, jnp.maximum(ix, 0))
    new = jax.vmap(lambda v, i, r: packed.scatter_rows_any(
        v, i, r, C, use_pallas=True))(vals, ix, rows)
    return got, new


def _compile(one_chip, monkeypatch, n, fn=_round):
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    sd = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    return jax.jit(fn, donate_argnums=0).lower(
        sd((T, C, D), jnp.float32), sd((T, n), jnp.int32),
        sd((T, n, D), jnp.float32)).compile()


def _table_sized(hlo):
    """The instructions (or fusions the compiler named after one) that
    slice a table out of the stack or write one back: a dynamic slice whose
    own result is as large as a table."""
    found = []
    for line in hlo.splitlines():
        name, _, rest = line.strip().partition(" = ")
        result, _, op = rest.partition(" ")
        made = name + " " + op.split("(", 1)[0]  # a fusion is named for it
        if ("dynamic-slice" in made or "dynamic-update-slice" in made) and (
                result.startswith(f"f32[{C},{D}]")
                or result.startswith(f"f32[{T},{C},{D}]")):
            found.append(line.strip()[:200])
    return found


@pytest.mark.parametrize("n", [2304, 8200, 16384])  # `.zipf`, `.uniform`: U + 8
def test_vmapped_row_kernels_compile_with_no_table_sized_slice(
        one_chip, monkeypatch, n):
    """One Mosaic call an operation and a table range (`.zipf`: one call
    for all 26 tables; past the SMEM budget a few, over ranges of the same
    whole array), no loop round them, nothing that slices a table out of
    the stack or writes one back, and no second values-sized buffer."""
    calls = len(fl._table_ranges(T, n))
    assert (calls == 1) == (n == 2304)
    compiled = _compile(one_chip, monkeypatch, n)
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2 * calls
    assert " while(" not in hlo
    assert _table_sized(hlo) == []
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < T * C * D * 4 // 4
    assert mem.alias_size_in_bytes >= T * C * D * 4  # in place


def test_mosaic_refuses_indices_past_a_cores_smem(one_chip, monkeypatch):
    """Why the indices cannot all ride one call whatever their number: a
    v5e core has 1 MiB of SMEM and the refusal names the operand. (The
    budget is far inside it for another reason: fused_lookup.py.)"""
    monkeypatch.setattr(fl, "_SMEM_INDEX_BYTES", 1 << 30)
    _compile(one_chip, monkeypatch, 10000)
    with pytest.raises(Exception, match="prefetched SMEM operand"):
        _compile(one_chip, monkeypatch, 12000)
