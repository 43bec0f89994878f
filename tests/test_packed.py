"""Packed small-dim layout (ops/packed.py): oracle tests.

The packed layout is the round-4 answer to "the headline DLRM shape
(dim 16) is ineligible for every Pallas kernel": P = 128/dim rows ride one
128-lane granule, so granule gathers/scatters reuse the measured dim-128
kernels. These tests pin the layout algebra (pack/unpack round-trip), the
gather/scatter semantics against the unpacked oracle (XLA path on CPU and
the Pallas branch in interpret mode), and the end-to-end table behavior at
dim 16 — the flagship shape.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeprec_tpu.ops.packed import (
    gather_rows_any,
    pack_array,
    pack_factor,
    row_factor,
    scatter_rows_any,
    unpack_array,
)


def test_pack_factor_rules():
    assert pack_factor(16, 1024) == 8
    assert pack_factor(1, 1024) == 128
    assert pack_factor(32, 1024) == 4
    assert pack_factor(128, 1024) == 1  # already lane-sized
    assert pack_factor(48, 1024) == 1  # does not divide 128
    assert pack_factor(16, 100) == 1  # capacity not a granule multiple
    assert pack_factor(128, 64) == 1
    # capacity smaller than the would-be factor
    assert pack_factor(1, 64) == 1


def test_pack_unpack_roundtrip_and_row_factor():
    C, D = 64, 16
    arr = jnp.arange(C * D, dtype=jnp.float32).reshape(C, D)
    p = pack_factor(D, C)
    packed = pack_array(arr, p)
    assert packed.shape == (C // p, p * D)
    assert row_factor(packed, C) == p
    assert row_factor(arr, C) == 1
    np.testing.assert_array_equal(unpack_array(packed, C), arr)
    # numpy unpack is a free view of the same row-major data
    np_packed = np.asarray(packed)
    np.testing.assert_array_equal(
        unpack_array(np_packed, C), np.asarray(arr)
    )


@pytest.mark.parametrize("use_pallas", [False, True])
def test_gather_packed_matches_oracle(use_pallas):
    C, D = 64, 16
    rng = np.random.RandomState(0)
    logical = jnp.asarray(rng.randn(C, D).astype(np.float32))
    packed = pack_array(logical, pack_factor(D, C))
    ix = jnp.asarray([0, 1, 7, 8, 9, 63, 62, 5, 5, 0], jnp.int32)
    out = gather_rows_any(packed, ix, C, use_pallas=use_pallas,
                          interpret=use_pallas)
    np.testing.assert_allclose(out, logical[ix], rtol=0, atol=0)


def test_gather_packed_clips_out_of_range():
    C, D = 32, 32
    logical = jnp.arange(C * D, dtype=jnp.float32).reshape(C, D)
    packed = pack_array(logical, pack_factor(D, C))
    ix = jnp.asarray([-3, C + 5, C - 1], jnp.int32)
    out = gather_rows_any(packed, ix, C)
    np.testing.assert_array_equal(out[0], logical[0])
    np.testing.assert_array_equal(out[1], logical[C - 1])
    np.testing.assert_array_equal(out[2], logical[C - 1])


@pytest.mark.parametrize("use_pallas", [False, True])
def test_scatter_packed_matches_oracle(use_pallas):
    """Updates hitting several rows of the same granule plus skips."""
    C, D = 64, 16
    rng = np.random.RandomState(1)
    logical = jnp.asarray(rng.randn(C, D).astype(np.float32))
    packed = pack_array(logical, pack_factor(D, C))
    slot_ix = jnp.asarray([0, 1, 2, 9, -1, 63], jnp.int32)  # 0..2 share g0
    rows = jnp.asarray(rng.randn(6, D).astype(np.float32))
    out = scatter_rows_any(packed, slot_ix, rows, C, seed=3,
                           use_pallas=use_pallas, interpret=use_pallas)
    expect = np.array(logical)
    for i, s in enumerate([0, 1, 2, 9, -1, 63]):
        if s >= 0:
            expect[s] = np.asarray(rows[i])
    np.testing.assert_allclose(unpack_array(out, C), expect, rtol=0, atol=0)


def test_scatter_packed_all_skipped_is_noop():
    C, D = 32, 16
    logical = jnp.ones((C, D), jnp.float32)
    packed = pack_array(logical, pack_factor(D, C))
    out = scatter_rows_any(
        packed, jnp.full((4,), -1, jnp.int32), jnp.zeros((4, D)), C
    )
    np.testing.assert_array_equal(out, packed)


def test_scatter_packed_bf16_preserves_untouched_lanes():
    """The SR-identity property the merge relies on: granule-mates of an
    updated row come back bit-identical."""
    C, D = 64, 16
    rng = np.random.RandomState(2)
    logical = jnp.asarray(rng.randn(C, D).astype(np.float32)).astype(
        jnp.bfloat16
    )
    packed = pack_array(logical, pack_factor(D, C))
    # update row 3 only; rows 0-7 share its granule
    out = scatter_rows_any(packed, jnp.asarray([3], jnp.int32),
                           jnp.full((1, D), 0.123, jnp.float32), C, seed=11)
    got = unpack_array(out, C)
    for r in [0, 1, 2, 4, 5, 6, 7, 8]:
        np.testing.assert_array_equal(
            np.asarray(got[r]), np.asarray(logical[r])
        )
    # the updated row is a stochastic rounding of 0.123 (one of the two
    # bf16 truncation neighbors, never something else)
    up = np.asarray(got[3].astype(jnp.float32))
    u = np.float32(0.123).view(np.uint32) & np.uint32(0xFFFF0000)
    lo = u.view(np.float32)
    hi = (u + np.uint32(0x10000)).view(np.float32)
    assert all(v in (lo, hi) for v in up), (up, lo, hi)


def test_scatter_packed_width1():
    """[C, 1] per-row slots pack 128 rows per granule."""
    C = 256
    logical = jnp.zeros((C, 1), jnp.float32)
    p = pack_factor(1, C)
    assert p == 128
    packed = pack_array(logical, p)
    assert packed.shape == (2, 128)
    slot_ix = jnp.asarray([0, 127, 128, 255, 7], jnp.int32)
    rows = jnp.asarray([[1.0], [2.0], [3.0], [4.0], [5.0]], jnp.float32)
    out = scatter_rows_any(packed, slot_ix, rows, C)
    got = unpack_array(out, C)
    for s, v in zip([0, 127, 128, 255, 7], [1, 2, 3, 4, 5]):
        assert float(got[s, 0]) == v
    back = gather_rows_any(out, slot_ix, C)
    np.testing.assert_array_equal(back, rows)


def test_packed_knob_resolution():
    """cfg.packed gates the layout: "auto" is backend-dependent (unpacked
    off-TPU — packing measured -36% train throughput on CPU, docs/perf.md), "on"/"off" force it. Slots follow the same policy."""
    from deeprec_tpu.config import TableConfig
    from deeprec_tpu.embedding.table import EmbeddingTable
    from deeprec_tpu.optim.apply import ensure_slots
    from deeprec_tpu.optim.sparse import Adagrad

    on = EmbeddingTable(TableConfig(name="a", dim=16, capacity=256,
                                    packed="on"))
    off = EmbeddingTable(TableConfig(name="b", dim=16, capacity=256,
                                     packed="off"))
    auto = EmbeddingTable(TableConfig(name="c", dim=16, capacity=256))
    assert auto.cfg.packed == "auto"
    assert on.pack() == 8
    assert off.pack() == 1
    # tests run with JAX_PLATFORMS=cpu (conftest) -> auto stays unpacked
    assert auto.pack() == 1

    s_on, s_off = on.create(), off.create()
    assert s_on.values.shape == (32, 128)
    assert s_off.values.shape == (256, 16)
    # layout is invisible to semantics: same lookups, same rows
    ids = jnp.asarray([5, 9, 700, 12], jnp.int32)
    s_on, r_on = on.lookup_unique(s_on, ids, step=1)
    s_off, r_off = off.lookup_unique(s_off, ids, step=1)
    assert r_on.embeddings.shape == r_off.embeddings.shape == (4, 16)
    # slot layout follows the knob too
    s_on = ensure_slots(on, s_on, Adagrad(lr=0.1))
    s_off = ensure_slots(off, s_off, Adagrad(lr=0.1))
    assert s_on.slots["accum"].shape == (32, 128)
    assert s_off.slots["accum"].shape == (256, 16)

    with pytest.raises(ValueError):
        TableConfig(name="x", dim=16, capacity=256, packed="maybe")


def test_packed_off_grow_stays_unpacked():
    from deeprec_tpu.config import TableConfig
    from deeprec_tpu.embedding.table import EmbeddingTable

    t = EmbeddingTable(TableConfig(name="g0", dim=16, capacity=64,
                                   packed="off"))
    s = t.create()
    ids = jnp.arange(10, dtype=jnp.int32) * 3 + 1
    s, res = t.lookup_unique(s, ids, step=1)
    grown = t.grow(s, 256)
    assert grown.values.shape == (256, 16)
    np.testing.assert_allclose(
        np.asarray(t.lookup_readonly(grown, ids)),
        np.asarray(res.embeddings)[np.asarray(res.inverse)],
        rtol=0, atol=0,
    )


def test_table_dim16_end_to_end_packed():
    """The flagship shape: a dim-16 table stores packed and trains."""
    from deeprec_tpu.config import TableConfig
    from deeprec_tpu.embedding.table import EmbeddingTable
    from deeprec_tpu.optim.apply import apply_gradients, ensure_slots
    from deeprec_tpu.optim.sparse import Adagrad

    cfg = TableConfig(name="pk", dim=16, capacity=256, packed="on")
    t = EmbeddingTable(cfg)
    assert t.pack() == 8
    s = t.create()
    assert s.values.shape == (32, 128)
    assert s.dim == 16 and s.capacity == 256

    ids = jnp.asarray([5, 9, 5, 1000, 77], jnp.int32)
    s, res = t.lookup_unique(s, ids, step=1)
    assert res.embeddings.shape[1] == 16
    # deterministic initializer: same ids re-looked-up give same rows
    s2, res2 = t.lookup_unique(s, ids, step=2)
    np.testing.assert_allclose(
        np.asarray(res.embeddings), np.asarray(res2.embeddings),
        rtol=0, atol=0,
    )

    opt = Adagrad(lr=0.1)
    s2 = ensure_slots(t, s2, opt)
    assert s2.slots["accum"].shape == (32, 128)  # packed slot too
    g = jnp.ones_like(res2.embeddings)
    s3 = apply_gradients(t, s2, opt, res2, g, step=2)
    s3, res3 = t.lookup_unique(s3, ids, step=3)
    # the update moved every looked-up row
    assert not np.allclose(
        np.asarray(res3.embeddings), np.asarray(res2.embeddings)
    )


def test_table_dim16_checkpoint_roundtrip_packed():
    """Checkpoint format stays LOGICAL rows: export from a packed table,
    import into a fresh one, values identical."""
    from deeprec_tpu.config import TableConfig
    from deeprec_tpu.embedding.table import EmbeddingTable
    from deeprec_tpu.training.checkpoint import (
        _state_to_np,
        export_table_arrays,
        import_rows,
    )

    cfg = TableConfig(name="ck", dim=16, capacity=256, packed="on")
    t = EmbeddingTable(cfg)
    s = t.create()
    ids = jnp.asarray([3, 14, 159, 26, 535], jnp.int32)
    s, res = t.lookup_unique(s, ids, step=7)

    out = export_table_arrays(t, _state_to_np(s), only_dirty=False)
    assert out["values"].shape[1] == 16  # logical rows on disk
    assert out["keys"].shape[0] == 5

    fresh = t.create()
    fresh = import_rows(t, fresh, out)
    emb = t.lookup_readonly(fresh, ids)
    # res.embeddings is in unique-id order; map back to ids order
    expect = np.asarray(res.embeddings)[np.asarray(res.inverse)]
    np.testing.assert_allclose(np.asarray(emb), expect, rtol=0, atol=1e-7)


def test_table_rebuild_grow_packed():
    """Rebuild/grow relocates logical rows across a layout change."""
    from deeprec_tpu.config import TableConfig
    from deeprec_tpu.embedding.table import EmbeddingTable

    cfg = TableConfig(name="gr", dim=16, capacity=64, packed="on")
    t = EmbeddingTable(cfg)
    s = t.create()
    ids = jnp.arange(20, dtype=jnp.int32) * 7 + 1
    s, res = t.lookup_unique(s, ids, step=1)
    before = np.asarray(res.embeddings)

    grown = t.grow(s, 256)
    assert grown.capacity == 256
    # pack factor is per-capacity: 64/8=8 granules before, 32 after
    assert grown.values.shape == (32, 128)
    emb = t.lookup_readonly(grown, ids)
    np.testing.assert_allclose(np.asarray(emb), before, rtol=0, atol=0)
