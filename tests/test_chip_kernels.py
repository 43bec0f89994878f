"""Per-kernel compile-and-parity census on the chip.

Every Pallas kernel in the package, compiled by Mosaic once at one aligned,
realistically sized shape and compared with its XLA oracle. Skipped unless
the backend is a TPU, so the CPU tier never runs it; through the chip tool:

    JAX_PLATFORMS=tpu python -m pytest tests/test_chip_kernels.py -v

(conftest.py pins the CPU only when JAX_PLATFORMS does not name another
platform.) A kernel the compiler refuses is `xfail(strict=True)` with the
first line of the refusal as its reason, so the day it compiles the test
fails and says so. The interpret-mode parity of the same kernels on the CPU
is tests/test_fused_lookup.py, test_fused_step.py and test_attention.py.

On the training path (DLRM defaults on a TPU: dim-16 f32 tables in the
packed [C/8, 128] layout): gather_rows and apply_rows_sr on f32 granules,
alone, under the 26-table vmap of a stacked bundle, and inside lax.scan;
and the same vmap at the benchmark cells' shape (26 tables of [2^18, 128],
2304 and 8200 rows a table), where the kernels' own table axis has to hold:
one Pallas call an operation and table range, no table-sized slice round it.
Off the path: the bf16 pair kernels, fused_gather_combine, the fused sparse
step, flash attention forward and backward.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeprec_tpu.ops import fused_lookup as fl
from deeprec_tpu.ops.dedup import resolve_size
from deeprec_tpu.ops.flash_attention import attention_reference, flash_attention
from deeprec_tpu.optim.sparse import Adagrad

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="compiles Mosaic kernels: needs a TPU backend",
)

# One DLRM table as the chip stores it: capacity 2^20, dim 16, packed x8.
G, LANES = (1 << 20) // 8, 128
N = 2048   # ids per table per step at batch 2048
T = 26     # tables in the stacked bundle


def _table(seed, shape=(G, LANES), dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype)


def _ix(seed, n=N, hi=G, shape=None):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, hi, shape or (n,)), jnp.int32)


def _unique_ix(seed, n=N, hi=G):
    """Scatter targets: unique (the kernel's contract), some skipped."""
    rng = np.random.default_rng(seed)
    ix = rng.choice(hi, n, replace=False)
    ix[::7] = -1
    return jnp.asarray(ix, jnp.int32)


def _equal(a, b):
    return bool(jax.jit(lambda x, y: jnp.array_equal(x, y))(a, b))


# ------------------------------------------------------------ on the path


def test_gather_rows_f32():
    vals, ix = _table(0), _ix(1)
    out = jax.jit(fl.gather_rows)(vals, ix)
    assert _equal(out, vals.at[ix].get(mode="clip"))


def test_apply_rows_sr_f32():
    vals, ix = _table(2), _unique_ix(3)
    rows = _table(4, (N, LANES))
    out = jax.jit(fl.apply_rows_sr, donate_argnums=0)(
        vals + 0, ix, rows, jnp.int32(5)
    )
    want = vals.at[jnp.where(ix >= 0, ix, G)].set(rows, mode="drop")
    assert _equal(out, want)


def _stacked():
    vals = _table(6, (T, G, LANES))
    ix = jnp.stack([_unique_ix(100 + t) for t in range(T)])
    rows = _table(7, (T, N, LANES))
    return vals, ix, rows


def _xla_round(vals, ix, rows, rows_a_table=G):
    """One gather + one scatter of a stacked bundle in plain XLA. The
    gather reads at max(ix, 0): callers never gather a negative slot (the
    table passes `safe_ix`), and there `.at[].get` wraps where the kernel
    clamps."""
    got = jax.vmap(lambda v, i: v.at[jnp.maximum(i, 0)].get(mode="clip"))(
        vals, ix
    )
    new = jax.vmap(
        lambda v, i, r: v.at[jnp.where(i >= 0, i, rows_a_table)].set(
            r, mode="drop")
    )(vals, ix, rows)
    return got, new


def _pallas_round(vals, ix, rows):
    got = jax.vmap(fl.gather_rows)(vals, jnp.maximum(ix, 0))
    new = jax.vmap(
        lambda v, i, r: fl.apply_rows_sr(v, i, r, jnp.int32(0))
    )(vals, ix, rows)
    return got, new


def test_row_kernels_under_table_vmap():
    vals, ix, rows = _stacked()
    got, new = jax.jit(_pallas_round)(vals, ix, rows)
    want_got, want_new = jax.jit(_xla_round)(vals, ix, rows)
    assert _equal(got, want_got)
    assert _equal(new, want_new)


def test_row_kernels_inside_scan():
    vals, ix, rows = _stacked()

    def steps(round_fn):
        def body(v, k):
            got, new = round_fn(v, ix, rows + k)
            # a wrapping integer sum of the bit patterns: exact whatever
            # order the two programs reduce in
            bits = jax.lax.bitcast_convert_type(got, jnp.uint32)
            return new, jnp.sum(bits, axis=(1, 2))

        return jax.jit(lambda v: jax.lax.scan(
            body, v, jnp.arange(4, dtype=jnp.float32)
        ))

    new, sums = steps(_pallas_round)(vals)
    want_new, want_sums = steps(_xla_round)(vals)
    assert _equal(new, want_new)
    assert _equal(sums, want_sums)


# The benchmark cells' bundle: 26 dim-128 tables of 2^18 rows (3.5 GB), the
# rows a table a step of `.zipf` (budget 2296 + 8) and `.uniform` (8192 + 8).
C_CELL = 1 << 18


def _cell(n, skip_all=False):
    vals = _table(20, (T, C_CELL, LANES))
    ix = jnp.stack([_unique_ix(200 + t, n, C_CELL) for t in range(T)])
    if skip_all:
        ix = jnp.full_like(ix, -1)
    return vals, ix, _table(21, (T, n, LANES))


def _rows_round(vals, ix, rows):
    """One gather and one scatter of the bundle through the funnels the
    engine calls, so under the `rows_*` scopes and the table vmap."""
    from deeprec_tpu.ops import packed

    got = jax.vmap(lambda v, i: packed.gather_rows_any(
        v, i, C_CELL, use_pallas=True))(vals, jnp.maximum(ix, 0))
    new = jax.vmap(lambda v, i, r: packed.scatter_rows_any(
        v, i, r, C_CELL, use_pallas=True))(vals, ix, rows)
    return got, new


@pytest.mark.parametrize("n", [2304, 8200])
def test_row_kernels_under_table_vmap_at_the_cells_shape(n):
    vals, ix, rows = _cell(n)
    want_got, want_new = jax.jit(
        lambda v, i, r: _xla_round(v, i, r, C_CELL))(vals, ix, rows)
    compiled = jax.jit(_rows_round, donate_argnums=0).lower(
        vals, ix, rows).compile()
    got, new = compiled(vals, ix, rows)  # in place on the donated stack
    assert _equal(got, want_got)
    assert _equal(new, want_new)
    # the mechanism, in the compiled program: one Mosaic call an operation
    # and a table range (tests/test_aot_kernels.py asks the same of the
    # compiler without a chip), no loop round them, and nothing that slices
    # a table out of the stack or writes one back
    hlo = compiled.as_text()
    calls = 2 * len(fl._table_ranges(T, n))
    assert hlo.count('custom_call_target="tpu_custom_call"') == calls, hlo
    assert " while(" not in hlo
    for line in hlo.splitlines():
        name, _, rest = line.strip().partition(" = ")
        result, _, op = rest.partition(" ")
        if "dynamic-" in name + " " + op.split("(", 1)[0]:
            assert not result.startswith(
                (f"f32[{C_CELL},{LANES}]", f"f32[{T},{C_CELL},{LANES}]")
            ), line


@pytest.mark.parametrize("n", [2304, 8200])
def test_half_skipped_scatter_at_the_cells_shape(n):
    """Every other slot skipped: a window of row DMAs in flight holds
    started and skipped rows side by side, and a group is waited for by
    the rows it started."""
    vals, ix, rows = _cell(n)
    ix = jnp.where(jnp.arange(n)[None] % 2 == 0, ix, -1)
    want = jax.jit(lambda v, i, r: _xla_round(v, i, r, C_CELL))(
        vals, ix, rows)
    got = jax.jit(_rows_round)(vals, ix, rows)
    assert _equal(got[0], want[0])
    assert _equal(got[1], want[1])


def test_all_skipped_scatter_at_the_cells_shape_touches_no_table():
    """The insert that creates no row: what `insert` pays a step for."""
    vals, ix, rows = _cell(2304, skip_all=True)
    _, new = jax.jit(_rows_round)(vals, ix, rows)
    assert _equal(new, vals)


def _walk(keys, uids, empty, max_probes):
    """The scalar walk over [T, C] keys and [T, n] ids, a slot a step in
    NumPy: (slot_ix, failed) of a read-only probe."""
    from deeprec_tpu.utils import hashing

    C = keys.shape[1]
    home = np.asarray(hashing.mix32(hashing.fold64(jnp.asarray(uids))))
    slot_ix = np.full(uids.shape, -1, np.int64)
    pending = uids != empty
    t = np.arange(keys.shape[0])[:, None]
    for off in range(max_probes):
        pos = ((home.astype(np.int64) + off) & (C - 1))
        k = keys[t, pos]
        found = pending & (k == uids)
        slot_ix[found] = pos[found]
        pending &= ~(found | (k == empty))
    return slot_ix, pending


@pytest.mark.parametrize("n", [2304, 8200])
def test_probe_under_table_vmap_at_the_cells_shape(n):
    """The find loop's window read at the cells' shape: 26 key arrays of
    2^18 slots filled to a half through the probe itself, then every id a
    table sees in a step (resident ones, absent ones, padding) found where
    the scalar walk finds it; in the compiled program the loop's reads are
    Mosaic calls, a table range each, and no scalar gather of the keys."""
    from deeprec_tpu import EmbeddingTable, TableConfig
    from deeprec_tpu.embedding.table import empty_key

    table = EmbeddingTable(TableConfig(name="t", dim=LANES, capacity=C_CELL))
    empty = empty_key(table.cfg)
    probe = jax.jit(jax.vmap(table._probe), donate_argnums=0)
    keys = jnp.full((T, C_CELL), empty, jnp.int32)
    rng = np.random.default_rng(300 + n)
    held = np.stack([rng.choice(1 << 30, C_CELL // 2, replace=False)
                     for _ in range(T)]).astype(np.int32)
    fill = 8192
    for j in range(0, C_CELL // 2, fill):
        chunk = jnp.asarray(held[:, j:j + fill])
        keys, _, created, failed = probe(
            keys, chunk, jnp.ones(chunk.shape, bool))
        assert bool(created.all()) and not bool(failed.any())
    uids = np.concatenate([
        held[:, rng.permutation(C_CELL // 2)[:n - 64]],
        rng.integers(1 << 30, (1 << 31) - 1, (T, 56)).astype(np.int32),
        np.full((T, 8), empty, np.int32)], axis=1)
    before = np.asarray(keys)
    want_ix, want_failed = _walk(before, uids, empty, table.cfg.max_probes)
    compiled = probe.lower(keys, jnp.asarray(uids),
                           jnp.zeros(uids.shape, bool)).compile()
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == len(
        fl._table_ranges(T, n))
    new_keys, slot_ix, created, failed = compiled(
        keys, jnp.asarray(uids), jnp.zeros(uids.shape, bool))
    np.testing.assert_array_equal(np.asarray(slot_ix), want_ix)
    np.testing.assert_array_equal(np.asarray(failed), want_failed)
    assert not bool(created.any())
    np.testing.assert_array_equal(np.asarray(new_keys), before)
    assert (want_ix[:, :n - 64] >= 0).all() and (want_ix[:, n - 64:] < 0).all()


@pytest.mark.parametrize("tables,capacity,dim", [
    (T, C_CELL, LANES),     # the cell's bundle: every table's 2^18 slots
    (1, 1 << 20, 16),       # one of chip_smoke's tables (packed rows)
])
def test_rebuild_at_capacity_walks_the_find_loop_in_slices(tables, capacity,
                                                           dim):
    """rebuild (and through it evict, grow, maintain) probes ALL slots of
    a table into a fresh one: tables filled to a half, two thirds of the
    keys kept. In the compiled program no window of keys is larger than a
    slice of the ids (table.py::_PROBE_SLICE) a table and no row-kernel
    call carries more indices than SMEM's budget; afterwards every kept
    key stands where a read-only probe finds it, with the row it had, and
    nothing else is held."""
    import dataclasses
    import re

    from deeprec_tpu import EmbeddingTable, TableConfig
    from deeprec_tpu.embedding import table as table_module
    from deeprec_tpu.embedding.table import empty_key
    from deeprec_tpu.ops.packed import pack_array, unpack_array

    table = EmbeddingTable(TableConfig(name="t", dim=dim, capacity=capacity))
    empty = empty_key(table.cfg)
    half = capacity // 2
    rng = np.random.default_rng(capacity + tables)
    held = np.stack([rng.choice(1 << 30, half, replace=False)
                     for _ in range(tables)]).astype(np.int32)
    probe = jax.jit(jax.vmap(table._probe), donate_argnums=0)
    keys = jnp.full((tables, capacity), empty, jnp.int32)
    for j in range(0, half, 8192):
        chunk = jnp.asarray(held[:, j:j + 8192])
        keys, _, created, failed = probe(
            keys, chunk, jnp.ones(chunk.shape, bool))
        assert bool(created.all()) and not bool(failed.any())
    # a row holds the number of the slot it stood in before the rebuild
    rows = jnp.broadcast_to(
        jnp.arange(capacity, dtype=jnp.float32)[:, None], (capacity, dim))
    state = jax.jit(jax.vmap(lambda k: dataclasses.replace(
        table.create(), keys=k,
        values=pack_array(rows, table.pack_width(dim)))))(keys)
    old_keys = np.asarray(keys)
    keep = (np.arange(capacity) % 3 != 0)
    rebuild = jax.jit(jax.vmap(
        lambda s: table.rebuild(s, keep=jnp.asarray(keep))), donate_argnums=0)
    compiled = rebuild.lower(state).compile()
    hlo = compiled.as_text()
    windows = {(int(a), int(b)) for a, b in
               re.findall(r"s32\[(\d+),(\d+),128\]", hlo)}
    assert windows and max(a * b for a, b in windows) <= (
        tables * table_module._PROBE_SLICE), windows
    assert 'custom_call_target="tpu_custom_call"' in hlo
    new = compiled(state)
    assert int(jnp.sum(new.insert_fails)) == 0
    new_keys = np.asarray(new.keys)
    for t in range(tables):
        kept = old_keys[t][keep & (old_keys[t] != empty)]
        assert np.array_equal(np.sort(new_keys[t][new_keys[t] != empty]),
                              np.sort(kept))
    # every kept key where a read-only probe finds it, with its old row
    first = jax.jit(jax.vmap(
        lambda v: unpack_array(v, capacity)[:, 0]))(new.values)
    was = np.where(keep & (old_keys != empty), np.arange(capacity), -1)
    uids = np.where(was >= 0, old_keys, empty)[:, :8192 * 4]
    _, slot_ix, _, failed = jax.jit(jax.vmap(table._probe))(
        new.keys, jnp.asarray(uids), jnp.zeros(uids.shape, bool))
    slot_ix = np.asarray(slot_ix)
    assert not bool(failed.any())
    assert ((slot_ix >= 0) == (uids != empty)).all()
    got = np.take_along_axis(np.asarray(first), np.maximum(slot_ix, 0), 1)
    assert np.array_equal(got[slot_ix >= 0],
                          was[:, :uids.shape[1]][slot_ix >= 0])


# ----------------------------------------------------------- off the path

C_BF16 = 1 << 17  # a dim-128 bf16 table: 32 MB

# Refused by Mosaic (jax 0.9.0, libtpu 0.0.34, TPU v5 lite): a bf16 row is
# half of a packed 32-bit sublane, and the kernels pick the half with a
# dynamic index. AUTO_TRUSTS_BF16_PAIR stays False; kernel="pallas" on a
# bf16 dim-128 table raises this at compile time on the chip.
_PAIR_REFUSAL = (
    "Mosaic failed to compile TPU kernel: cannot statically prove that "
    "index in dimension {} is a multiple of 2"
)


@pytest.mark.xfail(strict=True, reason=_PAIR_REFUSAL.format(1))
def test_gather_rows_pair_bf16():
    vals, ix = _table(8, (C_BF16, LANES), jnp.bfloat16), _ix(9, hi=C_BF16)
    out = jax.jit(fl.gather_rows_pair)(vals, ix)
    assert _equal(out, vals.at[ix].get(mode="clip"))


@pytest.mark.xfail(strict=True, reason=_PAIR_REFUSAL.format(0))
def test_apply_rows_sr_pair_bf16():
    vals = _table(10, (C_BF16, LANES), jnp.bfloat16)
    ix = _unique_ix(11, hi=C_BF16)
    rows = _table(12, (N, LANES))
    seed = jnp.int32(13)
    out = jax.jit(fl.apply_rows_sr_pair)(vals, ix, rows, seed)
    want = jax.jit(lambda v, i, r, s: fl.apply_rows_sr(
        v, i, r, s, use_pallas=False
    ))(vals, ix, rows, seed)
    assert _equal(out, want)


def test_fused_gather_combine_f32():
    B, L = 2048, 4
    vals = _table(14)
    ix = _ix(15, shape=(B, L)).at[::5, 2:].set(-1)
    w = jax.random.uniform(jax.random.PRNGKey(16), (B, L))
    out = jax.jit(fl.fused_gather_combine)(vals, ix, w)
    e = vals.at[jnp.clip(ix, 0, G - 1)].get(mode="clip")
    want = jnp.sum(e * jnp.where(ix >= 0, w, 0.0)[..., None], axis=1)
    np.testing.assert_allclose(out, want, rtol=1e-6, atol=1e-6)


def _bags(seed, B=512, L=4, vocab=1500):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, vocab, (B, L))
    ids[::9, 1:] = -1
    return jnp.asarray(ids, jnp.int32)


def _fused_step(use_pallas, U):
    opt = Adagrad(lr=0.05)

    def fn(vals, slots, ids):
        res = fl.fused_sparse_forward(
            vals, ids, combiner="sum", unique_size=U, use_pallas=use_pallas
        )
        new_vals, new_slots = fl.fused_sparse_backward(
            vals, slots, res.out * 0.25 + 1.0, ids, res, opt,
            combiner="sum", step=3, use_pallas=use_pallas,
        )
        return res.out, res.overflow, new_vals, new_slots

    return jax.jit(fn)


def test_fused_sparse_step_f32():
    ids = _bags(17)
    U = resolve_size(2048, ids.size)
    vals = _table(18, (1 << 14, LANES))
    slots = {
        name: jnp.full(vals.shape, init, jnp.float32)
        for name, (_, init) in Adagrad(lr=0.05).slot_specs(LANES).items()
    }
    out, ovf, new_vals, new_slots = _fused_step(True, U)(vals, slots, ids)
    w_out, w_ovf, w_vals, w_slots = _fused_step(False, U)(vals, slots, ids)
    assert int(ovf) == int(w_ovf) == 0
    assert _equal(out, w_out)
    assert _equal(new_vals, w_vals)
    for name in slots:
        assert _equal(new_slots[name], w_slots[name])


@pytest.mark.parametrize("head_dim", [128, 64])
def test_flash_attention_fwd_bwd(head_dim):
    B, H, L = 4, 4, 512
    ks = jax.random.split(jax.random.PRNGKey(19), 3)
    q, k, v = (jax.random.normal(kk, (B, H, L, head_dim)) for kk in ks)
    lens = jnp.asarray([512, 384, 130, 7])
    mask = jnp.arange(L)[None, :] < lens[:, None]

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v) ** 2)

    flash = jax.jit(jax.value_and_grad(
        loss(lambda q, k, v: flash_attention(q, k, v, mask, True)), (0, 1, 2)
    ))
    ref = jax.jit(jax.value_and_grad(
        loss(lambda q, k, v: attention_reference(q, k, v, mask, True)),
        (0, 1, 2),
    ))
    (lf, gf), (lr, gr) = flash(q, k, v), ref(q, k, v)
    # Both sides multiply in bf16 on the MXU at default precision, in
    # different orders (online softmax by blocks vs one row at a time), so
    # single elements differ by a few percent; the arrays as a whole agree.
    np.testing.assert_allclose(lf, lr, rtol=2e-2)
    for a, b in zip(gf, gr):
        err = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert err < 2e-2, err


def test_flash_attention_at_head_dim_64_over_eight_key_value_heads():
    """The short-convolution cell's attention (lfm2-8b-a1b.seq8k): 2
    sequences of 8,192, 32 query heads over 8 key/value heads of 64 (a
    head fills half a lane tile), causal, bf16 operands, blocks of 512,
    forward and the three gradients, against the float32 reference by
    blocks of 512 queries at highest precision."""
    B, H, Hkv, L, D = 2, 32, 8, 8192, 64
    ks = jax.random.split(jax.random.PRNGKey(43), 4)
    q = jax.random.normal(ks[0], (B, H, L, D))
    k, v = (jax.random.normal(kk, (B, Hkv, L, D)) for kk in ks[1:3])
    do = jax.random.normal(ks[3], (B, H, L, D))
    mask = jnp.ones((B, L), bool)

    def flash(q, k, v):
        cdt = jnp.bfloat16
        return flash_attention(q.astype(cdt), k.astype(cdt), v.astype(cdt),
                               mask, True, D ** -0.5, 512, 512)

    def blocked(q, k, v):
        k, v = jnp.repeat(k, H // Hkv, axis=1), jnp.repeat(v, H // Hkv, axis=1)

        @jax.checkpoint
        def one(args):
            qb, start = args                               # [B, H, 512, D]
            s = jnp.einsum("bhqd,bhkd->bhqk", qb, k,
                           precision="highest") * D ** -0.5
            seen = (start + jnp.arange(512))[:, None] >= jnp.arange(L)
            p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
            return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")

        qs = jnp.moveaxis(q.reshape(B, H, L // 512, 512, D), 2, 0)
        o = jax.lax.map(one, (qs, jnp.arange(L // 512) * 512))
        return jnp.moveaxis(o, 0, 2).reshape(B, H, L, D)

    def fwd_bwd(attn):
        def run(q, k, v):
            o, vjp = jax.vjp(attn, q, k, v)
            return (o,) + vjp(do.astype(o.dtype))
        return jax.jit(run)

    got = fwd_bwd(flash)(q, k, v)
    want = fwd_bwd(blocked)(q, k, v)
    # bf16 operands against float32: a few percent an element, the arrays
    # as a whole within 2 % (test_flash_attention_fwd_bwd's reasoning)
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        err = float(jnp.linalg.norm(a.astype(jnp.float32) - b)
                    / jnp.linalg.norm(b))
        assert err < 2e-2, (name, err)
