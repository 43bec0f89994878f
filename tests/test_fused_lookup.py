"""Fused Pallas lookup kernels vs XLA oracles (interpret mode on CPU).

Covers the three kernels in ops/fused_lookup.py — DMA gather, fused
gather+combine, stochastic-rounded scatter-apply — plus the XLA
stochastic_round utility's statistical contract.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeprec_tpu.ops import fused_lookup as fl
from deeprec_tpu.ops.fused_lookup import (
    apply_rows_sr,
    fused_gather_combine,
    gather_rows,
    stochastic_round,
)


def test_gather_rows_matches_oracle():
    rng = np.random.default_rng(0)
    vals = jnp.asarray(rng.normal(0, 1, (512, 128)).astype(np.float32))
    ix = jnp.asarray(rng.integers(0, 512, 128), jnp.int32)
    out = gather_rows(vals, ix, block=16, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(vals)[np.asarray(ix)], rtol=1e-6
    )


def test_gather_rows_clamps_and_pads():
    vals = jnp.arange(64, dtype=jnp.float32).reshape(8, 8) * jnp.ones((8, 8))
    # n=6 is NOT a multiple of block=8: exercises the pad-and-slice path.
    ix = jnp.array([-5, 100, 3, 0, 7, 2], jnp.int32)
    out = gather_rows(vals, ix, block=8, interpret=True)
    expect = np.asarray(vals)[np.clip(np.asarray(ix), 0, 7)]
    assert out.shape == (6, 8)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-6)


@pytest.mark.parametrize("combiner", ["sum", "mean"])
def test_fused_gather_combine_matches_oracle(combiner):
    rng = np.random.default_rng(1)
    C, D, B, L = 256, 16, 12, 5  # B=12 not a multiple of block_b=8
    vals = jnp.asarray(rng.normal(0, 1, (C, D)).astype(np.float32))
    row_ix = rng.integers(-1, C, (B, L)).astype(np.int32)  # -1 = pad
    n = np.maximum((row_ix >= 0).sum(1, keepdims=True), 1)
    w = np.where(row_ix >= 0, 1.0 if combiner == "sum" else 1.0 / n, 0.0)
    out = fused_gather_combine(
        vals, jnp.asarray(row_ix), jnp.asarray(w, jnp.float32),
        block_b=8, interpret=True,
    )
    e = np.asarray(vals)[np.clip(row_ix, 0, C - 1)]
    expect = (e * w[..., None]).sum(1)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5, atol=1e-6)


def test_apply_rows_f32_matches_oracle_interpret():
    rng = np.random.default_rng(2)
    C, D, U = 64, 8, 10  # U=10 pads to 16
    vals = jnp.asarray(rng.normal(0, 1, (C, D)).astype(np.float32))
    slot_ix = jnp.asarray([3, -1, 7, 0, 63, 5, -1, 9, 11, 2], jnp.int32)
    new_rows = jnp.asarray(rng.normal(0, 1, (U, D)).astype(np.float32))
    out = apply_rows_sr(vals, slot_ix, new_rows, jnp.int32(0),
                        block=8, interpret=True)
    expect = np.asarray(vals).copy()
    for u, s in enumerate(np.asarray(slot_ix)):
        if s >= 0:
            expect[s] = np.asarray(new_rows)[u]
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-6)


def test_apply_rows_bf16_rounds_to_neighbors_interpret():
    """bf16 writes must land on one of the two bf16 neighbors of the f32
    value (stochastic rounding), and skipped rows stay untouched."""
    C, D, U = 32, 8, 8
    vals = jnp.zeros((C, D), jnp.bfloat16)
    slot_ix = jnp.asarray([0, 1, 2, 3, -1, 5, 6, 7], jnp.int32)
    x = np.float32(1.0 + 1e-3)  # not bf16-representable
    new_rows = jnp.full((U, D), x, jnp.float32)
    out = apply_rows_sr(vals, slot_ix, new_rows, jnp.int32(7),
                        block=8, interpret=True)
    out = np.asarray(out, np.float32)
    lo = np.float32(jnp.bfloat16(1.0))
    hi = np.float32(np.nextafter(np.float32(lo), np.float32(2)))  # next bf16
    hi = np.float32(jnp.asarray(lo, jnp.float32) + 2.0 ** -7)
    written = out[[0, 1, 2, 3, 5, 6, 7]]
    assert np.isin(written, [lo, hi]).all(), np.unique(written)
    np.testing.assert_allclose(out[4], 0.0)


def test_stochastic_round_is_unbiased_and_exact_on_representable():
    key = jax.random.PRNGKey(0)
    # Exactly-representable values never move.
    x = jnp.asarray([0.0, 1.0, -2.5, 0.15625], jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(stochastic_round(x, key), np.float32), np.asarray(x)
    )
    # Unrepresentable values round to a neighbor, unbiased in expectation.
    v = np.float32(1.0 + 2.0 ** -9)  # 1/4 of the way between 1.0 and 1+2^-7
    xs = jnp.full((200_000,), v, jnp.float32)
    r = np.asarray(stochastic_round(xs, key), np.float32)
    assert set(np.unique(r)) <= {np.float32(1.0), np.float32(1.0 + 2.0 ** -7)}
    mean = r.mean()
    np.testing.assert_allclose(mean, v, rtol=3e-4)


def test_kernel_config_wiring_end_to_end():
    """kernel="pallas" tables train identically to kernel="xla" off-TPU
    (the fallback is the same XLA program); exercises the full wiring
    through lookup_unique + apply_gradients."""
    import dataclasses

    from deeprec_tpu import EmbeddingTable, TableConfig
    from deeprec_tpu.optim import Adagrad, apply_gradients, ensure_slots

    res_by_kernel = {}
    for kernel in ("xla", "pallas"):
        cfg = TableConfig(name="k", dim=8, capacity=128, kernel=kernel)
        t = EmbeddingTable(cfg)
        opt = Adagrad(lr=0.5)
        s = ensure_slots(t, t.create(), opt)
        ids = jnp.asarray([5, 9, 5, 13], jnp.int32)
        for step in range(3):
            s, res = t.lookup_unique(s, ids, step=step)
            s = apply_gradients(t, s, opt, res,
                                jnp.ones_like(res.embeddings), step=step)
        res_by_kernel[kernel] = np.asarray(
            t.lookup_readonly(s, jnp.asarray([5, 9, 13], jnp.int32))
        )
    np.testing.assert_allclose(
        res_by_kernel["xla"], res_by_kernel["pallas"], rtol=1e-6
    )


def test_bf16_table_sr_preserves_small_updates_in_expectation():
    """A bf16 table with updates far below ulp/2 must still drift: SR keeps
    E[stored] == target where round-to-nearest would freeze at 1.0."""
    from deeprec_tpu import EmbeddingTable, TableConfig
    from deeprec_tpu.optim import GradientDescent, apply_gradients, ensure_slots

    cfg = TableConfig(name="sr", dim=128, capacity=1024,
                      value_dtype="bfloat16",
                      ev=__import__("deeprec_tpu").EmbeddingVariableOption(
                          init=__import__("deeprec_tpu").InitializerOption(
                              kind="constant", constant=1.0)))
    t = EmbeddingTable(cfg)
    opt = GradientDescent(lr=1.0)
    s = ensure_slots(t, t.create(), opt)
    ids = jnp.arange(256, dtype=jnp.int32)
    # each step subtracts 1e-4 — ulp(1.0) in bf16 is 2^-7 ≈ 7.8e-3, so RTN
    # would never move off 1.0; SR moves the mean by ~1e-4 per step.
    g = jnp.full((256, 128), 1e-4, jnp.float32)
    for step in range(200):
        s, res = t.lookup_unique(s, ids, step=step)
        s = apply_gradients(t, s, opt, res, g, step=step)
    mean = float(jnp.mean(s.values[:].astype(jnp.float32)
                          [np.asarray(t.occupied(s))]))
    expect = 1.0 - 200 * 1e-4  # 0.98
    assert abs(mean - expect) < 4e-3, mean


def test_gather_rows_xla_fallback_identical():
    """Off-TPU the public entry points use XLA with identical semantics."""
    rng = np.random.default_rng(3)
    vals = jnp.asarray(rng.normal(0, 1, (128, 32)).astype(np.float32))
    ix = jnp.asarray(rng.integers(0, 128, 24), jnp.int32)
    a = gather_rows(vals, ix)  # XLA path on CPU
    b = gather_rows(vals, ix, interpret=True)  # Pallas interpreter
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_gather_rows_pair_bf16_matches_oracle():
    """bf16 pair-granule gather == XLA gather, including odd indices,
    duplicates, clamping, and non-block-multiple n."""
    from deeprec_tpu.ops.fused_lookup import gather_rows_pair

    rng = np.random.default_rng(3)
    vals = jnp.asarray(
        rng.normal(0, 1, (256, 128)).astype(np.float32)
    ).astype(jnp.bfloat16)
    ix = jnp.asarray([1, 1, 0, 255, 254, 7, -3, 300, 13, 13, 12, 200, 77],
                     jnp.int32)
    out = gather_rows_pair(vals, ix, block=8, interpret=True)
    expect = np.asarray(vals)[np.clip(np.asarray(ix), 0, 255)]
    assert out.dtype == jnp.bfloat16 and out.shape == (13, 128)
    np.testing.assert_array_equal(np.asarray(out), expect)

    # dispatch: gather_rows(pair_kernels=True) routes bf16 here under
    # interpret, and to XLA when pair_kernels=False
    out2 = gather_rows(vals, ix, block=8, interpret=True, pair_kernels=True)
    np.testing.assert_array_equal(np.asarray(out2), expect)


def test_apply_rows_sr_pair_bf16_matches_semantics():
    """Pair-granule RMW scatter: written rows round to a bf16 neighbor of
    the f32 target, untouched rows (including the OTHER half of a touched
    granule) are bit-identical, skips (<0) skip, and consecutive updates
    sharing a granule both land."""
    from deeprec_tpu.ops.fused_lookup import apply_rows_sr_pair

    rng = np.random.default_rng(4)
    vals = jnp.asarray(
        rng.normal(0, 1, (64, 128)).astype(np.float32)
    ).astype(jnp.bfloat16)
    before = np.asarray(vals).copy()
    # rows 6 and 7 share a granule; 11 is odd-half-only; 20 even-half-only
    slot_ix = jnp.asarray([6, 7, 11, 20, -1], jnp.int32)
    new = jnp.asarray(rng.normal(0, 1, (5, 128)).astype(np.float32))
    out = np.asarray(
        apply_rows_sr_pair(vals, slot_ix, new, jnp.int32(9), interpret=True)
    )
    newf = np.asarray(new, np.float32)
    for row, target in ((6, 0), (7, 1), (11, 2), (20, 3)):
        lo = np.asarray(jnp.asarray(newf[target]).astype(jnp.bfloat16))
        # stochastic rounding: each element equals a bf16 neighbor of the
        # f32 value (nextafter up or the truncation down)
        got = out[row]
        down = np.asarray(
            jax.lax.bitcast_convert_type(
                jax.lax.bitcast_convert_type(
                    jnp.asarray(newf[target]), jnp.uint32
                ) & jnp.uint32(0xFFFF0000), jnp.float32
            ).astype(jnp.bfloat16)
        )
        up = np.asarray(
            jax.lax.bitcast_convert_type(
                (jax.lax.bitcast_convert_type(
                    jnp.asarray(newf[target]), jnp.uint32
                ) & jnp.uint32(0xFFFF0000)) + jnp.uint32(0x10000),
                jnp.float32,
            ).astype(jnp.bfloat16)
        )
        ok = (got == down) | (got == up)
        assert ok.all(), (row, np.nonzero(~ok))
    # untouched rows — ESPECIALLY granule-mates 10 and 21 — unchanged
    untouched = [i for i in range(64) if i not in (6, 7, 11, 20)]
    np.testing.assert_array_equal(out[untouched], before[untouched])


@pytest.mark.parametrize("combiner", ["sum", "mean"])
def test_fused_gather_combine_pair_bf16(combiner):
    """bf16 pair-granule bag pooling == XLA oracle (weights carry the
    combiner; skips at -1; odd/even slots both land)."""
    rng = np.random.default_rng(6)
    vals = jnp.asarray(
        rng.normal(0, 1, (128, 128)).astype(np.float32)
    ).astype(jnp.bfloat16)
    B, L = 6, 5
    ix = rng.integers(-1, 128, (B, L)).astype(np.int32)
    n = np.maximum((ix >= 0).sum(axis=1), 1)
    w = np.where(ix >= 0, 1.0 / n[:, None] if combiner == "mean" else 1.0,
                 0.0).astype(np.float32)
    out = fused_gather_combine(
        vals, jnp.asarray(ix), jnp.asarray(w), block_b=4, interpret=True,
        pair_kernels=True,
    )
    e = np.asarray(vals, np.float32)[np.clip(ix, 0, 127)]
    expect = (e * w[..., None]).sum(axis=1)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=2e-2, atol=2e-2)


# ------------------------------------------------ the kernels' table axis
#
# gather_rows and apply_rows_sr batch themselves: a vmap over tables (and a
# second one over shards) folds into the table axis of ONE Pallas call,
# where jax's own rule for a batched scalar prefetch would loop over the
# tables and slice each out of the stack (module docstring, "Batching").

T_, C_, D_ = 3, 64, 128


def _stack(seed, dtype=jnp.float32, lead=(T_,)):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.normal(0, 1, (*lead, C_, D_)).astype(np.float32)
    ).astype(dtype)


def _gather_ix(seed, n, lead=(T_,)):
    """Reads with out-of-range indices on both sides (clipped)."""
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(-4, C_ + 4, (*lead, n)), jnp.int32)


def _scatter_ix(seed, n, lead=(T_,), skip_every=4):
    """Unique slots a table (the kernel's contract), some skipped."""
    rng = np.random.default_rng(seed)
    flat = np.stack([rng.permutation(C_)[:n]
                     for _ in range(int(np.prod(lead)))])
    flat[:, ::skip_every] = -1
    return jnp.asarray(flat.reshape(*lead, n), jnp.int32)


def _rows(seed, n, lead=(T_,)):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(0, 1, (*lead, n, D_)).astype(np.float32))


def _gather(v, i):
    return gather_rows(v, i, interpret=True)


def _apply(v, i, r, s):
    return apply_rows_sr(v, i, r, s, interpret=True)


def _loop(fn, *args):
    """fn over the leading axis of every argument, one call at a time."""
    return jnp.stack([fn(*(a[t] for a in args))
                      for t in range(args[0].shape[0])])


def _same(a, b):
    np.testing.assert_array_equal(
        np.asarray(a.astype(jnp.float32)), np.asarray(b.astype(jnp.float32))
    )


@pytest.mark.parametrize("n", [16, 13, 1])  # 13, 1: not a block multiple
def test_vmapped_gather_equals_the_per_table_loop(n):
    vals, ix = _stack(10), _gather_ix(11, n)
    _same(jax.vmap(_gather)(vals, ix), _loop(_gather, vals, ix))
    want = jnp.take_along_axis(
        vals, jnp.clip(ix, 0, C_ - 1)[:, :, None], axis=1)
    _same(jax.vmap(_gather)(vals, ix), want)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n", [16, 13])
def test_vmapped_scatter_equals_the_per_table_loop(n, dtype):
    """Skipped (negative) slots, padding, and for bf16 the same stochastic
    rounding bits a table as its own unbatched call draws."""
    vals, ix, rows = _stack(12, dtype), _scatter_ix(13, n), _rows(14, n)
    seeds = jnp.arange(T_, dtype=jnp.int32) + 5
    _same(jax.vmap(_apply)(vals, ix, rows, seeds),
          _loop(_apply, vals, ix, rows, seeds))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_vmapped_scatter_with_one_seed_for_all_tables(dtype):
    """The trainer's form: the seed is not mapped, every table draws from
    the same one, as each unbatched call would."""
    vals, ix, rows = _stack(15, dtype), _scatter_ix(16, 13), _rows(17, 13)
    seed = jnp.int32(9)
    got = jax.vmap(_apply, in_axes=(0, 0, 0, None))(vals, ix, rows, seed)
    _same(got, _loop(lambda v, i, r: _apply(v, i, r, seed), vals, ix, rows))


def test_vmapped_all_skipped_scatter_leaves_every_table_untouched():
    """The insert that creates no row."""
    vals, rows = _stack(18), _rows(19, 16)
    ix = jnp.full((T_, 16), -1, jnp.int32)
    _same(jax.vmap(_apply, in_axes=(0, 0, 0, None))(
        vals, ix, rows, jnp.int32(0)), vals)


def test_vmap_of_values_alone_and_of_indices_alone():
    vals, ix = _stack(20), _gather_ix(21, 13)
    _same(jax.vmap(_gather, in_axes=(0, None))(vals, ix[0]),
          _loop(lambda v: _gather(v, ix[0]), vals))
    # indices mapped over ONE table: T * n rows of that table
    _same(jax.vmap(_gather, in_axes=(None, 0))(vals[0], ix),
          _loop(lambda i: _gather(vals[0], i), ix))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_second_vmap_equals_the_double_loop(dtype):
    """Shards x tables, as ShardedTrainer maps them."""
    lead = (2, T_)
    vals = _stack(22, dtype, lead)
    ix, six = _gather_ix(23, 13, lead), _scatter_ix(24, 13, lead)
    rows = _rows(25, 13, lead)
    seeds = jnp.arange(6, dtype=jnp.int32).reshape(lead)
    _same(jax.vmap(jax.vmap(_gather))(vals, ix),
          _loop(lambda v, i: _loop(_gather, v, i), vals, ix))
    _same(jax.vmap(jax.vmap(_apply))(vals, six, rows, seeds),
          _loop(lambda *a: _loop(_apply, *a), vals, six, rows, seeds))


def _walk(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its parameters, a
    Pallas call's kernel body left out (its loops are the kernel's own)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk(sub)


def _mechanism(fn, *args):
    """(Pallas calls, loops, table-sized slices and write-backs) in the
    jaxpr of fn."""
    eqns = list(_walk(jax.make_jaxpr(fn)(*args).jaxpr))
    names = [e.primitive.name for e in eqns]
    table_sized = [
        e for e in eqns
        if e.primitive.name in ("dynamic_slice", "dynamic_update_slice")
        and e.invars[0].aval.size >= C_ * D_
    ]
    return names.count("pallas_call"), names.count("while"), table_sized


def test_vmapped_kernels_are_one_call_with_no_loop_and_no_table_slice():
    """Pins the mechanism: under vmap, and under two, each operation is
    exactly one pallas_call, with no `while` round it and no dynamic_slice
    or dynamic_update_slice of anything as large as a [C, D] table."""
    vals, ix, six, rows = (_stack(26), _gather_ix(27, 13),
                           _scatter_ix(28, 13), _rows(29, 13))
    seed = jnp.int32(1)
    apply_tables = jax.vmap(_apply, in_axes=(0, 0, 0, None))
    assert _mechanism(jax.vmap(_gather), vals, ix) == (1, 0, [])
    assert _mechanism(apply_tables, vals, six, rows, seed) == (1, 0, [])
    two = lambda a: jnp.stack([a, a])  # noqa: E731
    assert _mechanism(jax.vmap(jax.vmap(_gather)),
                      two(vals), two(ix)) == (1, 0, [])
    assert _mechanism(jax.vmap(apply_tables, in_axes=(0, 0, 0, None)),
                      two(vals), two(six), two(rows), seed) == (1, 0, [])


def test_tables_past_the_smem_budget_split_into_ranges(monkeypatch):
    """All tables' indices are one scalar prefetch; past the budget a call
    splits over table ranges of the same whole array: as many calls as
    ranges, still no loop and no table-sized slice, the same bits."""
    vals, ix, six, rows = (_stack(30), _gather_ix(31, 13),
                           _scatter_ix(32, 13), _rows(33, 13))
    seed = jnp.int32(2)
    apply_tables = jax.vmap(_apply, in_axes=(0, 0, 0, None))
    want_g = jax.vmap(_gather)(vals, ix)
    want_s = apply_tables(vals, six, rows, seed)
    monkeypatch.setattr(fl, "_SMEM_INDEX_BYTES", 2 * 16 * 4)  # two tables
    assert fl._table_ranges(T_, 16) == [(0, 2), (2, 1)]
    assert _mechanism(jax.vmap(_gather), vals, ix) == (2, 0, [])
    assert _mechanism(apply_tables, vals, six, rows, seed) == (2, 0, [])
    _same(jax.vmap(_gather)(vals, ix), want_g)
    _same(apply_tables(vals, six, rows, seed), want_s)


@pytest.mark.parametrize("n", [16, 17, 40, 64])
@pytest.mark.parametrize("tables", ["one", "indices_over_one", "stacked"])
def test_one_tables_rows_past_the_smem_budget_split_into_row_ranges(
        monkeypatch, n, tables):
    """A table range bottoms out at one table: where ONE table's indices
    pass the budget (a whole table's slots probed at once in rebuild; a
    vmap over indices folded into one unmapped table) the gather makes
    calls over row ranges of the same whole array, as many as it takes,
    with no loop and no table-sized slice, and the same bits."""
    rng = np.random.default_rng(70 + n)
    vals = _stack(37)
    monkeypatch.setattr(fl, "_SMEM_INDEX_BYTES", 16 * 4)  # 16 rows a call
    calls = -(-n // 16)
    if tables == "one":
        fn, args = _gather, (vals[0], jnp.asarray(
            rng.integers(0, C_ + 4, (n,)), jnp.int32))
        want = _xla_gather(*args)
    elif tables == "indices_over_one":   # 2 x n rows of the one table
        fn = jax.vmap(_gather, in_axes=(None, 0))
        args = (vals[0], jnp.asarray(
            rng.integers(0, C_ + 4, (2, n)), jnp.int32))
        want, calls = jax.vmap(_xla_gather, in_axes=(None, 0))(*args), -(
            -2 * n // 16)
    else:   # every table of the stack over the budget by itself
        fn, args = jax.vmap(_gather), (vals, jnp.asarray(
            rng.integers(0, C_ + 4, (T_, n)), jnp.int32))
        want = _xla_gather(*args)   # a row range, then its table ranges
        calls = sum(len(fl._table_ranges(T_, min(16, n - r0)))
                    for r0 in range(0, n, 16))
    assert _mechanism(fn, *args) == (calls, 0, [])
    _same(fn(*args), want)


def test_updates_mapped_over_one_table_are_noted_as_a_fallback():
    """Folding them into one call would break the unique-slot contract:
    each gets a copy of the table, and the repo's fallback counter says
    so."""
    from deeprec_tpu.obs.metrics import default_registry

    vals, six, rows = _stack(34), _scatter_ix(35, 13), _rows(36, 13)
    seed = jnp.int32(3)
    got = jax.vmap(_apply, in_axes=(None, 0, 0, None))(
        vals[0], six, rows, seed)
    _same(got, _loop(lambda i, r: _apply(vals[0], i, r, seed), six, rows))
    text = default_registry().render_prometheus()
    assert 'kernel="apply_rows_sr",reason="values_unmapped"' in text.replace(
        '", ', '",')


# ------------------------------------------- the window of DMAs in flight
#
# A grid step keeps up to fl._GROUP * fl._AHEAD row DMAs in flight, rows
# start in groups of fl._GROUP, and a row goes HBM to HBM: from the table to
# its row of the result (the gather), from its row of the updates to the
# table (the scatter). The kernels only copy, so every case is the XLA
# oracle's bit for bit.

K_ = fl._GROUP * fl._AHEAD
CW = 640   # rows a table here: room for unique slots past four windows


def _wvals(seed, dtype=jnp.float32, lead=(2,)):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.normal(0, 1, (*lead, CW, D_)).astype(np.float32)).astype(dtype)


def _wslots(seed, n, lead=(2,)):
    rng = np.random.default_rng(seed)
    flat = np.stack([rng.permutation(CW)[:n]
                     for _ in range(int(np.prod(lead)))])
    return flat.reshape(*lead, n).astype(np.int32)


def _xla_gather(vals, ix):
    return jnp.take_along_axis(
        vals, jnp.clip(ix, 0, vals.shape[-2] - 1)[..., None], axis=-2)


def _xla_scatter(vals, ix, rows):
    C = vals.shape[-2]
    one = lambda v, i, r: v.at[jnp.where(i >= 0, i, C)].set(  # noqa: E731
        r.astype(v.dtype), mode="drop")
    for _ in range(vals.ndim - 2):
        one = jax.vmap(one)
    return one(vals, ix, rows)


WINDOW_SHAPES = [  # (rows, block= or None: one grid step a table)
    (1, None), (K_ - 3, None), (K_ + 1, None),   # round the window's rows
    (13, None), (21, 8),                         # not a group, not a tile
    (31, 32), (33, 32),                          # a block less one, plus one
    (255, None), (257, None),                    # groups and a row, less one
    (2 * K_, K_),                                # a block of exactly a window
]


@pytest.mark.parametrize("n,block", WINDOW_SHAPES)
def test_windowed_gather_is_the_oracle_bit_for_bit(n, block):
    """Out-of-range indices on both sides (clipped) and duplicates."""
    vals = _wvals(40)
    rng = np.random.default_rng(41 + n)
    ix = rng.integers(-4, CW + 4, (2, n))
    ix[:, n // 2:] = ix[:, : n - n // 2]         # every row read twice
    ix = jnp.asarray(ix, jnp.int32)
    got = jax.vmap(lambda v, i: gather_rows(
        v, i, block=block, interpret=True))(vals, ix)
    _same(got, _xla_gather(vals, ix))


def _skip(ix, pattern, block):
    ix = ix.copy()
    if pattern == "all":
        ix[:] = -1
    elif pattern == "every_other":
        ix[..., ::2] = -1
    elif pattern == "odd":
        ix[..., 1::2] = -1
    elif pattern == "block_ends":    # the first and last row of a block or group
        ix[..., ::block] = -1
        ix[..., block - 1::block] = -1
    return ix


@pytest.mark.parametrize("pattern", ["none", "all", "every_other",
                                     "block_ends"])
@pytest.mark.parametrize("n,block", [(K_ - 3, None), (33, 32), (257, None)])
def test_windowed_gather_that_skips_negative_rows(n, block, pattern):
    """`skip_negative=True` (the probe's window read of int32 key rows): a
    negative index starts no DMA and is waited for never, wherever it
    stands in the window, and every other row of the result is the
    oracle's; what a skipped row holds is the caller's to mask."""
    vals = (_wvals(42) * 1000).astype(jnp.int32)
    rng = np.random.default_rng(43 + n)
    ix = _skip(rng.integers(0, CW + 4, (2, n)), pattern, block or fl._GROUP)
    ix = jnp.asarray(ix, jnp.int32)
    got = jax.vmap(lambda v, i: gather_rows(
        v, i, block=block, interpret=True, skip_negative=True))(vals, ix)
    read = np.asarray(ix) >= 0
    np.testing.assert_array_equal(np.asarray(got)[read],
                                  np.asarray(_xla_gather(vals, ix))[read])
    # off the TPU the same call is XLA's gather, which reads those rows too
    np.testing.assert_array_equal(
        np.asarray(jax.vmap(lambda v, i: gather_rows(
            v, i, skip_negative=True))(vals, ix))[read],
        np.asarray(_xla_gather(vals, ix))[read])


@pytest.mark.parametrize("pattern",
                         ["none", "all", "every_other", "odd", "block_ends"])
@pytest.mark.parametrize("n,block", [(K_ - 3, None), (33, 32), (96, 32),
                                     (257, None)])
def test_windowed_scatter_is_the_oracle_bit_for_bit(n, block, pattern):
    """A skipped slot starts nothing and is waited for never, wherever it
    stands in the window; every other row lands."""
    vals = _wvals(42)
    ix = jnp.asarray(_skip(_wslots(43 + n, n), pattern, block or fl._GROUP))
    rows = jnp.asarray(np.random.default_rng(44).normal(
        0, 1, (2, n, D_)).astype(np.float32))
    got = jax.vmap(lambda v, i, r: apply_rows_sr(
        v, i, r, jnp.int32(0), block=block, interpret=True))(vals, ix, rows)
    _same(got, _xla_scatter(vals, ix, rows))


@pytest.mark.parametrize("n,block", [(13, None), (33, 32), (48, 16)])
@pytest.mark.parametrize("seeds", ["mapped", "unmapped"])
def test_windowed_bf16_scatter_rounds_as_the_loop_and_as_xla(n, block, seeds):
    """The stochastic-rounding branch: a rounded copy of the block, rows
    sent from there. A table's bits are those of its own unbatched call and
    of the XLA scatter (drawn at [n, D], whatever the block)."""
    vals = _wvals(45, jnp.bfloat16, (3,))
    ix = jnp.asarray(_skip(_wslots(46, n, (3,)), "every_other", 8))
    rows = jnp.asarray(np.random.default_rng(47).normal(
        0, 1, (3, n, D_)).astype(np.float32))
    seed = (jnp.arange(3, dtype=jnp.int32) + 3 if seeds == "mapped"
            else jnp.int32(11))
    axes = (0, 0, 0, 0 if seeds == "mapped" else None)
    kernel = lambda v, i, r, s: apply_rows_sr(  # noqa: E731
        v, i, r, s, block=block, interpret=True)
    xla = lambda v, i, r, s: apply_rows_sr(  # noqa: E731
        v, i, r, s, use_pallas=False)
    got = jax.vmap(kernel, in_axes=axes)(vals, ix, rows, seed)
    each = jnp.broadcast_to(seed, (3,))
    _same(got, _loop(kernel, vals, ix, rows, each))
    _same(got, _loop(xla, vals, ix, rows, each))


@pytest.mark.parametrize("vmaps", [1, 2])
def test_windowed_kernels_under_one_and_two_vmaps_over_many_blocks(vmaps):
    """Shards x tables folded into the table axis, three blocks a table."""
    lead = (2, 2)[:vmaps]
    n, block = 3 * 32 - 5, 32
    vals = _wvals(48, lead=lead)
    ix = jnp.asarray(_skip(_wslots(49, n, lead), "block_ends", block))
    rows = jnp.asarray(np.random.default_rng(50).normal(
        0, 1, (*lead, n, D_)).astype(np.float32))
    g = lambda v, i: gather_rows(  # noqa: E731
        v, i, block=block, interpret=True)
    s = lambda v, i, r: apply_rows_sr(  # noqa: E731
        v, i, r, jnp.int32(0), block=block, interpret=True)
    for _ in range(vmaps):
        g, s = jax.vmap(g), jax.vmap(s)
    _same(g(vals, jnp.maximum(ix, 0)), _xla_gather(vals, jnp.maximum(ix, 0)))
    _same(s(vals, ix, rows), _xla_scatter(vals, ix, rows))


@pytest.mark.parametrize("n", [1, 6, 64, 2304, 8200, 16384])
def test_a_grid_step_carries_all_of_a_tables_rows(n):
    """Rows go HBM to HBM, so no block in VMEM bounds a grid step: a call
    of a few rows and the cells' calls alike are one step a table, and no
    index is padded (only an explicit `block=` splits a table's rows)."""
    vals = jnp.zeros((2, 64, D_), jnp.float32)
    ix = jnp.zeros((2, n), jnp.int32)
    rows = jnp.zeros((2, n, D_), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda v, i, r: (
        jax.vmap(_gather)(v, i),
        jax.vmap(_apply, in_axes=(0, 0, 0, None))(v, i, r, jnp.int32(0)),
    ))(vals, ix, rows).jaxpr
    calls = [e for e in _walk(jaxpr) if e.primitive.name == "pallas_call"]
    assert len(calls) == 2
    for eqn in calls:
        assert eqn.params["grid_mapping"].grid == (2, 1)
        assert eqn.invars[1].aval.shape == (2 * n,)
    split = jax.make_jaxpr(lambda v, i: gather_rows(
        v, i, block=8, interpret=True))(vals[0], ix[0]).jaxpr
    (eqn,) = [e for e in _walk(split) if e.primitive.name == "pallas_call"]
    assert eqn.params["grid_mapping"].grid == (1, -(-n // 8))


def test_the_schedule_a_table_rides_is_on_the_registry():
    from deeprec_tpu.obs.metrics import default_registry

    vals = _wvals(51)[0]
    gather_rows(vals, jnp.arange(40, dtype=jnp.int32), interpret=True)
    text = default_registry().render_prometheus().replace('", ', '",')
    assert "deeprec_pallas_row_schedule{" in text
    assert (f'block="40",kernel="gather_rows",rows="40",shape="1x{CW}x128",'
            f'window="{K_}"') in text
