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


# `.zipf`, `.uniform`: U + 8; 8 and 64: a serving-sized call
@pytest.mark.parametrize("n", [2304, 8200, 16384, 8, 64])
def test_vmapped_row_kernels_compile_with_no_table_sized_slice(
        one_chip, monkeypatch, n):
    """One Mosaic call an operation and a table range (`.zipf`: one call
    for all 26 tables; past the SMEM budget a few, over ranges of the same
    whole array), no loop round them, nothing that slices a table out of
    the stack or writes one back, and no second values-sized buffer."""
    calls = len(fl._table_ranges(T, n))
    assert (calls == 1) == (n <= 2304) and (n != 8200 or calls == 4)
    compiled = _compile(one_chip, monkeypatch, n)
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2 * calls
    assert " while(" not in hlo
    assert _table_sized(hlo) == []
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < T * C * D * 4 // 4
    assert mem.alias_size_in_bytes >= T * C * D * 4  # in place


def test_the_row_kernels_ask_for_no_vmem():
    """Rows go HBM to HBM: no operand is a block in VMEM and the only
    scratch is the DMA semaphore, so no count of rows a grid step carries
    can pass the compiler's limit on a kernel's VMEM (16 MiB by default on
    a v5e; a [256, 128] f32 block, double-buffered, would be 256 KiB)."""
    t0 = jnp.zeros((1,), jnp.int32)
    vals = jnp.zeros((2, 64, D), jnp.float32)
    ix = jnp.zeros((2, 16), jnp.int32)
    rows = jnp.zeros((2, 16, D), jnp.float32)
    # the calls' own functions, without their jit: the kernels stand at the
    # top of the jaxpr
    jaxpr = jax.make_jaxpr(lambda t0, v, i, r: (
        fl._gather_call.__wrapped__(t0, i, v, n=16, block=16,
                                    interpret=True),
        fl._apply_call.__wrapped__(t0, i, r, v, block=16, interpret=True),
    ))(t0, vals, ix, rows).jaxpr
    kernels = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(kernels) == 2
    for eqn in kernels:
        spaces = {str(v.aval).split("{")[0]
                  for v in eqn.params["jaxpr"].invars}
        assert spaces <= {"Ref<any>", "Ref<smem>", "Ref<semaphore_mem>"}, (
            spaces)


def test_mosaic_refuses_indices_past_a_cores_smem(one_chip, monkeypatch):
    """Why the indices cannot all ride one call whatever their number: a
    v5e core has 1 MiB of SMEM and the refusal names the operand. (The
    budget is far inside it for another reason: fused_lookup.py.)"""
    monkeypatch.setattr(fl, "_SMEM_INDEX_BYTES", 1 << 30)
    _compile(one_chip, monkeypatch, 10000)
    with pytest.raises(Exception, match="prefetched SMEM operand"):
        _compile(one_chip, monkeypatch, 12000)


# ------------------------------------ the token stack's kernels, real widths


def _sd(one_chip):
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)


def test_flash_kernels_compile_at_grouped_queries_and_head_dim_256(
        one_chip, monkeypatch):
    """16 query heads over 2 key/value heads, head dim 256, L = 8192,
    causal, bf16 operands, blocks of 512: forward and both backward
    kernels, and no copy of k or v repeated to the query heads."""
    from deeprec_tpu.ops.flash_attention import flash_attention
    from deeprec_tpu.utils import scopes

    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    sd = _sd(one_chip)

    def step(q, k, v, mask):
        o, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, mask, True, 256 ** -0.5, 512, 512), q, k, v)
        return o, vjp(o)

    compiled = jax.jit(step).lower(
        sd((1, 16, 8192, 256), jnp.bfloat16),
        sd((1, 2, 8192, 256), jnp.bfloat16),
        sd((1, 2, 8192, 256), jnp.bfloat16), sd((1, 8192), jnp.bool_)
    ).compile()
    hlo = compiled.as_text()
    for name in (scopes.KERNEL_FLASH_FWD, scopes.KERNEL_FLASH_BWD_DKDV,
                 scopes.KERNEL_FLASH_BWD_DQ):
        assert name in hlo, name
    assert "bf16[1,16,8192,256]" in hlo
    assert "bf16[16,8192,256]{2,1,0} broadcast" not in hlo


@pytest.mark.parametrize("window", [None, 4096])
def test_flash_kernels_compile_at_seven_query_heads_a_group_and_a_window(
        one_chip, monkeypatch, window):
    """28 query heads over 4 key/value heads, head dim 128, L = 16,384,
    causal with and without a window of 4,096, bf16 operands, blocks of
    512: the three kernels, their walks as long as the window makes them."""
    from deeprec_tpu.ops.flash_attention import (
        _visible_keys, _visible_queries, _walk, flash_attention)
    from deeprec_tpu.utils import scopes

    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    sd = _sd(one_chip)

    def step(q, k, v, mask):
        o, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, mask, True, 128 ** -0.5, 512, 512, False, window),
            q, k, v)
        return o, vjp(o)

    hlo = jax.jit(step).lower(
        sd((1, 28, 16384, 128), jnp.bfloat16),
        sd((1, 4, 16384, 128), jnp.bfloat16),
        sd((1, 4, 16384, 128), jnp.bfloat16), sd((1, 16384), jnp.bool_)
    ).compile().as_text()
    for name in (scopes.KERNEL_FLASH_FWD, scopes.KERNEL_FLASH_BWD_DKDV,
                 scopes.KERNEL_FLASH_BWD_DQ):
        assert name in hlo, name
    assert "bf16[28,16384,128]{2,1,0} broadcast" not in hlo
    walk = (512, 512, 32, True, window)
    assert _walk(_visible_keys, 32, *walk)[0] \
        == _walk(_visible_queries, 32, *walk)[0] \
        == (32 if window is None else 9)


def test_flash_kernels_compile_at_head_dim_64_over_eight_kv_heads(
        one_chip, monkeypatch):
    """The short-convolution cell's attention: 2 sequences of 8,192, 32
    query heads over 8 key/value heads of 64 (half a lane tile, taken
    whole), causal, bf16 operands, blocks of 512: forward and both backward
    kernels, and no copy of k or v repeated to the query heads."""
    from deeprec_tpu.ops.flash_attention import flash_attention
    from deeprec_tpu.utils import scopes

    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    sd = _sd(one_chip)

    def step(q, k, v, mask):
        o, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, mask, True, 64 ** -0.5, 512, 512), q, k, v)
        return o, vjp(o)

    hlo = jax.jit(step).lower(
        sd((2, 32, 8192, 64), jnp.bfloat16),
        sd((2, 8, 8192, 64), jnp.bfloat16),
        sd((2, 8, 8192, 64), jnp.bfloat16), sd((2, 8192), jnp.bool_)
    ).compile().as_text()
    for name in (scopes.KERNEL_FLASH_FWD, scopes.KERNEL_FLASH_BWD_DKDV,
                 scopes.KERNEL_FLASH_BWD_DQ):
        assert name in hlo, name
    assert "bf16[2,32,8192,64]" in hlo
    assert not [line for line in hlo.splitlines()
                if "bf16[2,32,8192,64]" in line and " broadcast(" in line]


def test_flash_kernels_compile_at_keys_wider_than_values(one_chip,
                                                         monkeypatch):
    """The latent cell's shape: 16 heads, queries and keys 192 wide (one
    and a half lane tiles, taken whole), values 128, L = 8192, causal, bf16
    operands, blocks of 512: forward and both backward kernels, the output
    and dV at the VALUE's width, dQ and dK at the key's."""
    from deeprec_tpu.ops.flash_attention import flash_attention
    from deeprec_tpu.utils import scopes

    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    sd = _sd(one_chip)

    def step(q, k, v, mask):
        o, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, mask, True, 192 ** -0.5, 512, 512), q, k, v)
        return o, vjp(o)

    compiled = jax.jit(step).lower(
        sd((1, 16, 8192, 192), jnp.bfloat16),
        sd((1, 16, 8192, 192), jnp.bfloat16),
        sd((1, 16, 8192, 128), jnp.bfloat16), sd((1, 8192), jnp.bool_)
    ).compile()
    hlo = compiled.as_text()
    for name in (scopes.KERNEL_FLASH_FWD, scopes.KERNEL_FLASH_BWD_DKDV,
                 scopes.KERNEL_FLASH_BWD_DQ):
        assert name in hlo, name
    o, (dq, dk, dv) = jax.eval_shape(
        step, *(jax.ShapeDtypeStruct(s, d) for s, d in (
            ((1, 16, 8192, 192), jnp.bfloat16),
            ((1, 16, 8192, 192), jnp.bfloat16),
            ((1, 16, 8192, 128), jnp.bfloat16), ((1, 8192), jnp.bool_))))
    assert o.shape == dv.shape == (1, 16, 8192, 128)
    assert dq.shape == dk.shape == (1, 16, 8192, 192)


def _pair_budget(mix: str) -> int:
    """The budget of pairs a token cell runs: its traffic mix's, as
    committed."""
    import json
    import pathlib

    path = pathlib.Path(__file__).parents[1] / "benchmark" / "traffic"
    return json.loads((path / f"{mix}.json").read_text())["pair_budget"]


@pytest.mark.parametrize("held,width,hidden,mix", [
    (32, 512, 2048, "seq8k-zipf11"), (8, 768, 2560, "seq16k-zipf11"),
    (8, 1408, 2048, "seq8k-zipf11-v20480")])
def test_grouped_products_compile_at_the_cells_widths(
        one_chip, monkeypatch, held, width, hidden, mix):
    """The expert layer's three kernels at the token cells' shapes (32
    held experts of width 512 on a hidden size of 2048; 8 of width 768 on
    2560; 8 of width 1,408 on 2048), each at the budget of pairs its
    traffic mix commits: both
    products' shapes, forward and backward."""
    from deeprec_tpu.ops import moe

    budget = _pair_budget(mix)
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    sd = _sd(one_chip)
    block = 128
    nb = moe.num_blocks(budget, held, block)
    for K, N in ((hidden, width), (width, hidden)):
        def step(x, w, block_expert):
            y, vjp = jax.vjp(lambda x, w: moe.grouped_matmul(
                x, w, block_expert, block), x, w)
            return y, vjp(y)

        compiled = jax.jit(step).lower(
            sd((nb * block, K), jnp.bfloat16), sd((held, K, N), jnp.float32),
            sd((nb,), jnp.int32)).compile()
        assert compiled.as_text().count("tpu_custom_call") >= 3


def test_a_row_wider_than_a_lane_tile_takes_the_xla_path(one_chip,
                                                         monkeypatch):
    """Mosaic takes no single-row DMA of a [C, 2048] table (its refusal is
    pinned here), so the funnels must not ask for one: at dim 2048 the
    gather and the scatter compile, without a Pallas call."""
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    sd = _sd(one_chip)
    c, d, n = 1 << 16, 2048, 2544

    def funnels(vals, ix, rows):
        return (packed.gather_rows_any(vals, jnp.maximum(ix, 0), c,
                                       use_pallas=True),
                packed.scatter_rows_any(vals, ix, rows, c, use_pallas=True))

    hlo = jax.jit(funnels, donate_argnums=0).lower(
        sd((c, d), jnp.float32), sd((n,), jnp.int32),
        sd((n, d), jnp.float32)).compile().as_text()
    assert "tpu_custom_call" not in hlo
    assert not fl._dma_ok(d, jnp.float32) and fl._dma_ok(128, jnp.float32)
    with pytest.raises(Exception, match="aligned to tiling"):
        jax.jit(lambda v, i: fl._gather_rows_op(8, False)(
            v[None], i[None])).lower(
            sd((c, d), jnp.float32), sd((n,), jnp.int32)).compile()


# ------------------------------------------ the probe's two loops, real shape


def _body_of(hlo, loop):
    """Every instruction the body of the `while` under the scope `loop`
    runs, through the fusions and reductions it calls: [(result, opcode)]
    (the structure by the benchmark's own reader of HLO text)."""
    from benchmark import trace_reduce

    instrs = trace_reduce.parse_hlo(hlo)
    (todo,) = [[i["body"]] for i in instrs.values() if i["op"] == "while"
               and i["op_name"].endswith(f"/{loop}/while")]
    inside = set()
    while todo:
        name = todo.pop()
        if name not in inside:
            inside.add(name)
            todo += [c for i in instrs.values() if i["computation"] == name
                     for c in i["calls"]]
    lines = {m.group(1): m.group(2) for m in map(
        trace_reduce._INSTR.match, hlo.splitlines()) if m}
    # what stands before an instruction's opcode is its result's type
    return [(lines[n].partition(f" {i['op']}(")[0], i["op"])
            for n, i in instrs.items() if i["computation"] in inside]


def _probe_hlo(one_chip, monkeypatch, n, key_dtype="int32"):
    from deeprec_tpu import EmbeddingTable, TableConfig

    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    sd = _sd(one_chip)
    table = EmbeddingTable(TableConfig(name="t", dim=D, capacity=C,
                                       key_dtype=key_dtype))
    kdt = jnp.dtype(key_dtype)
    return jax.jit(jax.vmap(table._probe), donate_argnums=0).lower(
        sd((T, C), kdt), sd((T, n), kdt),
        sd((T, n), jnp.bool_)).compile().as_text()


@pytest.mark.parametrize("n", [2304, 8200])   # `.zipf`, `.uniform`: U + 8
def test_the_find_loop_reads_a_window_of_keys_a_pass_through_the_row_kernel(
        one_chip, monkeypatch, n):
    """`jax.vmap(EmbeddingTable._probe)` over the bundle's 26 key arrays:
    the body of the find loop reads one `[128]` row of keys an id through
    `gather_rows`, a call a table range as the lookup's own gather splits
    (one in `.zipf`, four in `.uniform`: ROADMAP D0), and reduces it to
    `[26, n]`: no scalar gather of the keys, no sort, no scatter, and
    nothing that makes a value the key arrays' size (the loop passes the
    keys through untouched; the `select` that `vmap` makes of a `while`
    whose predicate differs by table is over the carry, and the keys are no
    carry). The claim loop, which runs no pass when no row is to be
    created, is where those stand."""
    hlo = _probe_hlo(one_chip, monkeypatch, n)
    passed_through = {"parameter", "get-tuple-element", "tuple"}
    find = _body_of(hlo, "probe_find")
    ops = {op for _, op in find}
    assert not {"gather", "sort", "scatter"} & ops, ops
    reads = [r for r, op in find if op == "custom-call"]
    ranges = fl._table_ranges(T, n)
    assert len(reads) == len(ranges) == (1 if n == 2304 else 4)
    assert sorted(r.split("{")[0] for r in reads) == sorted(
        f"s32[{tables},{n},128]" for _, tables in ranges)
    assert not [(r, op) for r, op in find if op not in passed_through
                and (f"s32[{T},{C}]" in r or f"s32[{T},{C // 128},128]" in r)]
    # one lane reduction of the windows to [26, n] (no value the windows'
    # size but what is fused into it), and the count of the rows that move
    # in each group of 16, which the kernel reads in the rows' place
    assert sorted(r.split("{")[0] for r, op in find
                  if op == "reduce" and r.startswith("s32")) == sorted(
        [f"s32[{T},{n}]", f"s32[{T},{-(-n // fl._GROUP)}]"])
    claim = {op for _, op in _body_of(hlo, "probe_claim")}
    assert {"sort", "scatter"} & claim, claim


@pytest.mark.parametrize("size", [2304, 8200])   # `.zipf`, `.uniform`
def test_route_is_three_sorts_and_no_loop_scatter_or_gather(one_chip, size):
    """`jax.vmap(route_ids)` over the bundle's `[26, 8192]` ids at the
    cells' budgets: the dedup is three native sorts along the id axis (by
    hash and id; back to input order; the groups' heads to the front) and
    prefix sums. What went with the claim loop must not come back: no
    `while`, no `scatter` and no `gather` at all (both step 3 and step 4
    kept the sort on the chip's readings, PERF.md section 6, PR 36: the
    number of per-id accesses this pins is ZERO), and no value the size of
    the old scratch (`[26, 65536]`)."""
    from benchmark import trace_reduce
    from deeprec_tpu.ops import dedup

    sent = int(jnp.iinfo(jnp.int32).min)
    hlo = jax.jit(jax.vmap(lambda ids: dedup.route_ids(
        ids, pad_value=-1, sentinel=sent, unique_size=size))).lower(
        _sd(one_chip)((T, 8192), jnp.int32)).compile().as_text()
    ops = [i["op"] for i in trace_reduce.parse_hlo(hlo).values()]
    assert ops.count("sort") == 3, ops
    assert not {"while", "scatter", "gather", "custom-call"} & set(ops), ops
    assert f"[{T},65536]" not in hlo
    sorts = [line for line in hlo.splitlines() if " sort(" in line]
    assert all(f"[{T},8192]" in line and "dimensions={1}" in line
               for line in sorts), sorts


def test_the_find_loop_of_int64_keys_takes_xlas_row_gather(one_chip,
                                                           monkeypatch):
    """A row of 128 int64 keys is 1 KiB, which the row kernel does not move
    (`_dma_ok` asks for 4-byte lanes): the window read is XLA's row gather,
    `[26, n, 128]` keys a pass, and no Pallas call stands in the program."""
    n = 2304
    with jax.enable_x64(True):
        hlo = _probe_hlo(one_chip, monkeypatch, n, key_dtype="int64")
    assert "tpu_custom_call" not in hlo
    # the compiler splits 64-bit keys into halves: a row gather of each
    find = _body_of(hlo, "probe_find")
    assert [r.split("{")[0] for r, op in find if op == "gather"] == [
        f"u32[{T},{n},128]"] * 2
    assert not fl._dma_ok(128, jnp.int64) and fl._dma_ok(128, jnp.int32)


@pytest.mark.parametrize("tables,capacity,dim", [
    (T, C, D),            # the cell's bundle in one vmap (Trainer.maintain)
    (2, 1 << 20, 16),     # chip_smoke's tables: 4 MiB of indices a table
])
def test_rebuild_compiles_with_windows_of_a_slice_of_the_ids(
        one_chip, monkeypatch, tables, capacity, dim):
    """rebuild probes all C slots of a table at once, into a fresh key
    array that `vmap` leaves unmapped: the find loop walks them a slice at
    a time (table.py::_PROBE_SLICE), so the key windows a pass holds are
    [tables x slice, 128] whatever the capacity, and the row kernel splits
    ONE table's indices over calls where they pass SMEM's budget (Mosaic
    refuses 1 MiB of them: the test above). Before, the windows were
    [tables x C, 128] and the indices C a call."""
    import re

    from deeprec_tpu import EmbeddingTable, TableConfig
    from deeprec_tpu.embedding import table as table_module

    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    table = EmbeddingTable(TableConfig(name="t", dim=dim, capacity=capacity))
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: jax.vmap(lambda _: table.create())(
            jnp.arange(tables))))
    compiled = jax.jit(jax.vmap(table.rebuild),
                       donate_argnums=0).lower(state).compile()
    hlo = compiled.as_text()
    windows = {(int(a), int(b))
               for a, b in re.findall(r"s32\[(\d+),(\d+),128\]", hlo)}
    ids = tables * table_module._PROBE_SLICE
    assert (1, ids) in windows and max(a * b for a, b in windows) == ids
    calls = [int(n) for n in re.findall(
        r"s32\[1,(\d+),128\]\S* custom-call\([^\n]*tpu_custom_call", hlo)]
    assert calls and max(calls) * 4 <= fl._SMEM_INDEX_BYTES, calls
    assert sum(calls) == ids
