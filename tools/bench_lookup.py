#!/usr/bin/env python
"""Benchmark the embedding hot path: XLA gather/scatter vs fused Pallas.

Answers the round-1 review's question "does op-composed lookup reach the
roofline on TPU, or does the fused kernel win?" — the reference spent 5.5k
LoC of CUDA on this exact question for GPUs (fused_embedding_ops.cc).

Run ON HARDWARE (falls back to CPU with a warning — CPU numbers say nothing
about the TPU answer):

    python tools/bench_lookup.py [--dim 64] [--capacity 20] [--batch 16384]

Prints per-op bandwidth + a verdict line. Whichever path wins becomes the
TableConfig.kernel="auto" default.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def bench(fn, *args, iters=50, warmup=5):
    import jax

    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--dim", type=int, default=64)
    p.add_argument("--capacity", type=int, default=20, help="log2 table slots")
    p.add_argument("--batch", type=int, default=16384, help="unique rows/step")
    p.add_argument("--dtype", default="float32", choices=["float32", "bfloat16"])
    p.add_argument("--packed", action="store_true",
                   help="bench the packed small-dim layout (ops/packed.py) "
                        "against the unpacked logical layout at this dim — "
                        "the measurement TableConfig.packed='auto' is "
                        "waiting on (use --dim 16 for the DLRM shape)")
    p.add_argument("--traffic", action="store_true",
                   help="lookup+apply traffic-diet microbench on zipf "
                        "batches: the diet path (forward-residual reuse + "
                        "fused metadata, no apply-side re-stamps) vs the "
                        "legacy apply (re-gather + version/dirty re-stamp), "
                        "with per-arm stablehlo op counts and modeled bytes")
    p.add_argument("--zipf", type=float, default=1.05,
                   help="--traffic: zipf exponent of the id stream")
    p.add_argument("--smoke", action="store_true",
                   help="--traffic/--fused-step: tiny shapes/iters so CI "
                        "just proves both arms compile and the gates hold")
    p.add_argument("--fused-step", action="store_true",
                   help="single-pass fused sparse step (probe+gather+"
                        "combine fwd, segment-sum+apply bwd; ops/"
                        "fused_lookup.fused_sparse_*) vs the split-phase "
                        "XLA path: step time, interpret-mode parity, and "
                        "the modeled HBM bytes roofline.py --assert-fused "
                        "gates on")
    p.add_argument("--out", default=None,
                   help="--fused-step: merge the record into this JSON "
                        "file (BENCH_r07.json for the committed run)")
    args = p.parse_args(argv)
    if args.fused_step:
        return main_fused_step(args)
    if args.traffic:
        return main_traffic(args)
    if args.packed:
        return main_packed(args)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeprec_tpu.ops.fused_lookup import apply_rows_sr, gather_rows

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"WARNING: running on {backend}; TPU is the question", file=sys.stderr)
    from deeprec_tpu.ops.fused_lookup import _dma_ok, _dma_pair_ok

    pair = _dma_pair_ok((1 << args.capacity, args.dim), jnp.dtype(args.dtype))
    if not _dma_ok(args.dim, jnp.dtype(args.dtype)) and not pair:
        print(
            f"WARNING: dim={args.dim} dtype={args.dtype} is ineligible for the "
            "Pallas row-DMA kernels (f32 dim%128==0) and the bf16 pair "
            "kernels (bf16 dim%128==0) — the 'pallas' rows below fall back "
            "to XLA, so the verdict is XLA-vs-XLA",
            file=sys.stderr,
        )

    C, D, U = 1 << args.capacity, args.dim, args.batch
    dt = jnp.dtype(args.dtype)
    rng = np.random.default_rng(0)
    values = jnp.asarray(rng.normal(0, 0.05, (C, D)), dt)
    ix = jnp.asarray(rng.integers(0, C, U), jnp.int32)
    rows = jnp.asarray(rng.normal(0, 0.05, (U, D)), jnp.float32)
    seed = jnp.int32(0)

    xla_gather = jax.jit(lambda v, i: v.at[i].get(mode="clip"))  # noqa: DRT001 — built once per bench invocation, reused across the timed loop
    pallas_gather = jax.jit(lambda v, i: gather_rows(v, i, pair_kernels=pair))  # noqa: DRT001 — built once per bench invocation, reused across the timed loop
    xla_scatter = jax.jit(  # noqa: DRT001 — built once per bench invocation, reused across the timed loop
        lambda v, i, r: apply_rows_sr(v, i, r, seed, use_pallas=False)
    )
    pallas_scatter = jax.jit(  # noqa: DRT001 — built once per bench invocation, reused across the timed loop
        lambda v, i, r: apply_rows_sr(v, i, r, seed, use_pallas=True,
                                      pair_kernels=pair)
    )

    bytes_g = U * D * dt.itemsize  # rows read
    bytes_s = U * D * (dt.itemsize + 4)  # f32 rows in, dt rows out

    results = _run_cases((
        ("gather/xla", xla_gather, (values, ix), bytes_g),
        ("gather/pallas", pallas_gather, (values, ix), bytes_g),
        ("scatter/xla", xla_scatter, (values, ix, rows), bytes_s),
        ("scatter/pallas", pallas_scatter, (values, ix, rows), bytes_s),
    ))
    _verdicts(results, ("xla", "pallas"))
    if pair:
        print(
            "note: bf16 pair kernels measured — if pallas won both ops, flip "
            "AUTO_TRUSTS_BF16_PAIR in ops/fused_lookup.py (measured-winners "
            "policy) so kernel='auto' serves them."
        )


def _run_cases(cases):
    """Shared bench loop: (name, fn, args, logical_bytes) -> {name: GB/s}."""
    results = {}
    for name, fn, fargs, nbytes in cases:
        dt_s = bench(fn, *fargs)
        gbps = nbytes / dt_s / 1e9
        results[name] = gbps
        print(f"{name:20s} {dt_s * 1e6:9.1f} us   {gbps:8.1f} GB/s")
    return results


def _verdicts(results, arms, threshold=1.05):
    """Per-op winner lines for a two-arm comparison, 5% tie band."""
    a, b = arms
    for op in ("gather", "scatter"):
        ka = next(k for k in results if k.startswith(f"{op}/{a}"))
        kb = next(k for k in results if k.startswith(f"{op}/{b}"))
        va, vb = results[ka], results[kb]
        winner = b if vb > va * threshold else (a if va > vb * threshold
                                                else "tie")
        print(f"verdict[{op}]: {winner} ({a} {va:.1f} vs {b} {vb:.1f} GB/s)")


def main_traffic(args):
    """Traffic-diet microbench: the full train lookup+apply pair for one
    table on zipf-skewed ids, diet arm vs legacy-apply arm.

    Both arms share the table layout (the fused [3, C] metadata leaf is
    structural); the arms differ exactly by what the diet removed from the
    apply — the [U, D] value re-gather and the version/dirty re-stamp pair
    (`apply_gradients(reuse_rows=, stamp_meta=)`) — so the delta isolates
    the diet's win.  The op-count lines additionally show the fused-meta
    structural saving against the recorded pre-diet inventory
    (ops/traffic.py).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeprec_tpu.config import TableConfig
    from deeprec_tpu.data.synthetic import zipf_ids
    from deeprec_tpu.embedding.table import EmbeddingTable
    from deeprec_tpu.ops import dedup
    from deeprec_tpu.ops.traffic import (
        count_stablehlo_ops, table_step_traffic,
    )
    from deeprec_tpu.optim import Adagrad
    from deeprec_tpu.optim.apply import apply_gradients, ensure_slots

    if args.smoke:
        cap_log2, N, iters = min(args.capacity, 14), 4096, 5
    else:
        cap_log2, N, iters = args.capacity, args.batch, 30
    D = args.dim
    cfg = TableConfig(name="traffic_bench", dim=D, capacity=1 << cap_log2,
                      value_dtype=args.dtype)
    t = EmbeddingTable(cfg)
    opt = Adagrad(lr=0.05)
    state0 = ensure_slots(t, t.create(), opt)
    rng = np.random.default_rng(0)
    vocab = min(1 << cap_log2, 1 << 20) // 2
    ids = jnp.asarray(zipf_ids(rng, vocab, args.zipf, (N,)), jnp.int32)
    U = dedup.resolve_size(max(N // 2, 8), N)

    def pair(diet):
        def fn(state, ids, step):
            state, res = t._lookup_unique_impl(
                state, ids, step, True, -1, U
            )
            g = jnp.ones_like(res.embeddings, jnp.float32)
            return apply_gradients(
                t, state, opt, res, g, step=step,
                reuse_rows=diet, stamp_meta=not diet,
            )
        return jax.jit(fn)  # noqa: DRT001 — built once per bench invocation, reused across the timed loop

    step = jnp.int32(1)
    arms = {"legacy_apply": pair(False), "diet": pair(True)}
    ops = {
        name: count_stablehlo_ops(fn.lower(state0, ids, step).as_text())
        for name, fn in arms.items()
    }
    # Warm the table once so every timed window hits resolved slots, then
    # INTERLEAVE the arms' timed windows (3 rounds, best window per arm) —
    # this box's single-core drift otherwise biases whichever arm runs
    # last, swamping the few-percent delta under measurement.
    st = arms["diet"](state0, ids, step)
    for fn in arms.values():  # compile both before any timing
        bench(fn, st, ids, step, iters=1, warmup=2)
    results = {name: [] for name in arms}
    for _ in range(1 if args.smoke else 3):
        for name, fn in arms.items():
            results[name].append(
                bench(fn, st, ids, step, iters=iters, warmup=1)
            )
    results = {name: min(ts) for name, ts in results.items()}
    for name in arms:
        print(f"{name:16s} {results[name] * 1e3:9.3f} ms/step (best)   "
              f"ops: {ops[name]['gather']} gathers, "
              f"{ops[name]['scatter']} scatters")
    saved_s = ops["legacy_apply"]["scatter"] - ops["diet"]["scatter"]
    speed = results["legacy_apply"] / results["diet"]
    model_b = table_step_traffic(
        unique=U, dim=D, value_bytes=jnp.dtype(args.dtype).itemsize,
        slot_widths=(D,), diet=True,
    )
    model_a = table_step_traffic(
        unique=U, dim=D, value_bytes=jnp.dtype(args.dtype).itemsize,
        slot_widths=(D,), diet=False,
    )
    print(
        f"verdict[traffic]: diet {speed:.2f}x vs legacy apply "
        f"(-{saved_s} scatter ops, -1 [U,D] gather; modeled "
        f"{model_a['hbm_bytes'] / 1e3:.1f} -> "
        f"{model_b['hbm_bytes'] / 1e3:.1f} KB/step/table, "
        f"{1 - model_b['hbm_bytes'] / model_a['hbm_bytes']:.1%} off; "
        f"fused metadata's 5->1 scatter collapse is structural and in "
        f"BOTH arms — see docs/perf.md for the full before/after)"
    )
    if saved_s <= 0:
        print("ERROR: diet removed no scatters — the apply-side "
              "re-stamps are back in the hot path", file=sys.stderr)
        sys.exit(1)
    if not args.smoke and speed < 1.0:
        print("WARNING: diet arm measured slower — investigate before "
              "trusting the removed ops on this backend", file=sys.stderr)


def main_fused_step(args):
    """Fused single-pass sparse step vs the split-phase XLA path.

    Both arms run the SAME contract (fused_sparse_forward/backward): the
    unfused arm takes the XLA fallback (dedup_at_budget -> gather -> combine;
    expand -> segment-add -> gather/update/scatter), the fused arm the
    Pallas kernel — interpret=True off-TPU, so off-TPU step times say
    nothing about the TPU answer and the verdict here is (a) parity and
    (b) the modeled HBM-byte ratio `roofline.py --assert-fused` gates on.
    Both arms are jitted (the parity contract: matching XLA FMA
    contraction — see docs/kernels.md) and timed interleaved best-of.
    """
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeprec_tpu.data.synthetic import zipf_ids
    from deeprec_tpu.ops import dedup
    from deeprec_tpu.ops import fused_lookup as fl
    from deeprec_tpu.ops.traffic import fused_sparse_step_traffic
    from deeprec_tpu.optim import Adagrad

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"WARNING: running on {backend}; fused arm runs "
              "interpret=True — times say nothing about TPU",
              file=sys.stderr)
    if args.smoke:
        B, L, cap_log2, budget, iters, rounds = 32, 4, 9, 31, 2, 1
    else:
        B, L, cap_log2, budget, iters, rounds = 256, 4, 12, 127, 8, 3
    D, C, N = args.dim, 1 << cap_log2, B * L
    U = dedup.resolve_size(budget, N)
    dt = jnp.dtype(args.dtype)
    interp = backend != "tpu"
    combiner = "mean"
    opt = Adagrad(lr=0.05)
    slot_widths = tuple(
        shape[0] for name, (shape, _) in opt.slot_specs(D).items()
    )

    rng = np.random.default_rng(0)
    values = jnp.asarray(rng.normal(0, 0.05, (C, D)), dt)
    slots = {
        name: jnp.full((C, D), init, jnp.float32)
        for name, (shape, init) in opt.slot_specs(D).items()
    }
    # vocab < budget so overflow == 0: with overflow, WHICH distinct ids
    # make the budget is path-dependent (both answers valid), and the
    # bitwise parity probe below would compare two different samples. The
    # heavy duplication this produces is also the regime the dedup engine
    # exists for (zipf-skewed bag features).
    ids = np.asarray(zipf_ids(rng, max(budget // 2, 4), args.zipf, (B, L)))
    ids[rng.random((B, L)) < 0.1] = -1  # pads, like real bag features
    ids = jnp.asarray(ids, jnp.int32)

    def make_fwd(fused):
        def fn(v, i):
            return fl.fused_sparse_forward(
                v, i, combiner=combiner, unique_size=U,
                interpret=fused and interp, use_pallas=fused,
            )
        return jax.jit(fn)  # noqa: DRT001 — built once per bench invocation, reused across the timed loop

    def make_step(fused):
        def fn(v, s, i):
            res = fl.fused_sparse_forward(
                v, i, combiner=combiner, unique_size=U,
                interpret=fused and interp, use_pallas=fused,
            )
            g = res.out + 1.0  # any grad; keeps fwd in the timed graph
            return fl.fused_sparse_backward(
                v, s, g, i, res, opt, combiner=combiner, step=1, seed=7,
                interpret=fused and interp, use_pallas=fused,
            )
        return jax.jit(fn)  # noqa: DRT001 — built once per bench invocation, reused across the timed loop

    # --- parity probe (the oracle contract, both sides jitted) ---
    out_u = make_fwd(False)(values, ids)
    out_f = make_fwd(True)(values, ids)
    fwd_ok = bool(jnp.array_equal(out_u.out, out_f.out))
    (v_u, s_u), (v_f, s_f) = (
        make_step(False)(values, slots, ids),
        make_step(True)(values, slots, ids),
    )
    bwd_ok = bool(jnp.array_equal(v_u, v_f)) and all(
        bool(jnp.array_equal(s_u[k], s_f[k])) for k in s_u
    )
    vb16 = values.astype(jnp.bfloat16)
    vb_u, _ = make_step(False)(vb16, slots, ids)
    vb_f, _ = make_step(True)(vb16, slots, ids)
    sr_ok = bool(jnp.array_equal(vb_u, vb_f))

    # --- timing: interleaved best-of, like --traffic ---
    arms = {"unfused": make_step(False), "fused": make_step(True)}
    for fn in arms.values():
        bench(fn, values, slots, ids, iters=1, warmup=2)
    times = {name: [] for name in arms}
    for _ in range(rounds):
        for name, fn in arms.items():
            times[name].append(
                bench(fn, values, slots, ids, iters=iters, warmup=1)
            )
    times = {name: min(ts) for name, ts in times.items()}

    model = {
        arm: fused_sparse_step_traffic(
            positions=N, batch=B, unique=U, dim=D, value_bytes=dt.itemsize,
            slot_widths=slot_widths, fused=(arm == "fused"),
        )["hbm_bytes"]
        for arm in ("unfused", "fused")
    }
    ratio = model["fused"] / model["unfused"]
    for name in arms:
        print(f"{name:10s} {times[name] * 1e3:9.3f} ms/step (best)   "
              f"modeled {model[name] / 1e3:10.1f} KB/step/table")
    print(
        f"verdict[fused-step]: modeled HBM {ratio:.3f}x unfused "
        f"(gate <= 0.6); parity fwd={fwd_ok} bwd={bwd_ok} bf16_sr={sr_ok} "
        f"on {backend}" + (" (interpret)" if interp else "")
    )
    record = {
        "fused_step": {
            "shapes": {
                "batch": B, "bag": L, "positions": N, "unique": U,
                "dim": D, "capacity": C, "dtype": str(dt),
                "optimizer": "adagrad", "combiner": combiner,
                "slot_widths": list(slot_widths),
            },
            "arms": {n: {"ms": times[n] * 1e3} for n in arms},
            "modeled": {
                "unfused_hbm_bytes": model["unfused"],
                "fused_hbm_bytes": model["fused"],
                "ratio": ratio,
            },
            "parity": {
                "forward_bitwise": fwd_ok,
                "backward_bitwise": bwd_ok,
                "bf16_sr_bitwise": sr_ok,
            },
            "backend": backend + ("/interpret" if interp else ""),
        }
    }
    if args.out:
        merged = {}
        if os.path.exists(args.out):
            with open(args.out) as f:
                merged = json.load(f)
        merged.update(record)
        with open(args.out, "w") as f:
            json.dump(merged, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"recorded -> {args.out}")
    if not (fwd_ok and bwd_ok and sr_ok):
        print("ERROR: fused step lost oracle parity vs the split-phase "
              "path", file=sys.stderr)
        sys.exit(1)


def main_packed(args):
    """Packed-vs-unpacked layout at dim < 128: same logical op, two
    storage layouts, each arm running the kernels production's
    kernel='auto' would serve it (the packed array is DMA-eligible at
    128 lanes; the unpacked small-dim arm self-gates to XLA). On TPU the
    packed array dodges the 128-lane minor-dim padding (P× less HBM read
    per gather); on CPU it measured -36% (docs/perf.md) — this
    prints the per-backend verdict the TableConfig.packed='auto' gate
    encodes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeprec_tpu.ops.fused_lookup import (
        AUTO_TRUSTS_BF16_PAIR, AUTO_TRUSTS_F32_ROW,
    )
    from deeprec_tpu.ops.packed import (
        gather_rows_any, pack_array, pack_factor, scatter_rows_any,
    )

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"WARNING: running on {backend}; TPU is the question",
              file=sys.stderr)
    C, D = 1 << args.capacity, args.dim
    U = min(args.batch, C)  # scatter contract needs unique slots
    if U < args.batch:
        print(f"note: batch clamped to capacity ({U}) for unique-slot "
              "scatter", file=sys.stderr)
    P = pack_factor(D, C)
    if P == 1:
        print(f"dim={D} capacity=2^{args.capacity} does not pack "
              "(need dim<128, dim|128, capacity%(128//dim)==0)",
              file=sys.stderr)
        return
    dt = jnp.dtype(args.dtype)
    rng = np.random.default_rng(0)
    logical = jnp.asarray(rng.normal(0, 0.05, (C, D)), dt)
    packed = pack_array(logical, P)
    ix = jnp.asarray(rng.integers(0, C, U), jnp.int32)
    rows = jnp.asarray(rng.normal(0, 0.05, (U, D)), jnp.float32)
    uix = jnp.asarray(rng.permutation(C)[:U].astype(np.int32))

    # Match production's kernel='auto' flags; the layout-polymorphic ops
    # dispatch per arm from the array shape, and ineligible shapes
    # self-gate back to XLA exactly as they do in the table hot path.
    kw = dict(use_pallas=AUTO_TRUSTS_F32_ROW,
              pair_kernels=AUTO_TRUSTS_BF16_PAIR)
    g = jax.jit(lambda v, i: gather_rows_any(v, i, C, **kw))  # noqa: DRT001 — built once per bench invocation, reused across the timed loop
    s = jax.jit(lambda v, i, r: scatter_rows_any(v, i, r, C, **kw))  # noqa: DRT001 — built once per bench invocation, reused across the timed loop

    bytes_g = U * D * dt.itemsize
    bytes_s = U * D * (dt.itemsize + 4)
    results = _run_cases((
        ("gather/unpacked", g, (logical, ix), bytes_g),
        (f"gather/packed_x{P}", g, (packed, ix), bytes_g),
        ("scatter/unpacked", s, (logical, uix, rows), bytes_s),
        (f"scatter/packed_x{P}", s, (packed, uix, rows), bytes_s),
    ))
    _verdicts(results, ("unpacked", "packed"))
    print("note: GB/s counts LOGICAL bytes, so the packed arm's TPU "
          "advantage (no lane padding) shows up as higher throughput; on "
          "TPU a packed win validates TableConfig.packed='auto' — record "
          "the numbers in docs/perf.md.")


if __name__ == "__main__":
    main()
