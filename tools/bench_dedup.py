"""Microbenchmark: `jnp.unique` (`sort_unique`) vs `dedup_at_budget`.

Times the two dedup implementations behind the embedding hot path
(`ops/dedup.py`) at identical static output sizes, across flattened batch
size N, unique-budget ratios U/N and zipf skew — the knob space of
`TableConfig.unique_budget`. The reference shape is the DLRM bench batch:
N = 26 features x 2048 = 53,248 flattened ids, U/N = 0.25, zipf α = 1.05
(the heaviest-tail column of the CriteoStats generator).

ITS GRID IS A CPU GRID: it runs on whatever platform jax resolves (named in
the JSON), times each call from the host, and has only ever been recorded
on this box's CPU (docs/perf.md), where the claim-race loop PR 36 replaced
beat `jnp.unique` 1.45-1.89x and the sort form that replaced it is about
3x slower than that loop. None of that carries to a TPU, where the
order is the other way round (PERF.md section 6, PR 36); what the chip
pays for the dedup is `route_device_ms_per_step` of the benchmark's
cells, read from a device trace.

Prints ONE JSON line (the bench.py convention):
  rows[]    — per-(N, ratio, alpha): sort_ms (`sort_unique`), budget_ms
              (`dedup_at_budget`), speedup, true_unique_frac, overflow
              (ids past the budget, served the default by the contract)
  reference — the DLRM reference-shape row

`--smoke` shrinks the grid and the timed windows so CI merely proves both
paths compile and run (cibuild/run_tests.sh).
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _bench_one(N, ratio, alpha, reps, vocab=None):
    import jax
    import jax.numpy as jnp

    from deeprec_tpu.data.synthetic import zipf_ids
    from deeprec_tpu.ops import dedup

    vocab = vocab or max(1024, N)
    rng = np.random.default_rng(7)
    ids = zipf_ids(rng, vocab, alpha, (N,)).astype(np.int32)
    sentinel = int(np.iinfo(np.int32).min)
    # ~2% padding, collapsed onto the sentinel like the lookup path does.
    flat = np.where(rng.random(N) < 0.02, sentinel, ids).astype(np.int32)
    true_unique = int(np.unique(flat[flat != sentinel]).size)
    size = dedup.resolve_size(max(1, int(N * ratio)), N)

    sort_fn = jax.jit(  # noqa: DRT001 — built once per bench invocation, reused across the timed loop
        lambda f: dedup.sort_unique(f, size, sentinel=sentinel)
    )
    budget_fn = jax.jit(  # noqa: DRT001 — built once per bench invocation, reused across the timed loop
        lambda f: dedup.dedup_at_budget(f, size, sentinel=sentinel)
    )
    x = jnp.asarray(flat)

    def timed(fn):
        jax.block_until_ready(fn(x))  # compile + warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x))
            best = min(best, time.perf_counter() - t0)
        return best * 1e3

    sort_ms = timed(sort_fn)
    budget_ms = timed(budget_fn)
    overflow = int(budget_fn(x)[3])
    return {
        "N": N,
        "ratio": ratio,
        "alpha": alpha,
        "size": size,
        "sort_ms": round(sort_ms, 3),
        "budget_ms": round(budget_ms, 3),
        "speedup": round(sort_ms / budget_ms, 2) if budget_ms else None,
        "true_unique_frac": round(true_unique / N, 4),
        "overflow": overflow,
    }


REFERENCE = {"N": 26 * 2048, "ratio": 0.25, "alpha": 1.05}


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--smoke", action="store_true",
                   help="tiny grid + short windows: CI compile check")
    args = p.parse_args()

    import jax

    if args.smoke:
        grid = [(4096, 0.25, 1.05)]
        reps = 2
    else:
        grid = [
            (N, ratio, alpha)
            for N in (8192, 26 * 2048)
            for ratio in (0.25, 0.5, 1.0)
            for alpha in (1.05, 1.2)
        ]
        reps = args.reps

    rows = [_bench_one(N, r, a, reps) for (N, r, a) in grid]
    ref = next(
        (
            row for row in rows
            if (row["N"], row["ratio"], row["alpha"])
            == (REFERENCE["N"], REFERENCE["ratio"], REFERENCE["alpha"])
        ),
        None,
    )
    if ref is None and not args.smoke:
        ref = _bench_one(REFERENCE["N"], REFERENCE["ratio"],
                         REFERENCE["alpha"], reps)
    print(json.dumps({
        "metric": "dedup_unique_vs_budget",
        "rows": rows,
        "reference": ref,
        "device": jax.devices()[0].platform,
        "backend": jax.default_backend(),
    }))


if __name__ == "__main__":
    main()
