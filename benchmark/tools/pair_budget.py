#!/usr/bin/env python3
"""Reckon the expert layer's static budget of (token, expert) pairs for a
cell of the `qwen3next` family, on the chip, at the cell's own size: the
pairs routed to the experts held here in the FULLEST layer of every step of
several seeds' first steps, in a run's own order (the steps read for
`correct`, the fill, the window's batches): the router trains, so the count
drifts over a window and from seed to seed; run as many steps as set-up and
a window make.

    python3 benchmark/tools/pair_budget.py --workload <cell> \
        --seeds 1000003,2000006,... --steps 120 --out <file.json>

Prints the largest count, its standard deviation over the steps, and the
budget: the largest count plus 4 sd, as tools/budget.py sizes the unique
budget. The number goes into the mix's file (`pair_budget`) by hand. The
mix's present budget has to hold every step (an overflowing step leaves
pairs out and its count is then of another trajectory): the tool fails
where one did not.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--out", required=True)
    ap.add_argument("--any-platform", action="store_true")
    args = ap.parse_args()

    from benchmark import harness

    _, cell, config, mix, builder, _, _, generator = harness.load_cell(
        args.workload)
    if not args.any_platform:
        from deeprec_tpu.utils.backend import enable_compile_cache

        enable_compile_cache()
    harness.device_facts(cell["chips"], not args.any_platform)
    program = builder.Program(config, mix)
    record = {"workload": args.workload, "pair_budget": mix["pair_budget"],
              "fullest_layer_pairs": {}, "all_layers_pairs": {}}
    for seed in (int(s) for s in args.seeds.split(",") if s):
        state = program.fresh_state(harness.program_seed(seed))
        fullest, total, over = [], [], []
        n_fill = generator.fill_steps(mix)
        for k in range(args.steps):
            # a run's own order: the steps read for `correct`, the fill,
            # then the window's batches (harness.run_cell)
            j = k - harness.CHECK_STEPS
            host = (generator.fill_batch(mix, seed, j) if 0 <= j < n_fill
                    else generator.make_batch(mix, seed, k))
            batch = program.put(host)
            state, mets = program.trainer.train_step(state, batch)
            fullest.append(mets["moe_pairs_max"])
            total.append(mets["moe_pairs"])
            over.append(mets["moe_overflow"])
        fullest, total, over = (np.asarray([int(x) for x in xs])
                                for xs in (fullest, total, over))
        if over.any():
            raise SystemExit(f"seed {seed}: the present budget "
                             f"{mix['pair_budget']} overflowed")
        record["fullest_layer_pairs"][seed] = fullest.tolist()
        record["all_layers_pairs"][seed] = total.tolist()
        harness.log(f"seed {seed}: fullest layer's pairs a step: first "
                    f"{fullest[0]}, last {fullest[-1]}, max {fullest.max()}, "
                    f"sd {fullest.std():.1f}")
        del state
    counts = np.concatenate([np.asarray(v) for v in
                             record["fullest_layer_pairs"].values()])
    top, sd = int(counts.max()), float(counts.std())
    record["summary"] = {"max": top, "sd": sd, "mean": float(counts.mean()),
                         "budget": top + int(np.ceil(4 * sd))}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(record, f)
    print(json.dumps(record["summary"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
