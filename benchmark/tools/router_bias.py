#!/usr/bin/env python3
"""Hold the rule that owns the routers' selection bias to the reference's,
on the chip, at a cell's own size: after the steps read for `correct`
(`harness.CHECK_STEPS`), how many entries of every expert layer's bias the
program and the plain reference agree on, entry for entry. The comparison
that decides `correct` leaves these leaves out of the change (their
reference gradient is 0); the CPU tests hold them exactly at a small size.
On the chip the program's bf16 products can put an output whose load is
within a few tokens of the mean on the other side of it, and its entry
then moves the other way: the tool counts those.

    python3 benchmark/tools/router_bias.py --workload <cell> \
        --seeds 3000003501,... --out <file.json>

For a family whose reference's `run` gives "bias" ({leaf: entries}) and
whose builder's `dense_params(state)` names the same leaves.
"""
from __future__ import annotations

import argparse
import gc
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
    ap.add_argument("--out", required=True)
    ap.add_argument("--any-platform", action="store_true")
    args = ap.parse_args()

    from benchmark import harness

    _, cell, config, mix, builder, reference, _, generator = \
        harness.load_cell(args.workload)
    if not args.any_platform:
        from deeprec_tpu.utils.backend import enable_compile_cache

        enable_compile_cache()
    harness.device_facts(cell["chips"], not args.any_platform)
    program = builder.Program(config, mix)
    record = {"workload": args.workload, "steps": harness.CHECK_STEPS,
              "seeds": {}}
    for seed in (int(s) for s in args.seeds.split(",") if s):
        pseed = harness.program_seed(seed)
        state = program.fresh_state(pseed)
        k = iter(range(harness.CHECK_STEPS))

        def next_batch():
            host = generator.make_batch(mix, seed, next(k))
            return host, program.put(host)

        state, _, batches = harness.check_steps(
            program, state, next_batch, config, reference)
        ours = {name: np.asarray(leaf) for name, leaf in
                program.dense_params(state).items()
                if name.endswith(".bias")}
        del state
        gc.collect()
        ref = reference.run(config, batches, pseed)
        rate = config["bias_update_rate"]
        rows = {}
        for name, want in ref["bias"].items():
            want = np.asarray(want, np.float32)
            rows[name] = {
                "entries": int(want.size),
                "equal": int(np.sum(ours[name] == want)),
                "largest_gap_in_moves": float(
                    np.max(np.abs(ours[name] - want)) / rate)}
        # how near the mean the reference's loads of the disagreeing
        # outputs stood, last step: a sign can flip only there
        last = np.asarray(ref["loads"][-1], np.float64)
        record["seeds"][seed] = {
            "by_leaf": rows,
            "entries": sum(r["entries"] for r in rows.values()),
            "equal": sum(r["equal"] for r in rows.values()),
            "last_step_load_sd": float(last.std(axis=1).mean()),
            "last_step_load_max_over_mean": float(
                (last.max(axis=1) / last.mean(axis=1)).mean())}
        harness.log(f"seed {seed}: " + json.dumps(record["seeds"][seed]))
    total = sum(r["entries"] for r in record["seeds"].values())
    equal = sum(r["equal"] for r in record["seeds"].values())
    record["summary"] = {"entries": total, "equal": equal,
                         "share_equal": equal / max(total, 1)}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps(record["summary"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
