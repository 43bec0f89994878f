#!/usr/bin/env python3
"""Read what `correct` compares, on the chip, at a cell's own size, over
several seeds in one process: the program against the reference (the lower
readings), and in the program's place each of the reference's `CONTROLS`
(for `reference/dlrm.py`: the control, the reference with float8 operands;
the planted fault, half of the batch left out; the second witness, the
reference in the program's own bfloat16 arithmetic). The limits in
`limits/<cell>.json` are set from what this prints; PERF.md keeps the
readings.

    python3 benchmark/tools/calibrate.py --workload <cell> \
        --seeds 101,102,... --control-seeds 101,102,103 --out <file.json>
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", required=True)
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--data", default=os.path.join(ROOT, "benchmark"))
    ap.add_argument("--any-platform", action="store_true",
                    help="for the CPU rehearsal; readings are then no "
                         "device readings")
    args = ap.parse_args()

    from benchmark import correct, harness

    _, cell, config, mix, builder, reference, _, generator = \
        harness.load_cell(args.workload, args.root, args.data)
    kinds = dict(reference.CONTROLS)
    if args.any_platform:
        cache = None
    else:
        from deeprec_tpu.utils.backend import enable_compile_cache

        cache = enable_compile_cache()
    device = harness.device_facts(cell["chips"], not args.any_platform)
    program = builder.Program(config, mix)
    record = {"workload": args.workload, "device": device, "cache": cache,
              "program": {}, **{kind: {} for kind in kinds}, "readings": {}}

    def numbers(a, b):
        return {k: [v["value"], v["leaf"]]
                for k, v in correct.compare(a, b).items()}

    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    for seed in seeds:
        t0 = time.perf_counter()
        pseed = harness.program_seed(seed)
        state = program.fresh_state(pseed)
        k = iter(range(harness.CHECK_STEPS))

        def next_batch():
            host = generator.make_batch(mix, seed, next(k))
            return host, program.put(host)

        state, prog, batches = harness.check_steps(
            program, state, next_batch, config, reference)
        del state
        gc.collect()
        ref = reference.run(config, batches, pseed)
        record["program"][seed] = numbers(prog, ref)
        full = record["readings"].setdefault(seed, {})
        full["program"], full["reference"] = prog, ref
        if seed in controls:
            for kind, kw in kinds.items():
                full[kind] = reference.run(config, batches, pseed, **kw)
                record[kind][seed] = numbers(full[kind], ref)
        harness.log(f"seed {seed}: {time.perf_counter() - t0:.1f} s "
                    + json.dumps(record["program"][seed]))
        for kind in kinds:
            if seed in record[kind]:
                harness.log(f"   {kind}: " + json.dumps(record[kind][seed]))
    summary = {}
    for n in next(iter(record["program"].values())):
        prog_max = max(r[n][0] for r in record["program"].values())
        row = {"program_max": prog_max}
        for kind in kinds:
            if record[kind]:
                row[kind + "_min"] = min(r[n][0]
                                         for r in record[kind].values())
        summary[n] = row
    record["summary"] = summary
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps(summary, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
