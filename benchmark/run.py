#!/usr/bin/env python3
"""The benchmark's command (BENCHMARK.json `command`):

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up, measures one window, checks the timed path against the
plain reference and prints, as the last line of stdout, one JSON object:
correct, attempted, failed, metrics, device (and breakdown with --trace 1,
and last `compared`: each number compared beside its limit). Everything else
goes to stderr. Exits non-zero, printing no result, off a TPU or with fewer
chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from benchmark import harness

    # the program's own placement of the compile cache: the directory
    # JAX_COMPILATION_CACHE_DIR names, else <checkout>/.jax_cache
    from deeprec_tpu.utils.backend import enable_compile_cache

    cache_dir = enable_compile_cache()
    harness.log(f"compile cache: {cache_dir}")
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START)
    for name, row in result["compared"].items():
        harness.log(f"compared {name}: {row['value']:.6g} "
                    f"(limit {row['limit']:.6g})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
