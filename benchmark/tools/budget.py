#!/usr/bin/env python3
"""Reckon a traffic mix's unique budget on the CPU, from the generator alone
(one that gives `draw_ids(mix, seed, k) -> [fields, n]`, as `criteo` does).

    python3 benchmark/tools/budget.py <mix> [--batches 400] [--seeds 5]

Prints the largest per-field unique-id count in any of the first batches of
several seeds, and the budget: that count plus a slack of 4 standard
deviations of the per-batch count and never less than 2 %, so that no step
of a window overflows on any seed. The number goes into the mix's file by
hand.
"""
from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmark import harness  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("mix")
    ap.add_argument("--batches", type=int, default=400)
    ap.add_argument("--seeds", type=int, default=5)
    args = ap.parse_args()
    mix, generator = harness.load_mix(args.mix)
    counts = np.asarray([[len(np.unique(r))
                          for r in generator.draw_ids(mix, 1_000_003 * seed, k)]
                         for seed in range(1, args.seeds + 1)
                         for k in range(args.batches)])
    top, sd = int(counts.max()), float(counts.std())
    slack = max(int(np.ceil(4 * sd)), int(np.ceil(0.02 * top)))
    print(f"{args.mix}: per-field uniques a batch: mean {counts.mean():.1f} "
          f"sd {sd:.1f} max {top} over {len(counts)} batches x "
          f"{counts.shape[1]} fields; unique fraction "
          f"{counts.mean() / mix['batch']:.4f}; budget {top} + {slack} = "
          f"{min(top + slack, mix['batch'])}")


if __name__ == "__main__":
    main()
