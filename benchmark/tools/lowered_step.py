#!/usr/bin/env python3
"""Lower each cell's whole train step for a v5e that is described and not
attached, and print the sha256 of its text: two trees whose hashes agree
run the same step program, so a change to shared code that leaves a cell's
hash alone cannot move that cell. The Mosaic payloads carry no source lines
(`jax_traceback_in_locations_limit` 0), so the text does not depend on
where the code lives or on its line numbers.

    python3 benchmark/tools/lowered_step.py --root <a checkout> \
        [--out <file.json>]

`--root` is the tree whose program and benchmark are lowered (by default
this one), every cell of its BENCHMARK.json.
Needs libtpu to describe the chip, no chip; abstract shapes only, nothing
is allocated at the cells' sizes.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    out = os.path.abspath(args.out) if args.out else ""
    sys.path.insert(0, root)
    os.chdir(root)

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import harness
    from deeprec_tpu.utils import backend

    jax.config.update("jax_traceback_in_locations_limit", 0)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    backend.on_tpu = lambda: True

    def sd(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=dev)

    hashes = {}
    for cell in (w["name"] for w in harness.load_manifest(root)["workloads"]):
        c = harness.load_cell(cell, root, os.path.join(root, "benchmark"))
        tr = c.builder.Program(c.config, c.mix).trainer
        shapes = jax.eval_shape(tr.init, np.int32(1))
        batch = jax.eval_shape(tr.stage_batch,
                               c.generator.make_batch(c.mix, 1, 0))
        text = tr._train_step.lower(
            jax.tree.map(sd, shapes), jax.tree.map(sd, batch),
            jax.ShapeDtypeStruct((), np.float32)).as_text()
        hashes[cell] = {"bytes": len(text),
                        "sha256": hashlib.sha256(text.encode()).hexdigest()}
        print(cell, hashes[cell]["bytes"], hashes[cell]["sha256"],
              flush=True)
    if out:
        os.makedirs(os.path.dirname(out), exist_ok=True)
        with open(out, "w") as f:
            json.dump(hashes, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
