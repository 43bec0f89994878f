#!/usr/bin/env python3
"""Record a small scoped trace for the benchmark's tests, on the chip:

    python3 benchmark/tools/record_phase_trace.py <out.xplane.pb.xz> \\
        [<expected.json>] [--cell <workload> --root <dir>]

Without `--cell`: the trace benchmark/tests/test_phase_reduce.py reads
(benchmark/tests/data/phases.xplane.pb.xz), of the test configuration
`tiny-dcn` at dim 128 (so that the rows take the Pallas row kernels, as in
the cells), batch 64, budget 48. With `--cell`: a cell of the manifest
under `<dir>`, which also holds its data files and whatever modules the
benchmark itself does not (a family that is test data), as it stands.
Either way three steps to compile and insert, then three traced steps
through the program's `put` and `step` with the harness's profiler options,
in a process set up as the benchmark's (`enable_compile_cache()`). With a
second path, what `phase_reduce.reduce_file` makes of the file is pinned
there.
"""
from __future__ import annotations

import argparse
import json
import lzma
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
STEPS = 3


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("out")
    ap.add_argument("expected", nargs="?")
    ap.add_argument("--cell")
    ap.add_argument("--root")
    args = ap.parse_args()

    import jax

    from benchmark import harness, phase_reduce, trace_reduce
    from deeprec_tpu.utils.backend import enable_compile_cache

    enable_compile_cache()   # as benchmark/run.py does
    harness.device_facts(1, require_tpu=True)
    if args.cell:
        data = os.path.abspath(args.root)
        _, _, config, mix, builder, _, _, generator = harness.load_cell(
            args.cell, data, data)
    else:
        data = os.path.join(ROOT, "benchmark", "tests", "data")
        with open(os.path.join(data, "configs", "tiny-dcn.json")) as f:
            config = dict(json.load(f), emb_dim=128, bottom_mlp=[32, 128],
                          capacity=1024)
        mix, generator = harness.load_mix("tiny-zipf-u48", data)
        mix = dict(mix, vocab=512)
        builder = harness.load_module("builders", config["builder"], data)
    program = builder.Program(config, mix)
    state = program.fresh_state(7)
    batches = [generator.make_batch(mix, 7, k) for k in range(2 * STEPS)]
    for host in batches[:STEPS]:
        state, loss = program.step(state, program.put(host))
    jax.block_until_ready(loss)
    trace_dir = tempfile.mkdtemp(prefix="phase_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    for host in batches[STEPS:]:
        state, loss = program.step(state, program.put(host))
    jax.block_until_ready(loss)
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(trace_dir)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(path, "rb") as src, lzma.open(args.out, "wb", preset=9) as dst:
        shutil.copyfileobj(src, dst)
    print(f"recorded {os.path.getsize(path)} bytes -> {args.out} "
          f"({os.path.getsize(args.out)} bytes)")
    if args.expected:
        red = phase_reduce.reduce_file(path, 1, data)
        device = jax.devices()[0].device_kind
        with open(args.expected, "w") as f:
            json.dump({
                "note": "benchmark/phase_reduce.py::reduce_file of "
                        f"{os.path.basename(args.out)} (one {device}; "
                        "benchmark/tools/record_phase_trace.py), seconds "
                        f"of the {STEPS} traced steps",
                **{k: red[k] for k in ("busy_s", "by_phase_s", "by_stage_s",
                                       "rows_s", "kernels_s")},
                "probe_passes": red["loop_passes"].get("engine_probe", 0.0),
                "by_scope_s": red["by_scope_s"],
                "step_nums": [n for n, *_ in red["train_steps"]],
            }, f, indent=1)
    shutil.rmtree(trace_dir)
    return 0


if __name__ == "__main__":
    sys.exit(main())
