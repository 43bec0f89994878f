#!/usr/bin/env python3
"""Record the small scoped trace that benchmark/tests/test_phase_reduce.py
reads (benchmark/tests/data/phases.xplane.pb.xz), on the chip:

    python3 benchmark/tools/record_phase_trace.py <out.xplane.pb.xz> \\
        [<expected.json>]

The test configuration `tiny-dcn` at dim 128 (so that the rows take the
Pallas row kernels, as in the cells), batch 64, budget 48: three steps to
compile and insert, then three traced steps through `stage_batch` and
`train_step` with the harness's profiler options, in a process set up as
the benchmark's (`enable_compile_cache()`). With a second path, what
`phase_reduce.reduce_file` makes of the file is pinned there.
"""
from __future__ import annotations

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
    import jax

    from benchmark import harness, phase_reduce, trace_reduce, traffic
    from benchmark.builders import dlrm
    from deeprec_tpu.utils.backend import enable_compile_cache

    out = sys.argv[1]
    enable_compile_cache()   # as benchmark/run.py does
    harness.device_facts(1, require_tpu=True)
    data = os.path.join(ROOT, "benchmark", "tests", "data")
    with open(os.path.join(data, "configs", "tiny-dcn.json")) as f:
        config = dict(json.load(f), emb_dim=128, bottom_mlp=[32, 128],
                      capacity=1024)
    mix = dict(traffic.load_mix("tiny-zipf-u48", data), vocab=512)
    program = dlrm.Program(config, mix)
    state = program.fresh_state(7)
    batches = [traffic.make_batch(mix, 7, k) for k in range(2 * STEPS)]
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
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(path, "rb") as src, lzma.open(out, "wb", preset=9) as dst:
        shutil.copyfileobj(src, dst)
    print(f"recorded {os.path.getsize(path)} bytes -> {out} "
          f"({os.path.getsize(out)} bytes)")
    if len(sys.argv) > 2:
        red = phase_reduce.reduce_file(path, 1)
        device = jax.devices()[0].device_kind
        with open(sys.argv[2], "w") as f:
            json.dump({
                "note": "benchmark/phase_reduce.py::reduce_file of "
                        f"phases.xplane.pb.xz (one {device}; "
                        "benchmark/tools/record_phase_trace.py), seconds "
                        f"of the {STEPS} traced steps",
                **{k: red[k] for k in ("busy_s", "by_phase_s", "by_stage_s",
                                       "rows_s", "kernels_s",
                                       "probe_passes")},
                "step_nums": [n for n, *_ in red["train_steps"]],
            }, f, indent=1)
    shutil.rmtree(trace_dir)
    return 0


if __name__ == "__main__":
    sys.exit(main())
