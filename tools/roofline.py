#!/usr/bin/env python
"""Roofline analysis of the DLRM training step: measured throughput vs the
hardware's memory-bandwidth and compute ceilings.

Methodology (docs/perf.md): count the step's algorithmic HBM traffic and
MXU FLOPs from the model config, run the step, and report how much of each
ceiling the measured examples/sec implies. The larger of the two fractions
identifies the binding roof; tuning stops being worth it as it approaches
1.0. Run on the target TPU:

    python tools/roofline.py [--batch 2048] [--emb_dim 16]
        [--peak_bw_gbs 1228] [--peak_tflops 275]   # v4 defaults

CPU runs exercise the accounting but say nothing about TPU roofs.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def mlp_flops(dims, batch):
    """2*in*out MACs->FLOPs per layer, forward only."""
    total = 0
    for a, b in zip(dims[:-1], dims[1:]):
        total += 2 * a * b * batch
    return total


def assert_traffic(json_path: str) -> int:
    """CI gate: the traffic model (deeprec_tpu/ops/traffic.py) must match
    the gather/scatter op counts bench.py measured off the actually-lowered
    lookup+apply program.  Drift — an op added to or removed from the hot
    path without the model learning about it — fails the smoke run."""
    import json

    with open(json_path) as f:
        rec = json.load(f)
    tr = rec.get("traffic")
    if not tr:
        print(f"roofline: {json_path} has no 'traffic' record", file=sys.stderr)
        return 1
    rc = 0
    for arm in ("diet", "legacy_apply"):
        # bench.py records both the measurement (op counts off the lowered
        # program) and the model's prediction from the same checkout; the
        # re-import here also catches a bench JSON produced by stale code.
        measured = tr["ops_measured"][arm]
        recorded = tr["ops_model"][arm]
        for kind in ("gather", "scatter"):
            if measured[kind] != recorded[kind]:
                print(
                    f"roofline: traffic-model drift [{arm}/{kind}]: "
                    f"measured {measured[kind]} vs model {recorded[kind]} "
                    f"— update deeprec_tpu/ops/traffic.py's op inventory "
                    f"to match the hot path",
                    file=sys.stderr,
                )
                rc = 1
    diet_s = tr["ops_measured"]["diet"]["scatter"]
    legacy_s = tr["ops_measured"]["legacy_apply"]["scatter"]
    if diet_s >= legacy_s:
        print(
            f"roofline: the diet no longer removes scatters "
            f"(diet {diet_s} vs legacy {legacy_s})", file=sys.stderr,
        )
        rc = 1
    if rc == 0:
        print(
            f"roofline: traffic model matches measurement "
            f"(diet {tr['ops_measured']['diet']}, legacy "
            f"{tr['ops_measured']['legacy_apply']}; diet removes "
            f"{legacy_s - diet_s} scatters)"
        )
    return rc


def assert_overlap(json_path: str, tol: float) -> int:
    """CI gate for the in-step pipelining grid (bench.py 'pipeline'
    section): the pipelined K-scan arms must exist, must not regress past
    `tol` relative to the sequential arm, the overlap model must be
    internally consistent (the overlapped schedule can never model SLOWER
    than the sequential sum), and the overlap efficiency
    (modeled max(exchange, dense) step vs the measured pipelined step)
    must be recorded. On CPU the efficiency is informational (no async
    collectives to realize the overlap); the regression bound is the
    enforced contract, and on TPU the printed efficiency is the number
    the ROADMAP item asks to close."""
    import json

    with open(json_path) as f:
        rec = json.load(f)
    pipe = rec.get("pipeline")
    if not pipe:
        print(f"roofline: {json_path} has no 'pipeline' record "
              "(run bench.py with --pipeline-mode grid)", file=sys.stderr)
        return 1
    modes = pipe.get("modes", {})
    if "off" not in modes or not any(m != "off" for m in modes):
        print("roofline: pipeline record needs an 'off' arm and at least "
              f"one pipelined arm, got {sorted(modes)}", file=sys.stderr)
        return 1
    rc = 0
    off_ms = modes["off"]["ms_per_step"]
    modeled = pipe.get("modeled_ms", {})
    eff = pipe.get("overlap_efficiency", {})
    for mode, stats in modes.items():
        if mode == "off":
            continue
        ms = stats["ms_per_step"]
        if ms > off_ms * (1.0 + tol):
            print(
                f"roofline: pipeline_mode={mode} REGRESSES the K-scan step "
                f"beyond tolerance: {ms:.3f} ms vs off {off_ms:.3f} ms "
                f"(bound {1.0 + tol:.2f}x) — the lookahead restructure "
                f"is costing more than the overlap hides",
                file=sys.stderr,
            )
            rc = 1
        if mode not in eff:
            print(f"roofline: pipeline arm {mode} missing its "
                  "overlap_efficiency entry", file=sys.stderr)
            rc = 1
        if mode in modeled and "off" in modeled and \
                modeled[mode] > modeled["off"] + 1e-9:
            print(
                f"roofline: overlap model inconsistent — modeled "
                f"{mode} {modeled[mode]} ms > modeled off "
                f"{modeled['off']} ms", file=sys.stderr,
            )
            rc = 1
    if rc == 0:
        arms = ", ".join(
            f"{m} {s['ms_per_step']:.2f}ms"
            f" (eff {eff.get(m, float('nan')):.2f},"
            f" modeled {modeled.get(m, '?')}ms)"
            for m, s in modes.items() if m != "off"
        )
        print(
            f"roofline: overlap gate ok — off {off_ms:.2f}ms vs {arms} "
            f"(phase_ms {pipe.get('phase_ms')})"
        )
    return rc


def assert_imbalance(json_path: str, factor: float, tol: float) -> int:
    """CI gate for the skew-aware placement arm (bench.py 'placement'
    section): on the skewed multi-table workload the adopted ShardPlan
    must cut the measured per-shard exchange-bytes imbalance (max/mean,
    ops/traffic.py shard_imbalance) by at least `factor` vs the uniform
    hash, with the plan arm's step time no worse than the uniform arm's
    beyond `tol` (re-routing hot keys and rotating owners must not buy
    balance with a slower step). The same counters back
    Trainer.dedup_stats()['per_shard'], so a violation here means live
    telemetry regressed too."""
    import json

    with open(json_path) as f:
        rec = json.load(f)
    pl = rec.get("placement")
    if not pl:
        print(f"roofline: {json_path} has no 'placement' record "
              "(run bench.py with --placement)", file=sys.stderr)
        return 1
    if "imbalance_after" not in pl:
        print("roofline: placement record has no plan arm "
              f"(mode={pl.get('mode')!r}) — run --placement grid",
              file=sys.stderr)
        return 1
    rc = 0
    before, after = pl["imbalance_before"], pl["imbalance_after"]
    if after * factor > before:
        print(
            f"roofline: placement gate FAILED — imbalance {before:.3f} -> "
            f"{after:.3f} is under the required {factor:.1f}x reduction "
            f"(the plan no longer flattens the skewed workload)",
            file=sys.stderr,
        )
        rc = 1
    ms = pl.get("step_ms", {})
    if "uniform" in ms and "plan" in ms and \
            ms["plan"] > ms["uniform"] * (1.0 + tol):
        print(
            f"roofline: placement gate FAILED — plan step "
            f"{ms['plan']:.3f} ms vs uniform {ms['uniform']:.3f} ms "
            f"(bound {1.0 + tol:.2f}x): the routing table / migration "
            f"overhead outweighs the balance win",
            file=sys.stderr,
        )
        rc = 1
    rc |= _assert_drift(pl.get("drift"))
    if rc == 0:
        print(
            f"roofline: placement gate ok — imbalance {before:.3f} -> "
            f"{after:.3f} ({before / max(after, 1e-9):.2f}x, bound "
            f"{factor:.1f}x), step {ms.get('uniform')} -> {ms.get('plan')}"
            f" ms, moved {pl.get('moved_rows')} rows, "
            f"{pl.get('hot_keys')} hot keys"
        )
    return rc


def _assert_drift(drift, peak_floor: float = 2.0,
                  recover_bound: float = 1.3) -> int:
    """Drifting-skew replanning gates (bench.py placement 'drift' arm,
    round 19): after the hot set rotates mid-stream the stale plan's
    measured imbalance must spike past `peak_floor`, an AUTOMATIC
    (drift-triggered, amortization-approved, never forced) replan must
    fire, and the trajectory must recover to <= `recover_bound` — with
    ZERO a2a overflow across the whole run (the per-dest budget's
    drift-safety margin covers the stale window) and the per-dest-budget
    wire model strictly below the v1 global-headroom model with the
    compiled buckets matching the budget vector exactly."""
    if not drift:
        print("roofline: placement record has no 'drift' arm — run "
              "bench.py --placement grid", file=sys.stderr)
        return 1
    rc = 0
    reps = drift.get("replans", {})
    if reps.get("post_drift_auto", 0) < 1:
        print("roofline: drift gate FAILED — no automatic post-drift "
              f"replan fired (replans: {reps})", file=sys.stderr)
        rc = 1
    if reps.get("forced", 0):
        print("roofline: drift gate FAILED — replans were forced "
              f"({reps}); the trigger path was not exercised",
              file=sys.stderr)
        rc = 1
    peak = drift.get("peak_post_drift") or 0.0
    if peak < peak_floor:
        print(
            f"roofline: drift gate FAILED — post-drift imbalance peaked "
            f"at {peak:.3f} < {peak_floor:.1f}: the rotation no longer "
            f"stresses the stale plan (workload drifted?)",
            file=sys.stderr)
        rc = 1
    rec = drift.get("recovered_imbalance")
    if rec is None or rec > recover_bound:
        print(
            f"roofline: drift gate FAILED — imbalance recovered to "
            f"{rec} > {recover_bound} after the replan(s): the replanner "
            f"no longer flattens the rotated hot set", file=sys.stderr)
        rc = 1
    if drift.get("a2a_overflow", 1) != 0:
        print(
            f"roofline: drift gate FAILED — {drift.get('a2a_overflow')} "
            f"a2a overflow(s): the per-dest budget degraded rows "
            f"(default-served) somewhere in the drift window",
            file=sys.stderr)
        rc = 1
    if not drift.get("budgets_measured_eq_modeled"):
        print(
            "roofline: drift gate FAILED — a compiled a2a bucket "
            "diverged from the modeled per-dest budget vector "
            f"(budgets: {drift.get('budgets')})", file=sys.stderr)
        rc = 1
    wp = drift.get("wire_bytes_per_dest_model")
    wg = drift.get("wire_bytes_global_headroom_model")
    if wp is None or wg is None or not wp < wg:
        print(
            f"roofline: drift gate FAILED — per-dest-budget wire bytes "
            f"{wp} not strictly below the global-headroom model {wg}",
            file=sys.stderr)
        rc = 1
    if rc == 0:
        print(
            f"roofline: drift gate ok — peak {peak:.3f} -> recovered "
            f"{rec:.3f} (bound {recover_bound}), "
            f"{reps.get('post_drift_auto')} automatic post-drift "
            f"replan(s), 0 overflow, wire {wp:.0f} < global {wg:.0f} "
            f"({wg / max(wp, 1e-9):.2f}x diet)"
        )
    return rc


def assert_compiles(json_path: str, budget: int) -> int:
    """CI gate for the steady-state retrace contract (bench.py
    'trace_guard' section, analysis/trace_guard.py): after each arm's
    warmup window, the timed measurement loops must compile ZERO new XLA
    programs. A nonzero count means something inside the measured step
    re-traces per call (a fresh jit wrapper, an unstable cache key, an
    unwarmed shape) — the DRT001/PR 5 class — and every throughput
    number in the file was measured through compile stalls."""
    import json

    with open(json_path) as f:
        rec = json.load(f)
    tg = rec.get("trace_guard")
    if not tg:
        print(f"roofline: {json_path} has no 'trace_guard' record "
              "(bench.py too old?)", file=sys.stderr)
        return 1
    total = tg.get("steady_state_compiles")
    if total is None:
        print("roofline: trace_guard record has no steady_state_compiles",
              file=sys.stderr)
        return 1
    if total > budget:
        bad = {a: n for a, n in tg.get("per_arm", {}).items() if n}
        print(
            f"roofline: steady-state compile gate FAILED — {total} XLA "
            f"compile(s) inside timed windows (budget {budget}): {bad} — "
            "something in the measured step retraces per call; run the "
            "static analyzer (python -m deeprec_tpu.analysis --check) "
            "and check for fresh jit wrappers on the hot path",
            file=sys.stderr,
        )
        return 1
    print(
        f"roofline: steady-state compile gate ok — 0 compiles across "
        f"{len(tg.get('per_arm', {}))} timed arm(s) "
        f"(budget {budget})"
    )
    return 0


def assert_hierarchy(json_path: str, inter_ratio: float, tol: float) -> int:
    """CI gate for the pod-scale 2-D mesh arm (bench.py 'mesh' section,
    round 19): the hierarchical two-tier exchange must actually put the
    expensive tier on a diet, exactly, and for free.

    Checks: (1) the modeled inter-tier wire bytes at the reference 2x4
    shape sit at <= `inter_ratio` x the flat a2a's inter-host bytes AND
    <= 1/intra of the flat a2a's TOTAL bytes (the hierarchy must beat
    both the same-tier column and the naive per-link share); (2) the
    compiled inter bucket equals the model's budget max per bundle
    (model and program share `ops/traffic.py hier_dest_budgets` — drift
    means one changed without the other); (3) ZERO budget overflow
    (group aggregation stayed inside U_g = group_factor x U); (4) ZERO
    steady-state compiles across every arm's timed windows (the nested
    pipeline restructure must not retrace); (5) BITWISE first-step loss
    parity across flat 1-D, hier, and nested arms (the forward under the
    hierarchy is exact — one contributor per psum_scatter position);
    (6) the nested K-scan within `tol` of the unpipelined hier K-scan
    (same discipline as --assert-overlap: on CPU the restructure cost is
    the enforced bound, the overlap win is the TPU number)."""
    import json

    with open(json_path) as f:
        rec = json.load(f)
    mesh = rec.get("mesh")
    if not mesh:
        print(f"roofline: {json_path} has no 'mesh' record "
              "(run bench.py with --mesh)", file=sys.stderr)
        return 1
    arms = mesh.get("arms", {})
    hier = mesh.get("hier")
    need = {"1d_a2a", "2d_hier", "2d_nested"}
    if not need <= set(arms) or not hier:
        print(f"roofline: mesh record needs arms {sorted(need)} + the "
              f"'hier' tier model (mode={mesh.get('mode')!r}) — run "
              "--mesh grid", file=sys.stderr)
        return 1
    rc = 0
    r_inter = hier.get("inter_ratio_vs_flat_inter")
    if r_inter is None or r_inter > inter_ratio:
        print(
            f"roofline: hierarchy gate FAILED — modeled inter-tier bytes "
            f"are {r_inter}x the flat a2a's inter-host bytes (bound "
            f"{inter_ratio}): the two-tier exchange no longer diets the "
            f"expensive tier", file=sys.stderr)
        rc = 1
    r_total = hier.get("inter_ratio_vs_flat_total_over_intra")
    if r_total is None or r_total > 1.0:
        print(
            f"roofline: hierarchy gate FAILED — modeled inter-tier bytes "
            f"are {r_total}x the flat total/intra share (bound 1.0): the "
            f"hierarchy moves MORE across the expensive tier than each "
            f"flat link's naive share", file=sys.stderr)
        rc = 1
    if not hier.get("buckets_measured_eq_modeled"):
        print(
            "roofline: hierarchy gate FAILED — a compiled inter bucket "
            "diverged from the modeled hier_dest_budgets max "
            f"(per_bundle: {hier.get('per_bundle')})", file=sys.stderr)
        rc = 1
    if mesh.get("overflow", 1) != 0:
        print(
            f"roofline: hierarchy gate FAILED — {mesh.get('overflow')} "
            "budget overflow(s): the group unique budget U_g degraded "
            "rows (default-served) on this stream", file=sys.stderr)
        rc = 1
    compiles = mesh.get("trace_guard", {}).get("steady_state_compiles")
    if compiles != 0:
        print(
            f"roofline: hierarchy gate FAILED — {compiles} steady-state "
            "XLA compile(s) inside timed windows (contract 0; per arm: "
            f"{ {a: s.get('steady_compiles') for a, s in arms.items()} })",
            file=sys.stderr)
        rc = 1
    if not mesh.get("first_loss_equal"):
        print(
            "roofline: hierarchy gate FAILED — first-step loss diverged "
            "across arms (forward must be BITWISE identical): "
            f"{ {a: s.get('first_loss') for a, s in arms.items()} }",
            file=sys.stderr)
        rc = 1
    off_ms = arms["2d_hier"]["scan_ms_per_step"]
    nested_ms = arms["2d_nested"]["scan_ms_per_step"]
    if nested_ms > off_ms * (1.0 + tol):
        print(
            f"roofline: hierarchy gate FAILED — nested K-scan "
            f"{nested_ms:.3f} ms vs unpipelined hier {off_ms:.3f} ms "
            f"(bound {1.0 + tol:.2f}x): the two-tier lookahead "
            "restructure costs more than tolerance", file=sys.stderr)
        rc = 1
    if rc == 0:
        mb = hier.get("modeled_bytes", {})
        print(
            f"roofline: hierarchy gate ok — inter tier "
            f"{mb.get('hier_inter')}B = {r_inter}x flat inter-host "
            f"(bound {inter_ratio}), {r_total}x flat total/intra, "
            f"0 overflow, 0 steady compiles, bitwise loss parity, "
            f"nested scan {nested_ms:.2f}ms vs {off_ms:.2f}ms "
            f"(bound {1.0 + tol:.2f}x)"
        )
    return rc


def assert_serving(json_path: str, scale_floor: float,
                   grouped_factor: float, quant_ratio: float) -> int:
    """CI gate for the serving scale-out grid (tools/bench_serving.py
    --processes/--quantize/--grouped JSON):

      * scaling — at the largest process count P the tier must reach
        `scale_floor`·P speedup over one process. On a host with enough
        cores the MEASURED speedup is gated; on a core-starved host
        (`cpu_limited`, e.g. single-core CI where N processes time-slice
        one core) the CPU-split Amdahl model carries the claim — same
        discipline as --assert-overlap, where single-core CI gates the
        contract and the capable host pins the measurement.
      * quantized residency — measured bytes must equal the
        ops/traffic.py model EXACTLY (the accounting is shape math, not
        an estimate), int8 must sit under `quant_ratio`× the fp32
        baseline, and the delta replay under the trace guard must have
        compiled ZERO programs (the zero-retrace serving contract on the
        quantized import path).
      * grouped — the two-tower arm's candidates/sec with sample-aware
        user-tower reuse must beat the plain arm by `grouped_factor`×.
    """
    import json

    with open(json_path) as f:
        rec = json.load(f)
    rc = 0

    so = rec.get("scale_out")
    if not so or not so.get("arms"):
        print(f"roofline: {json_path} has no 'scale_out' record "
              "(run bench_serving with --processes)", file=sys.stderr)
        rc = 1
    else:
        counts = sorted(int(k) for k in so["arms"])
        P = counts[-1]
        need = scale_floor * P
        measured = so.get("measured_speedup", {}).get(str(P))
        if so.get("cpu_limited"):
            sp = so.get("modeled", {}).get("speedup", {}).get(str(P))
            kind = f"modeled (host has {so.get('host_cores')} core(s) for " \
                   f"{P} backends + the edge: measured arms are core-bound)"
        else:
            sp = measured
            kind = "measured"
        if sp is None or sp < need:
            print(
                f"roofline: serving scale-out gate FAILED — {kind} speedup "
                f"at {P} processes is {sp} (need ≥ {need:.2f} = "
                f"{scale_floor:.2f}×{P}); measured {measured}",
                file=sys.stderr,
            )
            rc = 1
        else:
            print(
                f"roofline: serving scale-out ok — {kind} speedup {sp:.2f} "
                f"at {P} processes (floor {need:.2f}; measured "
                f"{measured}, cpu split {so.get('modeled', {}).get('frontend_cpu_per_req_ms')}"
                f"/{so.get('modeled', {}).get('backend_cpu_per_req_ms')} ms "
                f"front/back per request)"
            )

    qa = rec.get("quantized", {})
    q8 = qa.get("int8")
    if not q8:
        print(f"roofline: {json_path} has no int8 'quantized' record "
              "(run bench_serving with --quantize int8)", file=sys.stderr)
        rc = 1
    else:
        ri = q8["residency"]
        if ri["measured_bytes"] != ri["modeled_bytes"]:
            print(
                f"roofline: quantized residency gate FAILED — measured "
                f"{ri['measured_bytes']}B != modeled {ri['modeled_bytes']}B "
                f"(ops/traffic.py serving_residency_bytes drifted from the "
                f"actual table layout)", file=sys.stderr,
            )
            rc = 1
        if ri["measured_bytes"] > quant_ratio * ri["fp32_bytes"]:
            print(
                f"roofline: quantized residency gate FAILED — int8 bytes "
                f"{ri['measured_bytes']} exceed {quant_ratio:.2f}× the fp32 "
                f"baseline {ri['fp32_bytes']}", file=sys.stderr,
            )
            rc = 1
        if q8.get("serving_compiles", -1) != 0:
            print(
                f"roofline: quantized serving compile gate FAILED — "
                f"{q8.get('serving_compiles')} XLA compile(s) during the "
                f"guarded delta replay (the quantize-on-import path "
                f"retraces; must be 0)", file=sys.stderr,
            )
            rc = 1
        if rc == 0:
            print(
                f"roofline: quantized residency ok — int8 "
                f"{ri['measured_bytes'] / 2 ** 20:.2f} MiB = "
                f"{ri['measured_bytes'] / ri['fp32_bytes']:.3f}× fp32 "
                f"(bound {quant_ratio:.2f}), model exact, 0 replay compiles"
            )

    gr = rec.get("grouped")
    if not gr or not gr.get("factor"):
        print(f"roofline: {json_path} has no 'grouped' record "
              "(run bench_serving with --grouped)", file=sys.stderr)
        rc = 1
    elif gr["factor"] < grouped_factor:
        print(
            f"roofline: grouped serving gate FAILED — candidates/sec "
            f"factor {gr['factor']} under the {grouped_factor:.1f}× floor "
            f"(grouped {gr.get('grouped_cps')} vs ungrouped "
            f"{gr.get('ungrouped_cps')} at {gr.get('rows_per_request')} "
            f"candidates/request)", file=sys.stderr,
        )
        rc = 1
    else:
        print(
            f"roofline: grouped serving ok — {gr['factor']:.2f}× "
            f"candidates/sec ({gr.get('grouped_cps')} vs "
            f"{gr.get('ungrouped_cps')} at {gr.get('rows_per_request')} "
            f"candidates/request)"
        )

    rc |= _assert_multi_host(rec.get("multi_host"), json_path)
    return rc


def _assert_multi_host(mh, json_path: str) -> int:
    """The fleet gate (tools/bench_fleet.py `multi_host` section):
    sustained rps through a rolling restart of EVERY backend and a
    scale-out/-in event (2→4→2; the smoke tier runs the same walk) with
    ZERO failed requests anywhere — the ROADMAP's multi-host headline.
    Structural honesty only: rps floors belong to capable hosts, the
    zero-failure and coverage contracts hold on any host."""
    if not mh:
        print(f"roofline: {json_path} has no 'multi_host' record "
              "(run tools/bench_fleet.py --out onto this JSON)",
              file=sys.stderr)
        return 1
    rc = 0
    phases = {"steady": mh.get("steady", {}),
              "rolling_restart": mh.get("rolling_restart", {}),
              "scale": mh.get("scale", {}),
              **{f"faults.{k}": v
                 for k, v in mh.get("faults", {}).items()}}
    for name, ph in phases.items():
        if ph.get("failed_requests", 1) != 0:
            print(f"roofline: fleet gate FAILED — phase {name} recorded "
                  f"{ph.get('failed_requests')} failed request(s); the "
                  f"fleet contract is ZERO through every churn event",
                  file=sys.stderr)
            rc = 1
        if name in ("steady", "rolling_restart", "scale") and \
                not ph.get("rps"):
            print(f"roofline: fleet gate FAILED — phase {name} sustained "
                  f"no traffic (rps {ph.get('rps')})", file=sys.stderr)
            rc = 1
    roll = phases["rolling_restart"]
    if not roll.get("covered_all") or roll.get("restarted", 0) < 2:
        print(f"roofline: fleet gate FAILED — rolling restart covered "
              f"{roll.get('restarted')}/{roll.get('fleet_size')} backends "
              f"(must roll EVERY member)", file=sys.stderr)
        rc = 1
    if roll.get("unplanned_restarts", 0) != 0:
        print(f"roofline: fleet gate FAILED — "
              f"{roll.get('unplanned_restarts')} UNPLANNED supervisor "
              f"restart(s) during the roll (drain must exit via "
              f"EXIT_RESCALE, not crash)", file=sys.stderr)
        rc = 1
    sc = phases["scale"]
    path = sc.get("path") or []
    tmax = sc.get("target_max", 4)
    if (len(path) < 3 or path[0] != path[-1] or max(path) != tmax
            or max(path) - path[0] < 2):
        print(f"roofline: fleet gate FAILED — scale path {path} is not a "
              f"{path[0] if path else '?'}→{tmax}→"
              f"{path[0] if path else '?'} round trip", file=sys.stderr)
        rc = 1
    if not mh.get("zero_failed_requests"):
        print("roofline: fleet gate FAILED — zero_failed_requests is "
              "false", file=sys.stderr)
        rc = 1
    if rc == 0:
        print(
            f"roofline: fleet ok — rolled {roll.get('restarted')}/"
            f"{roll.get('fleet_size')} backends at "
            f"{roll.get('rps')} rps (p99 {roll.get('p99_ms')} ms), "
            f"scale {'→'.join(str(x) for x in path)} at "
            f"{sc.get('rps')} rps, {mh.get('total_requests')} requests, "
            f"0 failed"
        )
    return rc


def assert_retrieval(json_path: str, recall_floor: float,
                     sweep_factor: float, freshness_factor: float) -> int:
    """CI gate for full-corpus retrieval (tools/bench_retrieval.py
    'retrieval' section):

      * recall — the int8 blocked sweep must hold `recall_floor` at
        recall@100 against the exact fp32 full-scan argsort (tie-aware:
        identical-vector items are interchangeable answers).
      * sweep vs gather — the resident blocked sweep must beat the
        per-row gather-and-re-encode baseline by `sweep_factor`× at the
        1M-item smoke shape (the reason the corpus matrix exists).
      * freshness — ingest -> retrievable (trainer commit to the corpus
        fold that covers the delta) must sit within `freshness_factor`×
        the predictor's own pinned train_to_serve lag: retrieval
        freshness rides the SAME poll round as serving freshness, so a
        big gap means the fold left the round.
      * residency — measured sweep bytes must equal the
        `ops/traffic.py retrieval_sweep_bytes` model EXACTLY (shape
        math, not an estimate), and the int8 corpus must sit strictly
        under the fp32 arm's bytes.
      * compiles — delta replay folding into the corpus matrix must
        compile ZERO steady-state XLA programs (the PR 5 zero-retrace
        serving contract extended to the retrieval lane).
    """
    import json

    with open(json_path) as f:
        rec = json.load(f)
    rt = rec.get("retrieval")
    if not rt:
        print(f"roofline: {json_path} has no 'retrieval' record "
              "(run tools/bench_retrieval.py --out onto this JSON)",
              file=sys.stderr)
        return 1
    rc = 0
    rec100 = (rt.get("recall", {}).get("int8", {}) or {}).get(
        "recall_at_100")
    if rec100 is None or rec100 < recall_floor:
        print(f"roofline: retrieval gate FAILED — int8 recall@100 "
              f"{rec100} under the {recall_floor:.2f} floor vs exact "
              f"fp32 scan (quantized blocked sweep lost ranking "
              f"fidelity)", file=sys.stderr)
        rc = 1
    sg = rt.get("sweep_vs_gather") or {}
    if not sg.get("speedup") or sg["speedup"] < sweep_factor:
        print(f"roofline: retrieval gate FAILED — blocked sweep speedup "
              f"{sg.get('speedup')} under the {sweep_factor:.1f}× floor "
              f"vs the per-row gather baseline at "
              f"{sg.get('corpus_rows')} items", file=sys.stderr)
        rc = 1
    fr = rt.get("freshness") or {}
    retr = fr.get("retrievable_seconds")
    pinned = fr.get("pinned_lag_seconds")
    if retr is None or pinned is None or \
            retr > freshness_factor * max(pinned, 0.05):
        print(f"roofline: retrieval gate FAILED — ingest->retrievable "
              f"{retr}s exceeds {freshness_factor:.1f}× the pinned "
              f"train_to_serve lag {pinned}s (the corpus fold left the "
              f"poll round)", file=sys.stderr)
        rc = 1
    if fr.get("rows_folded", 0) < 1:
        print("roofline: retrieval gate FAILED — the freshness delta "
              "folded zero corpus rows (changed-key discovery broke)",
              file=sys.stderr)
        rc = 1
    resd = rt.get("residency") or {}
    q8, q32 = resd.get("int8"), resd.get("fp32")
    if not q8 or not q32:
        print("roofline: retrieval gate FAILED — residency arms missing "
              "(need int8 AND fp32)", file=sys.stderr)
        rc = 1
    else:
        for name, ri in (("int8", q8), ("fp32", q32)):
            if ri["measured_bytes"] != ri["modeled_bytes"]:
                print(f"roofline: retrieval gate FAILED — {name} sweep "
                      f"bytes measured {ri['measured_bytes']} != modeled "
                      f"{ri['modeled_bytes']} (retrieval_sweep_bytes "
                      f"drifted from the corpus layout)", file=sys.stderr)
                rc = 1
        if q8["measured_bytes"] >= q32["measured_bytes"]:
            print(f"roofline: retrieval gate FAILED — int8 corpus "
                  f"{q8['measured_bytes']}B not under fp32 "
                  f"{q32['measured_bytes']}B", file=sys.stderr)
            rc = 1
    if rt.get("steady_compiles", -1) != 0:
        print(f"roofline: retrieval gate FAILED — "
              f"{rt.get('steady_compiles')} XLA compile(s) during the "
              f"guarded delta-replay fold + retrieve (must be 0)",
              file=sys.stderr)
        rc = 1
    if rc == 0:
        arms = {n: a.get("int8", {}).get("qps")
                for n, a in (rt.get("arms") or {}).items()}
        print(f"roofline: retrieval gate ok — recall@100 {rec100} "
              f"(floor {recall_floor}), sweep {sg['speedup']}× gather "
              f"at {sg.get('corpus_rows')} items, freshness {retr}s ≤ "
              f"{freshness_factor:.0f}×{pinned}s, int8 corpus "
              f"{q8['measured_bytes'] / 2 ** 20:.1f} MiB = "
              f"{q8['measured_bytes'] / q32['measured_bytes']:.3f}× "
              f"fp32 (model exact), 0 fold compiles, qps {arms}")
    return rc


def assert_reuse(json_path: str, qps_factor: float,
                 hit_floor: float) -> int:
    """CI gate for the frontend compute-reuse layer (tools/bench_serving.py
    --compute-reuse JSON, serving/reuse.py):

      * effective qps — the zipf arm with the version-keyed answer cache
        ON must reach `qps_factor`× the cache-off arm's measured qps on
        the SAME request stream (the ROADMAP's ≥2× headline; the
        ops/traffic.py serving_reuse_speedup model is the recorded
        zero-hit-cost ceiling).
      * hit rate — the steady window must hold `hit_floor` (the zipf
        head is resident; below this the population/capacity drifted and
        the qps factor is measuring noise).
      * correctness — the miss/hit/`no_cache` probe must be
        byte-identical at one version (the cache is a pure memo), and a
        steady-window cache hit must compile ZERO XLA programs.
      * version boundary — the mid-load delta publish must show the
        invalidation dip (dip < pre) AND recovery (recovered > dip) with
        ≥1 invalidation and the version advanced: entries die exactly at
        the swap, never by sweep, and never serve across it.
      * memory — recorded occupancy must sit within the byte capacity.
    """
    import json

    with open(json_path) as f:
        rec = json.load(f)
    cr = rec.get("compute_reuse")
    if not cr:
        print(f"roofline: {json_path} has no 'compute_reuse' record "
              "(run bench_serving with --compute-reuse)", file=sys.stderr)
        return 1
    rc = 0
    arms = cr.get("arms", {})
    if "cache_on" not in arms or "cache_off" not in arms:
        print("roofline: compute_reuse needs cache_on and cache_off arms, "
              f"got {sorted(arms)}", file=sys.stderr)
        return 1
    factor = cr.get("effective_qps_factor")
    if factor is None or factor < qps_factor:
        print(
            f"roofline: reuse gate FAILED — effective qps factor {factor} "
            f"under the {qps_factor:.1f}× floor (cache on "
            f"{arms['cache_on'].get('rps')} vs off "
            f"{arms['cache_off'].get('rps')} rps at hit rate "
            f"{cr.get('hit_rate')}; modeled ceiling "
            f"{cr.get('modeled', {}).get('speedup_ceiling_at_hit_rate')})",
            file=sys.stderr,
        )
        rc = 1
    hr = cr.get("hit_rate")
    if hr is None or hr < hit_floor:
        print(
            f"roofline: reuse gate FAILED — steady hit rate {hr} under "
            f"the {hit_floor:.2f} floor (zipf α={cr.get('zipf_alpha')}, "
            f"{cr.get('users')} users): the resident head no longer "
            f"covers the stream", file=sys.stderr,
        )
        rc = 1
    if cr.get("bit_identical") is not True:
        print(
            "roofline: reuse gate FAILED — miss/hit/no_cache probe was "
            "not byte-identical: the cache is serving answers a fresh "
            "eval would not produce", file=sys.stderr,
        )
        rc = 1
    if cr.get("steady_compiles", -1) != 0:
        print(
            f"roofline: reuse gate FAILED — {cr.get('steady_compiles')} "
            "XLA compile(s) inside the guarded cache-on steady window "
            "(a cache hit must never trace; must be 0)", file=sys.stderr,
        )
        rc = 1
    pub = cr.get("publish") or {}
    pre, dip, recov = (pub.get("pre_hit_rate"), pub.get("dip_hit_rate"),
                       pub.get("recovered_hit_rate"))
    if pre is None or dip is None or recov is None or \
            not (dip < pre and recov > dip):
        print(
            f"roofline: reuse gate FAILED — publish window did not show "
            f"the invalidation dip + recovery (pre {pre} → dip {dip} → "
            f"recovered {recov}): the version swap is not the "
            f"invalidation edge", file=sys.stderr,
        )
        rc = 1
    if pub.get("invalidations", 0) < 1 or not pub.get("version_advanced"):
        print(
            f"roofline: reuse gate FAILED — the mid-load delta publish "
            f"invalidated {pub.get('invalidations')} entries with "
            f"version_advanced={pub.get('version_advanced')} (the swap "
            f"must drop every old-version entry)", file=sys.stderr,
        )
        rc = 1
    if not cr.get("occupancy_within_capacity"):
        print(
            f"roofline: reuse gate FAILED — cache occupancy "
            f"{arms.get('cache_on', {}).get('occupancy_bytes')}B exceeds "
            f"the {cr.get('capacity_bytes')}B budget (the byte bound is "
            f"the memory contract)", file=sys.stderr,
        )
        rc = 1
    if rc == 0:
        print(
            f"roofline: reuse gate ok — {factor:.2f}× effective qps "
            f"(floor {qps_factor:.1f}×; on {arms['cache_on'].get('rps')} "
            f"vs off {arms['cache_off'].get('rps')} rps), hit rate "
            f"{hr:.3f} (floor {hit_floor:.2f}), bit-identical probe, "
            f"0 steady compiles, publish dip {pre:.3f}→{dip:.3f}→"
            f"{recov:.3f} with {pub.get('invalidations')} "
            f"invalidation(s), occupancy "
            f"{arms['cache_on'].get('occupancy_bytes')}B ≤ "
            f"{cr.get('capacity_bytes')}B"
        )
    return rc


def assert_fused(json_path: str, ratio_bound: float) -> int:
    """CI gate for the fused sparse step (tools/bench_lookup.py
    --fused-step JSON, ops/fused_lookup.fused_sparse_*):

      * HBM diet — modeled fused-path bytes ≤ `ratio_bound`× the
        split-phase path at the recorded bench shapes. Both arms are
        RECOMPUTED here from the recorded shape params through
        ops/traffic.fused_sparse_step_traffic and must equal the recorded
        numbers — so neither the bench nor the model can drift away from
        the other and silently keep passing.
      * parity — the interpret-mode oracle probe (forward bitwise,
        backward bitwise at fp32, seeded-SR bitwise at bf16, both sides
        jitted) must have passed when the record was made.
    """
    import json

    from deeprec_tpu.ops.traffic import fused_sparse_step_traffic

    with open(json_path) as f:
        rec = json.load(f)
    fs = rec.get("fused_step")
    if not fs:
        print(f"roofline: {json_path} has no 'fused_step' record "
              "(run bench_lookup with --fused-step --out)", file=sys.stderr)
        return 1
    rc = 0
    sh, modeled = fs.get("shapes", {}), fs.get("modeled", {})
    try:
        model = {
            fused: fused_sparse_step_traffic(
                positions=sh["positions"], batch=sh["batch"],
                unique=sh["unique"], dim=sh["dim"],
                value_bytes={"float32": 4, "bfloat16": 2}[sh["dtype"]],
                slot_widths=tuple(sh["slot_widths"]), fused=fused,
            )["hbm_bytes"]
            for fused in (False, True)
        }
    except KeyError as e:
        print(f"roofline: fused_step record is missing shape param {e} — "
              "regenerate with the current bench_lookup", file=sys.stderr)
        return 1
    for arm, fused in (("unfused", False), ("fused", True)):
        got = modeled.get(f"{arm}_hbm_bytes")
        if got != model[fused]:
            print(
                f"roofline: fused gate FAILED — recorded {arm} model "
                f"{got} B != recomputed {model[fused]} B at the recorded "
                "shapes: bench and traffic model drifted apart",
                file=sys.stderr,
            )
            rc = 1
    ratio = model[True] / model[False]
    if ratio > ratio_bound:
        print(
            f"roofline: fused gate FAILED — modeled fused HBM "
            f"{ratio:.3f}× unfused exceeds the {ratio_bound:.2f}× bound "
            f"(fused {model[True] / 1e3:.1f} vs unfused "
            f"{model[False] / 1e3:.1f} KB/step at U={sh.get('unique')} "
            f"N={sh.get('positions')} D={sh.get('dim')})", file=sys.stderr,
        )
        rc = 1
    parity = fs.get("parity", {})
    bad = [k for k in ("forward_bitwise", "backward_bitwise",
                       "bf16_sr_bitwise") if parity.get(k) is not True]
    if bad:
        print(
            f"roofline: fused gate FAILED — oracle parity flags {bad} "
            f"not true in the record (backend {fs.get('backend')}): the "
            "fused kernels no longer match the split-phase path",
            file=sys.stderr,
        )
        rc = 1
    if rc == 0:
        print(
            f"roofline: fused gate ok — modeled fused HBM {ratio:.3f}× "
            f"unfused (bound {ratio_bound:.2f}×; fused "
            f"{model[True] / 1e3:.1f} vs unfused {model[False] / 1e3:.1f} "
            f"KB/step/table at the bench shapes), parity "
            f"fwd/bwd/bf16-SR all bitwise on {fs.get('backend')}"
        )
    return rc


def assert_obs(json_path: str, tol: float) -> int:
    """CI gate for the telemetry plane (bench.py / tools/bench_serving.py
    'obs_overhead' section): both arms (instrumented vs DEEPREC_OBS=off)
    must exist, the gated overhead — per-record registry cost × obs ops
    per step/request over the measured step/request time, a deterministic
    model (same discipline as the CPU-limited serving gate: wall-clock
    arm deltas on a shared CI box are noise beyond any honest overhead
    bound; the raw arms are recorded for inspection) — must sit under
    `tol`, and the recorded /metrics (or registry-render) parse check
    must have passed with a nonzero series count. Instrumentation whose
    cost grows past 2% of the hot path is a regression this fails."""
    import json

    with open(json_path) as f:
        rec = json.load(f)
    ob = rec.get("obs_overhead")
    if not ob:
        print(f"roofline: {json_path} has no 'obs_overhead' record "
              "(bench too old?)", file=sys.stderr)
        return 1
    rc = 0
    arms = ob.get("arms", {})
    if "on" not in arms or "off" not in arms:
        print("roofline: obs_overhead needs 'on' and 'off' arms, got "
              f"{sorted(arms)}", file=sys.stderr)
        rc = 1
    ov = ob.get("overhead_pct")
    if ov is None or ov > tol * 100.0:
        print(
            f"roofline: obs overhead gate FAILED — modeled overhead "
            f"{ov}% exceeds {tol * 100:.1f}% "
            f"(per_record_ns {ob.get('per_record_ns')}, ops "
            f"{ob.get('ops_per_step', ob.get('ops_per_request'))}) — the "
            "metrics plane got too expensive for the hot path",
            file=sys.stderr,
        )
        rc = 1
    me = ob.get("metrics_endpoint") or ob.get("metrics_parse")
    if not me or not me.get("parsed") or not me.get("series"):
        print(
            f"roofline: obs exposition gate FAILED — /metrics parse check "
            f"missing or failed ({me}) — the Prometheus rendering broke",
            file=sys.stderr,
        )
        rc = 1
    if rc == 0:
        print(
            f"roofline: obs gate ok — modeled overhead {ov}% "
            f"(bound {tol * 100:.1f}%; measured arms on/off "
            f"{arms['on']} / {arms['off']}), "
            f"{me['series']} metric series parsed"
        )
    return rc


def assert_guard(json_path: str, detect_budget: int,
                 recovery_ms: float) -> int:
    """CI gate for the model-quality firewall (tools/bench_guard.py
    'guard' section): under the injected poison matrix (NaN features,
    extreme magnitudes, label flips, stream-replayed repeats, an
    exploding-LR window) the served model's AUC must never cross the
    recorded floor, ZERO requests may fail, every poison delivery must
    be detected within `detect_budget` dispatches, the replayed batch
    must end permanently quarantined, the pre-swap canary must have
    rejected the out-of-band poisoned delta (health degraded:
    quality_gate), and the last rollback+replay must complete within
    `recovery_ms`."""
    import json

    with open(json_path) as f:
        rec = json.load(f)
    g = rec.get("guard")
    if not g:
        print(f"roofline: {json_path} has no 'guard' record "
              "(run tools/bench_guard.py --out onto this JSON)",
              file=sys.stderr)
        return 1
    rc = 0
    if g.get("failed_requests", 1) != 0:
        print(f"roofline: guard gate FAILED — {g.get('failed_requests')} "
              f"failed request(s) under poison "
              f"({g.get('request_errors')}); the firewall contract is "
              f"ZERO", file=sys.stderr)
        rc = 1
    events = g.get("events") or []
    if not events:
        print("roofline: guard gate FAILED — no poison deliveries "
              "recorded", file=sys.stderr)
        rc = 1
    for ev in events:
        if not ev.get("detected"):
            print(f"roofline: guard gate FAILED — poison delivery "
                  f"{ev.get('delivery')} ({ev.get('mode')}) was never "
                  f"detected", file=sys.stderr)
            rc = 1
        elif ev.get("detection_dispatches", 0) > detect_budget:
            print(f"roofline: guard gate FAILED — delivery "
                  f"{ev.get('delivery')} detected after "
                  f"{ev['detection_dispatches']} dispatches (budget "
                  f"{detect_budget})", file=sys.stderr)
            rc = 1
    auc = g.get("auc", {})
    if auc.get("min_served") is None or auc.get("floor") is None or \
            auc["min_served"] < auc["floor"]:
        print(f"roofline: guard gate FAILED — served AUC crossed the "
              f"floor ({auc})", file=sys.stderr)
        rc = 1
    if g.get("batches_quarantined", 0) < 1:
        print("roofline: guard gate FAILED — no batch reached permanent "
              "quarantine despite stream replays", file=sys.stderr)
        rc = 1
    if g.get("rollbacks", 0) < 1:
        print("roofline: guard gate FAILED — no rollback recorded",
              file=sys.stderr)
        rc = 1
    rb = g.get("rollback_ms_last")
    if rb is None or rb > recovery_ms:
        print(f"roofline: guard gate FAILED — rollback+replay took "
              f"{rb} ms (bound {recovery_ms:.0f} ms)", file=sys.stderr)
        rc = 1
    qg = g.get("quality_gate", {})
    if qg.get("rejections", 0) < 1 or \
            qg.get("degraded_reason") != "quality_gate":
        print(f"roofline: guard gate FAILED — the pre-swap canary did "
              f"not reject the poisoned delta visibly ({qg})",
              file=sys.stderr)
        rc = 1
    if rc == 0:
        print(
            f"roofline: guard gate ok — {len(events)} poison deliveries "
            f"all detected ≤ {detect_budget} dispatch(es), "
            f"{g.get('rollbacks')} rollback(s) "
            f"(last {rb} ms), {g.get('batches_quarantined')} permanently "
            f"quarantined, min served AUC {auc.get('min_served')} ≥ floor "
            f"{auc.get('floor')}, {g.get('requests')} requests / 0 failed, "
            f"{qg.get('rejections')} canary rejection(s)"
        )
    return rc


def assert_tier(json_path: str, loss_factor: float, step_tol: float) -> int:
    """CI gate for overlapped tier paging (bench.py --tier-paging
    'tier_paging' section; embedding/tier_prefetch.py +
    MultiTierTable.fold_candidates):

      * optimizer-state-loss diet — the fresh-init rate (batch positions
        hitting a tier-resident row, i.e. training from a re-initialized
        row that lost its optimizer state) with paging ON must be at
        least `loss_factor`× lower than the paging-OFF arm on the same
        recorded rotated-zipf stream. An ON rate of exactly 0 passes
        (recorded loss_factor is null — infinite suppression).
      * compile discipline — the fold path recorded 0 steady-state XLA
        compiles (the fixed-chunk sentinel-padded `import_rows`
        discipline applied to folds).
      * stall budget — the training-thread fold stall must not exceed
        the same arm's pinned sync_async boundary stall: paging may not
        cost the training thread more than the maintain machinery it
        relieves.
      * step time — ON step time within `step_tol` of OFF (same
        discipline as --assert-overlap: single-core CI boxes need a
        loose tolerance; accelerator hosts should pin --tier-step-tol
        back down to 0.03).
      * health — zero pump gather errors and a nonzero fold count (a
        bench where nothing folded measured nothing).
    """
    import json

    with open(json_path) as f:
        rec = json.load(f)
    tp = rec.get("tier_paging")
    if not tp:
        print(f"roofline: {json_path} has no 'tier_paging' record "
              "(run bench.py --tier-paging --out onto this JSON)",
              file=sys.stderr)
        return 1
    rc = 0
    off, on = tp.get("off", {}), tp.get("on", {})
    lf = tp.get("loss_factor")
    if lf is not None and lf < loss_factor:
        print(
            f"roofline: tier paging gate FAILED — fresh-init suppression "
            f"{lf}× under the {loss_factor:.0f}× floor (on rate "
            f"{on.get('fresh_init_rate')} vs off "
            f"{off.get('fresh_init_rate')}): folds are not landing before "
            "the lookups", file=sys.stderr,
        )
        rc = 1
    if on.get("steady_compiles") != 0:
        print(
            f"roofline: tier paging gate FAILED — "
            f"{on.get('steady_compiles')} steady-state compile(s) in the "
            "fold path (contract: fixed-chunk folds compile once per "
            "table during warmup, then never)", file=sys.stderr,
        )
        rc = 1
    fold_stall = on.get("fold_stall_ms")
    sync_stall = on.get("sync_stall_ms")
    if fold_stall is None or sync_stall is None or fold_stall > sync_stall:
        print(
            f"roofline: tier paging gate FAILED — training-thread fold "
            f"stall {fold_stall} ms exceeds the arm's sync_async boundary "
            f"stall {sync_stall} ms: paging costs more than the "
            "maintain machinery it relieves", file=sys.stderr,
        )
        rc = 1
    ratio = tp.get("step_time_ratio")
    if ratio is None or ratio > 1.0 + step_tol:
        print(
            f"roofline: tier paging gate FAILED — ON step time "
            f"{ratio}× OFF exceeds the 1+{step_tol:.2f} bound "
            f"(on {on.get('step_ms')} ms vs off {off.get('step_ms')} ms)",
            file=sys.stderr,
        )
        rc = 1
    if on.get("gather_errors", 1) != 0 or not on.get("folded_rows"):
        print(
            f"roofline: tier paging gate FAILED — pump health: "
            f"{on.get('gather_errors')} gather error(s), "
            f"{on.get('folded_rows')} folded row(s) (a run that folded "
            "nothing measured nothing)", file=sys.stderr,
        )
        rc = 1
    if rc == 0:
        print(
            f"roofline: tier paging gate ok — fresh-init suppression "
            f"{'∞' if lf is None else lf}× (floor {loss_factor:.0f}×; "
            f"on {on.get('fresh_init_rate')} vs off "
            f"{off.get('fresh_init_rate')}), {on.get('folded_rows')} rows "
            f"folded ({on.get('fold_bytes')} B), 0 steady compiles, fold "
            f"stall {fold_stall} ms ≤ sync stall {sync_stall} ms, step "
            f"{ratio}× off (bound 1+{step_tol:.2f})"
        )
    return rc


def assert_input(json_path: str, speedup_min: float, train_tol: float) -> int:
    """CI gate for the parallel host input pipeline (tools/bench_input.py
    'input' section; data/pipeline.py + criteo_block_parse):

      * parse throughput — the vectorized block parse must beat the
        serial per-line `criteo_line_parser` by at least `speedup_min`×
        on the same bytes, each at its real operating grain (blocks of
        shard_batches*B records vs B-line calls).
      * parity — the batch stream must be BIT-identical: block parse vs
        line parse on the same records, and the N-worker pipeline vs the
        serial single-reader assembly (any worker count). One mismatched
        element or dtype fails the gate.
      * training thread — host time per dispatch (a pop from the filled
        pipeline buffer) must not exceed `train_tol`× the serial inline
        parse it replaced: the pipeline may not cost the training thread
        more than the work it moved off of it.
    """
    import json

    with open(json_path) as f:
        rec = json.load(f)
    inp = rec.get("input")
    if not inp:
        print(f"roofline: {json_path} has no 'input' record "
              "(run tools/bench_input.py --out onto this JSON)",
              file=sys.stderr)
        return 1
    rc = 0
    speedup = inp.get("block_parse_speedup")
    if speedup is None or speedup < speedup_min:
        parse = inp.get("parse", {})
        print(
            f"roofline: input gate FAILED — block parse "
            f"{speedup}× the serial line parser, under the "
            f"{speedup_min:.1f}× floor ({parse.get('block_exps')} vs "
            f"{parse.get('serial_exps')} ex/s): the vectorized parse "
            "is not paying for the pipeline", file=sys.stderr,
        )
        rc = 1
    if not inp.get("parity_ok"):
        parse_ok = inp.get("parse", {}).get("parse_parity")
        print(
            f"roofline: input gate FAILED — batch-stream parity broken "
            f"(block-vs-line parse parity={parse_ok}; stream parity "
            "covers every benched worker count vs the serial reader): "
            "the pipeline is not bit-identical to the serial path",
            file=sys.stderr,
        )
        rc = 1
    ratio = inp.get("train_thread_ratio")
    if ratio is None or ratio > train_tol:
        tt = inp.get("train_thread", {})
        print(
            f"roofline: input gate FAILED — training-thread dispatch "
            f"cost {ratio}× the serial inline parse exceeds the "
            f"{train_tol:.2f}× bound (pop {tt.get('pop_us')} µs vs "
            f"inline {tt.get('serial_inline_us')} µs): the pipeline "
            "regressed the thread it exists to relieve", file=sys.stderr,
        )
        rc = 1
    if rc == 0:
        tt = inp.get("train_thread", {})
        print(
            f"roofline: input gate ok — block parse {speedup}× serial "
            f"(floor {speedup_min:.1f}×), batch stream bit-identical "
            f"across worker counts, training-thread dispatch "
            f"{tt.get('pop_us')} µs vs {tt.get('serial_inline_us')} µs "
            f"inline ({ratio}× ≤ {train_tol:.2f}×)"
        )
    return rc


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=2048)
    p.add_argument("--emb_dim", type=int, default=16)
    p.add_argument("--capacity", type=int, default=1 << 20)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--peak_bw_gbs", type=float, default=1228.0,
                   help="HBM bandwidth ceiling (GB/s); v4 default")
    p.add_argument("--peak_tflops", type=float, default=275.0,
                   help="bf16 MXU ceiling (TFLOP/s); v4 default")
    p.add_argument("--assert-traffic", metavar="BENCH_JSON", default=None,
                   help="don't run the step: validate the traffic model "
                        "against the op counts recorded in a bench.py JSON "
                        "(CI smoke gate; exits nonzero on drift)")
    p.add_argument("--assert-overlap", metavar="BENCH_JSON", default=None,
                   help="don't run the step: validate the in-step "
                        "pipelining grid recorded in a bench.py JSON "
                        "(pipelined arms present, no regression beyond "
                        "--overlap-tol, overlap efficiency recorded; CI "
                        "smoke gate, exits nonzero on violation)")
    p.add_argument("--overlap-tol", type=float, default=0.5,
                   help="allowed relative K-scan step-time regression of a "
                        "pipelined arm vs 'off' (default 0.5 — generous "
                        "because single-core CI has no overlap to win and "
                        "real noise; TPU runs should pin it down)")
    p.add_argument("--assert-compiles", metavar="BENCH_JSON", default=None,
                   help="don't run the step: validate the steady-state "
                        "compile counts recorded in a bench.py JSON "
                        "(trace_guard section; every timed arm must have "
                        "compiled nothing after its warmup — CI smoke "
                        "gate, exits nonzero on drift)")
    p.add_argument("--compiles-budget", type=int, default=0,
                   help="allowed total steady-state compiles across arms "
                        "(default 0 — the contract is exactly zero)")
    p.add_argument("--assert-hierarchy", metavar="BENCH_JSON", default=None,
                   help="don't run the step: validate the pod-scale 2-D "
                        "mesh arm recorded in a bench.py JSON ('mesh' "
                        "section, --mesh grid): inter-tier modeled bytes "
                        "<= --hierarchy-inter-ratio x flat a2a inter-host "
                        "AND <= flat total/intra, compiled buckets == "
                        "model, 0 overflow, 0 steady compiles, bitwise "
                        "loss parity, nested K-scan within "
                        "--hierarchy-tol; CI smoke gate)")
    p.add_argument("--hierarchy-inter-ratio", type=float, default=0.5,
                   help="required ceiling on modeled hier inter-tier bytes "
                        "as a fraction of the flat a2a's inter-host bytes "
                        "at the reference 2x4 shape (default 0.5)")
    p.add_argument("--hierarchy-tol", type=float, default=0.5,
                   help="allowed relative K-scan step-time regression of "
                        "the nested arm vs the unpipelined hier arm "
                        "(default 0.5 — same rationale as --overlap-tol)")
    p.add_argument("--assert-imbalance", metavar="BENCH_JSON", default=None,
                   help="don't run the step: validate the skew-aware "
                        "placement arm recorded in a bench.py JSON (the "
                        "plan must cut measured per-shard exchange-bytes "
                        "imbalance by --imbalance-factor with step time "
                        "within --imbalance-tol of uniform; CI smoke gate)")
    p.add_argument("--imbalance-factor", type=float, default=2.0,
                   help="required max/mean imbalance reduction of the "
                        "placed plan vs uniform hash (default 2.0)")
    p.add_argument("--imbalance-tol", type=float, default=0.25,
                   help="allowed relative plan-arm step-time regression vs "
                        "the uniform arm (default 0.25 — the skew workload "
                        "is tiny, single-core CI timing is noisy)")
    p.add_argument("--assert-serving", metavar="SERVING_JSON", default=None,
                   help="don't run the step: validate the serving "
                        "scale-out grid recorded by tools/bench_serving.py "
                        "(process scaling floor, quantized residency bytes "
                        "vs the traffic model + zero replay compiles, "
                        "grouped candidates/sec floor; CI smoke gate)")
    p.add_argument("--serving-scale-floor", type=float, default=0.8,
                   help="required per-process speedup fraction at the "
                        "largest process count (default 0.8 — e.g. ≥3.2× "
                        "at 4 processes); gated on the measured arms where "
                        "the host has the cores, on the CPU-split model "
                        "where it doesn't")
    p.add_argument("--serving-grouped-factor", type=float, default=2.0,
                   help="required grouped/ungrouped candidates-per-sec "
                        "factor on the two-tower arm (default 2.0)")
    p.add_argument("--assert-retrieval", metavar="RETRIEVAL_JSON",
                   default=None,
                   help="don't run the step: validate the full-corpus "
                        "retrieval record written by "
                        "tools/bench_retrieval.py (int8 recall@100 "
                        "floor vs exact fp32 scan, blocked-sweep "
                        "speedup over the per-row gather baseline, "
                        "ingest->retrievable freshness vs the pinned "
                        "train_to_serve lag, sweep bytes measured == "
                        "modeled, zero fold compiles; CI smoke gate)")
    p.add_argument("--retrieval-recall-floor", type=float, default=0.95,
                   help="required int8 recall@100 vs exact fp32 scan "
                        "(default 0.95)")
    p.add_argument("--retrieval-sweep-factor", type=float, default=3.0,
                   help="required blocked-sweep speedup over the "
                        "per-row gather baseline (default 3.0)")
    p.add_argument("--retrieval-freshness-factor", type=float,
                   default=2.0,
                   help="bound on ingest->retrievable as a multiple of "
                        "the pinned train_to_serve lag (default 2.0)")
    p.add_argument("--assert-reuse", metavar="SERVING_JSON", default=None,
                   help="don't run the step: validate the frontend "
                        "compute-reuse record written by "
                        "tools/bench_serving.py --compute-reuse "
                        "(cache-on effective qps ≥ --reuse-qps-factor × "
                        "cache-off on the zipf stream, steady hit rate ≥ "
                        "--reuse-hit-floor, miss/hit/no_cache probe "
                        "byte-identical, zero steady compiles, mid-load "
                        "publish dip + recovery with ≥1 invalidation, "
                        "occupancy within the byte budget; CI smoke gate)")
    p.add_argument("--reuse-qps-factor", type=float, default=2.0,
                   help="required cache-on/cache-off effective-qps factor "
                        "on the zipf arm (default 2.0 — the ROADMAP "
                        "headline)")
    p.add_argument("--reuse-hit-floor", type=float, default=0.5,
                   help="required steady-window answer-cache hit rate "
                        "(default 0.5 — the zipf head must be resident)")
    p.add_argument("--assert-fused", metavar="BENCH_JSON", default=None,
                   help="don't run the step: validate the fused-sparse-"
                        "step record written by tools/bench_lookup.py "
                        "--fused-step --out (modeled fused-path HBM "
                        "bytes ≤ --fused-ratio × the split-phase path at "
                        "the recorded shapes, model recomputed here so "
                        "bench and ops/traffic.py can't drift apart, and "
                        "the interpret-mode oracle parity flags all "
                        "true; CI smoke gate)")
    p.add_argument("--fused-ratio", type=float, default=0.6,
                   help="required fused/unfused modeled HBM-byte bound "
                        "(default 0.6 — the no-[U,D]-round-trip, "
                        "no-[N,D]-expansion diet)")
    p.add_argument("--assert-obs", metavar="BENCH_JSON", default=None,
                   help="don't run the step: validate the telemetry-plane "
                        "cost recorded in a bench.py or bench_serving.py "
                        "JSON (instrumented vs DEEPREC_OBS=off arms "
                        "present, modeled overhead under --obs-tol, "
                        "/metrics parse check green; CI smoke gate)")
    p.add_argument("--obs-tol", type=float, default=0.02,
                   help="allowed obs-plane overhead as a fraction of the "
                        "measured step/request time (default 0.02)")
    p.add_argument("--assert-guard", metavar="GUARD_JSON", default=None,
                   help="don't run the step: validate the model-quality "
                        "firewall record written by tools/bench_guard.py "
                        "(every injected poison detected within "
                        "--guard-detect-budget dispatches, served AUC "
                        "never under the recorded floor, zero failed "
                        "requests, permanent quarantine + canary "
                        "rejection observed; CI smoke gate)")
    p.add_argument("--guard-detect-budget", type=int, default=1,
                   help="max dispatches between a poison delivery and its "
                        "sentinel trip (default 1 — the deferred-read "
                        "contract)")
    p.add_argument("--guard-recovery-ms", type=float, default=120000.0,
                   help="bound on the recorded rollback+replay wall time "
                        "(default 120 s — generous for single-core CI; "
                        "capable hosts should pin it down)")
    p.add_argument("--assert-tier", metavar="BENCH_JSON", default=None,
                   help="don't run the step: validate the overlapped "
                        "tier-paging record written by bench.py "
                        "--tier-paging (fresh-init rate with paging on "
                        "≥ --tier-loss-factor× lower than off, 0 "
                        "steady-state fold compiles, fold stall ≤ the "
                        "arm's sync_async stall, step time within "
                        "--tier-step-tol of paging-off; CI smoke gate)")
    p.add_argument("--tier-loss-factor", type=float, default=10.0,
                   help="required fresh-init (optimizer-state-loss) "
                        "suppression factor, paging on vs off "
                        "(default 10)")
    p.add_argument("--tier-step-tol", type=float, default=0.03,
                   help="allowed ON/OFF step-time ratio slack (default "
                        "0.03; CPU CI boxes pass a looser value, same "
                        "precedent as --overlap-tol)")
    p.add_argument("--assert-input", metavar="INPUT_JSON", default=None,
                   help="don't run the step: validate the host input "
                        "pipeline record written by tools/bench_input.py "
                        "(block parse ≥ --input-speedup-min× the serial "
                        "line parser, bit-identical batch stream at every "
                        "benched worker count, training-thread dispatch "
                        "≤ --input-train-tol× the inline parse it "
                        "replaced; CI smoke gate)")
    p.add_argument("--input-speedup-min", type=float, default=2.0,
                   help="required block-parse throughput multiple over "
                        "the serial criteo_line_parser (default 2)")
    p.add_argument("--input-train-tol", type=float, default=1.0,
                   help="allowed training-thread dispatch cost as a "
                        "multiple of the serial inline parse (default 1 "
                        "— the pipeline must never cost the training "
                        "thread more than the work it moved off of it)")
    p.add_argument("--serving-quant-ratio", type=float, default=0.55,
                   help="int8 residency bytes bound as a fraction of fp32 "
                        "(default 0.55 — int8 + per-row scale must at "
                        "least halve the value storage)")
    args = p.parse_args(argv)
    if args.assert_traffic:
        sys.exit(assert_traffic(args.assert_traffic))
    if args.assert_overlap:
        sys.exit(assert_overlap(args.assert_overlap, args.overlap_tol))
    if args.assert_compiles:
        sys.exit(assert_compiles(args.assert_compiles,
                                 args.compiles_budget))
    if args.assert_hierarchy:
        sys.exit(assert_hierarchy(args.assert_hierarchy,
                                  args.hierarchy_inter_ratio,
                                  args.hierarchy_tol))
    if args.assert_imbalance:
        sys.exit(assert_imbalance(args.assert_imbalance,
                                  args.imbalance_factor, args.imbalance_tol))
    if args.assert_serving:
        sys.exit(assert_serving(args.assert_serving,
                                args.serving_scale_floor,
                                args.serving_grouped_factor,
                                args.serving_quant_ratio))
    if args.assert_retrieval:
        sys.exit(assert_retrieval(args.assert_retrieval,
                                  args.retrieval_recall_floor,
                                  args.retrieval_sweep_factor,
                                  args.retrieval_freshness_factor))
    if args.assert_reuse:
        sys.exit(assert_reuse(args.assert_reuse, args.reuse_qps_factor,
                              args.reuse_hit_floor))
    if args.assert_fused:
        sys.exit(assert_fused(args.assert_fused, args.fused_ratio))
    if args.assert_obs:
        sys.exit(assert_obs(args.assert_obs, args.obs_tol))
    if args.assert_guard:
        sys.exit(assert_guard(args.assert_guard, args.guard_detect_budget,
                              args.guard_recovery_ms))
    if args.assert_tier:
        sys.exit(assert_tier(args.assert_tier, args.tier_loss_factor,
                             args.tier_step_tol))
    if args.assert_input:
        sys.exit(assert_input(args.assert_input, args.input_speedup_min,
                              args.input_train_tol))

    import jax
    import jax.numpy as jnp

    from deeprec_tpu.data import SyntheticCriteo
    from deeprec_tpu.models import DLRM
    from deeprec_tpu.optim import Adagrad
    from deeprec_tpu.training import Trainer

    B, D = args.batch, args.emb_dim
    model = DLRM(emb_dim=D, capacity=args.capacity,
                 bottom=(512, 256, 64, D) if D <= 64 else (512, 256, D))
    trainer = Trainer(model, Adagrad(lr=0.05))
    state = trainer.init(0)
    gen = SyntheticCriteo(batch_size=B, vocab=1_000_000, seed=0)
    batches = [
        {k: jnp.asarray(v) for k, v in gen.batch().items()} for _ in range(8)
    ]
    for i in range(3):
        state, mets = trainer.train_step(state, batches[i % 8])
    jax.block_until_ready(mets["loss"])
    t0 = time.perf_counter()
    for i in range(args.steps):
        state, mets = trainer.train_step(state, batches[i % 8])
    jax.block_until_ready(mets["loss"])
    dt = (time.perf_counter() - t0) / args.steps
    eps = B / dt

    # ---- algorithmic cost accounting (per step) ----
    # Embedding-engine traffic comes from the SHARED model in
    # deeprec_tpu/ops/traffic.py (the one bench.py records and
    # --assert-traffic validates): per unique id, probe key gather + claim
    # scatter, ONE value row gather (the apply reuses the forward
    # residual), one value row scatter, slot row R/W, and one fused [3]
    # int32 metadata gather + scatter.
    from deeprec_tpu.ops.traffic import table_step_traffic

    F = model.num_cat
    vbytes = jnp.dtype(model.features[0].table.value_dtype).itemsize
    U = B  # worst case: all ids unique (synthetic zipf dedups below this)
    per_table = table_step_traffic(
        unique=U, dim=D, value_bytes=vbytes, slot_widths=(D,), diet=True,
    )
    per_table_before = table_step_traffic(
        unique=U, dim=D, value_bytes=vbytes, slot_widths=(D,), diet=False,
    )
    emb_bytes = F * per_table["hbm_bytes"]
    emb_bytes_before = F * per_table_before["hbm_bytes"]
    dense_in = model.num_dense
    fwd = mlp_flops([dense_in] + list(model.bottom), B)
    inter_f = (F + 1) * (F + 1) * D  # dot-interaction matmul per example
    fwd += 2 * inter_f * B
    inter_dim = (F + 1) * F // 2
    fwd += mlp_flops([model.bottom[-1] + inter_dim] + list(model.top), B)
    flops = 3 * fwd  # fwd + ~2x for bwd

    bw_used = emb_bytes / dt / 1e9
    tf_used = flops / dt / 1e12
    frac_bw = bw_used / args.peak_bw_gbs
    frac_tf = tf_used / args.peak_tflops
    roof = "HBM-bandwidth" if frac_bw >= frac_tf else "MXU-compute"
    print(f"backend           : {jax.default_backend()}")
    print(f"examples/sec      : {eps:,.0f}   ({dt * 1e3:.2f} ms/step, batch {B})")
    print(f"embedding traffic : {emb_bytes / 1e6:.1f} MB/step -> {bw_used:,.1f} GB/s "
          f"({frac_bw:.1%} of {args.peak_bw_gbs:.0f} GB/s roof)")
    print(f"   pre-diet model : {emb_bytes_before / 1e6:.1f} MB/step "
          f"({1 - emb_bytes / emb_bytes_before:.1%} removed by "
          f"residual-reuse + fused metadata)")
    print(f"dense compute     : {flops / 1e9:.2f} GFLOP/step -> {tf_used:.2f} TFLOP/s "
          f"({frac_tf:.1%} of {args.peak_tflops:.0f} TFLOP/s roof)")
    print(f"binding roof      : {roof}")
    print(f"headroom          : {1 / max(frac_bw, frac_tf):,.1f}x before the roof "
          f"(upper bound {eps / max(frac_bw, frac_tf):,.0f} ex/s)")


if __name__ == "__main__":
    main()
