#!/usr/bin/env python3
"""chip_smoke.py — does the training main path still start on the chip?

Drives, in ONE process and through the entry points a user calls, the DLRM
the reference modelzoo trains (26 hashed tables of dim 16 and capacity 2^20,
f32, Adagrad; 13 dense features; bottom MLP 512-256-64-16, top 512-256-1;
batch 2048; SyntheticCriteo(vocab=1_000_000) from a seed):

  train    modelzoo/common.py::main("dlrm", ...): a few tens of steps with a
           log line, an evaluate and a full checkpoint save in the middle
  resume   a fresh Trainer restored from that checkpoint, continued with
           train_steps (K-step lax.scan) under unique_budget="auto" with an
           update_budgets between dispatches — the path bench.py times
  serve    Predictor + ModelServer on the same checkpoint answering three
           requests of different sizes, against the trainer's own forward
  kernels  kernel="auto" against kernel="xla" on one batch, bit for bit, and
           what the compiled step is made of (Mosaic calls, fallbacks)
  sharded  with >= 4 devices: ShardedTrainer over make_mesh(4), comm="a2a"
           and "allgather", placement, memory balance, loss parity

Any assertion or exception in any phase ends the process non-zero. On a
machine without a TPU it exits non-zero and names the platform it found; it
never falls back. The timings it prints are smoke readings of one short
run, NOT benchmark numbers. It writes only under chip_smoke_out/ and the
compile cache (JAX_COMPILATION_CACHE_DIR if set, else <repo>/.jax_cache).

The last line of stdout is one JSON object with these keys and no other:
  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
The line before it, "chip_smoke summary: {...}", carries versions and every
phase's facts and ends with "claim": null; summary.json holds the same.

tests/test_chip_smoke.py drives the same phase functions on the CPU at tiny
size with the kernels in interpret mode.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import time
from contextlib import contextmanager
from typing import Dict, List, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chip_smoke_out")

SHARDED_COMMS = ("a2a", "allgather")


@dataclasses.dataclass(frozen=True)
class Size:
    """The run's shape. The defaults are the reference modelzoo's; the CPU
    rehearsal (tests/test_chip_smoke.py) shrinks rows and steps, never the
    model's widths."""

    capacity: int = 1 << 20
    batch: int = 2048
    vocab: int = 1_000_000
    steps: int = 40           # train phase; eval + save at steps // 2
    k: int = 4                # train_steps scan length
    steady_dispatches: int = 3
    max_batch: int = 256      # top of the serving bucket ladder
    requests: Tuple[int, ...] = (1, 37, 200)
    sharded_steps: int = 10

    def argv(self) -> List[str]:
        return ["--batch_size", str(self.batch), "--capacity",
                str(self.capacity), "--vocab", str(self.vocab), "--seed", "0"]


# ------------------------------------------------------------------ plumbing


class CompileClock:
    """Seconds jax spent compiling (or loading from the persistent cache)
    and how many programs the cache served — so a phase's set-up cost is
    read off the compiler, not guessed from a wall clock that also holds
    the first step. A view of the package's one recorder of compile events
    (deeprec_tpu/obs/compile_log.py), which hears jax.monitoring."""

    def __init__(self):
        from deeprec_tpu.obs import compile_log

        compile_log.install()
        self._log = compile_log

    def _now(self) -> Tuple[float, int, int]:
        snap = self._log.snapshot()
        return (snap["total_s"].get(self._log.BACKEND, 0.0),
                snap["spans"].get(self._log.BACKEND, 0),
                snap["cache"]["hit"])

    @contextmanager
    def phase(self, name: str, facts: Dict):
        (s0, p0, h0), t0 = self._now(), time.perf_counter()
        print(f"== phase {name}", flush=True)
        rec = facts.setdefault(name, {})
        yield rec
        s1, p1, h1 = self._now()
        rec["wall_s"] = round(time.perf_counter() - t0, 2)
        rec["compile_s"] = round(s1 - s0, 2)
        rec["programs"] = p1 - p0
        rec["cache_hits"] = h1 - h0
        print(f"== phase {name} ok: set-up (compile or cache load) "
              f"{rec['compile_s']} s for {rec['programs']} programs "
              f"({rec['cache_hits']} from the cache), "
              f"{rec['wall_s']} s wall", flush=True)


def device_report() -> Dict:
    import jax
    import jaxlib

    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    dev = jax.devices()
    rep = {
        "platform": dev[0].platform,
        "kind": dev[0].device_kind,
        "count": len(dev),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu_version,
    }
    print("chip_smoke: " + json.dumps(rep), flush=True)
    return rep


def rebuild_native() -> None:
    """Build the host library from the tracked sources on THIS machine, so a
    binary that rode in with the tree cannot be what passed."""
    from deeprec_tpu import native

    subprocess.run(["make", "-s", "-C", os.path.dirname(native.__file__),
                    "clean"], check=True)
    import numpy as np

    kv = native.HostKV(dim=4)
    assert kv.native, "libdeeprec_host.so did not build from source"
    kv.put(np.arange(3), np.ones((3, 4), np.float32))
    assert kv.get(np.arange(4))[3].tolist() == [True, True, True, False]


@functools.lru_cache(maxsize=1)
def modelzoo():
    """(common, dlrm train.py) exactly as `python modelzoo/dlrm/train.py`
    imports them."""
    sys.path.insert(0, os.path.join(ROOT, "modelzoo"))
    import common

    spec = importlib.util.spec_from_file_location(
        "modelzoo_dlrm_train", os.path.join(ROOT, "modelzoo", "dlrm", "train.py")
    )
    train = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(train)
    return common, train


def build(size: Size):
    """(model, sparse_opt, dense_opt) as modelzoo/dlrm/train.py builds them
    from its defaults at this size."""
    common, train = modelzoo()
    args = common.build_argparser("dlrm").parse_args(size.argv())
    return (train.model_fn(args), *common.make_optimizers(args))


def batches(size: Size, n: int, seed: int):
    import jax.numpy as jnp

    from deeprec_tpu.data import SyntheticCriteo

    gen = SyntheticCriteo(batch_size=size.batch, vocab=size.vocab, seed=seed)
    return [{k: jnp.asarray(v) for k, v in gen.batch().items()}
            for _ in range(n)]


def table_health(trainer, state) -> Dict[str, int]:
    """Live rows, insert failures, dedup overflow and a2a overflow summed
    over every table (and shard). Asserts the three that must hold."""
    import jax.numpy as jnp

    rows = fails = a2a = 0
    for bname, b in trainer.bundles.items():
        ts = state.tables[bname]
        rows += int(jnp.sum(b.table.occupied(ts)))
        fails += int(jnp.sum(ts.insert_fails))
        a2a += int(jnp.sum(ts.a2a_overflow))
    overflow = sum(
        s["dedup_overflow"] for s in trainer.dedup_stats(state).values()
    )
    health = {"rows": rows, "insert_fails": fails,
              "dedup_overflow": int(overflow), "a2a_overflow": a2a}
    assert rows > 0 and fails == 0 and overflow == 0, health
    return health


def _finite(x) -> bool:
    import numpy as np

    return bool(np.all(np.isfinite(np.asarray(x))))


# -------------------------------------------------------------------- phases


def phase_train(size: Size, rec: Dict) -> str:
    """modelzoo main(): Trainer.stage -> train_step -> evaluate ->
    CheckpointManager.save. Returns the checkpoint directory."""
    common, train = modelzoo()
    ckpt = os.path.join(OUT, "ckpt")
    metrics = os.path.join(OUT, "train_metrics.jsonl")
    half = size.steps // 2
    ev = common.main("dlrm", train.model_fn, "criteo", argv=size.argv() + [
        "--steps", str(size.steps), "--log_every", "1",
        "--eval_every", str(half), "--eval_batches", "2",
        "--save_steps", str(half), "--checkpoint", ckpt,
        "--metrics_file", metrics,
    ])
    with open(metrics) as f:
        log = [json.loads(line) for line in f]
    losses = [r["loss"] for r in log]
    assert len(losses) == size.steps and _finite(losses), losses
    window = losses[-(size.steps // 4):]
    tail = sum(window) / len(window)
    assert tail < losses[0], (losses[0], tail)
    assert _finite(list(ev.values())) and ev["auc"] > 0.5, ev
    # one log line per step: the first holds the compile, the one after a
    # save holds the save, so the median is the steady reading
    ms = sorted(1e3 / r["steps_per_sec"] for r in log[2:])
    rec.update(
        steps=size.steps, first_loss=losses[0], last_window_loss=round(tail, 5),
        eval={k: round(v, 5) for k, v in ev.items()},
        first_step_s=round(1.0 / log[0]["steps_per_sec"], 2),
        smoke_ms_per_step=round(ms[len(ms) // 2], 3),
    )
    print(f"train: loss {losses[0]:.5f} -> {tail:.5f} (mean of the last "
          f"{len(window)} steps), "
          f"eval {rec['eval']}; smoke reading {rec['smoke_ms_per_step']} "
          "ms/step (median, one host sync per step)", flush=True)
    return ckpt


def phase_resume(size: Size, rec: Dict, ckpt: str, first_loss: float):
    """Fresh Trainer <- checkpoint, then the K-step scan with auto budgets.
    Returns (reference rows, their probabilities under the restored model)
    for the serving phase."""
    import jax
    import numpy as np

    from deeprec_tpu.analysis.trace_guard import trace_guard
    from deeprec_tpu.training import Trainer
    from deeprec_tpu.training.checkpoint import CheckpointManager
    from deeprec_tpu.training.trainer import stack_batches

    model, sparse_opt, dense_opt = build(size)
    trainer = Trainer(model, sparse_opt, dense_opt, unique_budget="auto")
    # chunk=: one import program for all 26 tables. The default restore
    # imports each table at its own row count — on a cold chip that was
    # ~1,230 tiny programs and ~215 s of this phase (PERF.md, PR 21).
    state = CheckpointManager(ckpt, trainer).restore(
        chunk=min(1 << 14, size.capacity))
    assert int(state.step) == size.steps, int(state.step)
    restored = table_health(trainer, state)

    ref_rows = batches(size, 1, seed=7)[0]
    ref_probs = np.asarray(trainer.eval_step(state, ref_rows)[1])
    assert ref_probs.shape == (size.batch,) and _finite(ref_probs)

    n = 2 + size.steady_dispatches
    flat = batches(size, n * size.k, seed=1)
    stacked = [stack_batches(flat[i * size.k:(i + 1) * size.k])
               for i in range(n)]
    t0 = time.perf_counter()
    state, mets = trainer.train_steps(state, stacked[0])
    losses = [np.asarray(mets["loss"])]
    first_dispatch_s = time.perf_counter() - t0
    state, budgets = trainer.update_budgets(state)
    state, mets = trainer.train_steps(state, stacked[1])
    losses.append(np.asarray(mets["loss"]))
    with trace_guard(max_compiles=0, note="K-scan steady window"):
        t0 = time.perf_counter()
        for s in stacked[2:]:
            state, mets = trainer.train_steps(state, s)
            losses.append(np.asarray(mets["loss"]))
        jax.block_until_ready(state.step)
        steady = time.perf_counter() - t0
    losses = np.concatenate(losses)
    assert losses.shape == (n * size.k,) and _finite(losses), losses
    assert float(losses[-size.k:].mean()) < first_loss, (losses, first_loss)
    assert int(state.step) == size.steps + n * size.k
    rec.update(
        restored_rows=restored["rows"], k=size.k, dispatches=n,
        budget_fraction={b: r.get("unique_budget_fraction")
                         for b, r in budgets.items()},
        loss_first=float(losses[0]), loss_last=float(losses[-1]),
        health=table_health(trainer, state),
        first_dispatch_s=round(first_dispatch_s, 2),
        smoke_ms_per_step=round(
            steady * 1e3 / (size.steady_dispatches * size.k), 3),
    )
    print(f"resume: step {size.steps} -> {int(state.step)}, loss "
          f"{losses[0]:.5f} -> {losses[-1]:.5f}, budgets "
          f"{rec['budget_fraction']}, {rec['health']}; smoke reading "
          f"{rec['smoke_ms_per_step']} ms/step (K={size.k} scan)", flush=True)
    return ref_rows, ref_probs


def phase_serve(size: Size, rec: Dict, ckpt: str, ref_rows, ref_probs):
    """Predictor + ModelServer on the checkpoint: label-free jitted
    predictor, read-only lookup, bucket ladder."""
    import numpy as np

    from deeprec_tpu.analysis.trace_guard import trace_guard
    from deeprec_tpu.serving.predictor import ModelServer, Predictor

    model, _, _ = build(size)
    feats = {k: np.asarray(v) for k, v in ref_rows.items() if k != "label"}
    server = ModelServer(Predictor(model, ckpt), max_batch=size.max_batch)
    try:
        rec["buckets_warmed"] = server.warmup(feats)
        lat = []
        with trace_guard(max_compiles=0, note="serving after warmup"):
            for n in size.requests:
                t0 = time.perf_counter()
                probs = np.asarray(server.request(
                    {k: v[:n] for k, v in feats.items()}))
                lat.append(round((time.perf_counter() - t0) * 1e3, 2))
                assert probs.shape == (n,) and _finite(probs), (n, probs)
                # same checkpoint, same forward: the trainer's eval_step
                # is the reference (rows are independent; the MXU's bf16
                # products may tile differently at another batch size)
                np.testing.assert_allclose(probs, ref_probs[:n], atol=5e-3)
    finally:
        server.close()
    rec.update(requests=list(size.requests), smoke_request_ms=lat)
    print(f"serve: {rec['buckets_warmed']} buckets warmed, requests of "
          f"{list(size.requests)} rows agree with the trainer's forward; "
          f"smoke reading {lat} ms each", flush=True)


def _pallas_calls(jaxpr) -> List[Tuple[str, bool]]:
    """(kernel name, interpreted?) of every pallas_call under a jaxpr."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((str(eqn.params.get("name")
                              or eqn.params["jaxpr"].debug_info.func_name),
                          bool(eqn.params.get("interpret"))))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found.extend(_pallas_calls(sub))
    return found


def fallback_counts() -> Dict[Tuple, float]:
    """deeprec_pallas_fallback_total by (kernel, reason), as of now."""
    from deeprec_tpu.obs.metrics import default_registry

    snap = default_registry().snapshot()["metrics"]
    return {tuple(sorted(s["labels"].items())): s["value"]
            for name, m in snap.items()
            if name.startswith("deeprec_pallas_fallback")
            for s in m["series"]}


def phase_kernels(size: Size, rec: Dict, fallbacks0: Dict) -> float:
    """kernel="auto" against kernel="xla", one step on one batch: loss and
    every table leaf bit for bit; then what the auto step compiled to.
    `fallbacks0` is fallback_counts() from before the run: a fallback counts
    against this run only if it was noted during it. Returns the one-chip
    first-step loss (the sharded phase's reference)."""
    import jax
    import jax.numpy as jnp

    from deeprec_tpu.analysis.trace_guard import trace_guard
    from deeprec_tpu.training import Trainer

    common, _ = modelzoo()
    data = batches(size, 1 + size.steady_dispatches, seed=0)
    arms = {}
    for kernel in ("auto", "xla"):
        model, sparse_opt, dense_opt = build(size)
        if kernel != "auto":
            model = common._retable(model, kernel=kernel)
        trainer = Trainer(model, sparse_opt, dense_opt)
        state = trainer.init(0)
        if kernel == "auto":
            traced = trainer._train_step.trace(
                state, data[0], jnp.asarray(sparse_opt.lr, jnp.float32))
            calls = _pallas_calls(traced.jaxpr.jaxpr)
            rec["pallas_calls"] = len(calls)
            rec["pallas_kernels"] = sorted({name for name, _ in calls})
            rec["interpreted_pallas_calls"] = sum(i for _, i in calls)
            rec["mosaic_custom_calls"] = traced.lower().as_text().count(
                "tpu_custom_call")
            rec["layout"] = {b.name: list(state.tables[b.name].values.shape)
                             for b in trainer.bundles.values()}
        state, mets = trainer.train_step(state, data[0])
        arms[kernel] = (trainer, state, jax.device_get(mets["loss"]))
    la, lx = arms["auto"][2], arms["xla"][2]
    assert _finite(la) and la.tobytes() == lx.tobytes(), (la, lx)
    same = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)),
                        arms["auto"][1].tables, arms["xla"][1].tables)
    assert all(jax.tree.leaves(same)), same
    ms = {}
    while arms:  # pop: each arm's state is donated here and must not linger
        kernel, (trainer, state, _) = arms.popitem()
        with trace_guard(max_compiles=0, note=f"kernel={kernel} steady"):
            t0 = time.perf_counter()
            for b in data[1:]:
                state, mets = trainer.train_step(state, b)
            jax.block_until_ready(mets["loss"])
            ms[kernel] = round(
                (time.perf_counter() - t0) * 1e3 / size.steady_dispatches, 3)
    rec.update(first_loss=float(la), auto_equals_xla=True,
               fallbacks=[dict(k) for k, v in fallback_counts().items()
                          if v > fallbacks0.get(k, 0)],
               smoke_ms_per_step=ms)
    print(f"kernels: {rec['pallas_calls']} pallas_calls "
          f"{rec['pallas_kernels']} ({rec['interpreted_pallas_calls']} "
          f"interpreted), {rec['mosaic_custom_calls']} Mosaic custom calls "
          f"in the lowered step, layout {rec['layout']}, fallbacks "
          f"{rec['fallbacks']}; kernel=auto == kernel=xla bit for bit on "
          f"loss {float(la):.6f} and all table leaves; smoke reading "
          f"{rec['smoke_ms_per_step']} ms/step", flush=True)
    return float(la)


def _sharded_arm(size: Size, comm: str, mesh, data, one_chip_loss: float,
                 devices, stats0) -> Dict:
    import numpy as np

    from deeprec_tpu.parallel import ShardedTrainer, shard_batch

    model, sparse_opt, dense_opt = build(size)
    trainer = ShardedTrainer(model, sparse_opt, dense_opt, mesh=mesh,
                             comm=comm)
    state = trainer.init(0)
    losses = []
    t0 = time.perf_counter()
    for batch in data:
        batch = shard_batch(mesh, batch)
        state, mets = trainer.train_step(state, batch)
        losses.append(float(mets["loss"]))
    wall = time.perf_counter() - t0
    assert _finite(losses), losses
    np.testing.assert_allclose(losses[0], one_chip_loss, rtol=2e-2)
    placed = dict(batch)
    for bname in trainer.bundles:
        ts = state.tables[bname]
        placed[f"{bname}.values"] = ts.values
        placed.update({f"{bname}.{s}": a for s, a in ts.slots.items()})
    spread = {n: len(a.sharding.device_set) for n, a in placed.items()}
    assert set(spread.values()) == {4}, spread
    health = table_health(trainer, state)
    assert health["a2a_overflow"] == 0, health
    used = None
    if all(s is not None for s in stats0):
        used = [d.memory_stats()["bytes_in_use"] - s["bytes_in_use"]
                for d, s in zip(devices, stats0)]
        assert min(used) > 0 and max(used) - min(used) <= 0.2 * max(used), used
    print(f"sharded[{comm}]: loss {losses[0]:.5f} (one chip "
          f"{one_chip_loss:.5f}) -> {losses[-1]:.5f}, every array on 4 "
          f"devices, bytes in use per device {used}, {health}", flush=True)
    return {"steps": len(losses), "loss_first": losses[0],
            "loss_last": losses[-1], "one_chip_loss": one_chip_loss,
            "devices_per_array": 4, "health": health,
            "bytes_in_use_delta": used, "wall_s": round(wall, 2)}


def phase_sharded(size: Size, rec: Dict, one_chip_loss: float) -> None:
    """ShardedTrainer over four devices, both exchanges: state and batch on
    four devices, memory balanced, first-step loss equal to one chip's at
    the CPU oracle's rtol (tests/test_sharded.py), no a2a overflow."""
    import jax

    from deeprec_tpu.parallel import make_mesh

    data = batches(size, size.sharded_steps, seed=0)
    devices = jax.devices()[:4]
    for comm in SHARDED_COMMS:
        gc.collect()
        stats0 = [d.memory_stats() for d in devices]
        rec[comm] = _sharded_arm(size, comm, make_mesh(4), data,
                                 one_chip_loss, devices, stats0)


def chip_violations(device: Dict, facts: Dict) -> List[str]:
    """What a CPU cannot fake, over the facts the phases recorded. Empty on
    a passing chip run; the CPU rehearsal must trip it."""
    k = facts["kernels"]
    bad = []
    if device["platform"] != "tpu":
        bad.append(f"platform is {device['platform']!r}, not 'tpu'")
    if k["mosaic_custom_calls"] == 0:
        bad.append("no Mosaic custom call in the lowered train step: the "
                   "XLA path ran in the kernels' name")
    if k["pallas_calls"] == 0 or k["interpreted_pallas_calls"]:
        bad.append(f"{k['interpreted_pallas_calls']} of {k['pallas_calls']} "
                   "pallas_calls on the path run interpreted")
    for labels in k["fallbacks"]:
        if labels["reason"] == "not_tpu" or labels["kernel"] in (
                "gather_rows", "apply_rows_sr"):
            bad.append(f"pallas fallback on the path: {labels}")
    for comm in SHARDED_COMMS:
        arm = facts.get("sharded", {}).get(comm)
        if arm and arm["bytes_in_use_delta"] is None:
            bad.append(f"sharded[{comm}]: devices report no memory_stats")
    return bad


def run(size: Size) -> Dict:
    """Every phase, in order, on an empty output directory."""
    import jax

    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    clock = CompileClock()
    fallbacks0 = fallback_counts()
    facts: Dict = {}
    with clock.phase("train", facts) as rec:
        ckpt = phase_train(size, rec)
    with clock.phase("resume", facts) as rec:
        ref_rows, ref_probs = phase_resume(
            size, rec, ckpt, facts["train"]["first_loss"])
    with clock.phase("serve", facts) as rec:
        phase_serve(size, rec, ckpt, ref_rows, ref_probs)
    with clock.phase("kernels", facts) as rec:
        one_chip_loss = phase_kernels(size, rec, fallbacks0)
    if len(jax.devices()) >= 4:
        with clock.phase("sharded", facts) as rec:
            phase_sharded(size, rec, one_chip_loss)
    else:
        facts["sharded"] = {"ran": False}
        print(f"== phase sharded did not run: it needs 4 devices and jax "
              f"reports {len(jax.devices())}", flush=True)
    shutil.rmtree(os.path.join(OUT, "ckpt"))  # hundreds of MB; facts stay
    return facts


def report(device: Dict, facts: Dict) -> None:
    """A passing run's summary, to summary.json and one stdout line; then,
    last, the result object the driver parses: "ok" and "device" with
    "platform", "kind" and "count", and no other key."""
    result = {
        "ok": True,
        "device": {k: device[k] for k in ("platform", "kind", "count")},
    }
    summary = {
        **result,
        "versions": {k: device[k] for k in ("jax", "jaxlib", "libtpu")},
        "phases": facts,
        "note": "timings are smoke readings of one short run, not a benchmark",
        "claim": None,
    }
    with open(os.path.join(OUT, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print("chip_smoke summary: " + json.dumps(summary), flush=True)
    print(json.dumps(result), flush=True)


def main() -> None:
    # first, so that the script alone (no package beside it) fails before
    # it has printed anything
    from deeprec_tpu.utils.backend import enable_compile_cache

    import jax

    if jax.default_backend() != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; jax found platform "
                 f"{jax.default_backend()!r} "
                 f"({jax.devices()[0].device_kind}). Not falling back.")
    device = device_report()
    print(f"chip_smoke: compile cache at {enable_compile_cache()}",
          flush=True)
    rebuild_native()
    facts = run(Size())
    bad = chip_violations(device, facts)
    assert not bad, bad
    report(device, facts)


if __name__ == "__main__":
    main()
