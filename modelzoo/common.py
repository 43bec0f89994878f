"""Shared modelzoo training driver — the `python train.py` CLI every model
directory exposes (reference: modelzoo/<model>/train.py argument surface:
--batch_size --steps --checkpoint ... README per model).

Supports synthetic data (default; no dataset mounted) or real Criteo TSV /
parquet files, single-device or mesh-sharded execution, full + incremental
checkpointing, periodic eval with AUC, and benchmark-harness-compatible log
lines:  `global_step/sec: <v>`  and  `Eval AUC: <v>`  (scraped by
modelzoo/benchmark/benchmark.py the way log_process.py does).
"""
from __future__ import annotations

import argparse
import sys
import time
from typing import Callable, Dict, Optional

import numpy as np


def build_argparser(name: str) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=f"Train {name} on TPU (deeprec_tpu)")
    p.add_argument("--batch_size", type=int, default=2048)
    p.add_argument("--steps", type=int, default=2000)
    p.add_argument("--emb_dim", type=int, default=16)
    p.add_argument("--capacity", type=int, default=1 << 20)
    p.add_argument("--vocab", type=int, default=1_000_000,
                   help="synthetic id vocabulary per feature")
    p.add_argument("--learning_rate", type=float, default=0.05)
    p.add_argument("--dense_lr", type=float, default=1e-3)
    p.add_argument("--optimizer", default="adagrad",
                   choices=["sgd", "adagrad", "adagrad_decay", "adam",
                            "adam_async", "adamw", "ftrl"])
    p.add_argument("--data", default="synthetic",
                   help="'synthetic', 'criteo_stats' (pinned Criteo-marginal stream), a criteo .tsv glob, or a .parquet glob")
    p.add_argument("--sharded", action="store_true",
                   help="shard tables + batch over all local devices")
    p.add_argument("--comm", default="allgather", choices=["allgather", "a2a"],
                   help="sharded embedding exchange: exact allgather or "
                        "budgeted all2all (SOK path)")
    p.add_argument("--checkpoint", default="",
                   help="checkpoint directory (enables save/restore)")
    p.add_argument("--save_steps", type=int, default=1000)
    p.add_argument("--incremental_save_steps", type=int, default=0)
    p.add_argument("--eval_every", type=int, default=500)
    p.add_argument("--eval_batches", type=int, default=8)
    p.add_argument("--log_every", type=int, default=100)
    p.add_argument("--filter_freq", type=int, default=0,
                   help="counter-filter admission threshold")
    p.add_argument("--steps_to_live", type=int, default=0,
                   help="TTL eviction in steps (0 = off)")
    p.add_argument("--evict_every", type=int, default=0,
                   help="run eviction policies every N steps (0 = only with "
                        "checkpoints)")
    p.add_argument("--bf16", action="store_true", default=False,
                   help="bfloat16 embedding tables (halves table HBM; "
                        "updates use stochastic rounding). Dense compute "
                        "is bf16-on-MXU regardless (nn.py).")
    p.add_argument("--kernel", default="auto", choices=["auto", "xla", "pallas"],
                   help="embedding hot-path kernel (TableConfig.kernel)")
    p.add_argument("--micro_batch", type=int, default=0,
                   help="split each batch into N micro-batches "
                        "(Auto-Micro-Batch: sparse applies per micro, dense "
                        "grads accumulated; batch_size must divide by N)")
    p.add_argument("--workqueue", action="store_true",
                   help="shard --data files through a WorkQueue (dynamic "
                        "work-item sharding; straggler-proof multi-worker "
                        "input). Requires --data.")
    p.add_argument("--num_slices", type=int, default=1,
                   help="with --workqueue: split each file into N slices")
    p.add_argument("--epochs", type=int, default=1,
                   help="with --workqueue: dataset epochs in the queue")
    p.add_argument("--maintain_every", type=int, default=0,
                   help="run capacity management (auto-grow / tiering) "
                        "every N steps (0 = off)")
    p.add_argument("--hbm_budget_mb", type=int, default=0,
                   help="with --maintain_every: total table-bytes budget; "
                        "growth beyond it auto-tiers to the host store")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--timeline", type=int, default=0,
                   help="trace steps [N, N+10) to --timeline_dir")
    p.add_argument("--timeline_dir", default="/tmp/deeprec_tpu_trace")
    p.add_argument("--metrics_file", default="",
                   help="append JSONL metrics records here")
    return p


def ev_option(args):
    from deeprec_tpu import (
        CounterFilter,
        EmbeddingVariableOption,
        GlobalStepEvict,
    )

    return EmbeddingVariableOption(
        counter_filter=CounterFilter(args.filter_freq) if args.filter_freq else None,
        global_step_evict=(
            GlobalStepEvict(args.steps_to_live) if args.steps_to_live else None
        ),
    )


def make_optimizers(args):
    import optax

    from deeprec_tpu.optim import make

    return make(args.optimizer, lr=args.learning_rate), optax.adam(args.dense_lr)


def make_data(args, kind: str):
    """kind: 'criteo' | 'multitask' | 'behavior' | 'twotower'."""
    import glob

    from deeprec_tpu import data as D

    if args.data == "criteo_stats":
        if kind != "criteo":
            raise ValueError(
                "criteo_stats generates Criteo-shaped batches; model kind "
                f"{kind!r} wants a different schema"
            )
        # The deterministic Criteo-marginal-matched stream (AUC protocol,
        # docs/auc_protocol.md): train and eval are disjoint splits of the
        # same fixed task, so eval AUC is held-out, not memorized.
        gen = D.CriteoStats(args.batch_size, seed=args.seed, split="train")
        args._eval_iter = iter(
            D.CriteoStats(args.batch_size, seed=args.seed, split="eval")
        )
        # Stream position checkpoints with the model (CriteoStats is a pure
        # function of index: a restore must NOT replay consumed batches and
        # must NOT skip un-consumed ones). The auto-stage ring runs ahead of
        # the train step, so run() wires gen.mark_consumed into the staged
        # iterator and save() records the CONSUMED index — the producer
        # index would silently skip the in-flight batches.
        args._datasets = {"criteo_stats": gen}
        return iter(gen)
    if args.data != "synthetic":
        paths = sorted(glob.glob(args.data))
        if not paths:
            raise FileNotFoundError(f"--data glob matched nothing: {args.data}")
        if getattr(args, "workqueue", False):
            parquet = paths[0].endswith(".parquet")
            if parquet and args.num_slices > 1:
                raise ValueError(
                    "--num_slices applies to TSV files only (parquet has no "
                    "byte-range slicing; shard by file instead)"
                )
            q = D.WorkQueue(paths, num_epochs=args.epochs, shuffle=True,
                            seed=args.seed, num_slices=args.num_slices)
            # registered with the CheckpointManager in run(): queue
            # position checkpoints WITH the model
            args._datasets = {"workqueue": q}
            # training wants one compiled batch shape: drop per-slice
            # remainders (size the slices >= batch_size)
            return q.input_dataset(
                args.batch_size, drop_remainder=True,
                reader_cls=D.ParquetReader if parquet else None,
            )
        if paths[0].endswith(".parquet"):
            return iter(D.ParquetReader(paths, args.batch_size))
        return iter(D.CriteoCSVReader(paths, args.batch_size))
    if kind == "criteo":
        gen = D.SyntheticCriteo(args.batch_size, vocab=args.vocab, seed=args.seed)
    elif kind == "multitask":
        gen = D.SyntheticMultiTask(
            args.batch_size, num_cat=8, num_dense=4, vocab=args.vocab,
            seed=args.seed,
        )
    elif kind == "behavior":
        gen = D.SyntheticBehaviorSequence(
            args.batch_size, vocab=args.vocab, seed=args.seed
        )
    elif kind == "twotower":
        gen = D.SyntheticTwoTower(args.batch_size, vocab=args.vocab,
                                  seed=args.seed)
    else:
        raise ValueError(kind)
    return iter(gen)


def _retable(model, **cfg_overrides):
    """Rewrite every sparse feature's TableConfig (bf16 values, kernel
    choice) — one hook instead of plumbing flags through every model."""
    import dataclasses

    from deeprec_tpu.features import SparseFeature

    model.features = [
        dataclasses.replace(
            f, table=dataclasses.replace(f.table, **cfg_overrides)
        )
        if isinstance(f, SparseFeature) and f.table is not None
        else f
        for f in model.features
    ]
    return model


def run(model, args, data_kind: str) -> Dict[str, float]:
    """The MonitoredTrainingSession loop: train, log steps/sec, eval AUC,
    checkpoint (full + incremental)."""
    import jax
    import jax.numpy as jnp

    from deeprec_tpu.training import Trainer
    from deeprec_tpu.training.checkpoint import CheckpointManager

    overrides = {}
    if args.bf16:
        overrides["value_dtype"] = "bfloat16"
    if args.kernel != "auto":
        overrides["kernel"] = args.kernel
    if overrides:
        model = _retable(model, **overrides)

    sparse_opt, dense_opt = make_optimizers(args)
    if args.sharded:
        from deeprec_tpu.parallel import ShardedTrainer, make_mesh

        mesh = make_mesh()
        trainer = ShardedTrainer(model, sparse_opt, dense_opt, mesh=mesh,
                                 comm=args.comm)
    else:
        trainer = Trainer(model, sparse_opt, dense_opt)
    state = trainer.init(args.seed)
    # data FIRST: make_data registers input-state carriers (WorkQueue,
    # CriteoStats) in args._datasets, which the CheckpointManager must
    # know about BEFORE restore() so stream positions rewind with the
    # model. Staging starts strictly AFTER restore: the prefetch ring
    # pulls ahead the moment it exists, and batches queued pre-restore
    # would replay data the checkpointed run already trained on.
    raw_data = make_data(args, data_kind)
    ck = None
    if args.checkpoint:
        ck = CheckpointManager(args.checkpoint, trainer,
                               datasets=getattr(args, "_datasets", None))
        try:
            state = ck.restore()
            print(f"restored from step {int(state.step)}")
        except FileNotFoundError:
            pass
    # Auto-stage (SmartStage analog): the trainer derives the staged
    # boundary from the model's input signature — IO, key filtering and
    # the (mesh-aware) host->device transfer overlap the train step with
    # zero manual staged() calls here or in make_data. Batches from
    # `data` are device-ready; only out-of-band eval batches need the
    # explicit stage_batch call.
    # Stream-position carriers track the CONSUMED index through the staging
    # ring (depth-2 prefetch runs the producer ahead; checkpoints must
    # record what the train loop actually received).
    marks = []
    for d in getattr(args, "_datasets", {}).values():
        if hasattr(d, "mark_consumed"):
            marks.append(d.mark_consumed)
            if hasattr(d, "attach_consumer"):
                # flip to consumed-position checkpointing BEFORE the ring's
                # producer runs ahead (a save prior to the first delivery
                # must not report the producer index)
                d.attach_consumer()
    on_consume = (lambda: [m() for m in marks]) if marks else None
    data = trainer.stage(raw_data, on_consume=on_consume)
    eval_src = getattr(args, "_eval_iter", None)
    eval_batches = [
        trainer.stage_batch(next(eval_src)) if eval_src else next(iter(data))
        for _ in range(args.eval_batches)
    ]

    tracer = None
    if args.timeline:
        from deeprec_tpu.training.profiler import StepWindowTracer

        tracer = StepWindowTracer(args.timeline, args.timeline + 10,
                                  args.timeline_dir)
    mlog = None
    if args.metrics_file:
        from deeprec_tpu.training.logging import MetricsLogger

        mlog = MetricsLogger(args.metrics_file)

    t0 = time.perf_counter()
    window_start = int(state.step)
    last_metrics = {}
    for batch in data:
        step = int(state.step)
        if step >= args.steps:
            break
        if tracer:
            tracer.on_step(step)
        if args.micro_batch > 1:
            state, mets = trainer.train_step_accum(
                state, batch, args.micro_batch
            )
        else:
            state, mets = trainer.train_step(state, batch)
        step += 1
        if step % args.log_every == 0:
            jax.block_until_ready(mets["loss"])
            dt = time.perf_counter() - t0
            sps = (step - window_start) / max(dt, 1e-9)
            print(
                f"step {step} loss {float(mets['loss']):.5f} "
                f"global_step/sec: {sps:.2f}",
                flush=True,
            )
            if mlog:
                mlog.log(step, loss=mets["loss"], steps_per_sec=sps)
            t0 = time.perf_counter()
            window_start = step
        if args.eval_every and step % args.eval_every == 0:
            ev = trainer.evaluate(state, eval_batches)
            for k, v in ev.items():
                if k.startswith("auc"):
                    print(f"Eval AUC: {v:.6f} ({k})", flush=True)
            last_metrics = ev
            t0 = time.perf_counter()
            window_start = step
        if args.evict_every and step % args.evict_every == 0:
            state = trainer.evict_tables(state)
        if args.maintain_every and step % args.maintain_every == 0:
            state, report = trainer.maintain(
                state,
                hbm_budget_bytes=args.hbm_budget_mb << 20 or None,
            )
            acted = {
                bn: r for bn, r in report.items()
                if "grew_to" in r or r.get("demoted") or r.get("auto_tiered")
            }
            if acted:
                print(f"maintain: {acted}", flush=True)
        if ck and args.save_steps and step % args.save_steps == 0:
            state = trainer.evict_tables(state)  # evict at ckpt time (ref cadence)
            state, path = ck.save(state)
            print(f"saved full checkpoint: {path}", flush=True)
        elif (
            ck
            and args.incremental_save_steps
            and step % args.incremental_save_steps == 0
        ):
            state, path = ck.save_incremental(state)
            print(f"saved incremental checkpoint: {path}", flush=True)

    if tracer:
        tracer.close()
    ev = trainer.evaluate(state, eval_batches)
    for k, v in ev.items():
        if k.startswith("auc"):
            print(f"Eval AUC: {v:.6f} ({k})", flush=True)
    if ck:
        state, path = ck.save(state)
        print(f"saved final checkpoint: {path}", flush=True)
    return ev


def main(name: str, model_fn: Callable, data_kind: str, argv=None,
         defaults: Optional[Dict] = None):
    """defaults: per-model argparse default overrides (the reference's
    per-model train.py files hard-code model-appropriate vocab/lr the same
    way)."""
    from deeprec_tpu.utils.backend import enable_compile_cache

    p = build_argparser(name)
    if defaults:
        p.set_defaults(**defaults)
    args = p.parse_args(argv)
    enable_compile_cache()
    model = model_fn(args)
    return run(model, args, data_kind)
