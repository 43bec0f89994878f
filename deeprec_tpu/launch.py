"""Multi-host launcher: `python -m deeprec_tpu.launch [...] -- script.py args`.

The counterpart of the reference's distributed launcher
(tensorflow/python/distribute/launch.py:55-97), which reads the cluster
layout from env vars, exports TF_CONFIG and execs the training script. The
JAX/TPU shape of the same job:

  * wire jax.distributed.initialize(coordinator, num_processes, process_id)
    BEFORE any jax import in the user script — after that, jax.devices()
    spans the whole pod and every shard_map/psum in this framework rides
    the global mesh (DCN between hosts, ICI within);
  * then run the target script in-process (runpy), so the user code needs
    zero changes to go multi-host.

Cluster layout comes from flags or, like the reference, from environment
variables: DEEPREC_COORDINATOR (host:port), DEEPREC_NUM_PROCESSES,
DEEPREC_PROCESS_ID. On TPU pods all three are optional —
jax.distributed.initialize() autodetects the pod topology.

Single-host multi-process CPU testing works the same way (the 2-process CI
test in tests/test_launch.py drives a psum and a file-coordinated WorkQueue
across processes).
"""
from __future__ import annotations

import argparse
import os
import runpy
import sys
from typing import Optional


def initialize(
    coordinator: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Wire the DCN control plane (idempotent — a no-op when already
    initialized, so scripts may call it defensively even under the CLI).
    Call before creating any arrays."""
    import jax

    if jax.distributed.is_initialized():
        return

    kw = {}
    coordinator = coordinator or os.environ.get("DEEPREC_COORDINATOR")
    if num_processes is None and os.environ.get("DEEPREC_NUM_PROCESSES"):
        num_processes = int(os.environ["DEEPREC_NUM_PROCESSES"])
    if process_id is None and os.environ.get("DEEPREC_PROCESS_ID"):
        process_id = int(os.environ["DEEPREC_PROCESS_ID"])
    if coordinator:
        kw["coordinator_address"] = coordinator
    if num_processes is not None:
        kw["num_processes"] = num_processes
    if process_id is not None:
        kw["process_id"] = process_id
    jax.distributed.initialize(**kw)


def supervise_elastic(
    script: str,
    script_args,
    num_processes: int,
    elastic_dir: str,
    max_generations: int = 100,
    env_extra: Optional[dict] = None,
) -> int:
    """Single-host elastic supervisor: the UpdateServerDef analog.

    jax pins the process set at jax.distributed.initialize, so a topology
    change means a new worker generation: spawn `num_processes` workers
    running `script` under this launcher; when they exit with
    elastic.EXIT_RESCALE (having checkpointed and acked the plan), respawn
    at the plan's target count and bump DEEPREC_ELASTIC_EPOCH so the plan
    isn't re-run. A zero exit from all workers ends the job. Mirrors the
    reference choreography (elastic_training.proto:38-76) with the
    supervisor in the coordinator role.

    Scope: SINGLE-host process sets (the CI topology, and one TPU-VM
    driving its local chips). A multi-host pod needs an external
    orchestrator (e.g. the K8s operator pattern the reference's modelzoo
    distribute recipes assume) running this same choreography across
    hosts: per-host supervisors alone cannot form one jax job, because
    each would pin its own coordinator address and process-id range.
    """
    import subprocess

    from deeprec_tpu.parallel.elastic import EXIT_RESCALE, ElasticCoordinator

    coord = ElasticCoordinator(elastic_dir)
    n = num_processes
    epoch_done = coord.plan()[0]  # plans at/below this are already applied
    for _generation in range(max_generations):
        port = _free_port()
        procs = []
        for pid in range(n):
            env = dict(os.environ)
            env.update(env_extra or {})
            env.update(
                DEEPREC_COORDINATOR=f"127.0.0.1:{port}",
                DEEPREC_NUM_PROCESSES=str(n),
                DEEPREC_PROCESS_ID=str(pid),
                DEEPREC_ELASTIC_DIR=elastic_dir,
                DEEPREC_ELASTIC_EPOCH=str(epoch_done),
            )
            procs.append(
                subprocess.Popen(
                    [sys.executable, "-m", "deeprec_tpu.launch", script]
                    + list(script_args),
                    env=env,
                )
            )
        rcs = [q.wait() for q in procs]
        if all(rc == 0 for rc in rcs):
            return 0
        if all(rc == EXIT_RESCALE for rc in rcs):
            # The workers acked the epoch they COLLECTIVELY decided on,
            # which may be older than the latest plan.json (an autoscaler
            # can post again mid-rescale); scan the acks, don't re-read
            # the plan. A newer plan triggers the next generation.
            epoch, target = coord.wait_acked_after(epoch_done, n)
            print(
                f"deeprec_tpu.launch: elastic rescale {n} -> {target} "
                f"(plan epoch {epoch})",
                flush=True,
            )
            n = target
            epoch_done = epoch
            continue
        bad = [(i, rc) for i, rc in enumerate(rcs) if rc not in (0, EXIT_RESCALE)]
        raise RuntimeError(f"elastic workers failed: {bad}")
    raise RuntimeError("elastic: max_generations exceeded")


def supervise_worker(
    script: str,
    script_args,
    heartbeat: Optional[str] = None,
    lease_secs: float = 30.0,
    max_restarts: int = 5,
    env_extra: Optional[dict] = None,
) -> int:
    """Run ONE worker under liveness supervision (deeprec_tpu.online):
    restart it on crash or wedged heartbeat lease with capped-backoff
    budget, respawn EXIT_RESCALE exits for free. The worker sees
    DEEPREC_HEARTBEAT_FILE and must stamp it per step (TrainLoop and the
    `deeprec_tpu.online.loop` CLI pick the env var up automatically;
    custom loops stamp a `Heartbeat` themselves); without a heartbeat
    only death is detected, not wedging. Returns the final exit code (0 done,
    1 budget exhausted). The continuous-training analog of
    `supervise_elastic` — see docs/fault-tolerance.md."""
    from deeprec_tpu.online.supervisor import ProcessSpec, Supervisor

    def env():
        # Fresh single-process jax.distributed layout per (re)spawn —
        # the coordinator service dies with the worker, so a respawn
        # must not try to rebind the old generation's port.
        e = {
            "DEEPREC_COORDINATOR": f"127.0.0.1:{_free_port()}",
            "DEEPREC_NUM_PROCESSES": "1",
            "DEEPREC_PROCESS_ID": "0",
            **(env_extra or {}),
        }
        if heartbeat:
            e["DEEPREC_HEARTBEAT_FILE"] = heartbeat
        return e

    spec = ProcessSpec(
        name="worker",
        argv=[sys.executable, "-m", "deeprec_tpu.launch", script]
        + list(script_args),
        heartbeat_path=heartbeat,
        lease_secs=lease_secs if heartbeat else None,
        max_restarts=max_restarts,
        env=env,
    )
    sup = Supervisor([spec])
    sup.run()  # foreground; returns when done or budget exhausted
    st = sup.stats()["worker"]
    return 0 if st["done"] else 1


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def main(argv=None):
    p = argparse.ArgumentParser(
        description="deeprec_tpu multi-host launcher",
        usage="python -m deeprec_tpu.launch [flags] -- script.py [args...]",
    )
    p.add_argument("--coordinator", default=None, help="host:port of proc 0")
    p.add_argument("--num_processes", type=int, default=None)
    p.add_argument("--process_id", type=int, default=None)
    p.add_argument(
        "--elastic_dir", default=None,
        help="run as elastic SUPERVISOR: spawn --num_processes workers and "
        "respawn the set at the plan's target size on rescale exits",
    )
    p.add_argument(
        "--supervised", action="store_true",
        help="run ONE worker under liveness supervision: restart on crash "
        "or wedged heartbeat lease (see --heartbeat), capped-backoff "
        "restart budget, EXIT_RESCALE respawns free",
    )
    p.add_argument("--heartbeat", default=None,
                   help="heartbeat lease file for --supervised wedge "
                   "detection (exported as DEEPREC_HEARTBEAT_FILE)")
    p.add_argument("--lease_secs", type=float, default=30.0)
    p.add_argument("--max_restarts", type=int, default=5)
    p.add_argument("script", help="training script to run after init")
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)

    if args.elastic_dir:
        sys.exit(
            supervise_elastic(
                args.script, args.script_args,
                args.num_processes or 1, args.elastic_dir,
            )
        )
    if args.supervised:
        sys.exit(
            supervise_worker(
                args.script, args.script_args, heartbeat=args.heartbeat,
                lease_secs=args.lease_secs, max_restarts=args.max_restarts,
            )
        )

    initialize(args.coordinator, args.num_processes, args.process_id)

    import jax

    from deeprec_tpu.utils.backend import enable_compile_cache

    enable_compile_cache()

    print(
        f"deeprec_tpu.launch: process {jax.process_index()}/"
        f"{jax.process_count()} up, {len(jax.local_devices())} local / "
        f"{len(jax.devices())} global devices",
        flush=True,
    )
    sys.argv = [args.script] + list(args.script_args)
    runpy.run_path(args.script, run_name="__main__")


if __name__ == "__main__":
    main()
