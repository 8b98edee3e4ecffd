"""Distributed launcher — `python -m paddle_tpu.distributed.launch`.

Reference parity: python/paddle/distributed/launch.py (:188
`launch_collective` — spawns one process per device with
`PADDLE_TRAINER_ID`/`PADDLE_TRAINER_ENDPOINTS`/`PADDLE_CURRENT_ENDPOINT` env,
watches children and aborts all on failure, launch_utils.py TrainerProc) and
the `fleetrun` CLI.

TPU-native design: the process unit is one per **host**, not one per device
(SURVEY.md §2.3 NCCL row: multi-host bootstrap is jax.distributed's
coordination service, device-level parallelism is in-process SPMD over the
mesh).  The launcher therefore:
  * computes the host list (``--hosts`` or localhost xN for simulation),
  * exports PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM /
    PADDLE_TRAINER_ENDPOINTS / PADDLE_COORDINATOR (consumed by ParallelEnv /
    init_parallel_env — the reference's exact env-var role-maker contract,
    role_maker.py:220),
  * spawns and babysits the children: first failure kills the rest (the
    reference's watch loop), exit codes propagate.
Multi-process-per-localhost remains supported for CPU simulation tests
(the reference's own distributed tests run 2 trainers on 127.0.0.1).  On a
TPU host it is refused: a chip belongs to one process, every local worker
inherits the same environment and would try to take every chip, and the
launcher itself never touches JAX for the same reason.
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time
import uuid
from typing import List, Optional

__all__ = ["launch", "main"]


def _free_port() -> int:
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _workers_take_tpu(env) -> bool:
    """Whether a worker started with ``env`` would initialise the TPU
    backend.  ``JAX_PLATFORMS`` without "tpu" answers it outright (every
    CPU simulation); otherwise a throw-away child is asked, so the
    launcher never holds the chip its workers need.  A probe that fails to
    start JAX at all answers False — the workers then fail with JAX's own
    message."""
    platforms = env.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return False
    probe = subprocess.run(
        [sys.executable, "-c", "import jax; print(jax.default_backend())"],
        env=env, capture_output=True, text=True, timeout=300)
    return probe.returncode == 0 and probe.stdout.strip().endswith("tpu")


def launch(training_script: str, script_args: List[str],
           nproc: int = 1, started_port: Optional[int] = None,
           log_dir: Optional[str] = None, backend_env: str = "",
           trace_dir: Optional[str] = None, max_restarts: int = 0,
           elastic_dir: Optional[str] = None,
           telemetry_port: Optional[int] = None,
           ledger_dir: Optional[str] = None,
           history_dir: Optional[str] = None) -> int:
    """Spawn `nproc` worker processes with the trainer-env contract.
    Returns the first nonzero exit code, or 0.

    Every job mints one trace_id (PDTPU_TRACE_ID) that all ranks share, so
    spans across workers and PS RPCs correlate into a single distributed
    trace (utils/trace.py).  With `trace_dir`, workers additionally get
    PDTPU_TRACE_DIR: each rank atexit-dumps a chrome trace
    (trace.rank<r>.json, mergeable via `python -m tools.tracecat`) and arms
    a flight-recorder post-mortem (flight.rank<r>.json) on crash/SIGTERM —
    a dead rank leaves more than an exit code; the launcher prints that
    dump's path when a rank dies.

    Elastic relaunch: with ``max_restarts > 0`` a crashed rank is respawned
    in place (same rank env, PDTPU_RESTART_COUNT incremented) up to
    ``max_restarts`` total restarts across the job before the default
    abort-everyone behavior kicks in — the ref fleet elastic relaunch loop.
    ``elastic_dir`` is exported as PDTPU_ELASTIC_DIR so workers can join
    the elastic membership (elastic/membership.py ``ElasticMember.from_env``)
    and evict ranks the launcher gave up on.

    Telemetry: with ``telemetry_port`` each rank gets
    PDTPU_TELEMETRY_PORT = telemetry_port + rank, and the ``paddle_tpu``
    import bootstrap starts that rank's HTTP telemetry plane on it
    (utils/telemetry.py) — deterministic ports, so an operator scrapes
    ``/metrics`` and ``/healthz`` of every rank of a live job without any
    discovery step.  A restarted rank reuses its port (same rank env).

    Calibration ledger: ``ledger_dir`` is exported as PDTPU_LEDGER_DIR so
    every rank appends its measured-vs-predicted records to
    ``ledger.rank<r>.jsonl`` in one shared directory (utils/ledger.py) —
    the durable twin of the ``/ledger`` endpoint ``tools/fleetview``
    scrapes live.

    Metrics history: ``history_dir`` is exported as PDTPU_HISTORY_DIR so
    every rank's SLO-engine sampler mirrors its history ticks to
    ``history.rank<r>.jsonl`` (utils/slo.py) — the durable twin of the
    ``/history`` endpoint."""
    base_port = started_port or _free_port()
    endpoints = ",".join(f"127.0.0.1:{base_port + i}" for i in range(nproc))
    job_trace_id = uuid.uuid4().hex
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
    if elastic_dir:
        os.makedirs(elastic_dir, exist_ok=True)
    if ledger_dir:
        os.makedirs(ledger_dir, exist_ok=True)
    if history_dir:
        os.makedirs(history_dir, exist_ok=True)
    procs: List[subprocess.Popen] = []
    logs = []
    exit_code = 0
    restart_counts = {rank: 0 for rank in range(nproc)}

    def _rank_env(rank: int) -> dict:
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(nproc),
            "PADDLE_TRAINER_ENDPOINTS": endpoints,
            "PADDLE_CURRENT_ENDPOINT": f"127.0.0.1:{base_port + rank}",
            "PADDLE_COORDINATOR": f"127.0.0.1:{base_port}",
            "PDTPU_TRACE_ID": job_trace_id,
            "PDTPU_RESTART_COUNT": str(restart_counts[rank]),
        })
        if trace_dir:
            env["PDTPU_TRACE_DIR"] = trace_dir
        if elastic_dir:
            env["PDTPU_ELASTIC_DIR"] = elastic_dir
        if telemetry_port:
            env["PDTPU_TELEMETRY_PORT"] = str(int(telemetry_port) + rank)
        if ledger_dir:
            env["PDTPU_LEDGER_DIR"] = ledger_dir
        if history_dir:
            env["PDTPU_HISTORY_DIR"] = history_dir
        for kv in backend_env.split(","):
            if "=" in kv:
                k, v = kv.split("=", 1)
                env[k] = v
        return env

    if nproc > 1 and _workers_take_tpu(_rank_env(0)):
        raise RuntimeError(
            f"launch: nproc={nproc} on a TPU host would start {nproc} "
            "processes that each try to take every local chip, and a chip "
            "belongs to one process.  The process unit is one per host "
            "(in-process SPMD over the mesh drives all local chips): use "
            "nproc=1 here, or --backend_env JAX_PLATFORMS=cpu for a CPU "
            "simulation.")

    def _spawn(rank: int) -> subprocess.Popen:
        env = _rank_env(rank)
        cmd = [sys.executable, "-u", training_script] + list(script_args)
        if log_dir:
            # append so a restarted rank's output lands after its crash log
            out = open(os.path.join(log_dir, f"worker.{rank}.log"), "a")
            logs.append(out)
            p = subprocess.Popen(cmd, env=env, stdout=out,
                                 stderr=subprocess.STDOUT)
        else:
            p = subprocess.Popen(cmd, env=env)
        procs.append(p)
        return p

    def _report_death(rank: int, rc: int) -> None:
        msg = f"[launch] worker rank {rank} exited with code {rc}"
        if trace_dir:
            msg += (" — flight dump: "
                    + os.path.join(trace_dir, f"flight.rank{rank}.json"))
        print(msg, file=sys.stderr)

    # spawn AND watch under one try/finally: a failure while spawning rank k
    # must not orphan ranks 0..k-1 or leak log handles
    try:
        watching = {rank: _spawn(rank) for rank in range(nproc)}
        restarts_left = max(0, int(max_restarts))

        # watch loop (ref launch_utils.py: abort everyone on first failure;
        # with a restart budget, respawn the dead rank in place first)
        while watching:
            failed = None
            for rank, p in list(watching.items()):
                rc = p.poll()
                if rc is None:
                    continue
                if rc == 0:
                    del watching[rank]
                    continue
                _report_death(rank, rc)
                if restarts_left > 0:
                    restarts_left -= 1
                    restart_counts[rank] += 1
                    print(f"[launch] restarting rank {rank} "
                          f"(restart {restart_counts[rank]}, "
                          f"{restarts_left} left)", file=sys.stderr)
                    watching[rank] = _spawn(rank)
                else:
                    failed = rc
                    break
            if failed is not None:
                exit_code = failed
                alive = [q for q in watching.values() if q.poll() is None]
                for q in alive:
                    q.send_signal(signal.SIGTERM)
                for q in alive:
                    try:  # escalate to SIGKILL if SIGTERM is ignored
                        q.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        q.kill()
                        q.wait()
                watching = {}
            if watching:
                time.sleep(0.2)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for f in logs:
            f.close()
    return exit_code


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="Launch one training process per host "
                    "(ref: paddle.distributed.launch / fleetrun)")
    parser.add_argument("--nproc_per_node", "--nprocs", type=int, default=1,
                        dest="nproc", help="worker processes to spawn "
                        "(localhost simulation; production = 1 per host)")
    parser.add_argument("--started_port", type=int, default=None)
    parser.add_argument("--log_dir", type=str, default=None)
    parser.add_argument("--backend_env", type=str, default="",
                        help="extra env as k=v,k=v passed to workers")
    parser.add_argument("--trace_dir", type=str, default=None,
                        help="directory for per-rank chrome traces + "
                        "flight-recorder post-mortems (merge with "
                        "`python -m tools.tracecat`)")
    parser.add_argument("--max-restarts", "--max_restarts", type=int,
                        default=0, dest="max_restarts",
                        help="elastic relaunch budget: respawn a crashed "
                        "rank in place up to this many times before "
                        "aborting the job (default 0 = classic "
                        "fail-fast)")
    parser.add_argument("--elastic_dir", type=str, default=None,
                        help="shared membership/heartbeat directory "
                        "exported to workers as PDTPU_ELASTIC_DIR "
                        "(elastic/membership.py)")
    parser.add_argument("--telemetry_port", type=int, default=None,
                        help="base port for the per-rank HTTP telemetry "
                        "plane: rank r serves /metrics, /healthz, /flight, "
                        "/xprof, /spans, /ledger, /history, /alerts on "
                        "telemetry_port + r (utils/telemetry.py)")
    parser.add_argument("--ledger_dir", type=str, default=None,
                        help="shared directory for per-rank calibration "
                        "ledger JSONL sinks, exported to workers as "
                        "PDTPU_LEDGER_DIR (utils/ledger.py)")
    parser.add_argument("--history_dir", type=str, default=None,
                        help="shared directory for per-rank metrics-history "
                        "JSONL mirrors, exported to workers as "
                        "PDTPU_HISTORY_DIR (utils/slo.py)")
    parser.add_argument("training_script")
    parser.add_argument("script_args", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)
    return launch(args.training_script, args.script_args, args.nproc,
                  args.started_port, args.log_dir, args.backend_env,
                  args.trace_dir, args.max_restarts, args.elastic_dir,
                  args.telemetry_port, args.ledger_dir,
                  args.history_dir)


if __name__ == "__main__":
    sys.exit(main())
