"""Multi-process training launcher (``python -m paddlebox_tpu.launch``).

The ``paddle.distributed.launch`` analog (reference:
/root/reference/python/paddle/distributed/launch_utils.py — per-rank process
spawn, env injection, log files, failure watch-and-kill).  On TPU there is no
per-rank GPU list to carve up: each host process owns all of its local chips
and joins the job through the JAX coordination service, so the launcher's
whole job is (1) pick a coordinator address, (2) spawn N processes with
``PBOX_COORDINATOR_ADDRESS / PBOX_NUM_PROCESSES / PBOX_PROCESS_ID`` set —
which ``parallel.mesh.initialize_distributed()`` consumes — and (3) babysit
them: tee per-rank logs, kill the survivors when any rank dies, propagate
the first bad exit code.

One process per host is the deployment: a chip belongs to one process at a
time, and one process drives all of its host's chips (``make_mesh()`` over
``jax.devices()``); ranks on several hosts are started one per host with
the same three variables set.  This launcher starts all of its ranks on
THIS host, so ``--nproc > 1`` is the CPU simulation of such a job and
takes ``--devices-per-proc``:

    python -m paddlebox_tpu.launch --nproc 2 --devices-per-proc 4 train.py

``--devices-per-proc K`` puts each child on a K-device virtual CPU mesh
(sets XLA_FLAGS host-platform device count + JAX_PLATFORMS=cpu) — the
multi-host simulation the reference runs with localhost pservers
(test_dist_base.py:754-900).  Without it every local rank asks for the
host's chips: the first gets them and the others fail at backend init
(libtpu: "multi-process lockfile"), which ends the job non-zero.

Children keep their compiled programs in the persistent compile cache
(utils/compile_cache.py), handed down as ``JAX_COMPILATION_CACHE_DIR``.
"""

from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time
from typing import Optional


def find_free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def rank_env(
    rank: int,
    nproc: int,
    coordinator: str,
    devices_per_proc: Optional[int] = None,
    base_env: Optional[dict] = None,
    liveness_deadline_s: Optional[float] = None,
    metrics_port: Optional[int] = None,
    trace_dir: Optional[str] = None,
    publish_root: Optional[str] = None,
    stream_root: Optional[str] = None,
    max_staleness_s: Optional[float] = None,
    flight_dir: Optional[str] = None,
) -> dict:
    """Child environment for one rank (exported for tests/embedders)."""
    env = dict(base_env if base_env is not None else os.environ)
    env["PBOX_COORDINATOR_ADDRESS"] = coordinator
    env["PBOX_NUM_PROCESSES"] = str(nproc)
    env["PBOX_PROCESS_ID"] = str(rank)
    if liveness_deadline_s is not None:
        # every rank's watchdog (parallel/watchdog.py) reads this flag:
        # one launcher knob bounds every stage stall in the fleet
        env["PBOX_LIVENESS_DEADLINE_S"] = str(liveness_deadline_s)
    if metrics_port is not None and metrics_port > 0:
        # one Prometheus /metrics listener per rank: base port + rank
        # (rank N scrapes at :base+N), consumed by telemetry.ensure_exporter
        env["PBOX_METRICS_PORT"] = str(metrics_port + rank)
    if trace_dir is not None and trace_dir:
        # per-pass host span traces (Chrome trace JSON, Perfetto-viewable);
        # file names carry the rank, so one shared dir works for the fleet
        env["PBOX_TRACE_DIR"] = trace_dir
    if publish_root:
        # online model delivery (serving_sync): the training script's
        # Publisher ships base/delta model units here each pass — one
        # launcher knob points the whole fleet at the serving plane
        env["PBOX_PUBLISH_ROOT"] = publish_root
    if stream_root:
        # streaming online learning (streaming/): the training script's
        # StreamingTrainer tails this root for live records
        # (StreamingConfig.from_flags consumes it)
        env["PBOX_STREAM_ROOT"] = stream_root
    if max_staleness_s is not None:
        # the freshness budget the deadline publisher must honor
        env["PBOX_MAX_STALENESS_S"] = str(max_staleness_s)
    if flight_dir:
        # one shared postmortem dir: every rank's flight-recorder dumps
        # (stall/rollback/sigterm capture) land here, file names carry
        # rank+pid, and tools/pbox_doctor.py correlates them offline
        env["PBOX_FLIGHT_DIR"] = flight_dir
    if devices_per_proc:
        import re

        flags = env.get("XLA_FLAGS", "")
        want = f"--xla_force_host_platform_device_count={devices_per_proc}"
        pat = r"--xla_force_host_platform_device_count=\d+"
        if re.search(pat, flags):
            flags = re.sub(pat, want, flags)  # replace an inherited count
        else:
            flags = (flags + " " + want).strip()
        env["XLA_FLAGS"] = flags
        env["JAX_PLATFORMS"] = "cpu"
    else:  # ranks on the accelerator: worth keeping what they compile
        from paddlebox_tpu.utils.compile_cache import compile_cache_dir

        cache = compile_cache_dir()
        if cache:
            env.setdefault("JAX_COMPILATION_CACHE_DIR", cache)
    return env


def serve_fleet_argv(
    publish_root: str,
    replicas: int,
    router_port: int,
) -> list[str]:
    """Command line of the auxiliary serving fleet a training launch can
    co-run: N CPU-pinned replica scorers syncing from the job's publish
    root behind a health-checked router (serving_fleet/) — one launcher
    invocation runs the whole train→publish→serve loop."""
    return [
        sys.executable, "-m", "paddlebox_tpu.serve",
        "--sync-root", publish_root,
        "--replicas", str(replicas),
        "--router-port", str(router_port),
        "--cpu",  # serving must never contend for the training chips
    ]


def launch(
    script_args: list[str],
    nproc: int,
    coordinator: Optional[str] = None,
    devices_per_proc: Optional[int] = None,
    log_dir: Optional[str] = None,
    poll_interval: float = 0.2,
    liveness_deadline_s: Optional[float] = None,
    job_timeout_s: Optional[float] = None,
    metrics_port: Optional[int] = None,
    trace_dir: Optional[str] = None,
    publish_root: Optional[str] = None,
    serve_replicas: int = 0,
    serve_router_port: Optional[int] = None,
    stream_root: Optional[str] = None,
    max_staleness_s: Optional[float] = None,
    flight_dir: Optional[str] = None,
) -> int:
    """Spawn nproc ranks of ``python script_args...``; return the first
    non-zero exit code (0 if all ranks succeed).  Any rank dying kills the
    rest — a half-alive job would hang in the next collective forever
    (reference: watch_local_trainers + terminate_local_procs).

    liveness_deadline_s: forwarded to every rank as
    PBOX_LIVENESS_DEADLINE_S (the per-stage stall bound the in-process
    watchdogs enforce).  job_timeout_s: the launcher's own last-resort
    bound — if the whole fleet is still alive past it (e.g. every rank
    wedged before its watchdog started), SIGTERM everyone and return 124.
    """
    coordinator = coordinator or f"127.0.0.1:{find_free_port()}"
    procs: list[subprocess.Popen] = []
    logs = []
    start_t = time.monotonic()
    serve_proc: Optional[subprocess.Popen] = None
    if serve_replicas > 0:
        if not publish_root:
            raise ValueError(
                "--serve-replicas needs --publish-root: the fleet syncs "
                "its models from the job's publish root"
            )
        from paddlebox_tpu.config import flags as _flags

        argv = serve_fleet_argv(
            publish_root, serve_replicas,
            serve_router_port if serve_router_port is not None
            else _flags.router_port,
        )
        if log_dir:
            os.makedirs(log_dir, exist_ok=True)
            out = open(os.path.join(log_dir, "serve-fleet.log"), "wb")
            logs.append(out)
            serve_proc = subprocess.Popen(argv, stdout=out,
                                          stderr=subprocess.STDOUT)
        else:
            serve_proc = subprocess.Popen(argv)
    for rank in range(nproc):
        env = rank_env(
            rank, nproc, coordinator, devices_per_proc,
            liveness_deadline_s=liveness_deadline_s,
            metrics_port=metrics_port, trace_dir=trace_dir,
            publish_root=publish_root,
            stream_root=stream_root, max_staleness_s=max_staleness_s,
            flight_dir=flight_dir,
        )
        if log_dir:
            os.makedirs(log_dir, exist_ok=True)
            out = open(os.path.join(log_dir, f"rank{rank}.log"), "wb")
            logs.append(out)
            stdout, stderr = out, subprocess.STDOUT
        else:
            stdout = stderr = None  # inherit: interleaved console
        procs.append(
            subprocess.Popen(
                [sys.executable] + script_args,
                env=env, stdout=stdout, stderr=stderr,
            )
        )
    rc = 0
    try:
        live = set(range(nproc))
        while live:
            if (
                job_timeout_s is not None
                and time.monotonic() - start_t > job_timeout_s
                and rc == 0
            ):
                # fleet-level liveness backstop: nothing below us freed the
                # job, so the launcher does (124 = the timeout convention)
                rc = 124
                for r in live:
                    procs[r].send_signal(signal.SIGTERM)
            if serve_proc is not None and serve_proc.poll() is not None:
                # serving is auxiliary: its death must never kill the
                # training job — log once and train on
                print(
                    f"WARNING: auxiliary serving fleet exited rc="
                    f"{serve_proc.returncode}; training continues",
                    file=sys.stderr,
                )
                serve_proc = None
            for r in sorted(live):
                code = procs[r].poll()
                if code is None:
                    continue
                live.discard(r)
                if code != 0 and rc == 0:
                    rc = code
                    if nproc > 1 and not devices_per_proc:
                        print(
                            f"rank {r} exited {code}.  If it could not "
                            "initialize the TPU backend: a chip belongs "
                            "to one process, so --nproc > 1 on ONE host "
                            "needs --devices-per-proc (CPU simulation); "
                            "on a pod, run one rank per host.",
                            file=sys.stderr,
                        )
                    # first failure: kill the survivors
                    for other in live:
                        procs[other].send_signal(signal.SIGTERM)
            time.sleep(poll_interval)
    except KeyboardInterrupt:
        rc = 130
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
    finally:
        if serve_proc is not None and serve_proc.poll() is None:
            serve_proc.terminate()
            try:
                serve_proc.wait(timeout=10.0)
            except subprocess.TimeoutExpired:
                serve_proc.kill()
        deadline = time.monotonic() + 10.0
        for p in procs:
            if p.poll() is None:
                try:
                    p.wait(timeout=max(0.1, deadline - time.monotonic()))
                except subprocess.TimeoutExpired:
                    p.kill()
        for f in logs:
            f.close()
    return rc


def main(argv: Optional[list[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m paddlebox_tpu.launch",
        description="spawn an N-process distributed training job",
    )
    ap.add_argument("--nproc", type=int, required=True,
                    help="number of processes (one per host on a pod)")
    ap.add_argument("--coordinator", default=None,
                    help="host:port of rank 0 (default: free local port)")
    ap.add_argument("--devices-per-proc", type=int, default=None,
                    help="virtual CPU devices per process (test/dev tier)")
    ap.add_argument("--log-dir", default=None,
                    help="write per-rank logs here instead of the console")
    ap.add_argument("--liveness-deadline", type=float, default=None,
                    help="per-stage stall bound (s) for every rank's "
                         "watchdog (PBOX_LIVENESS_DEADLINE_S)")
    ap.add_argument("--job-timeout", type=float, default=None,
                    help="kill the whole fleet after this many seconds "
                         "(last-resort bound; exit code 124)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus /metrics on this base port, "
                         "offset per rank (rank N at base+N; "
                         "PBOX_METRICS_PORT)")
    ap.add_argument("--trace-dir", default=None,
                    help="write per-pass host span traces (Chrome trace "
                         "JSON, Perfetto-viewable) here (PBOX_TRACE_DIR)")
    ap.add_argument("--publish-root", default=None,
                    help="online model delivery publish root for the "
                         "fleet's serving_sync Publisher "
                         "(PBOX_PUBLISH_ROOT)")
    ap.add_argument("--serve-replicas", type=int, default=0,
                    help="co-run an auxiliary serving fleet: this many "
                         "CPU-pinned replica scorers syncing from "
                         "--publish-root behind a health-checked router "
                         "(serving_fleet/; PBOX_SERVE_REPLICAS)")
    ap.add_argument("--serve-router-port", type=int, default=None,
                    help="port of the co-run fleet's router "
                         "(default PBOX_ROUTER_PORT)")
    ap.add_argument("--stream-root", default=None,
                    help="streaming online learning: the tail-source "
                         "root the job's StreamingTrainer follows "
                         "(PBOX_STREAM_ROOT)")
    ap.add_argument("--max-staleness-s", type=float, default=None,
                    help="streaming freshness budget: publish_delta "
                         "fires on this deadline rather than pass "
                         "cadence (PBOX_MAX_STALENESS_S)")
    ap.add_argument("--flight-dir", default=None,
                    help="shared postmortem dir: every rank's "
                         "flight-recorder dumps land here for "
                         "tools/pbox_doctor.py (PBOX_FLIGHT_DIR)")
    ap.add_argument("script", help="training script to run")
    ap.add_argument("script_args", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    return launch(
        [args.script] + args.script_args,
        nproc=args.nproc,
        coordinator=args.coordinator,
        devices_per_proc=args.devices_per_proc,
        log_dir=args.log_dir,
        liveness_deadline_s=args.liveness_deadline,
        job_timeout_s=args.job_timeout,
        metrics_port=args.metrics_port,
        trace_dir=args.trace_dir,
        publish_root=args.publish_root,
        serve_replicas=args.serve_replicas,
        serve_router_port=args.serve_router_port,
        stream_root=args.stream_root,
        max_staleness_s=args.max_staleness_s,
        flight_dir=args.flight_dir,
    )


if __name__ == "__main__":
    sys.exit(main())
