"""One-command scoring server over self-contained artifacts.

    python -m paddlebox_tpu.serve --artifact /path/to/art [...more] \\
        [--port 8080] [--host 0.0.0.0] [--cpu]
    python -m paddlebox_tpu.serve --sync-root /publish/root \\
        [--sync-model live] [--sync-interval 10] [--cpu]
    python -m paddlebox_tpu.serve --artifact ART --replicas 3 \\
        [--router-port 8180] [--max-queue 64] [--request-deadline-ms 500]

Each --artifact may be DIR or NAME=DIR (NAME defaults to the directory
basename; the first one registered is the default model).  Artifacts must
carry their feed schema (export_model(feed_conf=...)); endpoints are
POST /score[/NAME], GET /healthz, GET /models (inference/server.py).

--sync-root attaches the online delivery plane (serving_sync/): the
server follows the publish root's donefile, hot-applies sparse deltas
into the live model between requests, and falls back to full reloads on
any verification failure — the trainer keeps it minutes-fresh with no
restart.  GET /models reports each model's version lineage (base tag,
applied delta count, publish time) and freshness age.

--replicas N switches to FLEET mode (serving_fleet/): a
ReplicaSupervisor spawns N single-server replica processes of this same
command (each with its own Syncer when --sync-root is given, its own
admission queue always; with --cpu on a TPU host, whose chips one process
must own — a replica that cannot get the TPU exits non-zero and says so) and a FleetRouter front door on --router-port
spreads /score traffic over them with health-checked membership,
per-request failover and crash restarts — a killed replica is never
client-visible.  Router endpoints: POST /score[/NAME], GET /healthz
(fleet summary), GET /fleet (per-replica state + freshness), GET
/metrics.  --autoscale adds the FleetAutoscaler: replicas spawn under
sustained pressure and drain-retire when idle, clamped to
PBOX_AUTOSCALE_MIN_REPLICAS / PBOX_AUTOSCALE_MAX_REPLICAS (--replicas
is the floor).

Admission control (--max-queue / --request-deadline-ms, env
PBOX_SERVE_MAX_QUEUE / PBOX_REQUEST_DEADLINE_MS) bounds every replica's
queue: past the cap, or once the estimated wait exceeds the request
deadline, the server sheds with 429 + Retry-After instead of queuing
into saturation.

The reference's serving story is the C++ AnalysisPredictor stack plus
demo servers (/root/reference/paddle/fluid/inference/); this is the
whole of it as one module over the StableHLO artifact.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys


def _build_parser() -> argparse.ArgumentParser:
    from paddlebox_tpu.config import flags

    ap = argparse.ArgumentParser(
        prog="python -m paddlebox_tpu.serve", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--artifact", action="append", default=[],
                    metavar="[NAME=]DIR",
                    help="artifact directory (repeatable); first = default")
    ap.add_argument("--port", type=int, default=8080)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--cpu", action="store_true",
                    help="run on the CPU backend instead of the "
                         "accelerator JAX finds (required for --replicas "
                         "> 1 on one TPU host: a chip has one owner)")
    ap.add_argument("--sync-root", default=None,
                    help="publish root to keep a model synced from "
                         "(serving_sync delivery plane)")
    ap.add_argument("--sync-model", default="live",
                    help="model name the synced root serves under "
                         "(default: live)")
    ap.add_argument("--sync-interval", type=float, default=None,
                    help="donefile poll interval seconds "
                         "(default: PBOX_SYNC_INTERVAL_S)")
    ap.add_argument("--sync-cache", default=None,
                    help="local cache dir for fetched model units")
    ap.add_argument("--sync-timeout", type=float, default=300.0,
                    help="max seconds to wait for the first synced model "
                         "at startup")
    # -- fleet mode + admission control (serving_fleet/) -------------------- #
    ap.add_argument("--replicas", type=int, default=flags.serve_replicas,
                    help="fleet mode: spawn this many replica server "
                         "processes behind a health-checked router "
                         "(PBOX_SERVE_REPLICAS; 0 = single server)")
    ap.add_argument("--router-port", type=int, default=flags.router_port,
                    help="port the fleet router front door binds "
                         "(PBOX_ROUTER_PORT; fleet mode only)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="admission queue bound per server: requests "
                         "beyond it shed with 429 "
                         "(default PBOX_SERVE_MAX_QUEUE)")
    ap.add_argument("--request-deadline-ms", type=float, default=None,
                    help="default per-request deadline: arrivals whose "
                         "estimated queue wait exceeds it shed with 429 "
                         "+ Retry-After (clients override via the "
                         "X-Request-Deadline-Ms header; default "
                         "PBOX_REQUEST_DEADLINE_MS, 0 = no deadline)")
    ap.add_argument("--max-batch", type=int, default=None,
                    help="continuous micro-batching width: up to this "
                         "many queued /score requests coalesce into one "
                         "device call (default PBOX_SERVE_MAX_BATCH; 1 = "
                         "one-at-a-time)")
    ap.add_argument("--batch-linger-ms", type=float, default=None,
                    help="max wait for a forming micro-batch to fill "
                         "(default PBOX_SERVE_BATCH_LINGER_MS; an idle "
                         "queue never waits)")
    ap.add_argument("--serving-policy", action="append", default=[],
                    metavar="NAME:k=v[,k=v...]",
                    help="per-scenario serving policy (repeatable): "
                         "NAME[:deadline_ms=..][,batch_linger_ms=..]"
                         "[,embedding_dtype=fp32|int8|fp8]"
                         "[,max_staleness_s=..] — overrides the server "
                         "defaults for POST /score/NAME and "
                         "/retrieve/NAME")
    ap.add_argument("--log-dir", default=None,
                    help="fleet mode: write per-replica logs here")
    ap.add_argument("--autoscale", action="store_true",
                    help="fleet mode: run the FleetAutoscaler — grow/"
                         "drain-retire replicas off the fleet's own "
                         "telemetry, clamped to the "
                         "PBOX_AUTOSCALE_MIN_REPLICAS / "
                         "PBOX_AUTOSCALE_MAX_REPLICAS band")
    return ap


def _parse_serving_policy(spec: str):
    """``NAME:k=v,k=v`` -> ScenarioServingConfig.  Numeric keys take
    floats; embedding_dtype is passed through for the config's own
    validation to reject."""
    from paddlebox_tpu.config import ScenarioServingConfig

    name, _, rest = spec.partition(":")
    name = name.strip()
    if not name:
        raise ValueError(f"--serving-policy {spec!r}: empty scenario name")
    kw = {}
    for part in filter(None, (p.strip() for p in rest.split(","))):
        key, sep, val = part.partition("=")
        key = key.strip()
        if not sep:
            raise ValueError(
                f"--serving-policy {spec!r}: expected k=v, got {part!r}")
        if key in ("deadline_ms", "batch_linger_ms", "max_staleness_s"):
            kw[key] = float(val)
        elif key == "embedding_dtype":
            kw[key] = val.strip()
        else:
            raise ValueError(
                f"--serving-policy {spec!r}: unknown key {key!r}")
    return ScenarioServingConfig(name=name, **kw)


def _replica_argv(args, replica_id: int, port: int) -> list:
    """The single-server command line one fleet replica runs: this same
    module minus the fleet flags, plus its assigned port.  --replicas 0
    is explicit because the flag's DEFAULT follows PBOX_SERVE_REPLICAS
    and the children inherit the parent environment: without it, a fleet
    started via the env var would make every replica re-enter fleet mode
    and recursively spawn its own supervisor+router."""
    argv = [sys.executable, "-m", "paddlebox_tpu.serve",
            "--replicas", "0",
            "--port", str(port), "--host", args.host]
    for spec in args.artifact:
        argv += ["--artifact", spec]
    if args.cpu:
        argv += ["--cpu"]
    if args.sync_root:
        argv += ["--sync-root", args.sync_root,
                 "--sync-model", args.sync_model,
                 "--sync-timeout", str(args.sync_timeout)]
        if args.sync_interval is not None:
            argv += ["--sync-interval", str(args.sync_interval)]
        if args.sync_cache:
            # one Syncer per replica: the fetch caches must not collide
            argv += ["--sync-cache", f"{args.sync_cache}-r{replica_id}"]
    if args.max_queue is not None:
        argv += ["--max-queue", str(args.max_queue)]
    if args.request_deadline_ms is not None:
        argv += ["--request-deadline-ms", str(args.request_deadline_ms)]
    if args.max_batch is not None:
        argv += ["--max-batch", str(args.max_batch)]
    if args.batch_linger_ms is not None:
        argv += ["--batch-linger-ms", str(args.batch_linger_ms)]
    for spec in args.serving_policy:
        argv += ["--serving-policy", spec]
    return argv


def _main_fleet(args) -> None:
    from paddlebox_tpu import telemetry
    from paddlebox_tpu.serving_fleet import FleetRouter, ReplicaSupervisor

    # the router process's flight dumps read as "router" in pbox_doctor
    # timelines; SIGTERM (pod teardown) dumps the ring on the way out
    telemetry.set_process_name("router")
    telemetry.install_signal_dump()
    supervisor = ReplicaSupervisor(
        args.replicas,
        lambda rid, port: _replica_argv(args, rid, port),
        host=args.host if args.host != "0.0.0.0" else "127.0.0.1",
        log_dir=args.log_dir,
    )
    supervisor.start()
    router = FleetRouter(supervisor.endpoints())
    port = router.start(port=args.router_port, host=args.host)
    autoscaler = None
    if args.autoscale:
        from paddlebox_tpu.serving_fleet import (
            AutoscalerConfig, FleetAutoscaler,
        )

        conf = AutoscalerConfig.from_flags()
        # the operator-chosen --replicas is the floor: autoscaling may
        # only ever ADD capacity beyond what was explicitly requested
        conf = dataclasses.replace(
            conf, min_replicas=max(conf.min_replicas, args.replicas),
            max_replicas=max(conf.max_replicas, args.replicas),
        )
        autoscaler = FleetAutoscaler(supervisor, router, conf)
        autoscaler.start()
    print(f"fleet router on http://{args.host}:{port}/score "
          f"({args.replicas} replicas: "
          f"{', '.join(supervisor.endpoints())}"
          f"{', autoscaling' if autoscaler else ''})", flush=True)
    try:
        router.wait()
    except KeyboardInterrupt:
        pass
    finally:
        if autoscaler is not None:
            autoscaler.stop()
        router.stop()
        supervisor.stop()


def main(argv=None) -> None:
    ap = _build_parser()
    args = ap.parse_args(argv)
    if not args.artifact and not args.sync_root:
        ap.error("pass at least one --artifact or a --sync-root")
    if args.replicas and args.replicas > 0:
        # fleet mode needs no device in THIS process: the router is pure
        # host I/O; the replicas it spawns load the artifacts
        if (args.replicas > 1 or args.autoscale) and not args.cpu:
            from paddlebox_tpu.utils.backend import host_tpu_chips

            if host_tpu_chips():
                ap.error(
                    "several replica processes on a TPU host need --cpu: a "
                    "chip belongs to one process at a time, so the second "
                    "replica could not get one.  One server process "
                    "(--replicas 0) serves from all local chips."
                )
        _main_fleet(args)
        return

    from paddlebox_tpu.utils.backend import claim_devices, setup_backend

    setup_backend(cpu=args.cpu)
    claim_devices()

    from paddlebox_tpu import telemetry
    from paddlebox_tpu.inference import ScoringServer

    # a single server IS one fleet replica when spawned by the
    # supervisor: label its dumps and capture the ring on SIGTERM (the
    # supervisor's stop() delivers exactly that)
    telemetry.set_process_name("replica")
    telemetry.install_signal_dump()

    server = ScoringServer(
        max_queue=args.max_queue,
        request_deadline_ms=args.request_deadline_ms,
        max_batch=args.max_batch,
        batch_linger_ms=args.batch_linger_ms,
    )
    for spec in args.serving_policy:
        try:
            policy = _parse_serving_policy(spec)
        except ValueError as exc:
            ap.error(str(exc))
        server.set_serving_policy(policy.name, policy)
        print(f"serving policy {policy.name!r}: {policy.to_dict()}")
    for spec in args.artifact:
        name, sep, path = spec.partition("=")
        if not sep:
            name, path = os.path.basename(os.path.normpath(spec)), spec
        if name in server.model_names():
            ap.error(
                f"model name {name!r} given twice (basenames collide?) — "
                "disambiguate with NAME=DIR"
            )
        server.register(name, path)
        print(f"registered {name!r} <- {path}")

    syncer = None
    if args.sync_root:
        from paddlebox_tpu.serving_sync import Syncer

        syncer = Syncer(
            args.sync_root, server, args.sync_model,
            cache_dir=args.sync_cache,
            poll_interval_s=args.sync_interval,
        )
        print(f"syncing {args.sync_model!r} <- {args.sync_root}")
        if not args.artifact:
            # the HTTP server refuses to start with zero models: block
            # until the publish root delivers the first one
            if not syncer.wait_fresh(timeout_s=args.sync_timeout):
                ap.error(
                    f"no model appeared under {args.sync_root} within "
                    f"{args.sync_timeout:.0f}s"
                )
        else:
            syncer.poll_once()
        syncer.start()

    port = server.start(port=args.port, host=args.host)
    print(f"serving on http://{args.host}:{port}/score "
          f"(models: {', '.join(server.model_names())})", flush=True)
    try:
        server.wait()
    except KeyboardInterrupt:
        if syncer is not None:
            syncer.stop()
        server.stop()


if __name__ == "__main__":
    main()
