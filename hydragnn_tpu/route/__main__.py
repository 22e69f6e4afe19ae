"""CLI entry: ``python -m hydragnn_tpu.serve router ...`` (also
``python -m hydragnn_tpu.route``).

Builds a router over N replicas and serves the fleet /predict, /healthz,
/metrics until interrupted. Three replica modes, mixable:

* ``--replicas N`` — N in-process engines built from ``--config``/
  ``--ckpt`` (one process, one shared graftcache store: the single-host
  multi-engine topology);
* ``--replica-url URL`` (repeatable) — attach running
  ``python -m hydragnn_tpu.serve`` processes over HTTP;
* ``--spawn N`` — spawn N serve subprocesses on ephemeral ports (each
  pointed at the shared ``--compile-cache`` store so spin-up hydrates).

Config validation rides the same ``gate_config`` path as every other entry
point — router findings (replica weights, admission-class deadlines,
replica-count-vs-ladder-memory) are ``bad-router`` lines BEFORE any
checkpoint loads (docs/STATIC_ANALYSIS.md).
"""

from __future__ import annotations

import argparse
import sys
from typing import List, Optional

from ..cache.jaxcache import place_jax_cache


def parse_classes(spec: str) -> Optional[dict]:
    """``--classes "fast=2.0,ensemble=15.0"`` -> {name: {deadline_s}}."""
    if not spec:
        return None
    out = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                f'--classes entries are "name=deadline_s", got {part!r}'
            )
        name, deadline = part.split("=", 1)
        out[name.strip()] = {"deadline_s": float(deadline)}
    return out or None


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m hydragnn_tpu.serve router",
        description="Multi-replica front router for HydraGNN serving.",
    )
    ap.add_argument("--config", required=True, help="COMPLETED config JSON")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument(
        "--ckpt-format", choices=("auto", "native", "torch"), default="auto"
    )
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8100)
    ap.add_argument(
        "--replicas",
        type=int,
        default=0,
        help="in-process engine replicas to build from --config",
    )
    ap.add_argument(
        "--replica-url",
        action="append",
        default=[],
        metavar="URL",
        help="attach a running serve process (repeatable)",
    )
    ap.add_argument(
        "--spawn",
        type=int,
        default=0,
        help="serve subprocesses to spawn on ephemeral ports",
    )
    ap.add_argument(
        "--classes",
        default="",
        help='admission classes as "name=deadline_s,..." '
        '(default: fast=2.0,ensemble=15.0)',
    )
    ap.add_argument("--load-factor", type=float, default=1.25)
    ap.add_argument("--vnodes", type=int, default=64)
    ap.add_argument("--health-interval", type=float, default=0.5)
    ap.add_argument("--max-hops", type=int, default=3)
    ap.add_argument("--bucket-ladder", default="")
    ap.add_argument("--max-ladder-rungs", type=int, default=4)
    ap.add_argument("--packing", action="store_true")
    ap.add_argument("--max-batch-graphs", type=int, default=32)
    ap.add_argument("--max-delay-ms", type=float, default=5.0)
    ap.add_argument("--queue-limit", type=int, default=256)
    ap.add_argument(
        "--compile-cache",
        default=None,
        metavar="DIR",
        help="SHARED graftcache store for every replica (warm spin-up "
        "hydrates the whole ladder from here — docs/COMPILE_CACHE.md)",
    )
    ap.add_argument("--verbose", action="store_true")
    return ap


def _build_replicas(args, ladder, replicas, procs) -> None:
    """Build the fleet in the caller-provided lists (so a mid-build failure
    leaves the already-spawned subprocesses visible for cleanup)."""
    from ..serve.engine import InferenceEngine
    from . import HttpReplica, InProcessReplica
    from .replica import spawn_serve_replica

    for i in range(args.replicas):
        engine = InferenceEngine.from_config(
            args.config,
            checkpoint=args.ckpt,
            checkpoint_format=args.ckpt_format,
            max_batch_graphs=args.max_batch_graphs,
            max_delay_ms=args.max_delay_ms,
            queue_limit=args.queue_limit,
            bucket_ladder=ladder,
            warmup=ladder is not None,
            packing=args.packing,
            compile_cache=args.compile_cache,
        )
        replicas.append(InProcessReplica(f"local-{i}", engine))
    for i, url in enumerate(args.replica_url):
        replicas.append(HttpReplica(f"http-{i}", url))
    for i in range(args.spawn):
        # Forward the full engine shape: a fleet must be HOMOGENEOUS —
        # spawned replicas that batched/shed/packed differently from the
        # in-process ones would break the matched-buckets contract.
        serve_args = [
            "--config", args.config, "--port", "0",
            "--replica-id", f"spawn-{i}",
            "--ckpt-format", args.ckpt_format,
            "--max-batch-graphs", str(args.max_batch_graphs),
            "--max-delay-ms", str(args.max_delay_ms),
            "--queue-limit", str(args.queue_limit),
        ]
        if args.ckpt:
            serve_args += ["--ckpt", args.ckpt]
        if args.bucket_ladder:
            serve_args += ["--bucket-ladder", args.bucket_ladder]
        if args.packing:
            serve_args += ["--packing"]
        if args.compile_cache:
            serve_args += ["--compile-cache", args.compile_cache]
        replica, proc = spawn_serve_replica(f"spawn-{i}", serve_args)
        replicas.append(replica)
        procs.append(proc)


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    place_jax_cache()
    n_replicas = args.replicas + len(args.replica_url) + args.spawn
    if n_replicas < 1:
        print(
            "router needs at least one replica "
            "(--replicas / --replica-url / --spawn)",
            file=sys.stderr,
        )
        return 2
    if args.spawn:
        from ..parallel.distributed import tpu_expected

        if tpu_expected():
            # Checked before anything here touches the backend. A serve child
            # claims every chip of the host, so a second child (or a child of
            # a router that built --replicas first) finds them taken.
            print(
                "router --spawn cannot place its children on a TPU host: "
                "each spawned serve process claims every chip. Start each "
                "`python -m hydragnn_tpu.serve` yourself with its one chip "
                "made visible before JAX starts (TPU_VISIBLE_CHIPS=<n>) and "
                "attach it with --replica-url.",
                file=sys.stderr,
            )
            return 2

    from ..analysis.contracts import gate_config
    from ..graphs.packing import resolve_ladder_spec

    ladder = None
    parse_error = None
    if args.bucket_ladder:
        try:
            ladder = resolve_ladder_spec(
                args.bucket_ladder, max_rungs=args.max_ladder_rungs
            )
        except Exception as e:  # noqa: BLE001 — checker diagnoses it below
            parse_error = e
    classes = parse_classes(args.classes)
    gate_config(
        args.config,
        mode="serving",
        bucket_ladder=ladder
        if ladder is not None
        else (args.bucket_ladder or None),
        router={
            "replicas": n_replicas,
            "classes": classes,
            "load_factor": args.load_factor,
            "vnodes": args.vnodes,
        },
    )
    if parse_error is not None:
        raise parse_error

    from . import Router, RouterServer

    replicas: List = []
    procs = []
    try:
        _build_replicas(args, ladder, replicas, procs)
    except BaseException:
        # A failed spawn/build must not orphan the already-spawned serve
        # subprocesses (they outlive this process; in-process engines die
        # with it).
        for proc in procs:
            proc.terminate()
        raise

    router = Router(
        replicas,
        classes=classes,
        load_factor=args.load_factor,
        vnodes=args.vnodes,
        health_interval_s=args.health_interval,
        max_hops=args.max_hops,
        expected_rungs=len(ladder) if ladder else 0,
    )
    server = RouterServer(
        router, host=args.host, port=args.port, verbose=args.verbose
    )
    print(
        f"hydragnn_tpu.route listening on http://{server.host}:{server.port} "
        f"(replicas: {', '.join(r.name for r in replicas)})",
        flush=True,
    )
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.shutdown()
        router.close(close_replicas=True)
        for proc in procs:
            proc.terminate()
    return 0


if __name__ == "__main__":
    sys.exit(main())
