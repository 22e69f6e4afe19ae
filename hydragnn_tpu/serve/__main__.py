"""CLI entry: ``python -m hydragnn_tpu.serve --config ... [--ckpt ...]``.

Loads a checkpoint (native or reference-torch), optionally warms the bucket
ladder, and serves /predict, /healthz, /metrics until interrupted.

``python -m hydragnn_tpu.serve router ...`` starts the multi-replica front
router instead (hydragnn_tpu/route/, docs/SERVING.md "Multi-replica tier").

``python -m hydragnn_tpu.serve batch ...`` runs offline batch inference over
a GSHD corpus — streams shards through the packed bucket ladder and writes
digest-verified prediction shards (serve/batch.py, docs/DATA_PLANE.md).
"""

from __future__ import annotations

import argparse
import sys

from ..cache.jaxcache import place_jax_cache
from .engine import InferenceEngine
from .server import InferenceServer


def parse_ladder(spec: str, max_rungs: int = 4):
    """--bucket-ladder "512x4096,1024x8192" → [(512, 4096), (1024, 8192)];
    --bucket-ladder auto:<path> loads a fitted ladder JSON or fits one from
    a size-histogram JSON now (graphs/packing.resolve_ladder_spec)."""
    from ..graphs.packing import resolve_ladder_spec

    return resolve_ladder_spec(spec, max_rungs=max_rungs)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m hydragnn_tpu.serve",
        description="Online inference server for HydraGNN checkpoints.",
    )
    ap.add_argument(
        "--config",
        required=True,
        help="COMPLETED config JSON (the logs/<name>/config.json snapshot)",
    )
    ap.add_argument(
        "--ckpt",
        default=None,
        help="checkpoint path (native .pk or reference torch .pk); default: "
        "the config-derived logs/<log_name>/<log_name>.pk",
    )
    ap.add_argument(
        "--ckpt-format",
        choices=("auto", "native", "torch"),
        default="auto",
    )
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--max-batch-graphs", type=int, default=32)
    ap.add_argument("--max-delay-ms", type=float, default=5.0)
    ap.add_argument("--queue-limit", type=int, default=256)
    ap.add_argument(
        "--bucket-ladder",
        default="",
        help='comma-separated "NxE" padded shapes, e.g. "512x4096,1024x8192", '
        'or "auto:<path>" where <path> is a size-histogram JSON '
        "(logs/<name>/size_histogram.json, SERVE_rNN_hist.json) or a "
        "fit-ladder output JSON; compiled at startup unless --no-warmup",
    )
    ap.add_argument(
        "--max-ladder-rungs",
        type=int,
        default=4,
        help="compile budget when --bucket-ladder auto: fits from a "
        "histogram (ignored for literal and pre-fitted ladders)",
    )
    ap.add_argument(
        "--packing",
        action="store_true",
        help="bin-pack each flushed micro-batch under the top ladder rung "
        "(first-fit-decreasing) so over-capacity flushes split into "
        "tightest-rung bins instead of falling back to a worst-case shape",
    )
    ap.add_argument(
        "--ladder-step",
        choices=("pow2", "mult64"),
        default="pow2",
        help="round-up ladder for shapes that miss the bucket ladder: "
        "mult64 pads a 520-node batch to 576 instead of 1024",
    )
    ap.add_argument(
        "--precision",
        choices=("f32", "bf16", "int8"),
        default="f32",
        help="serving arm (docs/PRECISION.md): f32 keeps the bit-exactness "
        "contract; bf16 runs the forward in bf16 compute; int8 additionally "
        "quantizes weight matrices to a per-tensor symmetric int8 grid. "
        "Quantized arms require --tolerance and pass a startup gate against "
        "an f32 reference before taking traffic",
    )
    ap.add_argument(
        "--tolerance",
        type=float,
        default=None,
        metavar="MAX_ABS_DIFF",
        help="max absolute output divergence from the f32 reference the "
        "quantized arm may show (required with --precision bf16|int8; "
        "invalid with f32)",
    )
    ap.add_argument("--no-warmup", action="store_true")
    ap.add_argument(
        "--compile-cache",
        default=None,
        metavar="DIR",
        help="persistent compiled-executable store (graftcache, docs/"
        "COMPILE_CACHE.md): warmup hydrates the ladder's executables "
        "from DIR instead of recompiling — a restarted replica is warm "
        "in seconds; fresh compiles are serialized back. Default: the "
        "HYDRAGNN_COMPILE_CACHE env var (unset = no persistence)",
    )
    ap.add_argument(
        "--max-worker-restarts",
        type=int,
        default=1,
        help="fatal worker errors tolerated by restarting the pipeline "
        "(degraded, keeps serving) before the engine poisons; 0 = poison "
        "on the first (docs/FAULT_TOLERANCE.md)",
    )
    ap.add_argument(
        "--no-output-guard",
        action="store_true",
        help="disable the non-finite output guard (NaN outputs then return "
        "as 200s instead of failing the request)",
    )
    ap.add_argument(
        "--replica-id",
        default=None,
        metavar="NAME",
        help="label this serve process as one replica of a routed fleet: "
        "echoed as the X-HydraGNN-Replica response header and in /healthz "
        "so the router's hop logs and health map name it (docs/SERVING.md "
        '"Multi-replica tier")',
    )
    ap.add_argument(
        "--admin",
        action="store_true",
        help="enable the POST /swap admin endpoint so a LifecycleManager "
        "can hot-swap this replica's weights from a (shared-storage) "
        "checkpoint path — fleet-wide swap orchestration for spawned HTTP "
        'replicas (docs/SERVING.md "Live model lifecycle")',
    )
    ap.add_argument("--verbose", action="store_true")
    return ap


def build_batch_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m hydragnn_tpu.serve batch",
        description="Offline batch inference over a GSHD streaming corpus.",
    )
    ap.add_argument("--config", required=True,
                    help="COMPLETED config JSON (logs/<name>/config.json)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-format", choices=("auto", "native", "torch"),
                    default="auto")
    ap.add_argument("--dataset", required=True,
                    help="GSHD dataset directory (or its manifest JSON)")
    ap.add_argument("--out", required=True,
                    help="output directory for prediction shards + manifest")
    ap.add_argument("--chunk-size", type=int, default=64,
                    help="graphs per predict() call (default 64)")
    ap.add_argument("--limit", type=int, default=None,
                    help="stop after N samples (spot-check a campaign)")
    ap.add_argument("--skip-budget", type=int, default=0,
                    help="corrupt input shards tolerated (skipped loudly)")
    ap.add_argument("--max-batch-graphs", type=int, default=32)
    ap.add_argument("--max-delay-ms", type=float, default=0.0,
                    help="micro-batch flush delay; 0 = flush greedily "
                    "(offline work has no latency SLO)")
    ap.add_argument("--queue-limit", type=int, default=256)
    ap.add_argument("--bucket-ladder", default="")
    ap.add_argument("--max-ladder-rungs", type=int, default=4)
    ap.add_argument("--packing", action="store_true")
    ap.add_argument("--ladder-step", choices=("pow2", "mult64"),
                    default="pow2")
    ap.add_argument("--compile-cache", default=None, metavar="DIR")
    ap.add_argument("--no-warmup", action="store_true")
    return ap


def batch_main(argv) -> int:
    args = build_batch_parser().parse_args(argv)
    from ..analysis.contracts import gate_config

    ladder = (
        parse_ladder(args.bucket_ladder, max_rungs=args.max_ladder_rungs)
        if args.bucket_ladder
        else None
    )
    gate_config(args.config, mode="serving", bucket_ladder=ladder)
    engine = InferenceEngine.from_config(
        args.config,
        checkpoint=args.ckpt,
        checkpoint_format=args.ckpt_format,
        max_batch_graphs=args.max_batch_graphs,
        max_delay_ms=args.max_delay_ms,
        queue_limit=args.queue_limit,
        bucket_ladder=ladder,
        warmup=not args.no_warmup,
        packing=args.packing,
        ladder_step=args.ladder_step,
        compile_cache=args.compile_cache,
    )
    from .batch import run_batch_inference

    try:
        manifest = run_batch_inference(
            engine,
            args.dataset,
            args.out,
            chunk_size=args.chunk_size,
            limit=args.limit,
            skip_budget=args.skip_budget,
        )
    finally:
        engine.close()
    gps = manifest["graphs_per_sec"]
    print(
        f"batch inference: {manifest['num_samples']} graphs in "
        f"{manifest['wall_s']:.2f}s "
        f"({gps:.1f} graphs/s)" if gps else "batch inference: 0 graphs",
        flush=True,
    )
    if manifest["skipped_shards"]:
        print(f"skipped corrupt shards: {len(manifest['skipped_shards'])}")
    return 0


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    place_jax_cache()
    if argv and argv[0] == "router":
        # The front-router subcommand (hydragnn_tpu/route/__main__.py):
        # one CLI surface for both the single engine and the fleet.
        from ..route.__main__ import main as router_main

        return router_main(argv[1:])
    if argv and argv[0] == "batch":
        return batch_main(argv[1:])
    args = build_parser().parse_args(argv)
    # Static contract gate (docs/STATIC_ANALYSIS.md): a broken completed
    # config or an infeasible/unparseable bucket ladder — including the
    # auto:<path> form — is one actionable line at startup, not a mid-warmup
    # stack trace after the checkpoint loaded. The spec is resolved ONCE,
    # with the CLI's rung budget, and the checker validates the rungs that
    # will actually deploy; only when resolution itself fails does the RAW
    # spec go to the checker, whose own resolution failure becomes the
    # actionable oob-bucket line.
    from ..analysis.contracts import gate_config

    ladder = None
    parse_error = None
    if args.bucket_ladder:
        try:
            ladder = parse_ladder(
                args.bucket_ladder, max_rungs=args.max_ladder_rungs
            )
        except Exception as e:  # noqa: BLE001 — checker diagnoses it below
            parse_error = e
    gate_config(
        args.config,
        mode="serving",
        bucket_ladder=ladder
        if ladder is not None
        else (args.bucket_ladder or None),
        serve_precision=args.precision,
        serve_tolerance=args.tolerance,
    )
    if parse_error is not None:
        # The gate normally turns a bad spec into one actionable oob-bucket
        # line — but it honors HYDRAGNN_CHECK_CONFIG=off. An explicit
        # operator flag must never be silently dropped, so if the gate let
        # the broken spec through, the original parse failure still aborts.
        raise parse_error
    # graftel (docs/OBSERVABILITY.md): point the flight recorder at the
    # run's log dir so an engine poisoning dumps its timeline next to the
    # checkpoint it served.
    import json as _json
    import os as _os

    from .. import telemetry
    from ..utils.config_utils import get_log_name_config

    try:
        with open(args.config) as f:
            _cfg = _json.load(f)
        telemetry.configure(
            run_dir=_os.path.join("./logs", get_log_name_config(_cfg))
        )
    except (OSError, ValueError, KeyError):
        pass  # from_config reports config problems with better messages
    engine = InferenceEngine.from_config(
        args.config,
        checkpoint=args.ckpt,
        checkpoint_format=args.ckpt_format,
        max_batch_graphs=args.max_batch_graphs,
        max_delay_ms=args.max_delay_ms,
        queue_limit=args.queue_limit,
        bucket_ladder=ladder,
        warmup=not args.no_warmup,
        packing=args.packing,
        ladder_step=args.ladder_step,
        max_worker_restarts=args.max_worker_restarts,
        guard_outputs=not args.no_output_guard,
        compile_cache=args.compile_cache,
        precision=args.precision,
        tolerance=args.tolerance,
    )
    if args.precision != "f32":
        # The quantized arm's startup gate (docs/PRECISION.md): compare the
        # serving executable against the retained f32 reference on a seeded
        # probe batch BEFORE taking traffic — a PrecisionToleranceError here
        # aborts startup with the full per-head verdict.
        report = engine.check_tolerance()
        print(
            f"precision gate: arm={args.precision} "
            f"max_abs_diff={report['fwd_err']:.3e} "
            f"tolerance={args.tolerance:g} ok={report['ok']}",
            flush=True,
        )
    server = InferenceServer(
        engine,
        host=args.host,
        port=args.port,
        verbose=args.verbose,
        replica_id=args.replica_id,
        enable_admin=args.admin,
    )
    print(
        f"hydragnn_tpu.serve listening on http://{server.host}:{server.port} "
        f"(buckets compiled: {engine.compiled_buckets})",
        flush=True,
    )
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
