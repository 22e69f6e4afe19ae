"""Feed-vs-train profile of the PRODUCTION bucketed path.

Answers "is the pipeline input-bound at batch 256?" with two independent
measurements over ci_multihead.json + bucketed GraphDataLoader + the
TrainingDriver scan epochs (the same plumbing bench.py's production workload
times):

1. ablation: steady-epoch wall time with the REAL loader vs with the same
   batches pre-materialized in memory (zero feed cost). The difference is the
   true feed overhead — robust under async dispatch, where span timings lie.
2. spans: one epoch through the per-step path, summing the program's own
   graftel spans: "feed_wait" (consumer blocked on the device queue) vs
   "device_step" (dispatch + readback) vs "h2d" (transfer thread) — the
   same spans a real jax.profiler trace shows as host events.

Optionally captures a jax.profiler trace of one steady epoch (--trace) for
TensorBoard/Perfetto. Writes a JSON artifact (--out, e.g. PROFILE_r04.json).

Usage: python benchmarks/profile_epoch.py [--platform cpu|tpu] [--batch 256]
       [--epochs 4] [--trace] [--out PROFILE_r04.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)


class _PerStep:
    """Profiler stand-in for TrainingDriver.train_epoch: ``active=True``
    routes the driver onto the per-step path (the scan path hides step
    boundaries). The seconds come from the graftel spans that path opens."""

    active = True

    def step(self):
        pass


def _span_seconds(records):
    acc = {}
    for r in records:
        if r["kind"] == "span":
            acc[r["name"]] = acc.get(r["name"], 0.0) + r["dur_s"]
    return acc


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", choices=("cpu", "tpu"), default="cpu")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--epochs", type=int, default=4, help="steady epochs per arm")
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax

    # JAX starts on the named platform or fails: no silent other backend.
    jax.config.update("jax_platforms", args.platform)

    # The ONE production-pipeline constructor, shared with bench.py so the
    # profiler measures exactly the plumbing the benchmark times.
    from bench import build_production_pipeline

    pipe = build_production_pipeline(batch_size=args.batch)
    train_loader = pipe["train_loader"]
    driver = pipe["driver"]

    # Compile epoch (both paths get warmed: scan epoch now, per-step below).
    train_loader.set_epoch(0)
    t0 = time.perf_counter()
    driver.train_epoch(train_loader)
    compile_s = time.perf_counter() - t0

    # Arm 1a: real loader (feed included).
    t0 = time.perf_counter()
    for e in range(args.epochs):
        train_loader.set_epoch(e + 1)
        driver.train_epoch(train_loader)
    real_s = (time.perf_counter() - t0) / args.epochs
    # The driver's pipeline split for the LAST real epoch: H2D bytes/wire
    # seconds (overlapped, measured on the transfer thread) vs device step
    # seconds vs consumer queue-wait.
    feed_split = driver.feed_stats.as_dict()

    # Arm 1b: identical batches pre-materialized (zero feed cost). The epoch
    # consumed is the last real epoch's batch sequence, so shapes and chunk
    # boundaries match the scan-path caches exactly.
    cached = list(train_loader)
    t0 = time.perf_counter()
    for _ in range(args.epochs):
        driver.train_epoch(cached)
    cached_s = (time.perf_counter() - t0) / args.epochs

    # Arm 2: span timings through the per-step path. The scan-path warmup
    # above compiled only epoch_scan; the per-step train_step is a separate
    # jit, so run one discarded per-step epoch first or its compile would
    # land inside the measured "train_step" span.
    from hydragnn_tpu import telemetry

    driver.train_epoch(train_loader, profiler=_PerStep())
    was_collecting = telemetry.collecting()
    telemetry.configure(collect=True)
    before = len(telemetry.collected_records())
    driver.train_epoch(train_loader, profiler=_PerStep())
    spans = _span_seconds(telemetry.collected_records()[before:])
    telemetry.configure(collect=was_collecting)

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(REPO, "logs", "profile_epoch", "profiler_output")
        os.makedirs(trace_dir, exist_ok=True)
        jax.profiler.start_trace(trace_dir)
        driver.train_epoch(train_loader)
        jax.profiler.stop_trace()

    n_graphs = len(train_loader.dataset)
    feed_overhead = max(0.0, 1.0 - cached_s / real_s)
    result = {
        "ts_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "batch_size": args.batch,
        "train_graphs": n_graphs,
        "compile_epoch_s": round(compile_s, 3),
        "steady_epoch_s_real_feed": round(real_s, 4),
        "steady_epoch_s_cached_feed": round(cached_s, 4),
        "feed_overhead_share": round(feed_overhead, 4),
        "graphs_per_sec_production": round(n_graphs / real_s, 1),
        "span_feed_wait_s": round(spans.get("feed_wait", 0.0), 4),
        "span_train_dispatch_s": round(spans.get("device_step", 0.0), 4),
        "span_h2d_s": round(spans.get("h2d", 0.0), 4),
        "pipeline_split_last_epoch": feed_split,
        "trace_dir": trace_dir,
    }

    # Arm 3: the device-resident path (Training.reshuffle="batch") — steady
    # epochs replay device-cached stacked chunks, so this measures the
    # pipeline with feed cost engineered away rather than merely overlapped.
    # Warmups: epoch 0 compiles + builds the cache, epoch 1 compiles the
    # permuted replay (see bench._cached_epoch_workload).
    pipe_c = build_production_pipeline(
        batch_size=args.batch, training_overrides={"reshuffle": "batch"}
    )
    loader_c = pipe_c["train_loader"]
    driver_c = pipe_c["driver"]
    for e in range(2):
        loader_c.set_epoch(e)
        driver_c.train_epoch(loader_c)
    t0 = time.perf_counter()
    for e in range(args.epochs):
        loader_c.set_epoch(e + 2)
        driver_c.train_epoch(loader_c)
    cached_mode_s = (time.perf_counter() - t0) / args.epochs
    result["steady_epoch_s_device_cached_mode"] = round(cached_mode_s, 4)
    result["graphs_per_sec_device_cached"] = round(n_graphs / cached_mode_s, 1)
    print(json.dumps(result))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)


if __name__ == "__main__":
    main()
