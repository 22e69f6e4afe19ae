"""Benchmark: the full north-star metric (BASELINE.json) — PNA multi-task
(graph + 3 node heads) on the deterministic synthetic molecular dataset.

Reports ONE JSON line with:
  platform / backend / device_kind / device_count : the device, as JAX
      reports it. The default invocation runs on the TPU or not at all.
  value / vs_baseline : graphs/sec/chip on the fixed single-shape scan
      workload — directly comparable to the driver-recorded figure of
      2026-07-29 (812,122.95 graphs/sec/chip on a TPU v5 lite, the baseline
      pin).
  bucketed_throughput : graphs/sec/chip through the PRODUCTION path — the
      bucketed GraphDataLoader (2 shape buckets) + TrainingDriver scan epochs
      on ci_multihead.json, i.e. multiple batch shapes, real collation.
  mae_node / rmse_task_max : accuracy after training ci_multihead.json for
      its full epoch budget — node-head MAE and the WORST per-head RMSE (CI
      thresholds: node MAE < 0.20, every head RMSE < 0.20 —
      tests/test_graphs.py THRESHOLDS["PNA"]).
  mfu : model-FLOPs utilization — XLA cost-analysis FLOPs per step x steady
      steps/sec over the chip's bf16 peak (table below; a chip that is not
      in the table is an error).
  compile_s / steady_step_ms : compile-vs-steady-state split.

On any failure — no TPU, an unknown chip, a failing phase — prints a
diagnostic JSON line (error key, no figure that was not measured on the
device it names) and exits 1. Nothing is retried and nothing falls back.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

# Driver-recorded throughput of 2026-07-29 (TPU v5 lite, rc=0) — the first
# number with provenance; vs_baseline is measured against it.
BASELINE_GRAPHS_PER_SEC = 812122.95

BATCH_SIZE = 256
HIDDEN = 64
LAYERS = 3
STEPS = 60
EPOCHS = 5
# WINDOWS independent (EPOCHS x STEPS)-step windows; the best (min-time) is
# reported with the median alongside. Each window has the same dispatch
# pattern as the run that produced the baseline pin.
WINDOWS = 6

# bf16 peak FLOP/s per chip by device kind substring (public spec sheets).
_PEAK_BF16 = (
    ("v5p", 459e12),
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v6", 918e12),
    ("trillium", 918e12),
    ("v4", 275e12),
)


def _chip_peak_flops() -> float:
    import jax

    kind = jax.devices()[0].device_kind
    for tag, peak in _PEAK_BF16:
        if tag in kind.lower():
            return peak
    raise RuntimeError(
        f"device_kind {kind!r} is not in bench.py's _PEAK_BF16 table: add "
        "its published bf16 peak there — a utilization against a guessed "
        "or missing peak is not reported"
    )


def _device_block() -> dict:
    """The device every printed result names, as JAX reports it."""
    import jax

    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "backend": jax.default_backend(),
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
    }


def _scan_harness(
    batch, hidden, layers, steps, seed=0, compute_dtype=None, loss_scaling=None
):
    """Shared setup for the scan-workload arms: build graphs → collate →
    stack → model/optimizer/state → AOT-compile the epoch scan. Returns
    (compiled, state, stacked, key, flops_per_step, compile_s) — ONE
    protocol so the baseline, large-MFU, and precision A/B arms cannot drift
    apart. ``loss_scaling`` (a precision.LossScaleConfig) arms the full
    Training.precision='bf16' step — dynamic loss scale riding the scan
    carry — rather than compute-dtype-only bf16."""
    import jax

    from __graft_entry__ import DIMS, TYPES, _build_model, _make_graphs
    from hydragnn_tpu.graphs import collate_graphs
    from hydragnn_tpu.models import init_model_variables
    from hydragnn_tpu.train.trainer import (
        create_train_state,
        make_train_epoch_scan,
        stack_batches,
    )
    from hydragnn_tpu.utils.optimizer import select_optimizer

    rng = np.random.default_rng(seed)
    # QM9-like sizes: ~18 heavy+H atoms per molecule.
    graphs = _make_graphs(batch, rng, n_lo=12, n_hi=26)
    b = collate_graphs(graphs, TYPES, DIMS, edge_dim=1)
    stacked = stack_batches([b] * steps, steps)
    model = _build_model(hidden=hidden, layers=layers, compute_dtype=compute_dtype)
    variables = init_model_variables(model, b)
    opt = select_optimizer("AdamW", 1e-3)
    state = create_train_state(model, variables, opt)
    if loss_scaling is not None:
        from hydragnn_tpu.precision import make_loss_scale_state

        state = state.replace(loss_scale=make_loss_scale_state(loss_scaling))
    epoch = make_train_epoch_scan(model, opt, loss_scaling=loss_scaling)
    key = jax.random.PRNGKey(0)

    # AOT compile once: timed as compile_s, reused for cost analysis AND the
    # execution windows (a second lower().compile() would double compile cost).
    count = np.asarray(steps, np.int32)  # every stacked batch is real
    t0 = time.perf_counter()
    program = epoch.lower(state, stacked, count, key).compile()
    compile_s = time.perf_counter() - t0

    def compiled(state, stacked, key):
        return program(state, stacked, count, key)

    return compiled, state, stacked, key, _compiled_flops_of(program, steps), compile_s


def _mfu_workload(batch=512, hidden=256, layers=3, steps=12, windows=3):
    """MFU at a hardware-meaningful model size. The pinned CI workload
    (hidden=64, batch=256) is dispatch/HBM-bound — its MFU (~4e-4) measures
    the workload, not the chip. This arm trains a PNA big enough for the MXU
    to matter (post-MLP [17*hidden -> hidden] over ~13k nodes/batch) and
    reports FLOPs-per-step x steps/sec over the chip's bf16 peak — the
    framework's achievable utilization, reported alongside (never instead
    of) the baseline-comparable throughput. Measured twice: the f32 default
    AND Architecture.compute_dtype=bfloat16 mixed precision (the production
    TPU training configuration — halves activation HBM traffic and runs the
    MXU at its native multiply width)."""
    import jax

    out = {"mfu_large_model": f"PNA hidden={hidden} x{layers}, batch={batch}"}
    peak = _chip_peak_flops()
    for tag, dtype in (("", None), ("_bf16", "bfloat16")):
        compiled, state, stacked, key, flops_per_step, _ = _scan_harness(
            batch, hidden, layers, steps, seed=1, compute_dtype=dtype
        )
        state, metrics = compiled(state, stacked, key)
        jax.block_until_ready(metrics["loss"])
        times = []
        for _ in range(windows):
            t0 = time.perf_counter()
            state, metrics = compiled(state, stacked, key)
            jax.block_until_ready(metrics["loss"])
            times.append(time.perf_counter() - t0)
        best = min(times)
        out[f"mfu_large_step_ms{tag}"] = round(1000.0 * best / steps, 3)
        out[f"mfu_large{tag}"] = round(
            flops_per_step * (steps / best) / peak, 5
        )
        out[f"mfu_large_tflops_per_step{tag}"] = round(
            flops_per_step / 1e12, 4
        )
    return out


def _compiled_flops_of(compiled, steps) -> float:
    return float(compiled.cost_analysis()["flops"]) / steps


def _peak_workload():
    """The fixed single-shape scan workload (identical parameters to the run
    that produced the baseline pin): returns throughput + timing + MFU."""
    import jax

    compiled, state, stacked, key, flops_per_step, compile_s = _scan_harness(
        BATCH_SIZE, HIDDEN, LAYERS, STEPS, seed=0
    )

    # Warmup dispatch, then timed windows. The windows ride under the
    # recompile sentinel: everything was AOT-compiled above, so a compile
    # inside a timed window means the measurement is invalid — fail it
    # loudly rather than publish a number with compile time folded in.
    from hydragnn_tpu.analysis import no_recompile

    state, metrics = compiled(state, stacked, key)
    jax.block_until_ready(metrics["loss"])

    steps_per_window = STEPS * EPOCHS
    window_s = []
    with no_recompile(action="raise", label="bench steady windows"):
        for _ in range(WINDOWS):
            t0 = time.perf_counter()
            for _ in range(EPOCHS):
                state, metrics = compiled(state, stacked, key)
            jax.block_until_ready(metrics["loss"])
            window_s.append(time.perf_counter() - t0)
    # Headline = min-time window (as in the run that produced the baseline
    # pin: interference only ADDS time); the median is reported alongside so
    # contention is visible rather than hidden.
    median = sorted(window_s)[len(window_s) // 2]
    best = min(window_s)

    graphs_per_sec = BATCH_SIZE * steps_per_window / best
    mfu = flops_per_step * (steps_per_window / best) / _chip_peak_flops()
    return {
        "value": round(graphs_per_sec, 2),
        "value_median": round(BATCH_SIZE * steps_per_window / median, 2),
        "compile_s": round(compile_s, 3),
        "steady_step_ms": round(1000.0 * best / steps_per_window, 4),
        "mfu": round(mfu, 5),
        "flops_per_step": flops_per_step,
    }


def build_production_pipeline(
    batch_size: "int | None" = None,
    training_overrides: "dict | None" = None,
    dataset_overrides: "dict | None" = None,
) -> dict:
    """ci_multihead.json (the north-star multi-task config) through the real
    pipeline: serialized dataset -> bucketed loader (2 shape buckets) ->
    config completion -> model -> TrainingDriver. ONE implementation shared
    by the production workloads below."""
    from hydragnn_tpu.models.create import create_model_config, init_model_variables
    from hydragnn_tpu.preprocess.load_data import dataset_loading_and_splitting
    from hydragnn_tpu.train.train_validate_test import TrainingDriver
    from hydragnn_tpu.train.trainer import create_train_state
    from hydragnn_tpu.utils.config_utils import update_config
    from hydragnn_tpu.utils.optimizer import select_optimizer

    repo = os.path.dirname(os.path.abspath(__file__))
    os.environ.setdefault("SERIALIZED_DATA_PATH", repo)
    with open(os.path.join(repo, "tests/inputs/ci_multihead.json")) as f:
        config = json.load(f)
    # Self-contained: always raw -> serialized (a serialized .pkl found in the
    # untracked serialized_dataset/ is never preferred — it may predate the
    # generator), generating the deterministic raw dataset when the raw text
    # folder is missing OR partial (a crashed earlier generation must not be
    # silently benchmarked — same count guard as tests/test_graphs.py
    # ensure_raw_datasets). Paths are anchored at the repo dir and written
    # back ABSOLUTE so RawDataLoader (which resolves relative paths against
    # os.getcwd()) agrees regardless of invocation cwd.
    N_RAW = 500
    for split, p in config["Dataset"]["path"].items():
        raw = p if os.path.isabs(p) else os.path.join(repo, p)
        config["Dataset"]["path"][split] = raw
        existing = os.listdir(raw) if os.path.isdir(raw) else None
        if existing is None or len(existing) != N_RAW:
            sys.path.insert(0, os.path.join(repo, "tests"))
            from deterministic_graph_data import deterministic_graph_data

            os.makedirs(raw, exist_ok=True)
            for name in existing or ():
                os.remove(os.path.join(raw, name))
            deterministic_graph_data(raw, number_configurations=N_RAW)
    # Production bucketing plumbing: two shape buckets over the train split.
    config["Dataset"]["num_buckets"] = 2
    if batch_size is not None:
        config["NeuralNetwork"]["Training"]["batch_size"] = batch_size
    if training_overrides:
        config["NeuralNetwork"]["Training"].update(training_overrides)
    if dataset_overrides:
        config["Dataset"].update(dataset_overrides)

    train_loader, val_loader, test_loader, _ = dataset_loading_and_splitting(
        config=config
    )
    config = update_config(config, train_loader, val_loader, test_loader)
    arch = config["NeuralNetwork"]["Architecture"]
    training = config["NeuralNetwork"]["Training"]

    model = create_model_config(config=arch, verbosity=0)
    variables = init_model_variables(model, next(iter(train_loader)))
    opt = select_optimizer(training["optimizer"], training["learning_rate"])
    state = create_train_state(model, variables, opt)
    driver = TrainingDriver(model, opt, state)
    return {
        "config": config,
        "train_loader": train_loader,
        "val_loader": val_loader,
        "test_loader": test_loader,
        "model": model,
        "driver": driver,
    }


def _production_workload():
    """Production pipeline -> scan epochs + plateau scheduler -> test-split
    accuracy."""
    from hydragnn_tpu.utils.optimizer import (
        ReduceLROnPlateau,
        get_learning_rate,
        set_learning_rate,
    )

    pipe = build_production_pipeline()
    config = pipe["config"]
    val_loader = pipe["val_loader"]
    test_loader = pipe["test_loader"]
    driver = pipe["driver"]
    arch = config["NeuralNetwork"]["Architecture"]
    training = config["NeuralNetwork"]["Training"]
    bucketed = pipe["train_loader"]
    scheduler = ReduceLROnPlateau(factor=0.5, patience=5, min_lr=1e-5)

    num_epoch = training["num_epoch"]
    compile_s = steady_s = 0.0
    # Per-epoch transfer-vs-compute split of the streamed path, accumulated
    # over the steady epochs from the driver's pipeline stats: H2D bytes +
    # wire seconds (overlapped with compute on the transfer thread), consumer
    # queue-wait, and device step seconds.
    split = {"h2d_bytes": 0, "h2d_s": 0.0, "feed_wait_s": 0.0, "step_s": 0.0}
    for epoch in range(num_epoch):
        bucketed.set_epoch(epoch)
        t0 = time.perf_counter()
        driver.train_epoch(bucketed)
        dt = time.perf_counter() - t0
        if epoch == 0:
            compile_s = dt
        else:
            steady_s += dt
            fs = driver.feed_stats
            split["h2d_bytes"] += fs.h2d_bytes
            split["h2d_s"] += fs.h2d_s
            split["feed_wait_s"] += fs.feed_wait_s
            split["step_s"] += fs.step_s
        # Scheduler rides the (untimed) validation pass, like run_training.
        val_loss, _ = driver.evaluate(val_loader)
        lr = get_learning_rate(driver.state.opt_state)
        new_lr = scheduler.step(val_loss, lr)
        if new_lr != lr:
            driver.state = driver.state.replace(
                opt_state=set_learning_rate(driver.state.opt_state, new_lr)
            )

    _, rmse_task, tv, pv = driver.evaluate(test_loader, return_values=True)
    node_abs = [
        np.abs(np.asarray(t) - np.asarray(p)).ravel()
        for t, p, kind in zip(tv, pv, arch["output_type"])
        if kind == "node"
    ]
    mae_node = float(np.concatenate(node_abs).mean()) if node_abs else None

    n_train = len(bucketed.dataset)
    steady_epochs = max(num_epoch - 1, 1)
    return {
        "bucketed_throughput": round(n_train * (num_epoch - 1) / steady_s, 2),
        "bucketed_shapes": bucketed.num_buckets,
        "bucketed_compile_s": round(compile_s, 3),
        # The split below is PER STEADY EPOCH; h2d_s overlaps step_s (the
        # transfer thread moves batch k+1 during step k), so the two do not
        # sum to epoch wall time unless the pipeline is transfer-bound —
        # feed_wait_s is the stall the consumer actually saw.
        "h2d_mb_per_epoch": round(
            split["h2d_bytes"] / steady_epochs / (1 << 20), 3
        ),
        "h2d_s_per_epoch": round(split["h2d_s"] / steady_epochs, 4),
        "feed_wait_s_per_epoch": round(
            split["feed_wait_s"] / steady_epochs, 4
        ),
        "step_s_per_epoch": round(split["step_s"] / steady_epochs, 4),
        "mae_node": None if mae_node is None else round(mae_node, 5),
        "rmse_task_max": round(float(max(rmse_task)), 5),
    }


def _cached_epoch_workload(epochs: int = 8) -> dict:
    """The device-resident production path: same pipeline as
    _production_workload but with Training.reshuffle="batch", so after the
    first epoch the stacked chunks live on device and steady-state epochs do
    no host collation and no host->device transfer. Reported as its own
    metric alongside — never instead of — the parity-semantics bucketed
    number."""
    pipe = build_production_pipeline(training_overrides={"reshuffle": "batch"})
    driver = pipe["driver"]
    bucketed = pipe["train_loader"]
    # Two warmup epochs: epoch 0 compiles the scan and builds the device
    # cache; epoch 1 compiles the permuted-replay dispatch (_perm_scan).
    first_s = steady_s = 0.0
    for epoch in range(epochs):
        bucketed.set_epoch(epoch)
        t0 = time.perf_counter()
        driver.train_epoch(bucketed)
        dt = time.perf_counter() - t0
        if epoch <= 1:
            first_s += dt
        else:
            steady_s += dt
    n_train = len(bucketed.dataset)
    # Steady cached epochs replay device-resident chunks: the h2d split
    # must read ~0 — reported so the contrast with h2d_s_per_epoch is
    # visible in the same artifact.
    fs = driver.feed_stats
    return {
        "bucketed_throughput_cached": round(
            n_train * (epochs - 2) / steady_s, 2
        ),
        "cached_warmup_s": round(first_s, 3),
        "cached_h2d_s_per_epoch": round(fs.h2d_s, 4),
        "cached_step_s_per_epoch": round(fs.step_s, 4),
    }


def _latest_artifact_block(pattern, extract, search_dir=None):
    """Shared stale-fallback scan: newest (mtime) artifact matching the glob
    whose ``extract(doc)`` returns a block, stamped with capture time, source
    filename, and ``provenance: "stale"``. One implementation for every
    artifact family (BENCH_*, SERVE_*, ...)."""
    import glob

    search_dir = search_dir or os.path.dirname(os.path.abspath(__file__))
    best = None
    for path in glob.glob(os.path.join(search_dir, pattern)):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(doc, dict):
            continue
        block = extract(doc)
        if block is None:
            continue
        mtime = os.path.getmtime(path)
        if best is not None and mtime <= best[0]:
            continue
        block.update(
            captured_ts_utc=time.strftime(
                "%Y-%m-%dT%H:%M:%SZ", time.gmtime(mtime)
            ),
            source_artifact=os.path.basename(path),
            provenance="stale",
        )
        best = (mtime, block)
    return best[1] if best else None


def _last_known_serving(search_dir: "str | None" = None) -> "dict | None":
    """Most recent real serving measurement from any committed SERVE_*
    artifact. A failed
    ``--serve`` round embeds this block with ``provenance: "stale"`` so an
    rc=1 round still carries the last-known-good saturation throughput."""

    def extract(doc):
        if not doc.get("saturation_graphs_per_sec"):
            return None  # failure artifacts carry no saturation number
        closed = doc.get("closed_loop") or {}
        return {
            "saturation_graphs_per_sec": doc["saturation_graphs_per_sec"],
            "closed_loop_p95_ms": closed.get("p95_ms"),
            "recompiles_after_warmup": doc.get("recompiles_after_warmup"),
            "platform": doc.get("platform"),
            "device_kind": doc.get("device_kind"),
        }

    return _latest_artifact_block("SERVE_*.json", extract, search_dir)


def _last_known_router(search_dir: "str | None" = None) -> "dict | None":
    """Most recent completed multi-replica rig from any committed ROUTER_*
    artifact. A failed
    ``--router`` round embeds this block with ``provenance: "stale"`` so an
    rc=1 round still carries the last-known-good fleet drill record."""

    def extract(doc):
        kill = doc.get("kill_replica_drill") or {}
        scale = doc.get("scaleup_drill") or {}
        if not doc.get("open_loop") or not kill:
            return None
        top = doc["open_loop"][-1]
        return {
            "replicas": doc.get("replicas"),
            "fleet_p99_ms_at_top_load": top.get("fleet_p99_ms"),
            "offered_graphs_per_sec_top": top.get("offered_graphs_per_sec"),
            "kill_drill_zero_lost": kill.get("zero_lost"),
            "scaleup_warmup_xla_compiles": (
                scale.get("warm_spinup") or {}
            ).get("warmup_xla_compiles"),
            "platform": doc.get("platform"),
            "device_kind": doc.get("device_kind"),
        }

    return _latest_artifact_block("ROUTER_*.json", extract, search_dir)


def _last_known_swap(search_dir: "str | None" = None) -> "dict | None":
    """Most recent completed lifecycle rig from any committed SWAP_*
    artifact. A failed
    ``--swap`` round embeds this block with ``provenance: "stale"`` so an
    rc=1 round still carries the last-known-good swap drill record."""

    def extract(doc):
        sul = doc.get("swap_under_load") or {}
        if not doc.get("drills_total") or not sul:
            return None
        return {
            "drills_passed": doc.get("drills_passed"),
            "drills_total": doc.get("drills_total"),
            "p99_swap_over_steady": sul.get("p99_swap_over_steady"),
            "recompiles_after_swap": sul.get("recompiles_after_swap"),
            "zero_version_torn": sul.get("zero_version_torn"),
            "swap_wall_s": sul.get("swap_wall_s"),
            "platform": doc.get("platform"),
            "device_kind": doc.get("device_kind"),
        }

    return _latest_artifact_block("SWAP_*.json", extract, search_dir)


def _last_known_flywheel(search_dir: "str | None" = None) -> "dict | None":
    """Most recent completed continuous-learning soak from any committed
    FLYWHEEL_* artifact.
    A failed ``--flywheel`` round embeds this block with ``provenance:
    "stale"`` so an rc=1 round still carries the last known soak verdicts."""

    def extract(doc):
        soak = doc.get("soak") or {}
        if not doc.get("drills_total") or not soak:
            return None
        return {
            "drills_passed": doc.get("drills_passed"),
            "drills_total": doc.get("drills_total"),
            "promotions": (soak.get("counters") or {}).get("promotions"),
            "rejections": (soak.get("counters") or {}).get("rejections"),
            "poisoned_never_served": soak.get("poisoned_never_served"),
            "recompiles_after_warmup": soak.get("recompiles_after_warmup"),
            "lost_total": soak.get("lost_total"),
            "zero_version_torn": soak.get("zero_version_torn"),
            "platform": doc.get("platform"),
            "device_kind": doc.get("device_kind"),
        }

    return _latest_artifact_block("FLYWHEEL_*.json", extract, search_dir)


def _last_known_pilot(search_dir: "str | None" = None) -> "dict | None":
    """Most recent completed autopilot drill set from any committed PILOT_*
    artifact. A failed
    ``--pilot`` round embeds this block with ``provenance: "stale"`` so an
    rc=1 round still carries the last known fleet-autopilot verdicts."""

    def extract(doc):
        if not doc.get("drills_total") or "flash_crowd_drill" not in doc:
            return None
        crowd = doc.get("flash_crowd_drill") or {}
        zero = doc.get("scale_to_zero_drill") or {}
        return {
            "drills_passed": doc.get("drills_passed"),
            "drills_total": doc.get("drills_total"),
            "lost_total": crowd.get("lost_total"),
            "brownout_shed_non_ensemble": crowd.get(
                "brownout_shed_non_ensemble"
            ),
            "scale_up_total": crowd.get("scale_up_total"),
            "warmup_xla_compiles": zero.get("warmup_xla_compiles"),
            "platform": doc.get("platform"),
            "device_kind": doc.get("device_kind"),
        }

    return _latest_artifact_block("PILOT_*.json", extract, search_dir)


def _last_known_faults(search_dir: "str | None" = None) -> "dict | None":
    """Most recent completed drill matrix from any committed FAULTS_*
    artifact. A failed
    ``--faults`` round embeds this block with ``provenance: "stale"``."""

    def extract(doc):
        if doc.get("metric") != "fault_drills" or not doc.get("drills"):
            return None
        return {
            "value": doc.get("value"),
            "drills_passed": doc.get("drills_passed"),
            "drills_total": doc.get("drills_total"),
            "guard_overhead_pct": doc.get("guard_overhead_pct"),
            "guard_bit_inert": doc.get("guard_bit_inert"),
            "ckpt_save_stall_ms": doc.get("ckpt_save_stall_ms"),
        }

    return _latest_artifact_block("FAULTS_*.json", extract, search_dir)


def _last_known_packing(search_dir: "str | None" = None) -> "dict | None":
    """Most recent completed train-side packing A/B from any committed
    BENCH_*_packing artifact. A failed ``--packing`` round embeds this block
    with ``provenance: "stale"``."""

    def extract(doc):
        if doc.get("metric") != "train_packing_ab" or not doc.get("value"):
            return None
        return {
            "value": doc.get("value"),
            "padding_waste_nodes_unpacked": _get_arm(
                doc, "unpacked", "padding_waste_nodes"
            ),
            "padding_waste_nodes_packed": _get_arm(
                doc, "packed", "padding_waste_nodes"
            ),
            "val_loss_rel_diff": doc.get("val_loss_rel_diff"),
            "backend": doc.get("backend"),
        }

    return _latest_artifact_block("BENCH_*_packing.json", extract, search_dir)


def _last_known_trace(search_dir: "str | None" = None) -> "dict | None":
    """Most recent completed tracer-overhead A/B from any committed TRACE_*
    artifact. A failed
    ``--trace`` round embeds this block with ``provenance: "stale"``."""

    def extract(doc):
        if doc.get("metric") != "tracer_overhead" or doc.get(
            "overhead_pct"
        ) is None:
            return None
        return {
            "value": doc.get("value"),
            "overhead_pct": doc.get("overhead_pct"),
            "overhead_ok": doc.get("overhead_ok"),
            "backend": doc.get("backend"),
            "span_counts_per_layer": doc.get("span_counts_per_layer"),
        }

    return _latest_artifact_block("TRACE_*.json", extract, search_dir)


_TRACE_LAYERS = (
    ("train", ("train_epoch", "collate", "h2d", "device_step")),
    ("eval", ("evaluate", "eval_step")),
    ("serve", ("serve/",)),
    ("fault", ("fault/",)),
    ("jax", ("jax/",)),
)


def _spans_per_layer(counts: dict) -> dict:
    out = {layer: 0 for layer, _ in _TRACE_LAYERS}
    out["other"] = 0
    for name, n in counts.items():
        for layer, prefixes in _TRACE_LAYERS:
            if any(
                name == p or (p.endswith("/") and name.startswith(p))
                for p in prefixes
            ):
                out[layer] += n
                break
        else:
            out["other"] += n
    return out


def trace_main() -> int:
    """``python bench.py --trace``: the graftel tracer-overhead A/B on the
    production CPU workload (ci_multihead through the bucketed loader) —
    INTERLEAVED enabled/disabled steady epochs (min-of-window, the
    fault-drill overhead protocol) gated < 2%, the span census per layer,
    and a flight-recorder dump + JSONL export round-trip (schema-validated).
    Writes TRACE_rNN.json; failure embeds the last known round,
    stale-labeled, per the established convention."""
    import tempfile

    windows = 5
    result = {
        "metric": "tracer_overhead",
        "value": 0.0,
        "unit": "overhead_pct",
        "gate_pct": 2.0,
        "windows_per_arm": windows,
    }
    from hydragnn_tpu.utils.artifacts import round_tag

    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        f"TRACE_r{round_tag()}.json",
    )
    try:
        import jax

        from hydragnn_tpu import telemetry

        result.update(_device_block())
        pipe = build_production_pipeline()
        driver = pipe["driver"]
        loader = pipe["train_loader"]
        with tempfile.TemporaryDirectory(prefix="graftel_bench_") as tmp:
            telemetry.configure(run_dir=tmp, collect=True, enabled=True)
            # Two warmup epochs: compiles + both bucket shapes seen.
            for epoch in range(2):
                loader.set_epoch(epoch)
                driver.train_epoch(loader)
            # Interleaved A/B: tracer-off epoch then tracer-on epoch,
            # ``windows`` pairs; min-of-window per arm cancels drift (the
            # guard_overhead_pct protocol from bench.py --faults).
            off_s, on_s = [], []
            for w in range(windows):
                for enabled, sink in ((False, off_s), (True, on_s)):
                    telemetry.configure(enabled=enabled)
                    loader.set_epoch(2 + 2 * w + int(enabled))
                    t0 = time.perf_counter()
                    driver.train_epoch(loader)
                    sink.append(time.perf_counter() - t0)
            telemetry.configure(enabled=True)
            best_off, best_on = min(off_s), min(on_s)
            overhead_pct = 100.0 * (best_on - best_off) / best_off
            result.update(
                steady_epoch_s_disabled=round(best_off, 4),
                steady_epoch_s_enabled=round(best_on, 4),
                overhead_pct=round(overhead_pct, 3),
                overhead_ok=overhead_pct < 2.0,
                value=round(overhead_pct, 3),
            )
            # Span census per layer (the enabled epochs' records).
            counts = telemetry.span_counts()
            result["span_counts"] = counts
            result["span_counts_per_layer"] = _spans_per_layer(counts)
            # Flight-recorder dump + JSONL export round-trips.
            dump_path = telemetry.flight_dump("bench_trace_drill")
            dump_errors = (
                ["no dump written"]
                if dump_path is None
                else telemetry.validate_flight_file(dump_path)
            )
            jsonl_path = os.path.join(tmp, "trace_events.jsonl")
            n = telemetry.export_events_jsonl(jsonl_path)
            count, jsonl_errors = telemetry.validate_events_jsonl(jsonl_path)
            result["flight_roundtrip_ok"] = not dump_errors
            result["jsonl_roundtrip_ok"] = n > 0 and count == n and not jsonl_errors
            result["jsonl_events"] = n
            if dump_errors:
                result["flight_errors"] = dump_errors[:5]
            if jsonl_errors:
                result["jsonl_errors"] = jsonl_errors[:5]
        with open(out_path, "w") as f:
            json.dump(result, f, indent=2)
        result["artifact"] = os.path.basename(out_path)
    except Exception as e:
        import traceback

        result["error"] = f"{type(e).__name__}: {e}"
        result["trace_tail"] = traceback.format_exc()[-1500:]
        try:
            stale = _last_known_trace()
            if stale is not None:
                result["last_known_trace"] = stale
        except Exception:
            pass
        print(json.dumps(result))
        return 1
    print(json.dumps(result))
    ok = (
        result["overhead_ok"]
        and result["flight_roundtrip_ok"]
        and result["jsonl_roundtrip_ok"]
    )
    return 0 if ok else 1


def _get_arm(doc, arm, key):
    return (doc.get(arm) or {}).get(key)


def packing_main() -> int:
    """``python bench.py --packing``: the train-side packing A/B (ROADMAP
    item 1) on the production pipeline — ci_multihead through the bucketed
    loader, same seed, packing off vs on — reporting steady-epoch graphs/sec,
    measured padding waste from the loader's padded-row accounting, and
    same-seed convergence parity (final val loss rel-diff). Writes the
    round's BENCH_rNN_packing.json; failure embeds the last known A/B,
    stale-labeled, per the established convention."""
    epochs = 4
    result = {
        "metric": "train_packing_ab",
        "value": 0.0,
        "unit": "packed_vs_unpacked_graphs_per_sec",
        "epochs": epochs,
    }
    from hydragnn_tpu.utils.artifacts import round_tag

    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        f"BENCH_r{round_tag()}_packing.json",
    )
    try:
        import jax

        result.update(_device_block())
        for tag, overrides in (
            ("unpacked", None),
            ("packed", {"packing": True}),
        ):
            pipe = build_production_pipeline(dataset_overrides=overrides)
            driver = pipe["driver"]
            loader = pipe["train_loader"]
            loader.reset_padding_stats()
            val_losses = []
            steady_s = 0.0
            for epoch in range(epochs):
                loader.set_epoch(epoch)
                t0 = time.perf_counter()
                driver.train_epoch(loader)
                dt = time.perf_counter() - t0
                if epoch > 0:
                    steady_s += dt
                val_loss, _ = driver.evaluate(pipe["val_loader"])
                val_losses.append(round(float(val_loss), 6))
            stats = loader.padding_stats()
            result[tag] = {
                "steady_graphs_per_sec": round(
                    len(loader.dataset) * (epochs - 1) / steady_s, 2
                ),
                "batches_per_epoch": len(loader),
                "padding_waste_nodes": stats["padding_waste_nodes"],
                "padding_waste_edges": stats["padding_waste_edges"],
                "padding_waste_graphs": stats["padding_waste_graphs"],
                "val_loss_curve": val_losses,
            }
        up, pk = result["unpacked"], result["packed"]
        result["value"] = round(
            pk["steady_graphs_per_sec"] / up["steady_graphs_per_sec"], 3
        )
        result["padding_waste_nodes_reduction"] = round(
            up["padding_waste_nodes"] / max(pk["padding_waste_nodes"], 1e-9), 3
        )
        # Same-seed convergence parity: packed batches change membership,
        # not the objective — final val losses must agree to bench noise
        # (the tier-1 tolerance test lives in tests/test_packing.py).
        final_u, final_p = up["val_loss_curve"][-1], pk["val_loss_curve"][-1]
        result["val_loss_rel_diff"] = round(
            abs(final_p - final_u) / max(abs(final_u), 1e-9), 4
        )
        result["note"] = (
            "epoch-matched arms: packing raises the effective batch, so the "
            "packed arm takes fewer optimizer steps per epoch and its loss "
            "curve lags at equal epochs; the STEP-matched parity gate is "
            "tests/test_packing.py::"
            "pytest_packed_training_convergence_parity_same_seed"
        )
        with open(out_path, "w") as f:
            json.dump(result, f, indent=2)
        result["artifact"] = os.path.basename(out_path)
    except Exception as e:
        import traceback

        result["error"] = f"{type(e).__name__}: {e}"
        result["trace_tail"] = traceback.format_exc()[-1500:]
        try:
            stale = _last_known_packing()
            if stale is not None:
                result["last_known_packing"] = stale
        except Exception:
            pass
        print(json.dumps(result))
        return 1
    print(json.dumps(result))
    return 0


def _last_known_compile_cache(search_dir: "str | None" = None) -> "dict | None":
    """Most recent real cold-vs-warm measurement from any committed
    COMPILECACHE_* artifact. A failed ``--compile-cache`` round embeds this
    block with ``provenance: "stale"`` so an rc=1 round still carries the
    last-known-good warm-start speedup."""

    def extract(doc):
        if not doc.get("value") or doc.get("metric") != "compile_cache_warm_speedup":
            return None
        return {
            "value": doc["value"],
            "unit": doc.get("unit"),
            "recompiles_after_warmup": doc.get("recompiles_after_warmup"),
            "bit_exact_warm_vs_cold": doc.get("bit_exact_warm_vs_cold"),
            "corrupt_fallback_ok": doc.get("corrupt_fallback_ok"),
            "backend": doc.get("backend"),
        }

    return _latest_artifact_block("COMPILECACHE_*.json", extract, search_dir)


def compile_cache_main() -> int:
    """``python bench.py --compile-cache``: the graftcache cold-vs-warm A/B
    (benchmarks/compile_cache_ab.py) — three child processes over one store
    (cold compile+serialize, warm hydrate, corrupted-entry fallback), gated
    on warm warmup ≥5x faster, recompiles_after_warmup=0, bit-exact
    outputs, and a non-poisoning corruption fallback. Writes
    COMPILECACHE_rNN.json; failure embeds the last known round,
    stale-labeled, per the established convention."""
    result = {
        "metric": "compile_cache_warm_speedup",
        "value": 0.0,
        "unit": "x_cold_vs_warm_warmup_wall",
    }
    from hydragnn_tpu.utils.artifacts import round_tag

    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        f"COMPILECACHE_r{round_tag()}.json",
    )
    try:
        import jax

        result.update(_device_block())
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from benchmarks.compile_cache_ab import run_compile_cache_ab

        result.update(run_compile_cache_ab())
        with open(out_path, "w") as f:
            json.dump(result, f, indent=2)
        result["artifact"] = os.path.basename(out_path)
    except Exception as e:
        import traceback

        result["error"] = f"{type(e).__name__}: {e}"
        result["trace_tail"] = traceback.format_exc()[-1500:]
        try:
            stale = _last_known_compile_cache()
            if stale is not None:
                result["last_known_compile_cache"] = stale
        except Exception:
            pass
        print(json.dumps(result))
        return 1
    print(json.dumps(result))
    return 0 if result.get("ok") else 1


def _last_known_multichip(search_dir: "str | None" = None) -> "dict | None":
    """Most recent real overlapped-vs-single-psum A/B from any committed
    MULTICHIP_* artifact.
    A failed ``--multichip`` round embeds this block with
    ``provenance: "stale"`` so an rc=1 round still carries the last known
    overlap fraction + scaling curve. Pre-graftmesh MULTICHIP artifacts
    (dry-run smokes, no ``metric`` field) are skipped."""

    def extract(doc):
        if not doc.get("value") or doc.get("metric") != "multichip_overlap_ab":
            return None
        return {
            "value": doc["value"],
            "unit": doc.get("unit"),
            "devices": doc.get("devices"),
            "overlap_fraction": doc.get("overlap_fraction"),
            "grads_allclose_ok": doc.get("grads_allclose_ok"),
            "timings_meaningful": doc.get("timings_meaningful"),
            "backend": doc.get("backend"),
        }

    return _latest_artifact_block("MULTICHIP_*.json", extract, search_dir)


def multichip_main() -> int:
    """``python bench.py --multichip``: the graftmesh overlapped-vs-single-
    psum A/B (benchmarks/multichip_ab.py) — per-arm steady step times at the
    top mesh size, measured overlap fraction against the 1-device compute
    floor, a scaling curve over 1/2/4/8 (virtual) devices, and the
    cross-arm grads-allclose gate. Writes MULTICHIP_rNN.json; failure embeds
    the last known round, stale-labeled, per the established convention.
    CPU timings are labeled non-meaningful (virtual mesh oversubscription)."""
    result = {
        "metric": "multichip_overlap_ab",
        "value": 0.0,
        "unit": "x_single_psum_vs_bucketed_step",
    }
    from hydragnn_tpu.utils.artifacts import round_tag

    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        f"MULTICHIP_r{round_tag()}.json",
    )
    try:
        # Pin a >1-device topology BEFORE the first jax import (bench.py has
        # no top-level jax): a stock single-device CPU host must produce a
        # fresh artifact out of the box, on the same virtual-mesh terms as
        # the scaling sweep. HYDRAGNN_TPU_TESTS=1 leaves the real
        # accelerator as the backend for the hardware round.
        n = int(os.environ.get("HYDRAGNN_HOST_DEVICES", "8"))
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}"
        )
        import jax

        if os.environ.get("HYDRAGNN_TPU_TESTS") != "1":
            jax.config.update("jax_platforms", "cpu")

        result.update(_device_block())
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from benchmarks.multichip_ab import run_multichip_ab

        result.update(run_multichip_ab())
        with open(out_path, "w") as f:
            json.dump(result, f, indent=2)
        result["artifact"] = os.path.basename(out_path)
    except Exception as e:
        import traceback

        result["error"] = f"{type(e).__name__}: {e}"
        result["trace_tail"] = traceback.format_exc()[-1500:]
        try:
            stale = _last_known_multichip()
            if stale is not None:
                result["last_known_multichip"] = stale
        except Exception:
            pass
        print(json.dumps(result))
        return 1
    print(json.dumps(result))
    return 0 if result.get("ok") else 1


def _last_known_elastic(search_dir: "str | None" = None) -> "dict | None":
    """Most recent real elastic drill matrix from any committed ELASTIC_*
    artifact. A failed
    ``--elastic`` round embeds this block with ``provenance: "stale"`` so an
    rc=1 round still carries the last known drill verdicts."""

    def extract(doc):
        if not doc.get("drills_passed") or doc.get("metric") != "elastic_drills":
            return None
        return {
            "value": doc.get("value"),
            "unit": doc.get("unit"),
            "drills_passed": doc.get("drills_passed"),
            "drills_total": doc.get("drills_total"),
            "convergence_parity_ok": (doc.get("convergence_parity") or {}).get(
                "ok"
            ),
            "warm_restart_ok": (doc.get("warm_restart") or {}).get("ok"),
            "backend": doc.get("backend"),
        }

    return _latest_artifact_block("ELASTIC_*.json", extract, search_dir)


def elastic_main() -> int:
    """``python bench.py --elastic``: the graftelastic drill matrix
    (benchmarks/elastic_drills.py) — kill-a-worker shrink, join-under-load
    grow with warm-hydrate ``warmup_xla_compiles=0``, shrink/grow/shrink
    churn, kill-during-transition incarnation resume, plus the convergence-
    parity and warm-restart gates. Writes ELASTIC_rNN.json; failure embeds
    the last known round, stale-labeled, per the established convention.
    These are protocol/structural gates — CPU-meaningful by design."""
    result = {
        "metric": "elastic_drills",
        "value": 0.0,
        "unit": "drills_passed",
    }
    from hydragnn_tpu.utils.artifacts import round_tag

    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        f"ELASTIC_r{round_tag()}.json",
    )
    try:
        # Pin a multi-device topology BEFORE the first jax import (the
        # elastic worlds need max_workers devices; same convention as
        # --multichip).
        n = int(os.environ.get("HYDRAGNN_HOST_DEVICES", "8"))
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}"
        )
        import jax

        if os.environ.get("HYDRAGNN_TPU_TESTS") != "1":
            jax.config.update("jax_platforms", "cpu")

        result.update(_device_block())
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from benchmarks.elastic_drills import run_elastic_drills

        result.update(run_elastic_drills())
        result["value"] = float(result.get("drills_passed") or 0)
        with open(out_path, "w") as f:
            json.dump(result, f, indent=2)
        result["artifact"] = os.path.basename(out_path)
    except Exception as e:
        import traceback

        result["error"] = f"{type(e).__name__}: {e}"
        result["trace_tail"] = traceback.format_exc()[-1500:]
        try:
            stale = _last_known_elastic()
            if stale is not None:
                result["last_known_elastic"] = stale
        except Exception:
            pass
        print(json.dumps(result))
        return 1
    print(json.dumps(result))
    return 0 if result.get("ok") else 1


def _last_known_stream(search_dir: "str | None" = None) -> "dict | None":
    """Most recent real streaming data-plane A/B from any committed STREAM_*
    artifact. A failed
    ``--stream`` round embeds this block with ``provenance: "stale"`` so an
    rc=1 round still carries the last known A/B verdicts."""

    def extract(doc):
        if not doc.get("ok") or doc.get("metric") != "stream_ab":
            return None
        ab = doc.get("train_ab") or {}
        return {
            "value": doc.get("value"),
            "unit": doc.get("unit"),
            "params_bit_exact": ab.get("params_bit_exact"),
            "streamed_over_inmemory_wall": ab.get("streamed_over_inmemory_wall"),
            "drills_passed": doc.get("drills_passed"),
            "drills_total": doc.get("drills_total"),
            "backend": doc.get("backend"),
        }

    return _latest_artifact_block("STREAM_*.json", extract, search_dir)


def stream_main() -> int:
    """``python bench.py --stream``: the graftstream out-of-core data-plane
    A/B + drill matrix (benchmarks/stream_bench.py) — in-memory vs streamed
    steady-epoch wall with the FeedStats split, batch-inference graphs/s over
    prediction shards, corrupt-shard quarantine drill, and the elastic N→M
    transition over a streamed corpus. Writes STREAM_rNN.json; failure embeds
    the last known round, stale-labeled, per the established convention."""
    result = {
        "metric": "stream_ab",
        "value": 0.0,
        "unit": "batch_infer_graphs_per_sec",
    }
    from hydragnn_tpu.utils.artifacts import round_tag

    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        f"STREAM_r{round_tag()}.json",
    )
    try:
        import jax

        if os.environ.get("HYDRAGNN_TPU_TESTS") != "1":
            jax.config.update("jax_platforms", "cpu")

        result.update(_device_block())
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from benchmarks.stream_bench import run_stream_bench

        result.update(run_stream_bench())
        result["value"] = float(
            (result.get("batch_inference") or {}).get("graphs_per_sec") or 0.0
        )
        with open(out_path, "w") as f:
            json.dump(result, f, indent=2)
        result["artifact"] = os.path.basename(out_path)
    except Exception as e:
        import traceback

        result["error"] = f"{type(e).__name__}: {e}"
        result["trace_tail"] = traceback.format_exc()[-1500:]
        try:
            stale = _last_known_stream()
            if stale is not None:
                result["last_known_stream"] = stale
        except Exception:
            pass
        print(json.dumps(result))
        return 1
    print(json.dumps(result))
    return 0 if result.get("ok") else 1


def _last_known_precision(search_dir: "str | None" = None) -> "dict | None":
    """Most recent real mixed-precision A/B from any committed PRECISION_*
    artifact. A failed
    ``--precision`` round embeds this block with ``provenance: "stale"`` so
    an rc=1 round still carries the last-known-good speedup + gates."""

    def extract(doc):
        if not doc.get("value") or doc.get("metric") != "precision_ab":
            return None
        serve = doc.get("serve") or {}
        return {
            "value": doc["value"],
            "unit": doc.get("unit"),
            "timings_meaningful": doc.get("timings_meaningful"),
            "convergence_ok": (doc.get("convergence") or {}).get("ok"),
            # tri-state on purpose: True/False when arms were measured,
            # None (unknown) when the artifact carries no serve section —
            # a failing arm must read as False, never as null/True.
            "serve_arms_ok": (
                all(a.get("gate_ok") for a in serve.values())
                if serve
                else None
            ),
            "backend": doc.get("backend"),
        }

    return _latest_artifact_block("PRECISION_*.json", extract, search_dir)


def precision_main() -> int:
    """``python bench.py --precision``: the end-to-end mixed-precision A/B
    (ROADMAP item 3, docs/PRECISION.md). Four sections, one artifact:

    * interleaved f32-vs-bf16 steady-window A/B on the shared scan harness
      (min-of-windows; arms alternate within each window round so drift
      hits both equally). Includes the FULL bf16 policy arm (loss
      scaling riding the scan carry) so the scaling overhead is visible next
      to compute-dtype-only bf16. CPU timings are labeled non-meaningful —
      XLA:CPU emulates bf16.
    * step-matched same-seed convergence: identical batch sequence and step
      count through the f32 step vs the scaled bf16 step; the final-epoch
      loss rel-diff gate is committed here (acceptance pin).
    * loss-scale event counts from a seeded ``nan_grad@K`` drill through the
      faults layer (overflow/backoff/growth counters, zero rollbacks).
    * serve quantized arms: bf16 + int8 engines over a warmed ladder —
      tolerance-gate stats and recompiles_after_warmup.

    Writes PRECISION_rNN.json; failure embeds the last known A/B,
    stale-labeled, per the established convention."""
    result = {
        "metric": "precision_ab",
        "value": 0.0,
        "unit": "f32_over_bf16_policy_steady_window_time",
    }
    from hydragnn_tpu.utils.artifacts import round_tag

    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        f"PRECISION_r{round_tag()}.json",
    )
    try:
        import jax

        from hydragnn_tpu.precision import LossScaleConfig

        result.update(_device_block())
        backend = result["backend"]
        result["timings_meaningful"] = backend == "tpu"
        if backend != "tpu":
            result["timings_note"] = (
                "CPU backend: XLA:CPU emulates bf16 (typically SLOWER than "
                "f32) — the window timings certify workload health only; "
                "the TPU speedup claim waits on the next hardware batch. "
                "Convergence and tolerance gates are backend-valid."
            )

        # ------------------------- interleaved steady-window A/B (3 arms)
        steps, windows = 20, 4
        arm_specs = (
            ("f32", None, None),
            ("bf16_compute", "bfloat16", None),
            ("bf16_policy", "bfloat16", LossScaleConfig()),
        )
        arms = {}
        for name, dtype, scaling in arm_specs:
            compiled, state, stacked, key, _, compile_s = _scan_harness(
                128, HIDDEN, LAYERS, steps,
                seed=0, compute_dtype=dtype, loss_scaling=scaling,
            )
            state, metrics = compiled(state, stacked, key)  # warmup dispatch
            jax.block_until_ready(metrics["loss"])
            arms[name] = {
                "compiled": compiled, "state": state, "stacked": stacked,
                "key": key, "times": [], "compile_s": compile_s,
            }
        from hydragnn_tpu.analysis import no_recompile

        with no_recompile(action="raise", label="precision A/B windows"):
            for _ in range(windows):
                for name in arms:  # interleaved: each round times every arm
                    a = arms[name]
                    t0 = time.perf_counter()
                    a["state"], metrics = a["compiled"](
                        a["state"], a["stacked"], a["key"]
                    )
                    jax.block_until_ready(metrics["loss"])
                    a["times"].append(time.perf_counter() - t0)
        for name, a in arms.items():
            best = min(a["times"])
            result[name] = {
                "steady_step_ms": round(1000.0 * best / steps, 4),
                "steady_step_ms_median": round(
                    1000.0 * sorted(a["times"])[len(a["times"]) // 2] / steps,
                    4,
                ),
                "compile_s": round(a["compile_s"], 3),
            }
        result["value"] = round(
            min(arms["f32"]["times"]) / min(arms["bf16_policy"]["times"]), 3
        )
        result["bf16_compute_speedup"] = round(
            min(arms["f32"]["times"]) / min(arms["bf16_compute"]["times"]), 3
        )

        # --------------------- step-matched same-seed convergence (gated)
        epochs, conv_steps = 8, 10
        curves = {}
        for name, dtype, scaling in (
            ("f32", None, None),
            ("bf16_policy", "bfloat16", LossScaleConfig()),
        ):
            compiled, state, stacked, key, _, _ = _scan_harness(
                64, 32, LAYERS, conv_steps,
                seed=2, compute_dtype=dtype, loss_scaling=scaling,
            )
            curve = []
            for _ in range(epochs):
                state, metrics = compiled(state, stacked, key)
                curve.append(
                    round(
                        float(metrics["loss"]) / float(metrics["count"]), 6
                    )
                )
            curves[name] = curve
        final_f32, final_bf16 = curves["f32"][-1], curves["bf16_policy"][-1]
        # The pinned gate (acceptance criterion): bf16-with-master-weights
        # tracks the same-seed f32 trajectory step for step. Normalized by
        # the INITIAL loss — the tier-1 convention
        # (tests/test_mixed_precision.py pytest_bf16_tracks_f32_training):
        # once the loss has decayed by 10x+, a final-loss denominator turns
        # bf16 rounding noise into a fake divergence, while a real
        # divergence is O(initial) and still trips this gate. Measured on
        # CPU at ~0.016; 0.05 absorbs backend drift.
        rel = abs(final_bf16 - final_f32) / max(abs(curves["f32"][0]), 1e-9)
        gate = 0.05
        result["convergence"] = {
            "steps_per_epoch": conv_steps,
            "epochs": epochs,
            "f32_loss_curve": curves["f32"],
            "bf16_loss_curve": curves["bf16_policy"],
            "final_diff_rel_initial": round(rel, 6),
            "gate_rel_initial": gate,
            "ok": bool(rel < gate),
        }

        # ---------------------------- loss-scale events (faults-layer drill)
        from hydragnn_tpu.faults import FaultCounters, FaultPlan
        from hydragnn_tpu.graphs import GraphSample
        from hydragnn_tpu.models import create_model, init_model_variables
        from hydragnn_tpu.preprocess.dataloader import GraphDataLoader
        from hydragnn_tpu.telemetry import graftel as telemetry
        from hydragnn_tpu.train.train_validate_test import TrainingDriver
        from hydragnn_tpu.train.trainer import create_train_state
        from hydragnn_tpu.utils.optimizer import select_optimizer

        FaultCounters.reset()
        telemetry.clear_counters("prec/")
        rng = np.random.default_rng(0)
        drill_graphs = []
        for _ in range(48):
            n = int(rng.integers(4, 10))
            x = rng.normal(size=(n, 1)).astype(np.float32)
            ei = np.stack(
                [np.arange(n), (np.arange(n) + 1) % n]
            ).astype(np.int32)
            drill_graphs.append(
                GraphSample(
                    x=x, pos=np.zeros((n, 3), np.float32),
                    y=np.array([x.sum()], np.float32),
                    y_loc=np.array([[0, 1]], np.int64), edge_index=ei,
                )
            )
        loader = GraphDataLoader(drill_graphs, batch_size=8, shuffle=False)
        loader.set_head_spec(("graph",), (1,))
        heads = {
            "graph": {
                "num_sharedlayers": 1, "dim_sharedlayers": 8,
                "num_headlayers": 2, "dim_headlayers": [8, 8],
            }
        }
        model = create_model(
            "SAGE", 1, 8, (1,), ("graph",), heads, [1.0], 2
        )
        variables = init_model_variables(model, next(iter(loader)))
        opt = select_optimizer("AdamW", 5e-3)
        driver = TrainingDriver(
            model, opt, create_train_state(model, variables, opt),
            precision="bf16",
            loss_scale={"init": 2.0**12, "growth_interval": 1000},
            fault_tolerance={"enabled": 1, "max_bad_steps": 3},
            fault_plan=FaultPlan("nan_grad@2"),
        )
        drill_loss = None
        for epoch in range(2):
            loader.set_epoch(epoch)
            drill_loss, _ = driver.train_epoch(loader)
        result["loss_scale_events"] = {
            "drill": "nan_grad@2 under precision=bf16",
            "overflow": int(telemetry.counter_value("prec/overflow")),
            "backoff": int(telemetry.counter_value("prec/backoff")),
            "growth": int(telemetry.counter_value("prec/growth")),
            "bad_steps": FaultCounters.get("bad_steps"),
            "rollbacks": driver.guard.rollbacks,
            "final_scale": float(driver.state.loss_scale.scale),
            "final_loss_finite": bool(np.isfinite(drill_loss)),
        }

        # ------------------------------------ serve quantized-arm tolerance
        import __graft_entry__ as ge
        from hydragnn_tpu.graphs import collate_graphs
        from hydragnn_tpu.serve import InferenceEngine

        srng = np.random.default_rng(0)
        serve_graphs = ge._make_graphs(12, srng)
        smodel = ge._build_model(hidden=8, layers=2)
        sbatch = collate_graphs(serve_graphs[:2], ge.TYPES, ge.DIMS, edge_dim=1)
        svars = init_model_variables(smodel, sbatch)
        from hydragnn_tpu.serve import PrecisionToleranceError

        result["serve"] = {}
        for arm, tol in (("bf16", 5e-2), ("int8", 5e-2)):
            eng = InferenceEngine(
                smodel, svars, precision=arm, tolerance=tol,
                max_batch_graphs=8, bucket_ladder=[(256, 1024)], warmup=True,
            )
            try:
                try:
                    gate_report = eng.check_tolerance()
                except PrecisionToleranceError as gate_exc:
                    # A failed gate is a RESULT, not a crashed round: record
                    # the verdict (gate_ok=False fails the overall ok below)
                    # and keep measuring the other arm — the artifact must
                    # stay diagnosable.
                    gate_report = gate_exc.report
                arm_block = {
                    "gate_ok": bool(gate_report["ok"]),
                    "max_abs_diff": gate_report["fwd_err"],
                    "tolerance": tol,
                    "per_head": gate_report["per_head"],
                    **(
                        {"quantization": gate_report["quantization"]}
                        if "quantization" in gate_report
                        else {}
                    ),
                }
                if gate_report["ok"]:
                    misses0 = eng.metrics.snapshot()["bucket_cache"]["misses"]
                    eng.predict(serve_graphs[:8])
                    snap = eng.metrics.snapshot()
                    arm_block["recompiles_after_warmup"] = (
                        snap["bucket_cache"]["misses"] - misses0
                    )
                result["serve"][arm] = arm_block
            finally:
                eng.close()

        result["ok"] = bool(
            result["convergence"]["ok"]
            and result["loss_scale_events"]["rollbacks"] == 0
            and result["loss_scale_events"]["backoff"] >= 1
            and all(
                a["gate_ok"] and a.get("recompiles_after_warmup") == 0
                for a in result["serve"].values()
            )
        )
        with open(out_path, "w") as f:
            json.dump(result, f, indent=2)
        result["artifact"] = os.path.basename(out_path)
    except Exception as e:
        import traceback

        result["error"] = f"{type(e).__name__}: {e}"
        result["trace_tail"] = traceback.format_exc()[-1500:]
        try:
            stale = _last_known_precision()
            if stale is not None:
                result["last_known_precision"] = stale
        except Exception:
            pass
        print(json.dumps(result))
        return 1
    print(json.dumps(result))
    return 0 if result.get("ok") else 1


def faults_main() -> int:
    """``python bench.py --faults``: run the deterministic fault-drill matrix
    (benchmarks/fault_drills.py) and print it as the round's FAULTS_rNN.json
    line: per-drill pass/fail + mechanism + counters, guard bit-inertness,
    and the guard's steady-epoch overhead %. CPU-safe (the drills are seeded
    and hardware-independent); failure prints a diagnostic line embedding the
    last known drill matrix, stale-labeled, per the established convention."""
    result = {
        "metric": "fault_drills",
        "value": 0.0,
        "unit": "drills_passed_frac",
    }
    try:
        import jax

        result.update(_device_block())
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from benchmarks.fault_drills import run_fault_drills

        result.update(run_fault_drills())
    except Exception as e:
        import traceback

        result["error"] = f"{type(e).__name__}: {e}"
        result["trace_tail"] = traceback.format_exc()[-1500:]
        try:
            stale = _last_known_faults()
            if stale is not None:
                result["last_known_faults"] = stale
        except Exception:
            pass
        print(json.dumps(result))
        return 1
    print(json.dumps(result))
    return 0 if result["value"] == 1.0 else 1


def analyze_main() -> int:
    """``python bench.py --analyze``: the round's static-health line
    (ANALYSIS_rNN.json) — graftlint + graftrace + graftproto rule hit
    counts + the reasoned-suppression audit over the package, the
    thread-root/lock-graph summary, the lockstep-segment/persistence-point
    census with the full crash-consistency model-check verdict, the seeded
    tsan drill outcome over the serve + async-checkpoint paths, and
    check-config wall time over the committed CI configs — so the
    trajectory artifacts track static health alongside perf. CPU-safe and
    hardware-free by construction."""
    result = {
        "metric": "static_analysis",
        "value": 0.0,
        "unit": "unsuppressed_violations",
    }
    try:
        repo = os.path.dirname(os.path.abspath(__file__))
        sys.path.insert(0, repo)
        from hydragnn_tpu.analysis import (
            lint_paths,
            load_baseline,
            new_violations,
            trace_paths,
        )

        t0 = time.perf_counter()
        report = lint_paths([os.path.join(repo, "hydragnn_tpu")], root=repo)
        fresh = new_violations(report, load_baseline())
        t1 = time.perf_counter()
        # The concurrency pass (suppression meta-check owned by the lint
        # pass above — shared grammar, single catalogue).
        trace = trace_paths(
            [os.path.join(repo, "hydragnn_tpu")],
            root=repo,
            check_suppressions=False,
        )
        trace_fresh = new_violations(trace, load_baseline())
        result.update(
            value=float(len(report.violations) + len(trace.violations)),
            lint_s=round(t1 - t0, 3),
            files=report.files,
            traced_functions=report.traced_functions,
            rule_counts=report.counts(),
            new_vs_baseline=len(fresh) + len(trace_fresh),
            suppressions=len(report.suppressed) + len(trace.suppressed),
            suppression_reasons=[
                v.reason for v in report.suppressed + trace.suppressed
            ],
        )
        from hydragnn_tpu.analysis.rules import CONCURRENCY_RULES

        result["graftrace"] = {
            "trace_s": round(time.perf_counter() - t1, 3),
            "rule_counts": {
                rule: n
                for rule, n in trace.counts().items()
                if rule in CONCURRENCY_RULES
            },
            "thread_roots": sorted(trace.thread_roots),
            "shared_attrs": len(trace.shared_attrs),
            "declared_attrs": trace.declared_attrs,
            "lock_edges": len(trace.lock_edges),
            "lock_cycles": trace.lock_cycles,
        }
        # The runtime half: the seeded HYDRAGNN_TSAN=1 drill in a FRESH
        # process (class-level locks instrument at import time there).
        t2 = time.perf_counter()
        drill_proc = subprocess.run(
            [
                sys.executable,
                os.path.join(repo, "benchmarks", "tsan_drill.py"),
                "--seed",
                "0",
                "--json",
            ],
            capture_output=True,
            text=True,
            cwd=repo,
            timeout=900,
        )
        try:
            drill = json.loads(drill_proc.stdout.strip().splitlines()[-1])
        except Exception:
            drill = {
                "ok": False,
                "error": (drill_proc.stdout + drill_proc.stderr)[-800:],
            }
        result["tsan_drill"] = {
            "drill_s": round(time.perf_counter() - t2, 3),
            "ok": drill.get("ok", False),
            "seed": drill.get("seed"),
            "dynamic_inversions": drill.get("dynamic_inversions"),
            "unregistered_cross_thread": drill.get(
                "unregistered_cross_thread"
            ),
            "schedule_sha256": drill.get("schedule_sha256"),
            **({"error": drill["error"]} if "error" in drill else {}),
        }

        # The distributed-control-plane pass (graftproto) + its runtime
        # half: the FULL crash-consistency sweep (every scenario, every
        # auto-discovered persistence point, kill + exception per visit) —
        # the drill above only ran the CI smoke subset.
        t3 = time.perf_counter()
        from hydragnn_tpu.analysis import model_check, proto_paths
        from hydragnn_tpu.analysis.graftlint import Linter, Report
        from hydragnn_tpu.analysis.rules import PROTO_RULES

        proto = proto_paths(
            [os.path.join(repo, "hydragnn_tpu")],
            root=repo,
            check_suppressions=False,
        )
        proto_fresh = new_violations(proto, load_baseline())
        t4 = time.perf_counter()
        verdict = model_check(seed=0)
        audit_linter = Linter(
            [os.path.join(repo, "hydragnn_tpu")], root=repo
        )
        audit_linter.load(Report())
        audit = [
            {"file": m.relpath, "line": line, "rule": rule,
             "reason": reason or None}
            for m in audit_linter.modules
            for line, (rule, reason) in sorted(m.suppressions.items())
        ]
        result["graftproto"] = {
            "proto_s": round(t4 - t3, 3),
            "rule_counts": {
                rule: n
                for rule, n in proto.counts().items()
                if rule in PROTO_RULES
            },
            "new_vs_baseline": len(proto_fresh),
            "lockstep_segments": sorted(proto.lockstep_segments),
            "persistence_points": len(proto.persistence_points),
            "collective_functions": len(proto.collective_functions),
            "modelcheck_s": round(time.perf_counter() - t4, 3),
            "modelcheck": {
                "ok": verdict["ok"],
                "seed": verdict["seed"],
                "num_points": verdict["num_points"],
                "num_injections": verdict["num_injections"],
                "points": verdict["points"],
                "novel_points": verdict["novel_points"],
                "known_drilled": verdict["known_drilled"],
                "failures": verdict["failures"],
                "schedule_sha256": verdict["schedule_sha256"],
            },
            "suppression_audit": {
                "count": len(audit),
                "reasonless": [a for a in audit if not a["reason"]],
            },
        }
        result["value"] += float(len(proto.violations))

        from hydragnn_tpu.analysis import check_config

        cc = {}
        for name in ("ci.json", "ci_multihead.json", "ci_vectoroutput.json"):
            t0 = time.perf_counter()
            rep = check_config(
                os.path.join(repo, "tests/inputs", name),
                mode="training",
                strict=False,
            )
            cc[name] = {
                "ok": rep["ok"],
                "wall_s": round(time.perf_counter() - t0, 3),
                "eval_shape_s": rep["eval_shape_s"],
            }
        result["check_config"] = cc
        result["check_config_wall_s"] = round(
            sum(v["wall_s"] for v in cc.values()), 3
        )
        configs_ok = all(v["ok"] for v in cc.values())
    except Exception as e:
        import traceback

        result["error"] = f"{type(e).__name__}: {e}"
        result["trace_tail"] = traceback.format_exc()[-1500:]
        print(json.dumps(result))
        return 1
    print(json.dumps(result))
    ok = (
        result["new_vs_baseline"] == 0
        and configs_ok
        and result["tsan_drill"]["ok"]
        and not result["graftrace"]["lock_cycles"]
        and result["graftproto"]["new_vs_baseline"] == 0
        and result["graftproto"]["modelcheck"]["ok"]
        and not result["graftproto"]["suppression_audit"]["reasonless"]
    )
    return 0 if ok else 1


def serve_main() -> int:
    """``python bench.py --serve``: run the online-serving load benchmark
    (benchmarks/serve_load.py) and print its block as the round's serving
    JSON line. Failure prints a diagnostic line that embeds the last known
    serving measurement (stale-labeled)."""
    result = {
        "metric": "serve_saturation_throughput",
        "value": 0.0,
        "unit": "graphs/sec",
    }
    try:
        import jax

        result.update(_device_block())
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from benchmarks.serve_load import run_serve_benchmark

        block = run_serve_benchmark()
        result["value"] = block["saturation_graphs_per_sec"]
        result["serve"] = block
    except Exception as e:
        import traceback

        result["error"] = f"{type(e).__name__}: {e}"
        result["trace_tail"] = traceback.format_exc()[-1500:]
        try:
            stale = _last_known_serving()
            if stale is not None:
                result["last_known_serving"] = stale
        except Exception:
            pass
        print(json.dumps(result))
        return 1
    print(json.dumps(result))
    return 0


def router_main() -> int:
    """``python bench.py --router``: run the multi-replica router rig
    (benchmarks/serve_load.py run_router_benchmark — fleet open-loop sweep,
    kill-a-replica drill, scale-up-under-load drill) and print its block as
    the round's ROUTER JSON line. Failure embeds the last known router
    measurement (stale-labeled), mirroring the other bench arms."""
    result = {
        "metric": "router_fleet_p99_ms_at_top_load",
        "value": 0.0,
        "unit": "ms",
    }
    try:
        import jax

        result.update(_device_block())
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from benchmarks.serve_load import run_router_benchmark

        block = run_router_benchmark()
        result["value"] = block["open_loop"][-1]["fleet_p99_ms"]
        result["kill_drill_zero_lost"] = block["kill_replica_drill"][
            "zero_lost"
        ]
        result["scaleup_warmup_xla_compiles"] = block["scaleup_drill"][
            "warm_spinup"
        ]["warmup_xla_compiles"]
        result["router"] = block
    except Exception as e:
        import traceback

        result["error"] = f"{type(e).__name__}: {e}"
        result["trace_tail"] = traceback.format_exc()[-1500:]
        try:
            stale = _last_known_router()
            if stale is not None:
                result["last_known_router"] = stale
        except Exception:
            pass
        print(json.dumps(result))
        return 1
    print(json.dumps(result))
    return 0


def swap_main() -> int:
    """``python bench.py --swap``: run the live-lifecycle rig
    (benchmarks/serve_load.py run_swap_benchmark — swap-under-load +
    rollback, corrupt-candidate, shadow-gate-rejects, kill-during-swap
    drills) and print its block as the round's SWAP JSON line. Exit 1 when
    any drill fails OR the swap-window p99 exceeds 1.5x steady (the ISSUE 13
    acceptance gate); failure embeds the last known swap measurement
    (stale-labeled), mirroring the other bench arms."""
    result = {
        "metric": "swap_under_load_p99_ratio",
        "value": 0.0,
        "unit": "x_steady_fleet_p99",
    }
    try:
        import jax

        result.update(_device_block())
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from benchmarks.serve_load import run_swap_benchmark

        block = run_swap_benchmark()
        sul = block["swap_under_load"]
        result["value"] = sul.get("p99_swap_over_steady") or 0.0
        result["drills_passed"] = block["drills_passed"]
        result["drills_total"] = block["drills_total"]
        result["recompiles_after_swap"] = sul.get("recompiles_after_swap")
        result["zero_version_torn"] = sul.get("zero_version_torn")
        result["swap"] = block
        ok = (
            block["drills_passed"] == block["drills_total"]
            and result["value"] > 0
            and result["value"] <= 1.5
        )
        print(json.dumps(result))
        return 0 if ok else 1
    except Exception as e:
        import traceback

        result["error"] = f"{type(e).__name__}: {e}"
        result["trace_tail"] = traceback.format_exc()[-1500:]
        try:
            stale = _last_known_swap()
            if stale is not None:
                result["last_known_swap"] = stale
        except Exception:
            pass
        print(json.dumps(result))
        return 1


def flywheel_main() -> int:
    """``python bench.py --flywheel``: run the continuous-learning soak
    (benchmarks/flywheel_soak.py — serve load + concurrent fine-tuning with
    shadow-gated auto-promotions, a refused poisoned candidate, a
    drift-triggered ladder refit + fleet swap, and the kill-during-promotion
    incarnation drill) and print its block as the round's FLYWHEEL JSON
    line. Exit 1 when any drill fails; failure embeds the last known soak
    (stale-labeled), mirroring the other bench arms."""
    result = {
        "metric": "flywheel_soak",
        "value": 0.0,
        "unit": "drills_passed",
    }
    try:
        import jax

        result.update(_device_block())
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from benchmarks.flywheel_soak import run_flywheel_benchmark

        block = run_flywheel_benchmark()
        soak = block["soak"]
        result["value"] = float(block["drills_passed"])
        result["drills_passed"] = block["drills_passed"]
        result["drills_total"] = block["drills_total"]
        result["promotions"] = (soak.get("counters") or {}).get("promotions")
        result["rejections"] = (soak.get("counters") or {}).get("rejections")
        result["poisoned_never_served"] = soak.get("poisoned_never_served")
        result["recompiles_after_warmup"] = soak.get("recompiles_after_warmup")
        result["lost_total"] = soak.get("lost_total")
        result["flywheel"] = block
        ok = block["drills_passed"] == block["drills_total"]
        print(json.dumps(result))
        return 0 if ok else 1
    except Exception as e:
        import traceback

        result["error"] = f"{type(e).__name__}: {e}"
        result["trace_tail"] = traceback.format_exc()[-1500:]
        try:
            stale = _last_known_flywheel()
            if stale is not None:
                result["last_known_flywheel"] = stale
        except Exception:
            pass
        print(json.dumps(result))
        return 1


def pilot_main() -> int:
    """``python bench.py --pilot``: run the fleet-autopilot drills
    (benchmarks/pilot_drills.py — a 10x flash crowd under hysteresis
    autoscaling + the brownout ladder, tenant-bulkhead isolation,
    scale-to-zero with a zero-compile cold wake, and a replica kill under
    autoscale) and print the block as the round's PILOT JSON line. Exit 1
    when any drill fails; failure embeds the last known drill set
    (stale-labeled), mirroring the other bench arms."""
    result = {
        "metric": "pilot_drills",
        "value": 0.0,
        "unit": "drills_passed",
    }
    try:
        import jax

        result.update(_device_block())
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from benchmarks.pilot_drills import run_pilot_benchmark

        block = run_pilot_benchmark()
        crowd = block["flash_crowd_drill"]
        result["value"] = float(block["drills_passed"])
        result["drills_passed"] = block["drills_passed"]
        result["drills_total"] = block["drills_total"]
        result["lost_total"] = crowd.get("lost_total")
        result["brownout_shed_non_ensemble"] = crowd.get(
            "brownout_shed_non_ensemble"
        )
        result["scale_up_total"] = crowd.get("scale_up_total")
        result["warmup_xla_compiles"] = block["scale_to_zero_drill"].get(
            "warmup_xla_compiles"
        )
        result["pilot"] = block
        ok = block["drills_passed"] == block["drills_total"]
        print(json.dumps(result))
        return 0 if ok else 1
    except Exception as e:
        import traceback

        result["error"] = f"{type(e).__name__}: {e}"
        result["trace_tail"] = traceback.format_exc()[-1500:]
        try:
            stale = _last_known_pilot()
            if stale is not None:
                result["last_known_pilot"] = stale
        except Exception:
            pass
        print(json.dumps(result))
        return 1


def main():
    """The default invocation: the training benchmark, on the TPU. There is
    no fallback — no chip, an unknown chip, or a failing phase is a non-zero
    exit with a diagnostic line that carries no figure."""
    result = {
        "metric": "train_throughput_pna_multitask",
        "unit": "graphs/sec/chip",
    }
    try:
        result.update(_device_block())
        if result["platform"] != "tpu":
            raise RuntimeError(
                f"bench.py measures the TPU; JAX found platform "
                f"{result['platform']!r} ({result['device_kind']}). A CPU "
                "run gives no device metric and none is printed."
            )
        _chip_peak_flops()  # an unknown chip fails here, before any work
        result.update(_peak_workload())
        result.pop("flops_per_step", None)  # internal to the MFU computation
        result["vs_baseline"] = round(
            result["value"] / BASELINE_GRAPHS_PER_SEC, 3
        )
        result.update(_production_workload())
        # Device-resident variant (Training.reshuffle="batch").
        result.update(_cached_epoch_workload())
        # MFU at a hardware-meaningful model size (see _mfu_workload).
        result.update(_mfu_workload())
    except Exception as e:  # diagnostic JSON instead of a bare traceback
        import traceback

        result["error"] = f"{type(e).__name__}: {e}"
        result["trace_tail"] = traceback.format_exc()[-1500:]
        print(json.dumps(result))
        sys.exit(1)
    print(json.dumps(result))


if __name__ == "__main__":
    from hydragnn_tpu.cache.jaxcache import place_jax_cache

    place_jax_cache()
    if "--serve" in sys.argv:
        sys.exit(serve_main())
    if "--router" in sys.argv:
        sys.exit(router_main())
    if "--swap" in sys.argv:
        sys.exit(swap_main())
    if "--flywheel" in sys.argv:
        sys.exit(flywheel_main())
    if "--pilot" in sys.argv:
        sys.exit(pilot_main())
    if "--faults" in sys.argv:
        sys.exit(faults_main())
    if "--packing" in sys.argv:
        sys.exit(packing_main())
    if "--trace" in sys.argv:
        sys.exit(trace_main())
    if "--compile-cache" in sys.argv:
        sys.exit(compile_cache_main())
    if "--multichip" in sys.argv:
        sys.exit(multichip_main())
    if "--elastic" in sys.argv:
        sys.exit(elastic_main())
    if "--stream" in sys.argv:
        sys.exit(stream_main())
    if "--precision" in sys.argv:
        sys.exit(precision_main())
    if "--analyze" in sys.argv:
        sys.exit(analyze_main())
    main()
