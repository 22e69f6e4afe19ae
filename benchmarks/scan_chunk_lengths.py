#!/usr/bin/env python3
"""The table behind ``train/train_validate_test.py`` ``SCAN_CHUNK``: what a
chunk of ``L`` steps a dispatch gives and costs, on the chip, in the
benchmark's one-chip train cells.

A cell is built ONCE, as ``graftbench/drivers/train_epochs.py`` builds it
(its data, loaders, model, optimizer, plateau scheduler, ``TrainingDriver``),
and then for each ``L``, with ``driver.scan_chunk = L``:

    warm-up   one epoch of ``train_validate_test`` (compiles that length's
              scan program: one a batch shape)
    rate      whole epochs (train + validation + test, as the cells run them)
              for ``--seconds``, tracing off: train graphs a second
    traced    ``--traced-epochs`` epochs under the profiler, reduced by the
              benchmark's own ``graftbench/trace_reduce.py``: the device's idle
              share, the idle seconds under ``device_step`` a dispatch (the
              gap a chunk costs), the epoch's first ``feed_wait`` (what a short
              chunk saves), device milliseconds a step

The state trains on from one ``L`` to the next: the steps cost the same.
``L`` = 64 is what the scan path did before PR 36 (an epoch of 12-45 batches
in one chunk a shape).

The parent process never imports JAX; each cell is one child that holds the
chip alone. Refuses to run anywhere but on a TPU. Prints one JSON line a
(cell, L) and writes the table to ``chiprun_out/scan_chunk_lengths.json``:

    python3 benchmarks/scan_chunk_lengths.py [--cells a,b] [--lengths 1,2,4]

``--rehearse-on-cpu`` walks the same code over 96 graphs in batches of 8,
untraced, and writes nothing: it finds wrong arguments, and its times mean
nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = (
    "pna_multihead_h256.train_b512",
    "gatv2_h64x6_md17like.train_b512",
    "painn_f128.train_b512",
    "lfm2_8b_a1b_ep4.train_seq1k_b4",
    "laguna_xs2_ep8.train_seq4k_b1",
)
LENGTHS = (1, 2, 4, 8, 64)


def run_cell(name, lengths, seconds, traced_epochs, seed, rehearsal):
    """One child: build the cell, walk the lengths; a JSON line each."""
    sys.path.insert(0, ROOT)
    import jax

    from graftbench import run as harness
    from graftbench import trace_reduce
    from graftbench.drivers.train_epochs import build
    from graftbench.layer_metrics import device_step_ms, first_batch_wait_ms
    from hydragnn_tpu import telemetry
    from hydragnn_tpu.cache.jaxcache import place_jax_cache
    from hydragnn_tpu.train.train_validate_test import (
        TrainingDriver,
        train_validate_test,
    )
    from hydragnn_tpu.train.trainer import create_train_state
    from hydragnn_tpu.utils.optimizer import ReduceLROnPlateau, select_optimizer

    device = jax.devices()[0]
    if device.platform != "tpu" and not rehearsal:
        print(f"needs a TPU, found {device.platform}: a CPU's time is no device time")
        return 3
    place_jax_cache()
    _, entry, config, traffic = harness._load_cell(name)
    if rehearsal:
        traffic["graphs"]["graphs"], traffic["batch_size"] = 96, 8
    cell = harness.Cell(
        entry, config, traffic,
        types.SimpleNamespace(seed=seed, trace=0, seconds=seconds),
        jax.devices()[:1], None,
    )
    os.makedirs(cell.out_dir, exist_ok=True)
    os.chdir(cell.out_dir)  # the program's logs/ and serialized_dataset/
    os.environ["SERIALIZED_DATA_PATH"] = cell.out_dir
    b = build(cell)
    train_loader, val_loader, test_loader = b["loaders"]
    training = b["config"]["NeuralNetwork"]["Training"]
    optimizer = select_optimizer(
        training["optimizer"], training["learning_rate"],
        freeze_conv=b["arch"]["freeze_conv_layers"],
    )
    scheduler = ReduceLROnPlateau(
        factor=0.5, patience=int(traffic.get("plateau_patience", 5)), min_lr=0.00001,
    )
    state = create_train_state(b["model"], b.pop("variables"), optimizer)
    driver = TrainingDriver(
        b["model"], optimizer, state, verbosity=0,
        precision=training.get("precision"),
    )
    del state
    history, epoch = None, 0

    def one_epoch():
        nonlocal history, epoch
        history = train_validate_test(
            driver, train_loader, val_loader, test_loader, epoch + 1,
            scheduler=scheduler, verbosity=0, start_epoch=epoch,
            history=history, checkpoint_every=0,
        )
        epoch += 1

    graphs = len(train_loader.dataset)
    steps = len(train_loader)
    trace_dir = os.path.join(cell.out_dir, "scan_chunk_trace")
    for length in lengths:
        driver.scan_chunk = length
        telemetry.configure(collect=False, jax_annotations=False)
        t0 = time.perf_counter()
        one_epoch()
        row = {
            "cell": name, "L": length, "device": device.device_kind,
            "steps_an_epoch": steps, "warmup_epoch_s": time.perf_counter() - t0,
        }
        epochs, train_s, wait_s = 0, 0.0, 0.0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            one_epoch()
            gauges = telemetry.gauges_snapshot()
            train_s += gauges["train/epoch_wall_s"]
            wait_s += gauges["train/feed_wait_s_per_epoch"]
            epochs += 1
        wall = time.perf_counter() - t0
        row.update(
            epochs=epochs, graphs_per_s=epochs * graphs / wall,
            epoch_ms=1e3 * wall / epochs, train_epoch_ms=1e3 * train_s / epochs,
            feed_wait_ms_an_epoch=1e3 * wait_s / epochs,
            chunks_an_epoch=gauges["train/scan_chunks_per_epoch"],
        )
        if not rehearsal:
            import shutil

            shutil.rmtree(trace_dir, ignore_errors=True)
            telemetry.configure(collect=True, jax_annotations=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            with telemetry.span(trace_reduce.WINDOW):
                for _ in range(traced_epochs):
                    one_epoch()
            jax.profiler.stop_trace()
            spans = [r for r in telemetry.collected_records() if r["kind"] == "span"]
            reduced = trace_reduce.reduce_dir(trace_dir, {r["name"] for r in spans})
            dispatches = sum(r["name"] == "device_step" for r in spans)
            gaps = dict(reduced["idle_gaps"])
            # The benchmark's own readers, over this window.
            run = types.SimpleNamespace(
                spans=spans, trace=reduced, facts={"steps": traced_epochs * steps}
            )
            row.update(
                traced_window_s=reduced["window_s"],
                device_idle_share=reduced["idle_share_worst"],
                device_step_ms=device_step_ms.read(run),
                dispatches=dispatches,
                gap_ms_a_dispatch=1e3 * gaps.get("device_step", 0.0) / max(dispatches, 1),
                first_batch_wait_ms=first_batch_wait_ms.read(run),
                idle_gaps_s={k: round(v, 4) for k, v in reduced["idle_gaps"][:5]},
            )
        print("ROW " + json.dumps(row), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--lengths", default=",".join(map(str, LENGTHS)))
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--traced-epochs", type=int, default=2)
    ap.add_argument("--seed", type=int, default=3609280001)
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    ap.add_argument("--child", help=argparse.SUPPRESS)
    args = ap.parse_args()
    lengths = [int(v) for v in args.lengths.split(",")]
    if args.child:
        return run_cell(
            args.child, lengths, args.seconds, args.traced_epochs, args.seed,
            args.rehearse_on_cpu,
        )
    table = []
    for name in args.cells.split(","):
        cmd = [
            sys.executable, os.path.abspath(__file__), "--child", name,
            "--lengths", args.lengths, "--seconds", str(args.seconds),
            "--traced-epochs", str(args.traced_epochs), "--seed", str(args.seed),
        ] + ["--rehearse-on-cpu"] * args.rehearse_on_cpu
        child = subprocess.run(cmd, stdout=subprocess.PIPE, text=True, cwd=ROOT)
        rows = [
            json.loads(line[4:]) for line in child.stdout.splitlines()
            if line.startswith("ROW ")
        ]
        for row in rows:
            print(json.dumps(row), flush=True)
        table.extend(rows)
        if not args.rehearse_on_cpu:  # cell by cell: a cut call keeps the rest
            os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
            with open(
                os.path.join(ROOT, "chiprun_out", "scan_chunk_lengths.json"), "w"
            ) as fh:
                json.dump(table, fh, indent=1)
        if child.returncode:
            print(f"{name}: exit {child.returncode}\n{child.stdout[-2000:]}")
            return child.returncode
    if args.rehearse_on_cpu:
        print("rehearsal: no time here is a device time; nothing written")
    return 0


if __name__ == "__main__":
    sys.exit(main())
