#!/usr/bin/env python3
"""The engine's OWN stage clocks over an untraced window of the lattice
serving cell's closed loop (``pna_multihead_h256.serve_closed_lattice``).

The benchmark's ``--trace 0`` line holds the end-to-end metrics alone, and
under the profiler the host's stages read long (ROADMAP S6/S12), so where a
flush's host time goes is read here: the cell's pool, engine, warm-up and 64
clients exactly as ``graftbench/drivers/serve_closed.py`` builds them (its
functions, imported), ``--seconds`` of the loop with tracing off, then every
clock of ``ServeMetrics.latency`` as it moved over the window, as a mean in
ms (``prepare``, ``queue_wait``, ``e2e`` a request; ``collate``, ``handoff``,
``h2d``, ``device``, ``d2h``, ``resolve``, ``turnaround`` a flush; a clock the
engine has not: null), the counters beside
them and the loop's own rate and percentiles. One JSON line, also written to
``chiprun_out/``:

    python3 benchmarks/serve_stage_clocks.py --seed 4200000001 [--seconds 25]

Refuses to run anywhere but on a TPU (a CPU's time is no device time);
``--rehearse-on-cpu`` walks the same code over a pool of small lattices and
writes nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "pna_multihead_h256.serve_closed_lattice"
A_REQUEST = ("prepare", "queue_wait", "e2e")
A_FLUSH = ("collate", "handoff", "h2d", "device", "d2h", "resolve", "turnaround")


def clocks(engine) -> dict:
    lat = engine.metrics.latency
    return {s: (lat[s].sum, lat[s].count) for s in A_REQUEST + A_FLUSH if s in lat}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    args = ap.parse_args()

    import jax

    from graftbench.drivers import serve_closed as drv

    if jax.default_backend() != "tpu" and not args.rehearse_on_cpu:
        sys.exit("serve_stage_clocks: no TPU here; --rehearse-on-cpu walks the code")
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    config = json.load(open(os.path.join(ROOT, next(
        c["file"] for c in bench["configs"] if c["name"] == entry["config"]
    ))))
    traffic = json.load(open(os.path.join(
        ROOT, "graftbench", "traffic", entry["traffic"] + ".json"
    )))
    if args.rehearse_on_cpu:
        traffic["graphs"].update(cell_x=[2, 3], cell_y=[2, 3], cell_z=[2, 4], per_shape=4)
        traffic.update(clients=8, bucket_ladder=[[1024, 32768]])
        traffic["engine"]["max_batch_graphs"] = 8
        config["NeuralNetwork"]["Architecture"].update(hidden_dim=8, num_conv_layers=1)

    nn = config["NeuralNetwork"]
    pool, dataset = drv.make_pool(
        traffic["graphs"], float(nn["Architecture"]["radius"]),
        list(nn["Variables_of_interest"]["input_node_features"]), args.seed,
    )
    arch = drv.completed_arch(config, dataset, pool)
    clients = int(traffic["clients"])
    ladder = sorted(tuple(int(v) for v in r) for r in traffic["bucket_ladder"])
    drv.state_precision(traffic)
    model, template, _ = drv.init_model(arch)
    engine = drv.start_engine(model, drv.seeded_weights(template, args.seed), traffic)
    for flush in drv.rung_flushes(pool, ladder, clients, args.seed):
        engine.predict(flush, timeout=drv.REPLY_TIMEOUT_S)

    before, snap_before = clocks(engine), engine.metrics.snapshot()
    t0, rows = drv.closed_loop(
        engine, pool, drv.client_orders(len(pool), clients, args.seed), args.seconds
    )
    after, snap = clocks(engine), engine.metrics.snapshot()
    engine.close()

    line = {
        "device": jax.devices()[0].device_kind, "seed": args.seed,
        "seconds": args.seconds,
        "mean_ms": {
            s: None if s not in after or after[s][1] == before[s][1] else
            1e3 * (after[s][0] - before[s][0]) / (after[s][1] - before[s][1])
            for s in A_REQUEST + A_FLUSH
        },
        "moved": {
            k: snap[k] - snap_before[k] if k in snap else None
            for k in ("requests_total", "presorted_total", "batches_total", "graphs_total")
        },
        "loop": {
            k: v for k, v in drv.account(rows, t0).items()
            if k in ("attempted", "failed", "serve_graphs_per_s", "serve_p50_ms", "serve_p95_ms")
        },
    }
    print(json.dumps(line), flush=True)
    if not args.rehearse_on_cpu:
        out = os.path.join(ROOT, "chiprun_out")
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, f"serve_stage_clocks_{args.seed}.json"), "w") as f:
            json.dump(line, f, indent=1)


if __name__ == "__main__":
    main()
