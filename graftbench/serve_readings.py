"""The two readings a serving cell's limit stands between, in one process.

    python3 -m graftbench.serve_readings --workload <cell> --seeds 1,2,3 [--seconds 3]

For each seed: the cell's pool and weights from the seed, a short closed loop
at the cell's own load through the engine as the cell runs it (the lower
reading: the program as the configuration states it, float32), and the same
through the engine's own lower-precision arm, ``precision="bf16"`` (the
upper reading: the control, the step below the stated precision that would
tempt a later PR). Both engines are built once and take each seed's weights
through ``swap_weights``. Each reading is ``drivers/serve_closed.compare``'s
number: the widest ``|reply - reference| / (1 + |reference|)`` over as many
replies as a run compares, the largest graph among them. The benchmark's own
runs never run this; ``tests/test_serve_cell.py`` keeps the control at a
size a test run can hold. PERF.md section 2 has the readings and the limit
set from them.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from graftbench import run as bench_run
from graftbench.drivers import serve_closed as drv

# The control's own gate (``check_tolerance``) must not stand in its way.
ARMS = {
    "program": {},
    "control": dict(precision="bf16", tolerance=1e6),
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m graftbench.serve_readings")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--precision", default="stated",
                    help="stated (the traffic file's), default (XLA's own) or a JAX precision")
    ap.add_argument("--arms", default="program,control")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    _, entry, config, traffic = bench_run._load_cell(args.workload)

    import jax

    from hydragnn_tpu.cache.jaxcache import place_jax_cache

    dev = jax.devices()[0]
    print(f"[readings] platform={dev.platform} kind={dev.device_kind} cell={entry['name']}", flush=True)
    place_jax_cache()
    if args.precision != "stated":  # the look: the engine left to XLA's default
        traffic = dict(traffic, matmul_precision=None if args.precision == "default" else args.precision)
    drv.state_precision(traffic)
    nn = config["NeuralNetwork"]
    clients = int(traffic["clients"])
    engines, model, view = {}, None, None
    rows = []
    for seed in seeds:
        pool, dataset = drv.make_pool(
            traffic["graphs"], float(nn["Architecture"]["radius"]),
            list(nn["Variables_of_interest"]["input_node_features"]), seed,
        )
        if model is None:
            arch = drv.completed_arch(config, dataset, pool)
            model, template, _ = drv.init_model(arch)
            view = drv.reference_model(arch)
        weights = drv.seeded_weights(template, seed)
        row = {"seed": seed}
        for arm in args.arms.split(","):
            control = ARMS[arm]
            if arm not in engines:
                engines[arm] = drv.start_engine(model, weights, traffic, **control)
            else:
                engines[arm].swap_weights(weights, f"seed-{seed}")
            t0 = time.perf_counter()
            _, replies = drv.closed_loop(
                engines[arm], pool, drv.client_orders(len(pool), clients, seed),
                args.seconds,
            )
            compared, why_not, rms = drv.compare(
                replies, pool, view, weights, int(traffic["check_replies"]),
                seed, float(traffic["limit"]),
            )
            row[arm] = compared["reply_gap"]["value"]
            row[arm + "_rms"] = rms
            row[arm + "_correct"] = not why_not
            row[arm + "_requests"] = sum(len(r) for r in replies)
            row[arm + "_s"] = round(time.perf_counter() - t0, 1)
        rows.append(row)
        print("[readings] " + json.dumps(row), flush=True)
    for engine in engines.values():
        engine.close()
    lower = max(r["program"] for r in rows)
    upper = min(r.get("control", float("nan")) for r in rows)
    print("[readings] " + json.dumps({
        "lower_reading_program_max": lower, "upper_reading_control_min": upper,
        "ratio": upper / lower if lower else None,
        "limit": float(traffic["limit"]), "seeds": seeds,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
