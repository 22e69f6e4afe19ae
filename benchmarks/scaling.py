"""Weak-scaling study of the data-parallel train step — the DDP-scaling-
efficiency analog in BASELINE.json's north-star metric set. Fixed per-device
batch; the mesh 'data' axis grows 1 → N; ideal scaling keeps graphs/sec/device
constant.

Runs on whatever devices exist: a real TPU slice, or a virtual CPU mesh:

    python benchmarks/scaling.py            # all visible devices
    python benchmarks/scaling.py --devices 8 --cpu

Prints one JSON line per mesh size ("devices" = data_axis * graph_axis):
  {"devices": D, "mesh": "data:dxgraph:g", "graphs_per_sec": X,
   "per_device": X/D, "efficiency": X / (data_axis * X_smallest_mesh)}
"""

from __future__ import annotations

import argparse
import json
import sys
import os
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PER_DEV_BATCH = 64
STEPS = 20


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=0, help="max devices (0=all)")
    ap.add_argument("--cpu", action="store_true", help="force a virtual CPU mesh")
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument(
        "--graph-axis", type=int, default=1,
        help="shard each graph's edges over this many devices (the "
        "long-context analog axis); the data axis still sweeps 1,2,4,... "
        "so each line uses data_axis*graph_axis devices",
    )
    ap.add_argument(
        "--out", default=None,
        help="also append this sweep as ONE JSON line to an artifact file "
        "(per-round scaling provenance, e.g. SCALING_r04.jsonl; append-only "
        "so an interrupted write cannot lose prior sweeps)",
    )
    args = ap.parse_args()

    if args.cpu:
        n = args.devices or 8
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}"
        )
        import jax

        jax.config.update("jax_platforms", "cpu")
    else:
        import jax

    from __graft_entry__ import DIMS, TYPES, _build_model, _make_graphs
    from hydragnn_tpu.graphs import collate_graphs
    from hydragnn_tpu.models import init_model_variables
    from hydragnn_tpu.parallel import make_mesh
    from hydragnn_tpu.train.trainer import (
        create_train_state,
        make_train_step_dp,
        stack_batches,
    )
    from hydragnn_tpu.utils.optimizer import select_optimizer

    n_avail = len(jax.devices())
    max_dev = min(args.devices or n_avail, n_avail)
    ga = max(1, args.graph_axis)
    sizes = [
        d for d in (1, 2, 4, 8, 16, 32, 64)
        if d * ga <= max_dev
    ]

    if not sizes:
        sys.exit(
            f"graph_axis={ga} needs more devices than the {max_dev} available"
        )

    rng = np.random.default_rng(0)
    base = None
    rows = []
    for d in sizes:
        mesh = make_mesh(data_axis=d, graph_axis=ga)
        # Edge arrays are sharded over the graph axis: round the pad up to a
        # multiple of ga so shard_map's divisibility requirement holds.
        e_pad = -(-(PER_DEV_BATCH * 26 * 20) // ga) * ga
        per_dev = [
            collate_graphs(
                _make_graphs(PER_DEV_BATCH, rng, 12, 26), TYPES, DIMS,
                num_nodes_pad=PER_DEV_BATCH * 26,
                num_edges_pad=e_pad,
                num_graphs_pad=PER_DEV_BATCH + 1,
                edge_dim=1,
            )
            for _ in range(d)
        ]
        batch = stack_batches(per_dev, d)
        model = _build_model(hidden=args.hidden, layers=args.layers)
        variables = init_model_variables(model, per_dev[0])
        if ga > 1:
            # Bind the collective axis only for the sharded step (init ran
            # outside shard_map where the axis is unbound).
            model = model.clone(graph_axis="graph")
        opt = select_optimizer("AdamW", 1e-3)
        state = create_train_state(model, variables, opt)
        step = make_train_step_dp(model, opt, mesh)
        key = jax.random.PRNGKey(0)

        state, m = step(state, batch, key)  # compile
        jax.block_until_ready(m["loss"])
        t0 = time.perf_counter()
        for _ in range(STEPS):
            state, m = step(state, batch, key)
        jax.block_until_ready(m["loss"])
        el = time.perf_counter() - t0

        gps = PER_DEV_BATCH * d * STEPS / el
        if base is None:
            base = gps
        # Collective-time share estimate: per-device step time in excess of
        # the 1-device mesh's is time NOT spent on per-device compute —
        # cross-device collectives (grad psum on the data axis, segment-psum
        # on the graph axis) plus any device contention. On a real slice this
        # is the collective share; on a virtual CPU mesh host oversubscription
        # dominates it, which is why every row carries the mesh provenance.
        t_per_dev_step = el / STEPS  # same wall time on every device (SPMD)
        share = None
        if rows:
            t1 = rows[0]["_t_step"]
            share = round(max(0.0, 1.0 - t1 / t_per_dev_step), 3)
        row = {
            "devices": d * ga,
            "mesh": f"data:{d}xgraph:{ga}",
            "graphs_per_sec": round(gps, 1),
            "per_device": round(gps / (d * ga), 1),
            "efficiency": round(gps / (d * base), 3),
            "collective_share_est": share,
            "_t_step": t_per_dev_step,
        }
        rows.append(row)
        print(json.dumps({k: v for k, v in row.items() if k != "_t_step"}), flush=True)

    for row in rows:
        row.pop("_t_step", None)
    if args.out:
        virtual = jax.default_backend() == "cpu"
        entry = {
            "ts_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "platform": jax.default_backend(),
            # Provenance labels: a virtual CPU mesh
            # oversubscribes host cores, so its efficiency curve is a plumbing
            # canary, NOT scaling evidence; the north-star number is this same
            # sweep on a real multi-chip slice.
            "virtual_mesh": virtual,
            "note": (
                "virtual CPU mesh oversubscribes host cores; efficiency and "
                "collective_share_est are plumbing canaries only"
            ) if virtual else "real device mesh",
            "per_device_batch": PER_DEV_BATCH,
            "hidden": args.hidden,
            "layers": args.layers,
            "sweep": rows,
        }
        with open(args.out, "a") as f:
            f.write(json.dumps(entry) + "\n")


if __name__ == "__main__":
    main()
