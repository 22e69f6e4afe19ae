#!/usr/bin/env python3
"""Host-alone table behind ``graphs/collate.py`` ``_stable_order_by_receiver``:
three routes to the permutation of a STABLE sort of one graph's edges by
receiver, on the serving cell's own pool (``graftbench/drivers/serve_closed.py``
``make_pool``: 108 lattices of 288-768 atoms, 11.6-35.6k directed edges), and
the flush they feed.

    packed     value sort of ``(receiver << 32) | position`` int64 keys
               (``ndarray.sort``), the order read back off the low bits
    radix16    ``np.argsort(receivers.astype(np.uint16), kind="stable")``
               (a radix sort; a graph under 65,536 nodes): the module's own
    argsort32  ``np.argsort(receivers, kind="stable")`` on int32: the
               module's fall-back for a larger graph

A row is ms a graph IN TURN (one thread over the pool, the least of ``ROUNDS``)
and the wall ms of one flush's 64 graphs on 64 THREADS started together, as
the cell's callers are when their replies land. Below them: ``prepare_graph``
whole, ``collate_prepared`` over 64 prepared graphs, and the arena's flush
(``GraphArena(samples).collate(arange(64))``) that they replace, at the rung
the flush lands in. Every route's order is checked against ``argsort32``'s.

Uses no device: it times the HOST, so run it on the machine whose host serves
(through the chip tool). Prints one JSON line a row and writes the table to
``chiprun_out/``:

    python3 benchmarks/prepare_sort_routes.py
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from graftbench.drivers.serve_closed import make_pool
from hydragnn_tpu.graphs import collate

ROUNDS, FLUSH = 5, 64
TRAFFIC = "graftbench/traffic/serve_closed_lattice.json"
CONFIG = "graftbench/configs/pna_multihead_h256.json"


def packed(receivers: np.ndarray) -> np.ndarray:
    keys = receivers.astype(np.int64)
    keys <<= 32
    keys |= np.arange(len(keys), dtype=np.int64)
    keys.sort()
    keys &= 0xFFFFFFFF
    return keys


ROUTES = {
    "packed": packed,
    "radix16": lambda r: collate._stable_order_by_receiver(r, 1 << 16),
    "argsort32": lambda r: collate._stable_order_by_receiver(r, (1 << 16) + 1),
}


def in_turn(fn, items) -> float:
    """ms an item, one thread, the least of ROUNDS passes."""
    best = float("inf")
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        for item in items:
            fn(item)
        best = min(best, time.perf_counter() - t0)
    return 1e3 * best / len(items)


def together(fn, items) -> float:
    """Wall ms of one call an item, a thread each, started at one gate."""
    best = float("inf")
    for _ in range(ROUNDS):
        gate = threading.Event()
        threads = [
            threading.Thread(target=lambda it=it: (gate.wait(), fn(it)))
            for it in items
        ]
        for t in threads:
            t.start()
        time.sleep(0.05)  # every thread at the gate
        t0 = time.perf_counter()
        gate.set()
        for t in threads:
            t.join()
        best = min(best, time.perf_counter() - t0)
    return 1e3 * best


def main() -> None:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    traffic = json.load(open(os.path.join(root, TRAFFIC)))
    nn = json.load(open(os.path.join(root, CONFIG)))["NeuralNetwork"]
    pool, _ = make_pool(
        traffic["graphs"], float(nn["Architecture"]["radius"]),
        list(nn["Variables_of_interest"]["input_node_features"]), 4200000001,
    )
    receivers = [s.edge_index[1] for s in pool]
    flush = [pool[i] for i in np.random.default_rng(42).integers(0, len(pool), FLUSH)]
    rows = [{"host_cpus": os.cpu_count(), "numpy": np.__version__, "pool": len(pool),
             "edges_mean": float(np.mean([len(r) for r in receivers]))}]
    want = [ROUTES["argsort32"](r) for r in receivers]
    for name, fn in ROUTES.items():
        same = all(np.array_equal(fn(r), w) for r, w in zip(receivers, want))
        rows.append({
            "route": name, "same_order": same,
            "ms_a_graph_in_turn": in_turn(fn, receivers),
            "ms_64_threads": together(fn, [s.edge_index[1] for s in flush]),
        })
    rows.append({
        "step": "prepare_graph",
        "ms_a_graph_in_turn": in_turn(collate.prepare_graph, pool),
        "ms_64_threads": together(collate.prepare_graph, flush),
    })
    prepared = [collate.prepare_graph(s) for s in flush]
    nodes = sum(p.num_nodes for p in prepared)
    edges = sum(p.num_edges for p in prepared)
    n_pad, e_pad = next(
        (n, e) for n, e in sorted(map(tuple, traffic["bucket_ladder"]))
        if n > nodes and e >= edges
    )
    pads = dict(num_nodes_pad=n_pad, num_edges_pad=e_pad, num_graphs_pad=FLUSH + 1)
    rows.append({
        "step": "flush", "rung": [n_pad, e_pad], "edges": edges,
        "collate_prepared_ms": in_turn(
            lambda g: collate.collate_prepared(g, **pads), [prepared]
        ),
        "arena_flush_ms": in_turn(
            lambda s: collate.GraphArena(s).collate(np.arange(FLUSH), edge_dim=0, **pads),
            [flush],
        ),
    })
    for row in rows:
        print(json.dumps(row), flush=True)
    out = os.path.join(root, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "prepare_sort_routes.json"), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
