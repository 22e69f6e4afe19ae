#!/usr/bin/env python3
"""Kernel-alone table behind ``ops/segment_sorted.py`` ``WIDE_ROW``: the sorted
arm's two routes for a segment sum over non-decreasing ids, forward, on the
chip, at the benchmark cells' own shapes.

    prefix    ``segment_sorted._sum_count_prefix``: chunked cumsum, TwoSum
              carries, two [N, F] fetches (what every width took before PR 32)
    add       ``jax.ops.segment_sum``: XLA's scatter-add, ids not declared
    add_flag  ``segment_sorted._sum_count_scatter``: the same with
              ``indices_are_sorted=True``

Each route's function is called directly, jitted alone, on arrays already on
the device; a time is the wall clock round ``REPEATS`` calls ended by
``block_until_ready``, the least of ``ROUNDS``. The ids are laid out as the
cells lay them out: ``real`` of the nodes hold the real rows in runs of equal
length and every other row sits in the LAST node's run (the padding node's;
collation's contract), so a long run is part of every shape.

Refuses to run anywhere but on a TPU (a CPU's time is no device time). Prints
one JSON line a shape and writes the table to ``chiprun_out/``:

    python3 benchmarks/sorted_sum_routes.py [part of a row's name ...]

(``"gather backward"`` runs PR 46's four rows alone, ~1 min.)

``--rehearse-on-cpu`` walks the same code at 1/64 of the rows and writes
nothing: it finds wrong arguments, and its times mean nothing.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from hydragnn_tpu.ops import aggregate
from hydragnn_tpu.ops import segment_sorted as srt

REPEATS, ROUNDS = 20, 5
# (cell and use, rows E, width F, segments N, real rows, real segments)
SHAPES = (
    ("painn block sum", 262144, 512, 16384, 205900, 10752),
    ("gatv2 weighted sum", 262144, 384, 16384, 215040, 10752),
    ("gatv2 denominators", 262144, 6, 16384, 215040, 10752),
    ("pna large bucket", 524288, 256, 32768, 130000, 8600),
    ("pna small bucket", 131072, 256, 16384, 100000, 8000),
    ("pna input layer", 524288, 1, 32768, 130000, 8600),
    ("pool f128", 16384, 128, 512, 10752, 511),
    ("pool f256", 16384, 256, 512, 8600, 511),
    # Between the cells' widths: where the routes cross.
    ("width 32", 262144, 32, 16384, 205900, 10752),
    ("width 64", 262144, 64, 16384, 205900, 10752),
    ("width 128", 262144, 128, 16384, 205900, 10752),
    # PR 46: a receiver-side gather's BACKWARD is this sum over the cotangent
    # rows (``add`` is what autodiff writes for plain indexing), at the shapes
    # the cells have since PR 43's pads. These rows also time the whole
    # ``jax.grad`` through ``aggregate.gather_sorted`` and through ``table[ids]``.
    ("gather backward gatv2 x_dst", 215552, 384, 11264, 215040, 10752),
    ("gather backward gatv2 denom", 215552, 6, 11264, 215040, 10752),
    ("gather backward pna large bucket", 401920, 256, 18944, 290000, 18900),
    ("gather backward pna small bucket", 113152, 256, 8704, 100000, 8000),
)


def cell_like_ids(e: int, n: int, real_rows: int, real_segments: int) -> np.ndarray:
    """Non-decreasing ids: ``real_rows`` spread evenly over the first
    ``real_segments`` nodes, the rest in node ``n - 1``'s run."""
    ids = np.full((e,), n - 1, np.int32)
    ids[:real_rows] = (np.arange(real_rows, dtype=np.int64) * real_segments) // real_rows
    return ids


def routes(n: int):
    def prefix(data, ids, row_ptr):
        return srt._sum_count_prefix(data, ids, n, row_ptr)[0]

    def add(data, ids, row_ptr):
        return jax.ops.segment_sum(data, ids, num_segments=n)

    def add_flag(data, ids, row_ptr):
        return srt._sum_count_scatter(data, ids, n, row_ptr)[0]

    return {"prefix": prefix, "add": add, "add_flag": add_flag}


def time_ms(fn, *args) -> float:
    fn(*args).block_until_ready()  # compile, and warm
    best = float("inf")
    for _ in range(ROUNDS):
        start = time.perf_counter()
        for _ in range(REPEATS):
            out = fn(*args)
        out.block_until_ready()
        best = min(best, (time.perf_counter() - start) / REPEATS)
    return best * 1e3


def main() -> int:
    rehearsal = "--rehearse-on-cpu" in sys.argv[1:]
    device = jax.devices()[0]
    if device.platform != "tpu" and not rehearsal:
        print(f"needs a TPU, found {device.platform}: a CPU's time is no device time")
        return 3
    table = []
    wanted = [a for a in sys.argv[1:] if not a.startswith("--")]
    for what, e, f, n, real_rows, real_segments in SHAPES:
        if wanted and not any(w in what for w in wanted):
            continue
        if rehearsal:
            e, n, real_rows, real_segments = (
                e // 64, max(n // 64, 8), real_rows // 64, max(real_segments // 64, 7)
            )
        ids_h = cell_like_ids(e, n, real_rows, real_segments)
        rng = np.random.default_rng(e + f)
        data_h = rng.normal(size=(e, f)).astype(np.float32)
        data_h[real_rows:] = 0.0  # masked rows arrive zeroed
        data, ids = jnp.asarray(data_h), jnp.asarray(ids_h)
        row_ptr = jnp.asarray(np.searchsorted(ids_h, np.arange(n + 1)).astype(np.int32))
        truth = np.zeros((n, f))
        filled = np.flatnonzero(np.diff(np.asarray(row_ptr)))  # sorted ids: runs
        truth[filled] = np.add.reduceat(
            data_h.astype(np.float64), np.asarray(row_ptr)[filled], axis=0
        )
        row = {"what": what, "e": e, "f": f, "n": n, "device": device.device_kind}
        for name, fn in routes(n).items():
            jitted = jax.jit(fn)
            row[f"{name}_ms"] = time_ms(jitted, data, ids, row_ptr)
            row[f"{name}_ns_a_row"] = row[f"{name}_ms"] * 1e6 / e
            row[f"{name}_max_abs_err"] = float(
                np.abs(np.asarray(jitted(data, ids, row_ptr), np.float64) - truth).max()
            )
        if what.startswith("gather backward"):
            # What the train step runs: the gradient of a use of the gathered
            # rows with respect to the table, ids and boundaries as arguments.
            zeros = jnp.zeros((n, f), jnp.float32)
            for name, gather in (
                ("grad_plain", lambda t, i, p: t[i]),
                ("grad_gather_sorted", aggregate.gather_sorted),
            ):
                grad = jax.jit(jax.grad(
                    lambda t, w, i, p, gather=gather: jnp.sum(gather(t, i, p) * w)
                ))
                row[f"{name}_ms"] = time_ms(grad, zeros, data, ids, row_ptr)
                row[f"{name}_max_abs_err"] = float(np.abs(
                    np.asarray(grad(zeros, data, ids, row_ptr), np.float64) - truth
                ).max())
        print(json.dumps(row), flush=True)
        table.append(row)
    if rehearsal:
        print("rehearsal on", device.platform, "at 1/64 of the rows: no time here is a device time")
        return 0
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/sorted_sum_routes.json", "w") as fh:
        json.dump(table, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
