#!/usr/bin/env python3
"""Kernel-alone table behind ``ops/selective_scan.py`` (its chunk of 256 rows)
at the shapes of the cell
``jamba2_3b.serve_score_pages_c4``: the selective scan of ONE Mamba layer,
forward, 5120 channels x 16 states in float32, over the cell's mean rung
(9,728 rows, four documents of 1024 + 2048 + 2048 + 3072 tokens and the
padding rows' run) and its guard (16,896 rows, four documents of 4096):

    kernel         u, dt in, y out, at 128, 256 and 512 rows a grid step
    kernel + gate  the same and the gate ``y * silu(z)`` as an XLA fusion
                   after it (in the model that product is fused into the z
                   matmul's output and costs no pass of its own). A variant
                   that read z and gated INSIDE the kernel was timed in PR 45
                   and taken out again: docs/KERNELS.md has its numbers
    jax.numpy      the chunked route (``_scan_chunked``: what a CPU and a
                   gradient take), for scale

A row holds the milliseconds a call, the largest difference from the
``jax.numpy`` route, and the kernel's counted bytes over the HBM's bandwidth
as a share of the time. A time is the wall clock round ``REPEATS`` calls ended
by ``block_until_ready``, the least of ``ROUNDS``. Refuses to run anywhere but
on a TPU. Prints one JSON line a row and writes the table to ``chiprun_out/``:

    python3 benchmarks/selective_scan_routes.py

``--rehearse-on-cpu`` walks the same code at a small size with the kernel
interpreted and writes nothing: it finds wrong arguments, and its times mean
nothing.
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

from hydragnn_tpu.ops import selective_scan as ss

REPEATS, ROUNDS = 5, 3
CHANNELS, STATES = 5120, 16
RUNGS = {9728: (1024, 2048, 2048, 3072), 16896: (4096, 4096, 4096, 4096)}
CHUNKS = (128, 256, 512)
HBM_BYTES_PER_S = 819e9


def _inputs(rows: int, documents, channels: int, states: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    node_graph = np.full(rows, len(documents), np.int32)
    node_graph[: sum(documents)] = np.repeat(np.arange(len(documents)), documents)
    dt0 = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), channels))
    return dict(
        u=f32(rng.normal(size=(rows, channels)) * 0.6),
        dt=f32(dt0 * np.exp(rng.normal(size=(rows, channels)))),
        A=f32(-np.tile(np.arange(1, states + 1.0), (channels, 1))),
        B=f32(rng.normal(size=(rows, states))), C=f32(rng.normal(size=(rows, states))),
        skip=f32(np.ones(channels)), z=f32(rng.normal(size=(rows, channels))),
        first=ss.run_starts(jnp.asarray(node_graph)),
    )


def _time(fn, *args):
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        for _ in range(REPEATS):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / REPEATS)
    return 1e3 * best, out


def table(rungs, channels, states, chunks, interpret):
    for rows, documents in rungs.items():
        x = _inputs(rows, documents, channels, states)
        args = tuple(x[k] for k in ("u", "dt", "A", "B", "C", "skip", "first"))
        ms, want = _time(jax.jit(ss._scan_chunked), *args)
        yield dict(rows=rows, route="jax.numpy chunked", ms=ms)
        counted = 4 * rows * (3 * channels + 2 * states)
        for chunk in chunks:
            kernel = jax.jit(
                lambda *a, c=chunk: ss.selective_scan_tpu(*a, chunk=c, interpret=interpret)
            )
            gated = jax.jit(lambda z, *a, k=kernel: k(*a) * (z * jax.nn.sigmoid(z)))
            for route, fn, given in (
                ("kernel", kernel, args), ("kernel + gate", gated, (x["z"],) + args),
            ):
                ms, got = _time(fn, *given)
                row = dict(rows=rows, route=route, chunk=chunk, ms=ms)
                if route == "kernel":
                    row.update(
                        max_diff=float(jnp.max(jnp.abs(got - want))),
                        bytes_roofline_share=counted / HBM_BYTES_PER_S / (ms * 1e-3),
                    )
                yield row


def main(argv):
    rehearsal = "--rehearse-on-cpu" in argv
    if not rehearsal and jax.devices()[0].platform != "tpu":
        print("selective_scan_routes.py times kernels on a TPU; this is "
              f"{jax.devices()[0].platform} (--rehearse-on-cpu walks the code)")
        return 3
    if rehearsal:
        global REPEATS, ROUNDS
        REPEATS, ROUNDS = 1, 1
        made = table({512: (100, 156, 200)}, 1024, 4, (128,), True)
    else:
        made = table(RUNGS, CHANNELS, STATES, CHUNKS, False)
    device = jax.devices()[0]
    rows = []
    for row in made:
        row["device"] = f"{device.platform}:{device.device_kind}"
        print(json.dumps(row), flush=True)
        rows.append(row)
    if not rehearsal:
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/selective_scan_routes.json", "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
