#!/usr/bin/env python3
"""Kernel-alone table behind ``ops/block_attention.py`` (its schedule and
``HEADS_A_STEP``) at the shapes of the cell
``mistral_small4_ep8.serve_score_docs_c4``: the attention core of ONE layer,
forward, 32 heads of 128 in float32 rows, at the cell's four rungs (12,288 /
15,872 / 19,968 / 25,088 rows) under three document mixes each:

    4 x 2048    the shortest flush: 10 block pairs a document
    mean mix    four documents of the pool that fill the rung as its flushes
                do (2048 + 2048 + 3072 + 4096; 2048 + 3072 + 4096 + 6144;
                3072 + 4096 + 6144 + 6144; 4 x 6144)
    one run     ONE document of the whole rung: the range is the triangle,
                and the schedule has no surplus step

and, on each, JAX's flash kernel as a differentiated call still takes it
(``models/token_attention.py`` ``_flash_attention_tpu``: the whole triangle
whatever the mix) beside the block-range kernel with 1, 2, 4 and 8 query heads a grid
step. A row holds the milliseconds a call, the (query block, key block)
pairs visited and the triangle's, and whether the result equals the flash
kernel's bit for bit.

A time is the wall clock round ``REPEATS`` calls ended by
``block_until_ready``, the least of ``ROUNDS``. Refuses to run anywhere but on
a TPU. Prints one JSON line a row and writes the table to ``chiprun_out/``:

    python3 benchmarks/attention_block_range.py

``--rehearse-on-cpu`` walks the same code at a small size with the kernel
interpreted and writes nothing: it finds wrong arguments, and its times mean
nothing.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from hydragnn_tpu.models import token_attention
from hydragnn_tpu.ops import block_attention

REPEATS, ROUNDS = 10, 3
HEADS, HEAD_DIM = 32, 128
RUNGS = {  # rows: the mean mix's documents
    12288: (2048, 2048, 3072, 4096),
    15872: (2048, 3072, 4096, 6144),
    19968: (3072, 4096, 6144, 6144),
    25088: (6144, 6144, 6144, 6144),
}
HEADS_A_STEP = (1, 2, 4, 8)


def time_ms(fn, *args) -> float:
    jax.block_until_ready(fn(*args))  # compile, and warm
    best = float("inf")
    for _ in range(ROUNDS):
        start = time.perf_counter()
        for _ in range(REPEATS):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - start) / REPEATS)
    return best * 1e3


def node_graph(rows: int, documents) -> np.ndarray:
    """Documents end to end from row 0 and the padding graph's id after them,
    as collation lays a flush out."""
    ids = np.full(rows, len(documents), np.int32)
    ids[: sum(documents)] = np.repeat(np.arange(len(documents)), documents)
    return ids


def table(rungs, heads, hd, block, interpret):
    scale = hd ** -0.5
    for rows, mean_mix in rungs.items():
        key = jax.random.split(jax.random.PRNGKey(rows), 3)
        q, k, v = (jax.random.normal(s, (rows, heads, hd), jnp.float32) for s in key)
        short = min(mean_mix)
        mixes = {
            f"4 x {short}": (short,) * 4, "mean mix": mean_mix, "one run": (rows,),
        }
        if interpret:
            def flash(q, k, v, ids):
                return token_attention.segment_causal_attention(q, k, v, ids).reshape(q.shape)
        else:
            def flash(q, k, v, ids):
                return token_attention._flash_attention_tpu(q, k, v, ids, scale)
        routes = {"flash": jax.jit(flash)}
        for g in HEADS_A_STEP:
            routes[f"block range, {g} heads a step"] = functools.partial(
                block_attention.block_range_attention, scale=scale, block=block,
                heads_a_step=g, interpret=interpret,
            )
        for mix, documents in mixes.items():
            ids = node_graph(rows, documents)
            visited, causal = block_attention.block_pairs(
                block_attention.block_range(ids, block)
            )
            ids = jnp.asarray(ids)
            expected = routes["flash"](q, k, v, ids)
            for route, fn in routes.items():
                walked = causal if route == "flash" else visited
                got = fn(q, k, v, ids)
                yield {
                    "rows": rows, "mix": mix, "route": route,
                    "ms": time_ms(fn, q, k, v, ids),
                    "pairs_visited": walked, "pairs_causal": causal,
                    "equals_flash": bool((got == expected).all()),
                    "max_abs_from_flash": float(jnp.abs(got - expected).max()),
                }


def main(argv):
    rehearsal = "--rehearse-on-cpu" in argv
    if not rehearsal and jax.devices()[0].platform != "tpu":
        print("attention_block_range.py times kernels on a TPU; this is "
              f"{jax.devices()[0].platform} (--rehearse-on-cpu walks the code)")
        return 3
    if rehearsal:
        global REPEATS, ROUNDS
        REPEATS, ROUNDS = 1, 1
        token_attention.ATTN_BLOCK = 128
        made = table({1024: (128, 256, 256, 384)}, 8, 128, 128, True)
    else:
        made = table(RUNGS, HEADS, HEAD_DIM, token_attention.ATTN_BLOCK, False)
    device = jax.devices()[0]
    rows = []
    for row in made:
        row["device"] = f"{device.platform}:{device.device_kind}"
        print(json.dumps(row), flush=True)
        rows.append(row)
    if not rehearsal:
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/attention_block_range.json", "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
