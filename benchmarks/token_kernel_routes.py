#!/usr/bin/env python3
"""Kernel-alone table behind ``models/lfm2.py`` ``ATTN_BLOCK`` (the band) and
``GMM_TILING`` at the shapes of the cell ``laguna_xs2_ep8.train_seq4k_b1`` (one
sequence of 4096 tokens in a bucket of 4160 nodes; 8 key-value heads of 128):

    attention   ``segment_causal_attention`` as the layers call it, forward
                and forward + backward: the complete causal graph (the flash
                kernel; 48 heads, operands float32 as the layers hand them
                over, and rounded to bf16) and the causal band of 512 (the
                splash kernel; 64 heads) at block sizes 128 / 256 / 512,
                beside the triangle's kernel on the same 64 heads (what a
                sliding layer would cost with no window in the kernel)
    experts     ``grouped_matmul`` forward + backward over ``[33280, .]`` rows
                of which 4096 are live in 32 groups of 128 (uniform routing),
                for the up (2048 -> 512) and the down (512 -> 2048)
                projection, at row tiles 128 / 256 / 512

A time is the wall clock round ``REPEATS`` calls ended by
``block_until_ready``, the least of ``ROUNDS``. Refuses to run anywhere but on
a TPU. Prints one JSON line a route and writes the table to ``chiprun_out/``:

    python3 benchmarks/token_kernel_routes.py

``--rehearse-on-cpu`` walks the same code at a small size through the arms a
CPU takes and writes nothing: it finds wrong arguments, and its times mean
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

from hydragnn_tpu.models import lfm2

REPEATS, ROUNDS = 10, 3


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


def attention_rows(n, kv, hd, window, rehearsal):
    seg = jnp.asarray(np.where(np.arange(n) < n - 64, 0, 1).astype(np.int32))
    rng = np.random.default_rng(0)
    routes = [  # (name, heads, window, dtype, band block)
        ("full flash f32", 48, None, jnp.float32, None),
        ("full flash bf16", 48, None, jnp.bfloat16, None),
        ("triangle on 64 heads flash bf16", 64, None, jnp.bfloat16, None),
        ("band splash b128", 64, window, jnp.float32, 128),
        ("band splash b256", 64, window, jnp.float32, 256),
        ("band splash b512", 64, window, jnp.float32, 512),
        ("band splash b512 bf16", 64, window, jnp.bfloat16, 512),
    ]
    for name, heads, w, dtype, block in routes:
        if rehearsal:
            heads //= 8
        q, k, v = (
            jnp.asarray(rng.normal(size=(n, h, hd)), dtype) for h in (heads, kv, kv)
        )
        if block:
            lfm2.ATTN_BLOCK = block

        def fwd(q, k, v):
            return lfm2.segment_causal_attention(q, k, v, seg, window=w)

        def loss(q, k, v):
            return (fwd(q, k, v).astype(jnp.float32) ** 2).sum()

        yield {
            "what": name, "n": n, "heads": heads, "window": w,
            "fwd_ms": time_ms(jax.jit(fwd), q, k, v),
            "fwd_bwd_ms": time_ms(jax.jit(jax.grad(loss, argnums=(0, 1, 2))), q, k, v),
        }


def expert_rows(rows, live, groups, d, f):
    rng = np.random.default_rng(1)
    sizes = jnp.full((groups,), live // groups, jnp.int32)
    for name, k, n in (("up 2048 -> 512", d, f), ("down 512 -> 2048", f, d)):
        lhs = jnp.asarray(rng.normal(size=(rows, k)), jnp.float32)
        rhs = jnp.asarray(rng.normal(size=(groups, k, n)) / np.sqrt(k), jnp.float32)
        for tile in (128, 256, 512):
            lfm2.GMM_TILING = (tile, 1024, 1024)
            jax.clear_caches()  # the kernel's own jit is keyed by the tiling FUNCTION

            def loss(lhs, rhs):
                out = lfm2.grouped_matmul(lhs, rhs, sizes)
                return (jnp.where(jnp.arange(rows)[:, None] < live, out, 0.0) ** 2).sum()

            yield {
                "what": f"experts {name}, row tile {tile}", "rows": rows, "live": live,
                "fwd_bwd_ms": time_ms(jax.jit(jax.grad(loss, argnums=(0, 1))), lhs, rhs),
            }


def main() -> int:
    rehearsal = "--rehearse-on-cpu" in sys.argv[1:]
    device = jax.devices()[0]
    if device.platform != "tpu" and not rehearsal:
        print(f"needs a TPU, found {device.platform}: a CPU's time is no device time")
        return 3
    table = []
    shapes = (
        (attention_rows, (640, 2, 16, 128, True) if rehearsal else (4160, 8, 128, 512, False)),
        (expert_rows, (1024, 256, 4, 64, 32) if rehearsal else (33280, 4096, 32, 2048, 512)),
    )
    for rows, args in shapes:
        for row in rows(*args):
            row["device"] = device.device_kind
            print(json.dumps(row), flush=True)
            table.append(row)
    if rehearsal:
        print("rehearsal on", device.platform, "at a small size: no time here is a device time")
        return 0
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/token_kernel_routes.json", "w") as fh:
        json.dump(table, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
