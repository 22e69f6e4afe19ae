#!/usr/bin/env python3
"""Kernel-alone table behind ``models/token_attention.py`` ``ATTN_BLOCK`` (the
band) and ``models/token_routed.py`` ``GMM_TILING`` at the shapes of the cell
``laguna_xs2_ep8.train_seq4k_b1`` (one sequence of 4096 tokens in a bucket of
4160 nodes; 8 key-value heads of 128):

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
    routed      ``RoutedFFN`` alone (router, top-k, sort, row moves, grouped
                matmuls), forward and forward + backward, at BOTH token
                cells' shapes (4160 nodes of 2048; K 8, 32 of 256 held, 512
                wide; K 4, 8 of 32 held, 1792 wide): one pass over ``[K N, .]``
                row arrays (``capacity`` = K N) against the layer as it sizes
                them itself (``[C, .]``), with the router steered so that
                exactly ``live`` assignments reach held experts: half the
                uniform share, the share, ``C``, and ``C + 1`` (a second
                pass)
    way back    the compact rows' way back to node-major order and the
                weighted sum over a node's K rows, alone: a gather of K N
                rows out of ``[C, 2048]`` (one gather, or K of N rows) against
                the scatter-add of the C rows that the layer does. Forward
                only: either way the backward is one gather of C rows, and
                the backward of the way IN is this same operation

    expert tiles  ``grouped_matmul`` FORWARD over the rows of a layer that
                holds EVERY expert at a deployment's rows an expert (the cell
                ``mellum2_12b_l4.serve_score_docs_c4_v98k``'s commonest rung:
                ``[126976, .]`` rows, 64 groups of 1,658, 106,112 live), up
                (2304 -> 896) and down (896 -> 2304), whose widths are no
                whole number of ``GMM_TILING``'s 1024: the tile clipped to the
                matrix alone (1024 over 2304: 2.25 tiles, the kernel's
                remainder path) against tiles that divide it (1152, which
                ``_gmm_tiles`` takes, 768, 384) and a row tile of 512; beside
                it LFM2's 1792 under 1024 (1.75 tiles, what its cell runs)
                and 896

A time is the wall clock round ``REPEATS`` calls ended by
``block_until_ready``, the least of ``ROUNDS``. Refuses to run anywhere but on
a TPU. Prints one JSON line a route and writes the table to ``chiprun_out/``:

    python3 benchmarks/token_kernel_routes.py [attention_rows | expert_rows | routed_layer | way_back ...]

(every table unless some are named).
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

from hydragnn_tpu.models import lfm2, token_attention, token_routed

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
            token_attention.ATTN_BLOCK = block

        def fwd(q, k, v):
            return token_attention.segment_causal_attention(q, k, v, seg, window=w)

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
            token_routed.GMM_TILING = (tile, 1024, 1024)
            jax.clear_caches()  # the kernel's own jit is keyed by the tiling FUNCTION

            def loss(lhs, rhs):
                out = token_routed.grouped_matmul(lhs, rhs, sizes)
                return (jnp.where(jnp.arange(rows)[:, None] < live, out, 0.0) ** 2).sum()

            yield {
                "what": f"experts {name}, row tile {tile}", "rows": rows, "live": live,
                "fwd_bwd_ms": time_ms(jax.jit(jax.grad(loss, argnums=(0, 1))), lhs, rhs),
            }


def expert_tiles(rows, groups, per_group, d, f, tilings):
    """Forward alone (a served layer asks for no gradient). A tiling is
    (name, row tile, the tile along the ``d``-wide side, the tile along the
    ``f``-wide side); None takes ``token_routed._gmm_tiles``'s own."""
    rng = np.random.default_rng(2)
    live = groups * per_group
    sizes = jnp.full((groups,), per_group, jnp.int32)
    fitted = token_routed._gmm_tiles
    try:
        for name, k, n in (("up", d, f), ("down", f, d)):
            lhs = jnp.asarray(rng.normal(size=(rows, k)), jnp.float32)
            rhs = jnp.asarray(rng.normal(size=(groups, k, n)) / np.sqrt(k), jnp.float32)
            for what, tm, tile_d, tile_f in tilings:
                tiles = (tm, *fitted(rows, k, n)[1:]) if tile_d is None else (
                    (tm, tile_d, tile_f) if k == d else (tm, tile_f, tile_d)
                )
                token_routed._gmm_tiles = lambda m, k_, n_, t=tiles: t
                jax.clear_caches()  # the kernel's own jit is keyed by the tiling FUNCTION
                ms = time_ms(jax.jit(lambda a, b: token_routed.grouped_matmul(a, b, sizes)), lhs, rhs)
                yield {
                    "what": f"expert tiles {name} {k} -> {n}, {what}", "tiles": list(tiles),
                    "rows": rows, "live": live, "fwd_ms": ms,
                    "tflops": 2 * live * k * n / ms / 1e9,
                }
    finally:
        token_routed._gmm_tiles = fitted


def steered_layer(n, d, k, held, experts, f, live):
    """(layer, params, x, mask): a ``RoutedFFN`` whose router sends exactly
    ``live`` assignments to held experts, evenly over them: the gate reads
    expert ``e``'s score off column ``e`` of ``x``, where a node's chosen
    experts stand out."""
    cfg = lfm2.LFM2Config(
        layer_types=("conv",), num_dense_layers=0, intermediate_size=4 * f,
        moe_intermediate_size=f, num_experts=experts, num_experts_per_tok=k,
        num_experts_held=held, experts_offset=0, num_attention_heads=4,
        num_key_value_heads=2, head_dim=8, vocab_size=64, token_minmax=(0.0, 63.0),
    )
    layer = token_routed.RoutedFFN(d, cfg)
    real = n - 64  # the bucket's padding nodes
    mask = np.arange(n) < real
    rng = np.random.default_rng(2)
    x = 0.01 * rng.normal(size=(n, d)).astype(np.float32)
    to_held = np.full(real, live // real) + (np.arange(real) < live % real)
    assert to_held.max() <= min(k, held) and k - to_held.min() <= experts - held
    for i in range(real):
        m = to_held[i]
        chosen = [(i * m + j) % held for j in range(m)]
        chosen += [held + (i * k + j) % (experts - held) for j in range(k - m)]
        x[i, chosen] = 4.0
    # 64 nodes: on the TPU the K N rows of even this call are whole row tiles.
    params = layer.init(jax.random.PRNGKey(0), jnp.zeros((64, d)), jnp.ones((64,), bool))
    gate = np.zeros((d, experts), np.float32)
    gate[np.arange(experts), np.arange(experts)] = 1.0
    params = {"params": dict(params["params"], gate=jnp.asarray(gate))}
    return layer, params, jnp.asarray(x), jnp.asarray(mask)


def routed_layer(n, d, shapes):
    for cell, k, held, experts, f in shapes:
        cap = token_routed._capacity(n * k, held, experts)
        share = n * k * held // experts
        for live in (share // 2, share, cap, cap + 1):
            layer, params, x, mask = steered_layer(n, d, k, held, experts, f, live)
            for path, capacity in (("every row", (n * k,)), ("as the layer sizes them", ())):

                def fwd(params, x):
                    y, sown = layer.apply(
                        params, x, mask, *capacity, mutable=[token_routed.INTERMEDIATES]
                    )
                    return y, sown[token_routed.INTERMEDIATES]

                def loss(params, x):
                    return (fwd(params, x)[0] ** 2).sum()

                counted = jax.jit(fwd)(params, x)[1]
                assert int(counted["moe_rows_held"][0]) == live
                yield {
                    "what": f"routed layer {cell}, {path}", "rows": n * k, "cap": cap,
                    "live": live, "one_compact_pass": int(counted["moe_layers_compact"][0]),
                    "fwd_ms": time_ms(jax.jit(lambda p, x: fwd(p, x)[0]), params, x),
                    "fwd_bwd_ms": time_ms(jax.jit(jax.grad(loss, argnums=(0, 1))), params, x),
                }


def way_back(n, d, k, cap):
    rng = np.random.default_rng(3)
    out = jnp.asarray(rng.normal(size=(cap, d)), jnp.float32)
    weight = jnp.asarray(rng.uniform(size=(n, k)), jnp.float32)
    order = rng.permutation(n * k).astype(np.int32)
    back = np.empty_like(order)
    back[order] = np.arange(n * k, dtype=np.int32)
    forth, back = jnp.asarray(order[:cap]), jnp.asarray(back)

    def gather(out, weight):
        rows = jnp.take(out, back, axis=0, mode="fill", fill_value=0).reshape(n, k, d)
        return jnp.sum(rows * weight[:, :, None], axis=1)

    def gather_by_slot(out, weight):
        rows = jnp.concatenate([out, jnp.zeros((1, d), out.dtype)])
        place = jnp.minimum(back, cap).reshape(n, k)
        return sum(rows[place[:, j]] * weight[:, j, None] for j in range(k))

    def scatter_add(out, weight):
        rows = out * weight.reshape(-1)[forth][:, None]
        return jnp.zeros((n, d), out.dtype).at[forth // k].add(rows)

    for name, fn in (
        (f"one gather of {n * k} rows", gather),
        (f"{k} gathers of {n} rows", gather_by_slot),
        (f"scatter-add of {cap} rows", scatter_add),
    ):
        yield {
            "what": f"way back, K {k}: {name}", "rows": n * k, "cap": cap,
            "fwd_ms": time_ms(jax.jit(fn), out, weight),
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
        (routed_layer, (
            (320, 64, (("laguna", 8, 4, 32, 16), ("lfm2", 4, 4, 16, 24))) if rehearsal
            else (4160, 2048, (("laguna", 8, 32, 256, 512), ("lfm2", 4, 8, 32, 1792)))
        )),
        (way_back, (320, 64, 8, 512) if rehearsal else (4160, 2048, 8, 6400)),
        (way_back, (320, 64, 4, 512) if rehearsal else (4160, 2048, 4, 6400)),
        (expert_tiles, (1024, 4, 200, 288, 112, (("fitted", 256, None, None),)) if rehearsal else (
            126976, 64, 1658, 2304, 896, (
                ("clipped", 256, 1024, 896), ("fitted", 256, None, None),
                ("divides", 256, 768, 896), ("divides", 256, 384, 896),
                ("row tile 512, fitted", 512, None, None),
            ),
        )),
        (expert_tiles, (1024, 4, 200, 256, 224, (("divides", 256, 256, 112),)) if rehearsal else (
            6400, 8, 800, 2048, 1792,  # LFM2's expert, as its cell runs it, and a dividing tile
            (("clipped (the cell's)", 256, 1024, 1024), ("divides", 256, 1024, 896)),
        )),
    )
    named = [a for a in sys.argv[1:] if not a.startswith("--")]
    for rows, args in shapes:
        if named and rows.__name__ not in named:
            continue
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
