"""The attention entry point of the token stacks (``models/families.py``
``TOKEN_STACKS``): softmax aggregation over the complete causal graph of each
sequence, or over its causal band, computed blockwise from ``node_graph`` and
the flat node order; no ``[N, N]`` array and no edge list exists. Every
family's attention layer ends in ``segment_causal_attention``; which kernel
runs is decided here, from the execution platform, the window and whether the
call is differentiated (``ops/block_attention.py`` holds this repo's own
kernel, JAX's library the other two), and the host-side counts of the key
blocks a call visits (``attention_key_blocks``, ``band_key_blocks``: the
serving engine's counters) stand beside the callers they describe. No family's
name is in here, and this module imports no family's file.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.block_attention import (
    block_pairs,
    block_range,
    block_range_attention,
    whole_blocks,
)
from ..ops.segment import execution_platform

# Rows of a query block, and of a key block of the TPU kernels. The flat node
# array is padded up to a multiple of it inside ``segment_causal_attention``
# (the loaders' buckets are multiples of 64, not of 512). The band's kernel
# was timed at 128, 256 and 512 (benchmarks/token_kernel_routes.py; PERF.md
# section 6, PR 33): the largest wins though a window of 512 then spans 2 key
# blocks a query block, twice the band's pairs.
ATTN_BLOCK = 512


def _attention_rows(q, k, v, seg_q, seg_k, first_row: int, scale: float,
                    first_key: int = 0, window=None):
    """One block of query rows (the flat rows from ``first_row``) against the
    keys from ``first_key`` up to its last row: masked softmax in float32,
    over ``same graph and j <= i`` and, with a ``window``, ``i - j < window``.
    ``q`` [bq, KV, rep, hd]; ``k``, ``v`` [nk, KV, hd]."""
    s = jnp.einsum("qgrd,kgd->grqk", q, k) * scale
    rows = first_row + jnp.arange(q.shape[0])[:, None]
    keys = first_key + jnp.arange(k.shape[0])[None, :]
    keep = (seg_q[:, None] == seg_k[None, :]) & (keys <= rows)
    if window is not None:
        keep &= rows - keys < window
    s = jnp.where(keep[None, None], s.astype(jnp.float32), -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("grqk,kgd->qgrd", p.astype(v.dtype), v)


def _band_reach(window: int) -> Tuple[int, int]:
    """Node i sees j with 0 <= i - j < window: ``window - 1`` to the left,
    none to the right (the node itself counts)."""
    return window - 1, 0


def band_key_blocks(rows: int, window: int) -> int:
    """The (query block, key block) pairs ONE call of the band's core visits
    over ``rows`` rows (padded up to whole blocks), a head: the blocks of
    ``ATTN_BLOCK`` that hold a pair of ``_band_reach``, which are the splash
    kernel's grid under its static mask (a window of 1024 reaches into 3 key
    blocks of 512 a query block, where the triangle has up to all before
    it). Graph boundaries are not in it: a graph's end inside the band is
    masked, not skipped."""
    left, right = _band_reach(window)
    b = ATTN_BLOCK
    blocks = -(-rows // b)
    return sum(
        min((i * b + b - 1 + right) // b, blocks - 1) - max((i * b - left) // b, 0) + 1
        for i in range(blocks)
    )


def _band_attention_tpu(q, k, v, node_graph, window: int, scale: float):
    """The band on the TPU: the splash-attention Pallas kernel of JAX's own
    library under a ``LocalMask``, one call a key-value head (``vmap``) over
    its ``H / KV`` query heads. The mask is static, so the kernel's grid
    holds only the key blocks the band touches, forward, dq and dkv alike;
    the graph boundary is the kernel's segment ids. ``q`` [H, N, hd]
    (scaled here: the kernel takes no scale); ``k``, ``v`` [KV, N, hd]."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash,
        splash_attention_mask as masks,
    )

    heads, n, hd = q.shape
    kv = k.shape[0]
    rep = heads // kv
    b = ATTN_BLOCK
    band = masks.LocalMask((n, n), _band_reach(window), 0)
    kernel = splash.make_splash_mqa_single_device(
        masks.MultiHeadMask([band] * rep),
        block_sizes=splash.BlockSizes(
            block_q=b, block_kv=b, block_kv_compute=b,
            block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b,
            block_q_dq=b, block_kv_dq=b,
        ),
    )
    seg = node_graph.astype(jnp.int32)
    out = jax.vmap(kernel, in_axes=(0, 0, 0, None))(
        (q * scale).reshape(kv, rep, n, hd), k, v, splash.SegmentIds(q=seg, kv=seg)
    )
    return out.reshape(heads, n, hd)


def _flash_attention_tpu(q, k, v, node_graph, scale: float):
    """The complete causal graph on the TPU by JAX's own flash kernel, forward,
    dq and dkv: the whole array as ONE sequence under ``causal`` and segment
    ids, one call a query head (``k`` and ``v`` repeated). It skips a key
    block above the diagonal and no other: a block below it that belongs to
    another graph is multiplied and then masked. ``q`` [N, H, hd]; ``k``,
    ``v`` [N, KV, hd], ``N`` a whole number of blocks; returns [N, H, hd]."""
    from jax.experimental.pallas.ops.tpu import flash_attention as fa

    b = ATTN_BLOCK
    sizes = fa.BlockSizes(
        block_q=b, block_k_major=b, block_k=b, block_b=1,
        block_q_major_dkv=b, block_k_major_dkv=b, block_k_dkv=b, block_q_dkv=b,
        block_k_major_dq=b, block_k_dq=b, block_q_dq=b,
    )
    rep = q.shape[1] // k.shape[1]
    qh = q.transpose(1, 0, 2)[None]
    kh = jnp.repeat(k.transpose(1, 0, 2), rep, axis=0)[None]
    vh = jnp.repeat(v.transpose(1, 0, 2), rep, axis=0)[None]
    seg = node_graph.astype(jnp.int32)[None]
    out = fa.flash_attention(
        qh, kh, vh, segment_ids=fa.SegmentIds(q=seg, kv=seg), causal=True,
        sm_scale=scale, block_sizes=sizes,
    )
    return out[0].transpose(1, 0, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _full_attention_tpu(q, k, v, node_graph, scale: float):
    """The complete causal graph on the TPU. A call that is not
    differentiated (the engine's ``score_tokens``, an evaluation step) visits
    only the key blocks of a query block's own graphs
    (``ops/block_attention.py``); under a gradient the library kernel's
    forward, dq and dkv run as ``_flash_attention_tpu`` makes them (the
    backward kernels with the same block range are ROADMAP S10's)."""
    return block_range_attention(q, k, v, node_graph, scale, ATTN_BLOCK)


def _full_attention_tpu_fwd(q, k, v, node_graph, scale):
    def library(q, k, v):
        # XLA names a kernel's instruction after the first name that the
        # nested ``jvp`` wraps: under this scope it stays ``flash_attention``,
        # as in a trace and in the program before PR 40, not
        # ``jvp_jit_flash_attention__``.
        with jax.named_scope("library"):
            return _flash_attention_tpu(q, k, v, node_graph, scale)

    return jax.vjp(library, q, k, v)


def _full_attention_tpu_bwd(scale, vjp, g):
    return (*vjp(g), None)


_full_attention_tpu.defvjp(_full_attention_tpu_fwd, _full_attention_tpu_bwd)


def attention_key_blocks(node_graph, ranged: bool = True):
    """(visited, causal): the (query block, key block) pairs ONE call of the
    complete causal core visits on the host array ``node_graph`` [N], a head,
    and the pairs of the padded rows' whole triangle; by the function that
    hands the TPU's kernel its range. Not ``ranged`` (every path but the
    TPU's undifferentiated one) the triangle is walked."""
    padded = whole_blocks(np.asarray(node_graph), ATTN_BLOCK)
    visited, causal = block_pairs(block_range(padded, ATTN_BLOCK))
    return visited if ranged else causal, causal


def segment_causal_attention(q, k, v, node_graph, window=None, scale=None):
    """Softmax aggregation over the complete causal graph of each sequence:
    node ``i`` receives from every node ``j <= i`` of its own graph; with a
    ``window``, over the causal BAND: also ``i - j < window`` (the node
    itself counts). ``q`` [N, H, hd]; ``k``, ``v`` [N, KV, hd], each
    key-value head shared by ``H / KV`` query heads. Nodes of one graph are
    contiguous and in order (collation), so "earlier in the graph" is
    "earlier in the flat array": the mask is ``same graph and j <= i`` and
    nothing is gathered.

    On the TPU a Pallas kernel, and what each skips differs. The complete
    causal graph, not differentiated (the engine's ``score_tokens``, an
    evaluation step): ``ops/block_attention.py``, which visits for a block
    of query rows only the key blocks from its earliest graph's first row up
    to the diagonal, so neither the blocks above the diagonal nor those of
    other graphs. Under a gradient: the flash kernel of JAX's own library,
    forward, dq and dkv, which skips the blocks ABOVE the diagonal only (a
    block of another graph is multiplied, then masked). The band: the splash
    kernel of the same library, whose grid holds only the blocks the band
    touches (the flash kernel has no window and would do the triangle's
    work); a graph's end inside the band is masked, not skipped. Elsewhere a
    loop over blocks of query rows, each against ALL the keys up to its end
    (from ``window - 1`` rows before its start), other graphs' masked,
    rematerialized in the backward. Every way the largest score array is a
    block's, never ``[N, N]``. Padding nodes share
    the padding graph's id and attend among themselves (every row keeps its
    diagonal, so no softmax is empty). ``scale`` multiplies the scores:
    ``hd ** -0.5`` unless a stack states its own (a latent-attention stack:
    YaRN's ``mscale`` squared rides on it)."""
    n, heads, hd = q.shape
    kv = k.shape[1]
    if scale is None:
        scale = hd ** -0.5
    on_tpu = execution_platform() == "tpu"
    pad = -n % ATTN_BLOCK
    if pad:
        q, k, v = (jnp.pad(a, ((0, pad), (0, 0), (0, 0))) for a in (q, k, v))
        node_graph = jnp.pad(node_graph, (0, pad), constant_values=-1)
    total = n + pad
    if on_tpu and window is not None:
        out = _band_attention_tpu(
            *(a.transpose(1, 0, 2) for a in (q, k, v)), node_graph, window, scale
        )
        return out.transpose(1, 0, 2)[:n].reshape(n, heads * hd)
    if on_tpu:
        return _full_attention_tpu(q, k, v, node_graph, scale)[:n].reshape(n, heads * hd)
    q = q.reshape(total, kv, heads // kv, hd)
    block = jax.checkpoint(_attention_rows, static_argnums=(5, 6, 7, 8))
    out = []
    for start in range(0, total, ATTN_BLOCK):
        end = start + ATTN_BLOCK
        lo = 0 if window is None else max(0, start - window + 1)
        out.append(block(
            q[start:end], k[lo:end], v[lo:end], node_graph[start:end],
            node_graph[lo:end], start, scale, lo, window,
        ))
    return jnp.concatenate(out)[:n].reshape(n, heads * hd)
