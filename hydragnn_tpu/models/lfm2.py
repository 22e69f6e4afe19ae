"""LFM2-8B-A1B's block (LiquidAI, ``model_type`` ``lfm2_moe``;
https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json) on this
system's batch: a token is a node, a sequence is a graph with its nodes in
order, ``positions[:, 0]`` is the node's place in its graph. Equations and
this system's departures: PAPERS.md.

Two token mixers, both aggregations over graphs the batch IMPLIES and never
holds as an edge list:

* the gated short convolution sums over the banded causal graph (node ``i``
  receives from ``i``, ``i-1``, ``i-2`` of its own graph): two shifted reads
  of the flat node array masked by "same graph", not a gather;
* attention is the softmax aggregation over the complete causal graph of
  each sequence (524,800 edges at 1024 nodes), computed blockwise from
  ``node_graph`` and the flat node order; no ``[N, N]`` array exists.

The batch's own edges (the loaders' radius graph of a line at radius 2.5 is
that band: 4 edges a node) are carried by the unchanged loaders and LEFT
UNREAD here, as are ``row_ptr`` and the edge mask.

The routed feed-forward is a per-node update that is told which experts it
holds (``num_experts_held`` from ``experts_offset``): it routes over all
``num_experts``, computes its own experts' part of the result and leaves out
the rest -- one rank's share of an expert-parallel layer, without the
exchange (there is no code here that stands in for the absent ranks).

Precision, as the configuration states it: float32 parameters, residual
stream, norms, softmax, sigmoid; matrix multiplications at the backend's
default for float32 operands (on the TPU one bf16 pass with float32
accumulation), the router's ``W_g x`` at ``Precision.HIGHEST``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn
import numpy as np

from ..ops.block_attention import (
    block_pairs,
    block_range,
    block_range_attention,
    whole_blocks,
)
from ..ops.segment import execution_platform
from ..telemetry import scopes
from .layers import scaled_ids

# Rows of a query block, and of a key block of the TPU kernels. The flat node
# array is padded up to a multiple of it inside ``segment_causal_attention``
# (the loaders' buckets are multiples of 64, not of 512). The band's kernel
# was timed at 128, 256 and 512 (benchmarks/token_kernel_routes.py; PERF.md
# section 6, PR 33): the largest wins though a window of 512 then spans 2 key
# blocks a query block, twice the band's pairs.
ATTN_BLOCK = 512
# The collection the routed layers sow into: the experts each node chose and
# the router's input (read by the benchmark's check, which asks for the
# collection; a no-op in every program that does not), and the step's
# counters (asked for by the train step, train/trainer.py).
INTERMEDIATES = "intermediates"
COUNTERS = ("moe_rows_held", "moe_load_max", "moe_load_min", "moe_layers_compact")


def missing_fields(cls, arch: dict) -> list:
    """The fields of a stack's config dataclass ``cls`` that ``arch`` must
    have and lacks (the rank's share defaults to all the experts)."""
    return [
        f.name for f in dataclasses.fields(cls)
        if f.default is dataclasses.MISSING and f.name not in arch
        and f.name not in ("num_experts_held", "experts_offset")
    ]


def experts_share(arch: dict) -> Tuple[int, int]:
    """(``num_experts_held``, ``experts_offset``) of ``arch``: this rank's
    share of the routed experts, all of them unless told."""
    held = int(arch.get("num_experts_held", arch["num_experts"]))
    offset = int(arch.get("experts_offset", 0))
    if not 0 < held <= held + offset <= int(arch["num_experts"]):
        raise ValueError(
            f"experts {offset}..{offset + held} are not among "
            f"{arch['num_experts']}"
        )
    return held, offset


@dataclasses.dataclass(frozen=True)
class LFM2Config:
    """The stack's static sizes, keyed as the source's ``config.json`` names
    them, plus this rank's share (``num_experts_held``, ``experts_offset``)
    and the dataset's table for the token column (``token_minmax``)."""

    layer_types: Tuple[str, ...]
    num_dense_layers: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    num_experts_held: int
    experts_offset: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    vocab_size: int
    token_minmax: Tuple[float, float]
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0

    @classmethod
    def from_arch(cls, arch: dict, num_layers: int) -> "LFM2Config":
        """From a completed ``Architecture`` block. ``layer_types`` may be the
        published list whole: the first ``num_layers`` of it are built."""
        missing = cls.missing(arch)
        if missing:
            raise ValueError(
                f"LFM2 requires Architecture.{'/'.join(missing)} (token_minmax "
                "comes from config completion: the dataset's table)"
            )
        types = tuple(arch["layer_types"][:num_layers])
        if len(types) != num_layers or set(types) - {"conv", "full_attention"}:
            raise ValueError(
                f"LFM2 needs {num_layers} layer_types of 'conv' / "
                f"'full_attention', got {arch['layer_types']!r}"
            )
        held, offset = experts_share(arch)
        kw = {
            f.name: arch[f.name] for f in dataclasses.fields(cls) if f.name in arch
        }
        kw.update(
            layer_types=types, num_experts_held=held, experts_offset=offset,
            token_minmax=tuple(float(v) for v in arch["token_minmax"]),
        )
        return cls(**kw)

    missing = classmethod(missing_fields)

    def routed(self, layer: int) -> bool:
        return layer >= self.num_dense_layers


def token_ids(column: jnp.ndarray, cfg: LFM2Config) -> jnp.ndarray:
    """The token id of each node from its min-max-scaled column, exactly."""
    return scaled_ids(column, cfg.token_minmax, cfg.vocab_size)


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.initializers.ones, (x.shape[-1],))
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + self.eps) * w


def _same_graph_shift(z, node_graph, by: int):
    """``z`` moved down ``by`` rows, zero where the row ``by`` above belongs
    to another graph (or to none: before the first node)."""
    n = z.shape[0]
    moved = jnp.concatenate([jnp.zeros((by,) + z.shape[1:], z.dtype), z[: n - by]])
    above = jnp.concatenate([jnp.full((by,), -1, node_graph.dtype), node_graph[: n - by]])
    return jnp.where((above == node_graph)[:, None], moved, 0.0)


class ShortConv(nn.Module):
    """``(B, C, u) = split(W_in x)``; ``z = B * u``; the depthwise causal
    convolution ``c_i = sum_j k_j * z_{i-(L-1-j)}`` inside the node's own
    graph; ``W_out (C * c)``. No bias anywhere (``conv_bias`` false)."""

    features: int
    taps: int = 3

    @nn.compact
    def __call__(self, x, node_graph):
        d = self.features
        bcu = nn.Dense(3 * d, use_bias=False, name="in_proj")(x)
        with jax.named_scope(scopes.LFM2_CONV):
            k = self.param(
                "kernel", nn.initializers.variance_scaling(1.0, "fan_in", "uniform"),
                (self.taps, d),
            )
            b, c, u = bcu[:, :d], bcu[:, d : 2 * d], bcu[:, 2 * d :]
            z = b * u
            conv = k[self.taps - 1] * z
            for back in range(1, self.taps):
                conv = conv + k[self.taps - 1 - back] * _same_graph_shift(z, node_graph, back)
            y = c * conv
        return nn.Dense(d, use_bias=False, name="out_proj")(y)


def rotate(x, place, inv, factor: float = 1.0):
    """Rotary embedding over the last axis of ``x`` [N, heads, dim] at
    ``place`` [N] (float) with the frequencies ``inv`` [dim / 2], the halves
    convention of the source's ``rotate_half``; cos and sin times ``factor``
    (1 but for a scaled-context variant's attention factor)."""
    half = x.shape[-1] // 2
    angle = place.astype(jnp.float32)[:, None] * inv  # [N, half]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def rope(x, place, theta: float):
    """``rotate`` at the plain frequencies ``theta^(-2i/dim)``."""
    half = x.shape[-1] // 2
    return rotate(x, place, theta ** (-jnp.arange(half, dtype=jnp.float32) / half))


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
    ``hd ** -0.5`` unless a stack states its own (models/mistral4.py: YaRN's
    ``mscale`` squared rides on it)."""
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


class Attention(nn.Module):
    """Grouped-query attention with RMSNorm over each head of ``q`` and
    ``k`` and RoPE on the node's place in its graph."""

    features: int
    cfg: LFM2Config

    @nn.compact
    def __call__(self, x, node_graph, place):
        c = self.cfg
        n, h, kv, hd = x.shape[0], c.num_attention_heads, c.num_key_value_heads, c.head_dim
        q = nn.Dense(h * hd, use_bias=False, name="q_proj")(x).reshape(n, h, hd)
        k = nn.Dense(kv * hd, use_bias=False, name="k_proj")(x).reshape(n, kv, hd)
        v = nn.Dense(kv * hd, use_bias=False, name="v_proj")(x).reshape(n, kv, hd)
        with jax.named_scope(scopes.LFM2_ATTN):
            q = rope(RMSNorm(c.norm_eps, name="q_layernorm")(q), place, c.rope_theta)
            k = rope(RMSNorm(c.norm_eps, name="k_layernorm")(k), place, c.rope_theta)
            y = segment_causal_attention(q, k, v, node_graph)
        return nn.Dense(self.features, use_bias=False, name="out_proj")(y)


class DenseFFN(nn.Module):
    """SwiGLU: ``W2(silu(W1 x) * W3 x)``."""

    features: int
    width: int

    @nn.compact
    def __call__(self, x):
        a = nn.silu(nn.Dense(self.width, use_bias=False, name="w1")(x))
        b = nn.Dense(self.width, use_bias=False, name="w3")(x)
        return nn.Dense(self.features, use_bias=False, name="w2")(a * b)


_expert_init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1, batch_axis=(0,))
# (rows, contraction, columns) tiles of the TPU's grouped-matmul kernel; the
# row tile has to divide a row array's rows: ``_capacity`` is a multiple of
# it, and where a layer holds every expert the K N rows of a bucket
# (multiples of 64 nodes) are whole row tiles from K = 4 (the serving
# ladder's rungs of Mellum2's layer, K = 8: 98k-201k rows in ONE pass, 4.0 GB
# of temporaries at the largest, PERF.md section 6, PR 41); row arrays of
# another length go through ``ragged_dot`` (``grouped_matmul``).
GMM_TILING = (256, 1024, 1024)
# Rows of the routed layer's compact path over the rank's uniform share
# ``K N held / experts`` (``_capacity``).
CAPACITY_FACTOR = 1.5


def _capacity(assignments: int, held: int, experts: int) -> int:
    """Rows of the compact path for a layer that holds ``held`` of
    ``experts``: its share of the ``assignments`` under uniform routing times
    ``CAPACITY_FACTOR``, up to a whole row tile of the grouped matmul."""
    tile = GMM_TILING[0]
    share = assignments * held * CAPACITY_FACTOR / experts
    return -(-math.ceil(share) // tile) * tile


def _gmm_tile(tile: int, width: int) -> int:
    """A contraction or column tile for a matrix ``width`` wide: no wider
    than the matrix (a fine-grained expert, 512 wide, is narrower than a
    tile, and the kernel would multiply the tile); and where the last tile
    would be under half full, that remainder spread over the whole tiles
    before it (2304 is 2.25 tiles of 1024: 2 tiles of 1152) if that leaves
    whole lanes: the kernel multiplies a whole tile for a remainder. On the
    chip at 1,658 rows an expert 1152 beat 1024 by 12-15% and 768 by 1-5%
    (PERF.md section 6, PR 41). LFM2's 1792 keeps 1024 (its last tile is three
    quarters full)."""
    tile = min(tile, width)
    whole, rest = divmod(width, tile)
    if 0 < rest < tile // 2 and width % (128 * whole) == 0:
        tile = width // whole
    return tile


def _gmm_tiles(m: int, k: int, n: int):
    """``GMM_TILING`` fitted to the matrices (``_gmm_tile``)."""
    tm, tk, tn = GMM_TILING
    return tm, _gmm_tile(tk, k), _gmm_tile(tn, n)


@jax.custom_vjp
def _gmm_tpu(lhs, rhs, sizes):
    """``lhs[rows of group g] @ rhs[g]`` on the TPU: the grouped-matmul Pallas
    kernel of JAX's own library (megablox), operands rounded to bf16,
    float32 accumulation and results -- the stated precision, and what
    ``ragged_dot`` does there by default. Chosen over ``ragged_dot`` on the
    chip (PERF.md section 6, PR 31): XLA's own grouped kernel drops the
    operation's name, so its time could be booked to no scope."""
    return _gmm_tpu_fwd(lhs, rhs, sizes)[0]


def _gmm_tpu_fwd(lhs, rhs, sizes):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    lhs16, rhs16 = lhs.astype(jnp.bfloat16), rhs.astype(jnp.bfloat16)
    out = gmm(lhs16, rhs16, sizes, jnp.float32, _gmm_tiles)
    return out, (lhs16, rhs16, sizes)


def _gmm_tpu_bwd(residuals, ct):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    lhs16, rhs16, sizes = residuals
    ct16 = ct.astype(jnp.bfloat16)
    d_lhs = gmm(ct16, rhs16, sizes, jnp.float32, _gmm_tiles, transpose_rhs=True)
    d_rhs = tgmm(lhs16.swapaxes(0, 1), ct16, sizes, jnp.float32, _gmm_tiles)
    return d_lhs, d_rhs, None


_gmm_tpu.defvjp(_gmm_tpu_fwd, _gmm_tpu_bwd)


def grouped_matmul(lhs, rhs, sizes):
    """``[rows, k] x [groups, k, n] -> [rows, n]``: rows ``sizes[0]`` first
    by group 0, the next ``sizes[1]`` by group 1, ...; rows past the last
    group are NOT multiplied and hold whatever the kernel left there. The
    TPU's kernel takes whole row tiles: fewer rows than that (an initializer's
    example batch of 4 nodes, as ``InferenceEngine.from_config`` builds one)
    go through ``ragged_dot`` there too."""
    if execution_platform() == "tpu" and lhs.shape[0] % GMM_TILING[0] == 0:
        return _gmm_tpu(lhs, rhs, sizes)
    return jax.lax.ragged_dot(lhs, rhs, sizes)


def _held_experts(x, w1, w3, w2, weight, order, sizes, start=0, *, cap: int):
    """``sum over a node's K assignments of weight * SwiGLU_e(x)`` for the
    assignments to held experts that stand at ``start .. start + cap`` of the
    sorted order, over row arrays of ``cap`` rows. With ``cap = K N`` that is
    every assignment. A pure function of arrays."""
    n, d = x.shape
    k = weight.shape[1]
    if cap < n * k:
        order = jax.lax.dynamic_slice(
            jnp.pad(order, (0, -(n * k) % cap)), (start,), (cap,)
        )
        ends = jnp.cumsum(sizes) - start
        sizes = jnp.clip(ends, 0, cap) - jnp.clip(ends - sizes, 0, cap)
    # Zero outside the held groups, on the way in and (through the select's
    # transpose) on the way back: what a grouped matmul leaves in rows of no
    # group is its own business.
    live = (jnp.arange(cap) < sizes.sum())[:, None]
    node = order // k

    def grouped(lhs, rhs):
        return jnp.where(live, grouped_matmul(lhs, rhs, sizes), 0.0)

    with jax.named_scope(scopes.MOE_ROUTE):
        rows = jnp.where(live, x[node], 0.0)
    with jax.named_scope(scopes.MOE_EXPERTS):
        hidden = nn.silu(grouped(rows, w1)) * grouped(rows, w3)
        out = grouped(hidden, w2)
    with jax.named_scope(scopes.MOE_ROUTE):
        out = out * weight.reshape(-1)[order][:, None]
        return jnp.zeros_like(x).at[node].add(out)


_FLOATS = 5  # x, w1, w3, w2, weight lead the operands; order and sizes end them


def _further_passes(cap: int, operands, first, one_pass):
    """``first`` plus ``one_pass(start)`` for every further ``cap`` sorted
    rows the live rows reach into: none on a step whose live rows fit in
    ``cap``, and no loop at all where ``cap`` is every row."""
    weight, sizes = operands[_FLOATS - 1], operands[-1]
    if cap >= weight.size:
        return first

    def one_more(carry):
        start, total = carry
        return start + cap, jax.tree_util.tree_map(jnp.add, total, one_pass(start))

    if not isinstance(sizes, jax.core.Tracer):
        # Run eagerly (the initializer): the live rows are known, and a
        # ``while`` would be compiled a layer for passes that are never made
        # (a second each on the TPU, too short for the persistent cache).
        carry, live = (cap, first), sizes.sum()
        while carry[0] < live:
            carry = one_more(carry)
        return carry[1]
    return jax.lax.while_loop(
        lambda carry: carry[0] < sizes.sum(), one_more, (jnp.int32(cap), first)
    )[1]


def _one_pass(cap: int, operands):
    return lambda start: _held_experts(*operands, start, cap=cap)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _in_passes(cap: int, *operands):
    """``_held_experts`` over ``cap`` sorted rows at a time until the live
    rows are through: ONE pass on a step whose live rows fit in ``cap``, as
    many more as a step that overflows needs, each adding its part of the
    nodes' sums. Differentiated by hand, because a loop of unknown length has
    no reverse mode: the first pass keeps what its backward needs, as any
    straight-line code; a further pass keeps nothing, and its backward runs
    its forward again."""
    one_pass = _one_pass(cap, operands)
    return _further_passes(cap, operands, one_pass(0), one_pass)


def _in_passes_fwd(cap, *operands):
    y, pullback = jax.vjp(functools.partial(_held_experts, cap=cap), *operands)
    return _further_passes(cap, operands, y, _one_pass(cap, operands)), (operands, pullback)


def _in_passes_bwd(cap, residuals, ct):
    operands, pullback = residuals

    def again(start):
        _, pullback = jax.vjp(
            lambda *floats: _held_experts(*floats, *operands[_FLOATS:], start, cap=cap),
            *operands[:_FLOATS],
        )
        return pullback(ct)

    grads = _further_passes(cap, operands, pullback(ct)[:_FLOATS], again)
    return (*grads, None, None)


_in_passes.defvjp(_in_passes_fwd, _in_passes_bwd)


class RoutedFFN(nn.Module):
    """``s = sigmoid(W_g x)`` over all ``num_experts`` (``softmax(W_g x)``
    for a stack whose sizes say ``scoring_func = "softmax"``); the
    ``num_experts_per_tok`` largest of ``s + b`` are chosen (``b`` the expert
    bias: a buffer, no gradient); ``w_e = s_e / (sum over the chosen + 1e-6)``
    times ``routed_scaling_factor``, the sum over ALL chosen, held or not;
    ``y = sum over the chosen AND held of w_e SwiGLU_e(x)``. Dropless,
    compact with a fall-back.

    The ``K N`` assignments (node-major: node ``i``'s are rows ``K i ..``)
    are sorted by expert (stable), the held experts' rows first and, in ONE
    trailing group that is never multiplied, the assignments to absent
    experts and those of padding nodes. The held rows are gathered from
    their nodes, multiplied by one grouped matmul a projection, weighted, and
    added into their nodes' rows again (``_held_experts``).

    Static shapes, sized by what this rank can be sent and not by every
    assignment: the row arrays are ``[C, ·]``, ``C`` the rank's share of the
    ``K N`` assignments under uniform routing times ``CAPACITY_FACTOR``
    (``_capacity``; ``capacity`` overrides it: the tests' handle). A step
    whose routing sends the layer more than ``C`` rows falls back on further
    passes over the next ``C`` sorted rows until every live row has met its
    expert (``_in_passes``): no assignment is dropped, clipped or re-routed,
    and no ``[K N, ·]`` array exists on either path. A layer with
    ``C >= K N`` (one that holds every expert; tiny inputs) makes its one
    pass over all ``K N`` rows and compiles no loop."""

    features: int
    cfg: Any  # LFM2Config, or another stack's with the same routing fields

    @nn.compact
    def __call__(self, x, node_mask, capacity=None):
        c = self.cfg
        n, d = x.shape
        experts, k, held, f = (
            c.num_experts, c.num_experts_per_tok, c.num_experts_held,
            c.moe_intermediate_size,
        )
        gate = self.param("gate", nn.initializers.lecun_normal(), (d, experts))
        bias = (
            self.param("expert_bias", nn.initializers.zeros, (experts,))
            if c.use_expert_bias else None
        )
        w1 = self.param("w1", _expert_init, (held, d, f))
        w3 = self.param("w3", _expert_init, (held, d, f))
        w2 = self.param("w2", _expert_init, (held, f, d))
        self.sow(INTERMEDIATES, "moe_router_in", x)
        with jax.named_scope(scopes.MOE_ROUTE):
            s = jnp.dot(x, gate, precision=jax.lax.Precision.HIGHEST)
            if getattr(c, "scoring_func", "sigmoid") == "softmax":
                s = jax.nn.softmax(s, axis=-1)
            else:
                s = jax.nn.sigmoid(s)
            biased = s + jax.lax.stop_gradient(bias) if c.use_expert_bias else s
            _, chosen = jax.lax.top_k(biased, k)  # [N, K]
            # The chosen experts' own scores by a compare against an iota: a
            # gather of K N scalars costs a row each, forward and backward.
            picked = chosen[:, :, None] == jnp.arange(experts)[None, None, :]
            weight = jnp.sum(jnp.where(picked, s[:, None, :], 0.0), axis=-1)
            if c.norm_topk_prob:
                weight = weight / (weight.sum(axis=-1, keepdims=True) + 1e-6)
            weight = weight * c.routed_scaling_factor
            local = chosen - c.experts_offset
            here = (local >= 0) & (local < held) & node_mask[:, None]
            group = jnp.where(here, local, held).reshape(-1)  # [K N]
            order = jnp.argsort(group, stable=True)  # expert order <- node-major
            sizes = (group[:, None] == jnp.arange(held)[None, :]).sum(
                axis=0, dtype=jnp.int32
            )
            cap = _capacity(n * k, held, experts) if capacity is None else capacity
            cap = min(cap, n * k)
            y = _in_passes(cap, x, w1, w3, w2, weight, order, sizes)
            compact = (sizes.sum() <= cap) & (cap < n * k)
        self.sow(INTERMEDIATES, "moe_chosen", chosen)
        counted = (sizes.sum(), sizes.max(), sizes.min(), compact)
        for name, value in zip(COUNTERS, counted):
            self.sow(INTERMEDIATES, name, value.astype(jnp.float32))
        return y


class LFM2Block(nn.Module):
    """``h += op(RMSNorm(h))``; ``h += ffn(RMSNorm(h))``: the operator a short
    convolution or attention by ``layer_types``, the feed-forward dense for
    the ``num_dense_layers`` leading layers and routed after them."""

    features: int
    cfg: LFM2Config
    layer: int

    @nn.compact
    def __call__(self, h, node_graph, place, node_mask):
        c = self.cfg
        x = RMSNorm(c.norm_eps, name="operator_norm")(h)
        if c.layer_types[self.layer] == "conv":
            h = h + ShortConv(self.features, c.conv_L_cache, name="conv")(x, node_graph)
        else:
            h = h + Attention(self.features, c, name="self_attn")(x, node_graph, place)
        x = RMSNorm(c.norm_eps, name="ffn_norm")(h)
        if c.routed(self.layer):
            return h + RoutedFFN(self.features, c, name="feed_forward")(x, node_mask)
        return h + DenseFFN(self.features, c.intermediate_size, name="feed_forward")(x)


def split_intermediates(tree) -> Tuple[dict, dict]:
    """What the routed layers sowed, as (per-layer dict of the check's
    arrays keyed ``conv_<i>``, the step's counters summed over the layers)."""
    per_layer, counters = {}, dict.fromkeys(COUNTERS, 0.0)
    for module, sub in (tree or {}).items():
        sown = sub.get("feed_forward", {})
        if "moe_chosen" in sown:
            per_layer[module] = {
                "chosen": sown["moe_chosen"][-1], "router_in": sown["moe_router_in"][-1],
            }
            for name in COUNTERS:
                counters[name] = counters[name] + sown[name][-1]
    return per_layer, counters
