"""LFM2-8B-A1B's block (LiquidAI, ``model_type`` ``lfm2_moe``;
https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json) on this
system's batch: a token is a node, a sequence is a graph with its nodes in
order, ``positions[:, 0]`` is the node's place in its graph. Equations and
this system's departures: PAPERS.md.

Two token mixers, both aggregations over graphs the batch IMPLIES and never
holds as an edge list:

* the gated short convolution sums over the banded causal graph (node ``i``
  receives from ``i``, ``i-1``, ``i-2`` of its own graph): two shifted reads
  of the flat node array masked by "same graph", not a gather (``ShortConv``,
  this family's own);
* attention is the softmax aggregation over the complete causal graph of
  each sequence (524,800 edges at 1024 nodes), computed blockwise from
  ``node_graph`` and the flat node order; no ``[N, N]`` array exists
  (``token_attention.segment_causal_attention``, every family's).

The batch's own edges (the loaders' radius graph of a line at radius 2.5 is
that band: 4 edges a node) are carried by the unchanged loaders and LEFT
UNREAD here, as are ``row_ptr`` and the edge mask.

This file holds what is LFM2's alone: its sizes, the short convolution, its
attention layer (RMSNorm on each head of ``q`` and ``k``, plain rotary) and
the block that orders them. Norm, rotary and the shifted read inside a graph
come from ``token_common.py``, the
attention core from ``token_attention.py``, the dense and the routed
feed-forward (one rank's share of an expert-parallel layer) from
``token_routed.py``; no other family's file is imported here and none imports
this one.

Precision, as the configuration states it: float32 parameters, residual
stream, norms, softmax, sigmoid; matrix multiplications at the backend's
default for float32 operands (on the TPU one bf16 pass with float32
accumulation), the router's ``W_g x`` at ``Precision.HIGHEST``.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import flax.linen as nn

from ..telemetry import scopes
from .token_attention import segment_causal_attention
from .token_common import RMSNorm, missing_fields, rope, same_graph_shift
from .token_routed import DenseFFN, RoutedFFN, experts_share
# Read by graftbench/drivers/train_tokens.py under this module's name (the
# benchmark's files are not this PR's to edit: ROADMAP D25).
from .token_routed import COUNTERS, INTERMEDIATES, split_intermediates  # noqa: F401


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

    # The router's score function, as ``RoutedFFN`` reads it (the source's
    # ``sigmoid(W_g x)``; no key of the config names it).
    scoring_func = "sigmoid"

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
                conv = conv + k[self.taps - 1 - back] * same_graph_shift(z, node_graph, back)
            y = c * conv
        return nn.Dense(d, use_bias=False, name="out_proj")(y)


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
