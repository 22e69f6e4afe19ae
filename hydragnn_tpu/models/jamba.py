"""AI21-Jamba2-3B's block (ai21labs, ``model_type`` ``jamba``;
https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json) on this
system's batch: a token is a node, a document a graph with its nodes in
order, ``positions[:, 0]`` the node's place. Equations, assumptions and
departures: PAPERS.md.

Two token mixers by layer (``attn_layer_period`` 14, ``attn_layer_offset`` 7:
thirteen to one as published), and a dense SwiGLU after either
(``num_experts`` 1: nothing is routed):

* the Mamba mixer (``transformers``' ``JambaMambaMixer``: Mamba-1 with an
  RMSNorm on each of dt's low-rank input, B and C): a depthwise causal
  convolution of ``mamba_d_conv`` taps inside the node's own graph (shifted
  reads of the flat node array masked by "same graph", as LFM2's short
  convolution masks its own), then the selective scan over the graph's nodes in
  order with the state ``[d_inner, d_state]`` zero before its first
  (``ops/selective_scan.py``: a Pallas kernel on the TPU where no gradient is
  asked for, the same recurrence in ``jax.numpy`` by chunks elsewhere), then
  gated by the input projection's other half;
* attention over the complete causal graph with ONE key-value head for all
  the query heads, no rotary and no other position signal (the place column
  is read for nothing), no norm on ``q`` / ``k``, no bias.

The head is the embedding transposed (``tie_word_embeddings``: ``models/
base.py`` builds no head matrix where the sizes say so).

**The scan's own parameters are held as their distance from Mamba's published
starting point**, so that a tree of zeros IS that point and small seeded
values stay beside it: ``A = -exp(log(1..d_state) + A_log)``,
``dt = softplus(W_dt delta + b_dt + softplus^-1(dt0))`` with ``dt0`` spaced
log-uniformly over the channels from ``DT_MIN`` to ``DT_MAX``, ``D = 1 + D``.
A checkpoint of the source's names loads by subtracting the three constants.
Why: a state whose ``dt |A|`` is O(1) forgets in a few tokens, and the
model's point is the state that carries thousands (PAPERS.md).

This file holds what is Jamba's alone: its sizes, the mixer, the attention
layer and the block. Norm and ``same_graph_above`` come from
``token_common.py``, the attention core from ``token_attention.py``, the
SwiGLU from ``token_routed.py``, the scan from ``ops/`` (both imported by NAME
and called through this module's own globals: the benchmark's controls replace
them here); no other family's file is imported here and none imports this
one. Precision: float32 parameters, residual stream, norms, softplus and the
WHOLE recurrence (dt, ``exp``, the state, the sum over the states); matmul
operands rounded to bf16 on the TPU.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
import flax.linen as nn

from ..ops.selective_scan import selective_scan
from ..telemetry import scopes
from .token_attention import segment_causal_attention
from .token_common import RMSNorm, missing_fields, same_graph_above
from .token_routed import DenseFFN

# Mamba's published initializer draws each channel's first step size
# log-uniformly between these (``dt_min``, ``dt_max`` of ``mamba_ssm``'s
# ``Mamba``); here the channels are spaced over the range in order.
DT_MIN, DT_MAX = 1e-3, 1e-1


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    """The stack's static sizes, keyed as the source's ``config.json`` names
    them, plus the dataset's table for the token column (``token_minmax``)."""

    attn_layer_period: int
    attn_layer_offset: int
    intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    mamba_d_state: int
    mamba_d_conv: int
    mamba_dt_rank: int
    mamba_expand: int
    vocab_size: int
    token_minmax: Tuple[float, float]
    rms_norm_eps: float = 1e-6
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    tie_word_embeddings: bool = True
    num_experts: int = 1

    @property
    def norm_eps(self) -> float:
        return self.rms_norm_eps

    missing = classmethod(missing_fields)

    @classmethod
    def from_arch(cls, arch: dict, num_layers: int) -> "JambaConfig":
        missing = cls.missing(arch)
        if missing:
            raise ValueError(
                f"JAMBA requires Architecture.{'/'.join(missing)} (token_minmax "
                "comes from config completion: the dataset's table)"
            )
        if int(arch.get("num_experts", 1)) != 1:
            raise ValueError(
                "JAMBA builds the dense feed-forward of num_experts 1 in every "
                f"layer; got num_experts {arch['num_experts']}"
            )
        if int(arch["num_attention_heads"]) % int(arch["num_key_value_heads"]):
            raise ValueError(
                f"the {arch['num_attention_heads']} query heads share the "
                f"{arch['num_key_value_heads']} key-value heads evenly"
            )
        kw = {f.name: arch[f.name] for f in dataclasses.fields(cls) if f.name in arch}
        kw.update(token_minmax=tuple(float(v) for v in arch["token_minmax"]))
        return cls(**kw)

    def routed(self, layer: int) -> bool:
        return False

    def scans(self, layer: int) -> bool:
        """Whether the layer's mixer is the selective scan (the library's rule
        for this ``model_type``: attention where ``layer mod period`` is the
        offset, Mamba elsewhere)."""
        return layer % self.attn_layer_period != self.attn_layer_offset


def _first_steps(channels: int):
    """``softplus^-1`` of the channels' first step sizes, [channels]."""
    dt0 = np.exp(np.linspace(math.log(DT_MIN), math.log(DT_MAX), channels))
    return jnp.asarray(dt0 + np.log(-np.expm1(-dt0)), jnp.float32)


class InProj(nn.Module):
    """The mixer's input projection ``[u, z] = W_in x`` as ONE matrix whose
    halves are multiplied where each is used: ``u`` before the convolution,
    ``z`` after the scan, so that the gate's ``[N, d_inner]`` operand is not
    held through the scan (346 MB at the cell's guard rung)."""

    width: int  # d_inner; the matrix is [features, 2 width]
    use_bias: bool = False

    @nn.compact
    def __call__(self, x, half: int):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(), (x.shape[-1], 2 * self.width)
        )
        cols = slice(half * self.width, (half + 1) * self.width)
        y = x @ kernel[:, cols]
        if self.use_bias:
            y = y + self.param("bias", nn.initializers.zeros, (2 * self.width,))[cols]
        return y


class MambaMixer(nn.Module):
    """``[u, z] = W_in x``; ``u = silu(conv(u))`` inside the node's own graph;
    ``[delta, B, C] = W_x u``, each under its RMSNorm; ``dt = softplus(W_dt
    delta + b)``; the selective scan; ``W_out (y * silu(z))``."""

    features: int
    cfg: JambaConfig

    @nn.compact
    def __call__(self, x, node_graph):
        c = self.cfg
        d = c.mamba_expand * self.features
        s, r, taps = c.mamba_d_state, c.mamba_dt_rank, c.mamba_d_conv
        in_proj = InProj(d, c.mamba_proj_bias, name="in_proj")
        # ``taps - 1`` zero rows in front: each tap is then a slice of an array
        # LONGER than the convolution's result, which the compiler cannot
        # write that result into (``token_common.same_graph_shift`` says what
        # happened when it could).
        u = in_proj(jnp.pad(x, ((taps - 1, 0), (0, 0))), 0)
        with jax.named_scope(scopes.SSM_CONV):
            k = self.param(
                "conv_kernel", nn.initializers.variance_scaling(1.0, "fan_in", "uniform"),
                (taps, d),
            )
            n = x.shape[0]
            conv = k[taps - 1] * u[taps - 1 :]
            for back in range(1, taps):
                above = u[taps - 1 - back : taps - 1 - back + n]
                same = same_graph_above(node_graph, back)[:, None]
                conv = conv + k[taps - 1 - back] * jnp.where(same, above, 0.0)
            if c.mamba_conv_bias:
                conv = conv + self.param("conv_bias", nn.initializers.zeros, (d,))
            u = nn.silu(conv)
        ssm = nn.Dense(r + 2 * s, use_bias=False, name="x_proj")(u)
        with jax.named_scope(scopes.SSM_DT):
            delta = RMSNorm(c.rms_norm_eps, name="dt_layernorm")(ssm[:, :r])
            b = RMSNorm(c.rms_norm_eps, name="b_layernorm")(ssm[:, r : r + s])
            cc = RMSNorm(c.rms_norm_eps, name="c_layernorm")(ssm[:, r + s :])
            dt = nn.softplus(
                nn.Dense(d, use_bias=True, name="dt_proj")(delta) + _first_steps(d)
            )
        a_log = self.param("A_log", nn.initializers.zeros, (d, s))
        skip = self.param("D", nn.initializers.zeros, (d,))
        with jax.named_scope(scopes.SSM_SCAN):
            a = -jnp.exp(a_log + jnp.log(jnp.arange(1, s + 1, dtype=jnp.float32)))
            y = selective_scan(u, dt, a, b, cc, 1.0 + skip, node_graph)
        # The gate, outside the kernel and outside its scope: XLA fuses the
        # product into the z matmul's output, whose time is the module's.
        y = y * nn.silu(in_proj(x, 1))
        return nn.Dense(self.features, use_bias=c.mamba_proj_bias, name="out_proj")(y)


class Attention(nn.Module):
    """Grouped-query attention over the complete causal graph of the node's
    document: no rotary, no norm on ``q`` / ``k``, no bias, no gate."""

    features: int
    cfg: JambaConfig

    @nn.compact
    def __call__(self, x, node_graph):
        c = self.cfg
        n, h, kv = x.shape[0], c.num_attention_heads, c.num_key_value_heads
        hd = self.features // h
        q = nn.Dense(h * hd, use_bias=False, name="q_proj")(x).reshape(n, h, hd)
        k = nn.Dense(kv * hd, use_bias=False, name="k_proj")(x).reshape(n, kv, hd)
        v = nn.Dense(kv * hd, use_bias=False, name="v_proj")(x).reshape(n, kv, hd)
        with jax.named_scope(scopes.ATTN_FULL):
            y = segment_causal_attention(q, k, v, node_graph)
        return nn.Dense(self.features, use_bias=False, name="o_proj")(y)


class JambaBlock(nn.Module):
    """``h += mixer(RMSNorm(h))``; ``h += SwiGLU(RMSNorm(h))``: the mixer
    attention or Mamba by ``JambaConfig.scans``. The place column is not
    read."""

    features: int
    cfg: JambaConfig
    layer: int

    @nn.compact
    def __call__(self, h, node_graph, place, node_mask):
        c = self.cfg
        x = RMSNorm(c.rms_norm_eps, name="input_layernorm")(h)
        if c.scans(self.layer):
            h = h + MambaMixer(self.features, c, name="mamba")(x, node_graph)
        else:
            h = h + Attention(self.features, c, name="self_attn")(x, node_graph)
        x = RMSNorm(c.rms_norm_eps, name="pre_ff_layernorm")(h)
        return h + DenseFFN(self.features, c.intermediate_size, name="feed_forward")(x)
