"""Laguna-XS.2's block (poolside, ``model_type`` ``laguna``;
https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json) on this
system's batch: a token is a node, a sequence a graph with its nodes in
order, ``positions[:, 0]`` the node's place. Equations, assumptions and
departures: PAPERS.md.

What this stack adds to the token path, all of it per LAYER:

* attention of two kinds mixed by ``layer_types``: ``full_attention`` is the
  softmax aggregation over the complete causal graph of a sequence,
  ``sliding_attention`` the same over the causal BAND (node ``i`` receives
  from the ``j`` of its own graph with ``0 <= i - j < sliding_window``): a
  second implied graph beside the causal triangle, and like it never held as
  an edge list (``token_attention.segment_causal_attention``);
* the number of query heads by layer (``num_attention_heads_per_layer``; the
  key-value heads stay), so the projections' widths change with the layer;
* rotary embedding by kind (``rope_parameters``): full layers rotate the
  first ``partial_rotary_factor`` of each head with YaRN's blended
  frequencies and its attention factor and leave the rest as it is, sliding
  layers rotate the whole head at the plain frequencies;
* a sigmoid gate a head on the attention output, from the layer's input;
* a shared expert beside the routed ones (``token_routed.RoutedFFN``, told
  which experts it holds): every rank computes the shared expert whole.

This file holds what is Laguna's alone: its sizes, the gated attention layer
and the block. Norm and rotary (``Rope``, ``rotary``) come from
``token_common.py``, the attention core from ``token_attention.py``, the dense
and the routed feed-forward with the sown intermediates and counters from
``token_routed.py``; no other family's file is imported here and none imports
this one. Precision: float32 parameters, residual stream, norms, softmax,
sigmoids; matmul operands rounded to bf16 on the TPU; the router at
``Precision.HIGHEST``.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import flax.linen as nn

from ..telemetry import scopes
from .token_attention import segment_causal_attention
from .token_common import KINDS, RMSNorm, Rope, missing_fields, ropes_by_kind, rotary
from .token_routed import DenseFFN, RoutedFFN, experts_share


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """The stack's static sizes, keyed as the source's ``config.json`` names
    them, plus this rank's share (``num_experts_held``, ``experts_offset``)
    and the dataset's table for the token column (``token_minmax``). The
    three per-layer lists may be the published ones whole: the first
    ``num_layers`` entries are built."""

    layer_types: Tuple[str, ...]
    mlp_layer_types: Tuple[str, ...]
    num_attention_heads_per_layer: Tuple[int, ...]
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    num_experts_held: int
    experts_offset: int
    sliding_window: int
    rope_parameters: Tuple[Rope, Rope]  # by KINDS
    vocab_size: int
    token_minmax: Tuple[float, float]
    rms_norm_eps: float = 1e-6
    moe_routed_scaling_factor: float = 1.0

    # What ``RoutedFFN`` and the encoder read under the shared names
    # (token_common.py lists them). The router the config implies (sigmoid
    # scores, the chosen ones normalised, then the scaling; no expert bias) is
    # assumed: PAPERS.md.
    scoring_func = "sigmoid"
    norm_topk_prob = True
    use_expert_bias = False

    @property
    def routed_scaling_factor(self) -> float:
        return self.moe_routed_scaling_factor

    @property
    def norm_eps(self) -> float:
        return self.rms_norm_eps

    missing = classmethod(missing_fields)

    @classmethod
    def from_arch(cls, arch: dict, num_layers: int) -> "LagunaConfig":
        missing = cls.missing(arch)
        if missing:
            raise ValueError(
                f"LAGUNA requires Architecture.{'/'.join(missing)} (token_minmax "
                "comes from config completion: the dataset's table)"
            )
        lists = {
            name: tuple(arch[name][:num_layers])
            for name in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer")
        }
        if (
            any(len(v) != num_layers for v in lists.values())
            or set(lists["layer_types"]) - set(KINDS)
            or set(lists["mlp_layer_types"]) - {"dense", "sparse"}
        ):
            raise ValueError(
                f"LAGUNA needs {num_layers} layer_types of {KINDS}, as many "
                "mlp_layer_types of 'dense' / 'sparse' and as many "
                f"num_attention_heads_per_layer, got {lists}"
            )
        kv = int(arch["num_key_value_heads"])
        if any(h % kv for h in lists["num_attention_heads_per_layer"]):
            raise ValueError(
                f"each of num_attention_heads_per_layer "
                f"{lists['num_attention_heads_per_layer']} shares the {kv} "
                "key-value heads evenly"
            )
        if not arch.get("gating", True):
            raise ValueError("LAGUNA without the gate on the attention output is not built")
        held, offset = experts_share(arch)
        ropes = ropes_by_kind(arch["rope_parameters"])
        kw = {
            f.name: arch[f.name] for f in dataclasses.fields(cls) if f.name in arch
        }
        kw.update(
            lists, num_experts_held=held, experts_offset=offset,
            rope_parameters=ropes,
            token_minmax=tuple(float(v) for v in arch["token_minmax"]),
        )
        return cls(**kw)

    def routed(self, layer: int) -> bool:
        return self.mlp_layer_types[layer] == "sparse"

    def sliding(self, layer: int) -> bool:
        return self.layer_types[layer] == "sliding_attention"

    def rope(self, layer: int) -> Rope:
        return self.rope_parameters[KINDS.index(self.layer_types[layer])]


class GatedAttention(nn.Module):
    """Grouped-query attention over the layer's graph (complete causal, or
    the causal band of ``sliding_window``), its own number of query heads,
    rotary by kind, and ``sigmoid(W_g x)`` a head on the output before
    ``W_o``. No bias, no norm on ``q`` / ``k``."""

    features: int
    cfg: LagunaConfig
    layer: int

    @nn.compact
    def __call__(self, x, node_graph, place):
        c = self.cfg
        n, kv, hd = x.shape[0], c.num_key_value_heads, c.head_dim
        h = c.num_attention_heads_per_layer[self.layer]
        sliding, rope = c.sliding(self.layer), c.rope(self.layer)
        q = nn.Dense(h * hd, use_bias=False, name="q_proj")(x).reshape(n, h, hd)
        k = nn.Dense(kv * hd, use_bias=False, name="k_proj")(x).reshape(n, kv, hd)
        v = nn.Dense(kv * hd, use_bias=False, name="v_proj")(x).reshape(n, kv, hd)
        gate = nn.Dense(h, use_bias=False, name="g_proj")(x)
        with jax.named_scope(scopes.ATTN_WINDOW if sliding else scopes.ATTN_FULL):
            y = segment_causal_attention(
                rotary(q, place, rope), rotary(k, place, rope), v, node_graph,
                window=c.sliding_window if sliding else None,
            )
            y = (y.reshape(n, h, hd) * jax.nn.sigmoid(gate)[:, :, None]).reshape(n, h * hd)
        return nn.Dense(self.features, use_bias=False, name="o_proj")(y)


class LagunaBlock(nn.Module):
    """``h += attn(RMSNorm(h))``; ``h += ffn(RMSNorm(h))``: the feed-forward a
    dense SwiGLU on the ``dense`` layers and, on the ``sparse`` ones, the
    shared expert plus this rank's part of the routed sum. The routed layer
    is ``feed_forward``, as every family's: ``split_intermediates`` finds it
    there."""

    features: int
    cfg: LagunaConfig
    layer: int

    @nn.compact
    def __call__(self, h, node_graph, place, node_mask):
        c = self.cfg
        x = RMSNorm(c.rms_norm_eps, name="input_layernorm")(h)
        h = h + GatedAttention(self.features, c, self.layer, name="self_attn")(
            x, node_graph, place
        )
        x = RMSNorm(c.rms_norm_eps, name="post_attention_layernorm")(h)
        if not c.routed(self.layer):
            return h + DenseFFN(self.features, c.intermediate_size, name="feed_forward")(x)
        shared = DenseFFN(
            self.features, c.shared_expert_intermediate_size, name="shared_expert"
        )(x)
        return h + shared + RoutedFFN(self.features, c, name="feed_forward")(x, node_mask)
