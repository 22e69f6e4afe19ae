"""Mellum2-12B-A2.5B's block (JetBrains, ``model_type`` ``mellum``;
https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json)
on this system's batch: a token is a node, a sequence a graph with its nodes
in order, ``positions[:, 0]`` the node's place. Equations, assumptions and
departures: PAPERS.md.

The block is plain where the other families' are not, and every part of it
is a shared one: grouped-query attention with ONE head count, no gate, no
norm on ``q`` / ``k``, over the causal BAND on the ``sliding_attention``
layers and the complete causal graph on the ``full_attention`` ones (three to
one as published); rotary over the whole head by the layer's kind (plain on the
band, YaRN on the triangle); then a routed feed-forward on EVERY layer, with
no shared expert beside it and no leading dense layer, its router a softmax
over all experts whose chosen scores are normalised again.

This file holds what is Mellum2's alone: its sizes, the attention layer and
the block. Norm, ``rotary`` and its ``Rope`` record come from
``token_common.py``, the attention core from ``token_attention.py`` (imported
by NAME and called through this module's own global: the benchmark's control
replaces it here), the routed feed-forward with the sown intermediates and
counters from ``token_routed.py``; no other family's file is imported here and
none imports this one. Precision: float32 parameters, residual stream, norms,
softmax; matmul operands rounded to bf16 on the TPU; the router's ``W_r x`` at
``Precision.HIGHEST``.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import flax.linen as nn

from ..telemetry import scopes
# graftbench/tests/test_mellum_cell.py replaces this module's
# ``segment_causal_attention``: keep it imported by name (ROADMAP D25).
from .token_attention import segment_causal_attention
from .token_common import KINDS, RMSNorm, Rope, missing_fields, ropes_by_kind, rotary
from .token_routed import RoutedFFN, experts_share


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    """The stack's static sizes, keyed as the source's ``config.json`` names
    them, plus this rank's share (``num_experts_held``, ``experts_offset``:
    all 64 from 0 where the layer is held whole) and the dataset's table for
    the token column (``token_minmax``). The two per-layer lists may be the
    published ones whole: the first ``num_layers`` entries are built."""

    layer_types: Tuple[str, ...]
    mlp_layer_types: Tuple[str, ...]
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    moe_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    num_experts_held: int
    experts_offset: int
    sliding_window: int
    rope_parameters: Tuple[Rope, Rope]  # by KINDS
    vocab_size: int
    token_minmax: Tuple[float, float]
    rms_norm_eps: float = 1e-6
    norm_topk_prob: bool = True

    # What ``RoutedFFN`` and the encoder read under the shared names
    # (token_common.py lists them). The router the config implies (softmax
    # over all experts, the chosen ones normalised, no scaling, no bias: the
    # keys are Qwen3-MoE's, and so is the convention) is assumed: PAPERS.md.
    scoring_func = "softmax"
    routed_scaling_factor = 1.0
    use_expert_bias = False

    @property
    def norm_eps(self) -> float:
        return self.rms_norm_eps

    missing = classmethod(missing_fields)

    @classmethod
    def from_arch(cls, arch: dict, num_layers: int) -> "MellumConfig":
        missing = cls.missing(arch)
        if missing:
            raise ValueError(
                f"MELLUM requires Architecture.{'/'.join(missing)} (token_minmax "
                "comes from config completion: the dataset's table)"
            )
        lists = {
            name: tuple(arch[name][:num_layers])
            for name in ("layer_types", "mlp_layer_types")
        }
        if (
            any(len(v) != num_layers for v in lists.values())
            or set(lists["layer_types"]) - set(KINDS)
            or set(lists["mlp_layer_types"]) - {"sparse"}
        ):
            raise ValueError(
                f"MELLUM needs {num_layers} layer_types of {KINDS} and as many "
                f"mlp_layer_types, all 'sparse' (a dense layer is not built), got {lists}"
            )
        if int(arch["num_attention_heads"]) % int(arch["num_key_value_heads"]):
            raise ValueError(
                f"the {arch['num_attention_heads']} query heads share the "
                f"{arch['num_key_value_heads']} key-value heads evenly"
            )
        held, offset = experts_share(arch)
        kw = {f.name: arch[f.name] for f in dataclasses.fields(cls) if f.name in arch}
        kw.update(
            lists, num_experts_held=held, experts_offset=offset,
            rope_parameters=ropes_by_kind(arch["rope_parameters"]),
            token_minmax=tuple(float(v) for v in arch["token_minmax"]),
        )
        return cls(**kw)

    def routed(self, layer: int) -> bool:
        return True

    def sliding(self, layer: int) -> bool:
        return self.layer_types[layer] == "sliding_attention"

    def rope(self, layer: int) -> Rope:
        return self.rope_parameters[KINDS.index(self.layer_types[layer])]


class Attention(nn.Module):
    """Grouped-query attention over the layer's graph (complete causal, or
    the causal band of ``sliding_window``, the token itself counted), rotary
    over the whole head by the layer's kind. No bias, no norm on ``q`` /
    ``k``, no gate."""

    features: int
    cfg: MellumConfig
    layer: int

    @nn.compact
    def __call__(self, x, node_graph, place):
        c = self.cfg
        n, h, kv, hd = x.shape[0], c.num_attention_heads, c.num_key_value_heads, c.head_dim
        sliding, rope = c.sliding(self.layer), c.rope(self.layer)
        q = nn.Dense(h * hd, use_bias=False, name="q_proj")(x).reshape(n, h, hd)
        k = nn.Dense(kv * hd, use_bias=False, name="k_proj")(x).reshape(n, kv, hd)
        v = nn.Dense(kv * hd, use_bias=False, name="v_proj")(x).reshape(n, kv, hd)
        with jax.named_scope(scopes.ATTN_WINDOW if sliding else scopes.ATTN_FULL):
            y = segment_causal_attention(
                rotary(q, place, rope), rotary(k, place, rope), v, node_graph,
                window=c.sliding_window if sliding else None,
            )
        return nn.Dense(self.features, use_bias=False, name="o_proj")(y)


class MellumBlock(nn.Module):
    """``h += attn(RMSNorm(h))``; ``h += routed(RMSNorm(h))`` on every layer.
    The routed layer is ``feed_forward``, as every family's:
    ``split_intermediates`` finds it there."""

    features: int
    cfg: MellumConfig
    layer: int

    @nn.compact
    def __call__(self, h, node_graph, place, node_mask):
        c = self.cfg
        x = RMSNorm(c.rms_norm_eps, name="input_layernorm")(h)
        h = h + Attention(self.features, c, self.layer, name="self_attn")(x, node_graph, place)
        x = RMSNorm(c.rms_norm_eps, name="post_attention_layernorm")(h)
        return h + RoutedFFN(self.features, c, name="feed_forward")(x, node_mask)
