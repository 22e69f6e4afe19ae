"""Laguna-XS.2's block (poolside, ``model_type`` ``laguna``;
https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json) on this
system's batch, as ``models/lfm2.py`` puts LFM2's there: a token is a node, a
sequence a graph with its nodes in order, ``positions[:, 0]`` the node's
place. Equations, assumptions and departures: PAPERS.md.

What this stack adds to the token path, all of it per LAYER:

* attention of two kinds mixed by ``layer_types``: ``full_attention`` is the
  softmax aggregation over the complete causal graph of a sequence,
  ``sliding_attention`` the same over the causal BAND (node ``i`` receives
  from the ``j`` of its own graph with ``0 <= i - j < sliding_window``): a
  third implied graph beside LFM2's 3-wide band and causal triangle, and as
  they are never held as an edge list (``lfm2.segment_causal_attention``);
* the number of query heads by layer (``num_attention_heads_per_layer``; the
  key-value heads stay), so the projections' widths change with the layer;
* rotary embedding by kind (``rope_parameters``): full layers rotate the
  first ``partial_rotary_factor`` of each head with YaRN's blended
  frequencies and its attention factor and leave the rest as it is, sliding
  layers rotate the whole head at the plain frequencies;
* a sigmoid gate a head on the attention output, from the layer's input;
* a shared expert beside the routed ones (``lfm2.RoutedFFN``, told which
  experts it holds): every rank computes the shared expert whole.

Nothing of LFM2's is copied: norm, rotary, the attention core, the dense and
the routed feed-forward, the sown intermediates and counters are imported.
Precision as there: float32 parameters, residual stream, norms, softmax,
sigmoids; matmul operands rounded to bf16 on the TPU; the router at
``Precision.HIGHEST``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import flax.linen as nn

from ..telemetry import scopes
from .lfm2 import (
    DenseFFN, RMSNorm, RoutedFFN, experts_share, missing_fields, rotate,
    segment_causal_attention,
)

KINDS = ("full_attention", "sliding_attention")


@dataclasses.dataclass(frozen=True)
class Rope:
    """One entry of the source's ``rope_parameters``."""

    rope_theta: float
    rope_type: str = "default"
    partial_rotary_factor: float = 1.0
    factor: float = 1.0
    original_max_position_embeddings: Optional[int] = None
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None

    def __post_init__(self):
        if self.rope_type not in ("default", "yarn"):
            raise ValueError(f"rope_type {self.rope_type!r}: 'default' or 'yarn'")
        if self.rope_type == "yarn" and not self.original_max_position_embeddings:
            raise ValueError("yarn needs original_max_position_embeddings")

    def frequencies(self, head_dim: int):
        """(``inv`` [rotated / 2] float32, the factor on cos and sin, the
        number of leading dimensions of a head that are rotated).

        ``default``: ``theta^(-2i/r)``. ``yarn`` (Peng et al. 2023, as
        ``transformers`` ``_compute_yarn_parameters`` has it, ``truncate``
        true): per pair ``i`` the blend ``(1 - g_i) theta^(-2i/r) / factor +
        g_i theta^(-2i/r)`` with ``g_i = 1 - clip((i - low) / (high - low),
        0, 1)`` between the correction dimensions ``low = floor(c(beta_fast))``
        and ``high = ceil(c(beta_slow))``, ``c(b) = r ln(L / (2 pi b)) /
        (2 ln theta)``, ``L`` the original context; cos and sin times
        ``attention_factor`` (``0.1 ln(factor) + 1`` where the source gives
        none)."""
        r = int(head_dim * self.partial_rotary_factor)
        i = np.arange(r // 2, dtype=np.float64)
        plain = float(self.rope_theta) ** (-2.0 * i / r)
        if self.rope_type == "default":
            return plain.astype(np.float32), 1.0, r

        def correction(rotations):
            return (
                r * math.log(self.original_max_position_embeddings
                             / (rotations * 2 * math.pi))
                / (2 * math.log(self.rope_theta))
            )

        low = max(math.floor(correction(self.beta_fast)), 0)
        high = min(math.ceil(correction(self.beta_slow)), r - 1)
        ramp = np.clip((i - low) / ((high if high != low else high + 1e-3) - low), 0, 1)
        keep = 1.0 - ramp  # 1: the frequency as it is; 0: divided by factor
        inv = plain / self.factor * (1 - keep) + plain * keep
        scale = self.attention_factor
        if scale is None:
            scale = 0.1 * math.log(self.factor) + 1.0 if self.factor > 1 else 1.0
        return inv.astype(np.float32), float(scale), r


def ropes_by_kind(rope_parameters: dict) -> Tuple[Rope, Rope]:
    """The source's ``rope_parameters``, one section a kind, as records by
    ``KINDS`` (keys a ``Rope`` does not hold are left out)."""
    names = {f.name for f in dataclasses.fields(Rope)}
    return tuple(
        Rope(**{k: v for k, v in rope_parameters[kind].items() if k in names})
        for kind in KINDS
    )


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

    # What ``RoutedFFN`` and the encoder read under LFM2's names. The router
    # the config implies (sigmoid scores, the chosen ones normalised, then
    # the scaling; no expert bias) is assumed: PAPERS.md.
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


def rotary(x, place, rope: Rope):
    """``x`` [N, heads, hd] with the first ``partial_rotary_factor`` of each
    head rotated at ``place`` and the rest as it is."""
    inv, factor, r = rope.frequencies(x.shape[-1])
    turned = rotate(x[..., :r], place, jnp.asarray(inv), factor)
    return turned if r == x.shape[-1] else jnp.concatenate([turned, x[..., r:]], axis=-1)


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
    is ``feed_forward``, as LFM2's: ``split_intermediates`` finds it there."""

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
