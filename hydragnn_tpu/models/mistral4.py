"""Mistral-Small-4's block (mistralai, ``model_type`` ``mistral4``;
https://huggingface.co/mistralai/Mistral-Small-4-119B-2603/blob/main/config.json)
on this system's batch: a token is a node, a sequence a graph with its nodes
in order, ``positions[:, 0]`` the node's place. Equations, assumptions and
departures: PAPERS.md.

What this stack adds to the token path:

* **latent attention**: q and the keys and values come through two low-rank
  chains with an RMSNorm in the middle of each (``W_qb rms(W_qa x)``,
  ``W_kvb rms(c_kv)``); a head's key is its own ``qk_nope_head_dim`` part
  concatenated with ONE rotary part of ``qk_rope_head_dim`` that all heads
  share, a head's query the same two parts of its own; the value has a width
  of its own (``v_head_dim``);
* rotary over INTERLEAVED pairs ``(2i, 2i+1)`` of the rotary part alone
  (``rope_interleave``), YaRN's blended frequencies over those dimensions;
  computed in the halves convention after one fixed permutation of the
  rotary columns of q and k alike, which no dot product can see;
* a softmax scale of its own, ``qk_head_dim ** -0.5`` times YaRN's
  ``mscale(factor, mscale_all_dim)`` squared, and Llama-4's factor on q,
  ``1 + beta ln(1 + floor(place / original_max_position_embeddings))``;
* a router that scores by SOFTMAX over all experts (``RoutedFFN`` reads
  ``scoring_func`` by name), beside a shared expert that every rank computes
  whole.

This file holds what is Mistral-Small-4's alone: its sizes, the latent
attention layer and the block. Norm, the rotation and YaRN's frequencies
(``Rope``) come from ``token_common.py``, the attention core from
``token_attention.py``, the dense and the routed feed-forward with the sown
intermediates and counters from ``token_routed.py``; no other family's file is
imported here and none imports this one. Precision: float32 parameters,
residual stream, norms, softmax; matmul operands rounded to bf16 on the TPU;
the router's ``W_r x`` at ``Precision.HIGHEST``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from ..telemetry import scopes
from .token_attention import segment_causal_attention
from .token_common import RMSNorm, Rope, missing_fields, rotate
from .token_routed import DenseFFN, RoutedFFN, experts_share


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature as the DeepSeek-V3 code the ``mistral4``
    keys are named after has it: ``0.1 mscale ln(factor) + 1`` (1 where the
    context is not stretched)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


@dataclasses.dataclass(frozen=True)
class Mistral4Config:
    """The stack's static sizes, keyed as the source's ``config.json`` names
    them, plus this rank's share (``num_experts_held``, ``experts_offset``)
    and the dataset's table for the token column (``token_minmax``).
    ``rope_parameters`` is the source's one dict: YaRN's keys go into the
    ``Rope`` record, ``llama_4_scaling_beta`` and the two ``mscale`` beside it."""

    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    moe_intermediate_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    num_experts_held: int
    experts_offset: int
    rope_parameters: Rope
    vocab_size: int
    token_minmax: Tuple[float, float]
    llama_4_scaling_beta: float = 0.0
    mscale: float = 0.0
    mscale_all_dim: float = 0.0
    n_shared_experts: int = 1
    intermediate_size: int = 0  # read by a leading dense layer alone
    first_k_dense_replace: int = 0
    rms_norm_eps: float = 1e-6
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rope_interleave: bool = True

    # What ``RoutedFFN`` and the encoder read under the shared names
    # (token_common.py lists them). The score function and the absence of a
    # correction bias are assumed (the config has no key for either):
    # PAPERS.md.
    scoring_func = "softmax"
    use_expert_bias = False

    @property
    def num_experts(self) -> int:
        return self.n_routed_experts

    @property
    def norm_eps(self) -> float:
        return self.rms_norm_eps

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        """``qk_head_dim ** -0.5`` times ``mscale(factor, mscale_all_dim)``
        squared (0.19497 at the published numbers)."""
        m = yarn_mscale(self.rope_parameters.factor, self.mscale_all_dim)
        return self.qk_head_dim ** -0.5 * m * m

    missing = classmethod(missing_fields)

    @classmethod
    def from_arch(cls, arch: dict, num_layers: int) -> "Mistral4Config":
        missing = cls.missing(arch)
        if missing:
            raise ValueError(
                f"MISTRAL4 requires Architecture.{'/'.join(missing)} (token_minmax "
                "comes from config completion: the dataset's table)"
            )
        if int(arch.get("n_group", 1)) != 1 or int(arch.get("topk_group", 1)) != 1:
            raise ValueError("MISTRAL4 with n_group / topk_group != 1 (a group limit) is not built")
        if not arch.get("rope_interleave", True):
            raise ValueError("MISTRAL4 without rope_interleave is not built")
        held, offset = experts_share(dict(arch, num_experts=arch["n_routed_experts"]))
        rope = dict(arch["rope_parameters"])
        rope.setdefault("rope_type", rope.get("type", "default"))
        # cos and sin times mscale(factor, mscale) / mscale(factor,
        # mscale_all_dim): 1 where the two are equal, as published.
        factor = float(rope.get("factor", 1.0))
        own, all_dim = float(rope.get("mscale", 0.0)), float(rope.get("mscale_all_dim", 0.0))
        rope["attention_factor"] = (
            yarn_mscale(factor, own) / yarn_mscale(factor, all_dim)
            if rope["rope_type"] == "yarn" else 1.0
        )
        kw = {f.name: arch[f.name] for f in dataclasses.fields(cls) if f.name in arch}
        kw.update(
            num_experts_held=held, experts_offset=offset,
            rope_parameters=Rope(**{
                k: v for k, v in rope.items()
                if k in {f.name for f in dataclasses.fields(Rope)}
            }),
            llama_4_scaling_beta=float(rope.get("llama_4_scaling_beta", 0.0)),
            mscale=own, mscale_all_dim=all_dim,
            token_minmax=tuple(float(v) for v in arch["token_minmax"]),
        )
        cfg = cls(**kw)
        if cfg.first_k_dense_replace and not cfg.intermediate_size:
            raise ValueError("MISTRAL4's leading dense layers need intermediate_size")
        return cfg

    def routed(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace


def pairs_to_halves(x):
    """The fixed permutation of the rotary columns: the interleaved pairs
    ``(2i, 2i+1)`` become ``(i, i + r/2)``, the halves convention's. Applied
    to q and k alike it leaves every dot product as it was."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def llama4_factor(place, beta: float, original: int):
    """``1 + beta ln(1 + floor(place / original))``: 1 below the context the
    rotary was trained at."""
    return 1.0 + beta * jnp.log1p(jnp.floor(place.astype(jnp.float32) / original))


class LatentAttention(nn.Module):
    """Multi-head latent attention over the complete causal graph of each
    sequence. No bias anywhere."""

    features: int
    cfg: Mistral4Config

    @nn.compact
    def __call__(self, x, node_graph, place):
        c = self.cfg
        n, h = x.shape[0], c.num_attention_heads
        nope, rot, vd, qk = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim, c.qk_head_dim
        rope = c.rope_parameters
        with jax.named_scope(scopes.ATTN_LATENT):
            c_q = RMSNorm(c.rms_norm_eps, name="q_a_layernorm")(
                nn.Dense(c.q_lora_rank, use_bias=False, name="q_a_proj")(x)
            )
            q = nn.Dense(h * qk, use_bias=False, name="q_b_proj")(c_q).reshape(n, h, qk)
            kv_a = nn.Dense(c.kv_lora_rank + rot, use_bias=False, name="kv_a_proj_with_mqa")(x)
            c_kv = RMSNorm(c.rms_norm_eps, name="kv_a_layernorm")(kv_a[:, : c.kv_lora_rank])
            kv = nn.Dense(h * (nope + vd), use_bias=False, name="kv_b_proj")(c_kv)
            kv = kv.reshape(n, h, nope + vd)
            inv, factor, _ = rope.frequencies(rot)
            inv = jnp.asarray(inv)
            q_rot = rotate(pairs_to_halves(q[..., nope:]), place, inv, factor)
            k_rot = rotate(  # ONE head, shared by all
                pairs_to_halves(kv_a[:, None, c.kv_lora_rank:]), place, inv, factor
            )
            q = jnp.concatenate([q[..., :nope], q_rot], axis=-1)
            if c.llama_4_scaling_beta:
                q = q * llama4_factor(
                    place, c.llama_4_scaling_beta, rope.original_max_position_embeddings
                )[:, None, None]
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(k_rot, (n, h, rot))], axis=-1
            )
            v = kv[..., nope:]
            # The core takes one width for q, k and v: zeros pad the narrower
            # side (a dot product and a sliced-off column see none of them).
            wide = max(qk, vd)
            if qk < wide:
                q, k = (jnp.pad(a, ((0, 0), (0, 0), (0, wide - qk))) for a in (q, k))
            if vd < wide:
                v = jnp.pad(v, ((0, 0), (0, 0), (0, wide - vd)))
        with jax.named_scope(scopes.ATTN_FULL):
            y = segment_causal_attention(q, k, v, node_graph, scale=c.softmax_scale)
        if vd < wide:
            y = y.reshape(n, h, wide)[..., :vd].reshape(n, h * vd)
        return nn.Dense(self.features, use_bias=False, name="o_proj")(y)


class Mistral4Block(nn.Module):
    """``h += attn(RMSNorm(h))``; ``h += shared(RMSNorm(h)) + routed(...)``
    on every layer from ``first_k_dense_replace`` (0 as published), a dense
    SwiGLU before it. The routed layer is ``feed_forward``, as every family's:
    ``split_intermediates`` finds it there."""

    features: int
    cfg: Mistral4Config
    layer: int

    @nn.compact
    def __call__(self, h, node_graph, place, node_mask):
        c = self.cfg
        x = RMSNorm(c.rms_norm_eps, name="input_layernorm")(h)
        h = h + LatentAttention(self.features, c, name="self_attn")(x, node_graph, place)
        x = RMSNorm(c.rms_norm_eps, name="post_attention_layernorm")(h)
        if not c.routed(self.layer):
            return h + DenseFFN(self.features, c.intermediate_size, name="feed_forward")(x)
        with jax.named_scope(scopes.MOE_SHARED):
            shared = DenseFFN(
                self.features, c.moe_intermediate_size * c.n_shared_experts,
                name="shared_experts",
            )(x)
        return h + shared + RoutedFFN(self.features, c, name="feed_forward")(x, node_mask)
