"""What every token stack (``models/families.py`` ``TOKEN_STACKS``) does to a
token before and between its mixers, and no family's own: the token id read
exactly off the node column, RMSNorm, the shifted read inside a node's own
graph (a causal convolution's tap), and the rotary embedding by the source's
``rope_parameters`` (plain frequencies, or YaRN's blended ones over a part of
the head). A family's file imports from here, from ``token_attention.py`` and
from ``token_routed.py``; this module imports none of them.

**What the shared token code reads off a stack's sizes object** (the frozen
dataclass a family builds ``from_arch``; the model holds it as
``HydraGNN.token_cfg``). A fifth family's sizes class has these under these
names, as a field, a property or a class attribute, whatever its source calls
them:

* the encoder (``models/base.py``) and ``token_ids``: ``vocab_size``,
  ``token_minmax``, ``norm_eps`` (the final norm's), ``routed(layer)``
  (whether the layer sows the routed layer's counters) and, of a stack whose
  class head is the embedding transposed ALONE, ``tie_word_embeddings``;
* ``token_routed.RoutedFFN`` and ``pass_rows``: ``num_experts``,
  ``num_experts_per_tok``, ``num_experts_held``, ``experts_offset``,
  ``moe_intermediate_size``, ``use_expert_bias``, ``norm_topk_prob``,
  ``routed_scaling_factor``, ``scoring_func`` (``"sigmoid"`` or
  ``"softmax"``: stated by every sizes class, defaulted nowhere);
* the serving engine (``serve/engine.py``): ``token_minmax`` and
  ``vocab_size`` (a request's ids), ``num_experts_per_tok``,
  ``num_experts_held``, ``experts_offset`` (a flush's routing counters,
  against ``pass_rows``) and, of a stack with band layers ALONE,
  ``sliding(layer)`` and ``sliding_window`` (a stack whose every layer is the
  complete causal graph has neither); of a stack with selective-scan layers
  ALONE, ``scans(layer)``;
* config checking (``analysis/contracts.py``) and ``create_model``: the
  class's ``missing(arch)`` and ``from_arch(arch, num_layers)``.

The attention entry point takes arrays, a window and a scale, and reads no
sizes object.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import flax.linen as nn

from .layers import scaled_ids

# The two kinds of attention layer a source's ``layer_types`` and
# ``rope_parameters`` are keyed by.
KINDS = ("full_attention", "sliding_attention")


def missing_fields(cls, arch: dict) -> list:
    """The fields of a stack's config dataclass ``cls`` that ``arch`` must
    have and lacks (the rank's share defaults to all the experts)."""
    return [
        f.name for f in dataclasses.fields(cls)
        if f.default is dataclasses.MISSING and f.name not in arch
        and f.name not in ("num_experts_held", "experts_offset")
    ]


def token_ids(column: jnp.ndarray, cfg) -> jnp.ndarray:
    """The token id of each node from its min-max-scaled column, exactly."""
    return scaled_ids(column, cfg.token_minmax, cfg.vocab_size)


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.initializers.ones, (x.shape[-1],))
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + self.eps) * w


def same_graph_above(node_graph, by: int):
    """[N] bool: whether the row ``by`` above belongs to the row's own graph
    (False before the first node)."""
    n = node_graph.shape[0]
    above = jnp.concatenate([jnp.full((by,), -1, node_graph.dtype), node_graph[: n - by]])
    return above == node_graph


def same_graph_shift(z, node_graph, by: int):
    """``z`` moved down ``by`` rows, zero where the row ``by`` above belongs
    to another graph (or to none: before the first node): the read of a
    causal convolution's tap inside a node's own graph.

    Not for a ``z`` that nothing reads afterwards in a program with no
    backward: the TPU compiler makes ``z[: n - by]`` a VIEW of ``z``, lets a
    fusion that also reads ``z`` itself write its result into ``z``'s buffer,
    and walks that fusion in windows of rows, so the first ``by`` rows of
    every window read rows the window before has overwritten (my chip runs,
    PR 45: rows 368 k .. 368 k + 2 of a 2560-row array off by 40-60%). A train
    step keeps ``z`` for its backward and is safe; a served layer reads its
    taps off an array of ANOTHER length than its result (models/jamba.py)."""
    n = z.shape[0]
    moved = jnp.concatenate([jnp.zeros((by,) + z.shape[1:], z.dtype), z[: n - by]])
    return jnp.where(same_graph_above(node_graph, by)[:, None], moved, 0.0)


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


def rotary(x, place, rope: Rope):
    """``x`` [N, heads, hd] with the first ``partial_rotary_factor`` of each
    head rotated at ``place`` and the rest as it is."""
    inv, factor, r = rope.frequencies(x.shape[-1])
    turned = rotate(x[..., :r], place, jnp.asarray(inv), factor)
    return turned if r == x.shape[-1] else jnp.concatenate([turned, x[..., r:]], axis=-1)
