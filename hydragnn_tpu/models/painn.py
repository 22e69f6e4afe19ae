"""PaiNN: the polarizable atom interaction network (Schütt, Unke, Gastegger,
ICML 2021, arXiv:2102.03150; reference implementation
``schnetpack.representation.PaiNN``; upstream ``hydragnn/models/PAINNStack.py``).
Equations and this system's departures: PAPERS.md.

The encoder carries TWO states: a scalar ``s`` [N, F] and an equivariant
vector ``v``. A batch norm or a ReLU over ``v`` breaks equivariance and the
paper has neither over ``s``, so ``models/base.py`` runs these blocks in a
loop of their own, with no norm, no activation and no dropout between them.

Layout. ``v`` is FLAT ``[N, 3F]``, xyz-major: columns ``k·F … (k+1)·F`` hold
component k. Every edge array is rank 2 (``[E, F]`` or ``[E, 3F]``), from
the gathers to the aggregation and in what the backward saves: a ``[3, F]``
row pads to a whole (8, 128) tile on the TPU and gathers and scatter-adds
pay by the padded row (PERF.md §6, PR 24). ``Σ_xyz`` is a sum of three
lane-aligned slices and ``c ⊗ u`` a concatenation of three ``[E, F]``
products.

Padding. A padding edge joins the padding node to itself, so its length is
0 and ``sin(nπd/r_c)/d`` and ``r_ij/d`` would be NaN; a zero cotangent times
a NaN activation is a NaN weight gradient. ``edge_geometry`` replaces ``d``
by 1 on such rows BEFORE any division and folds ``edge_mask`` into the
cutoff factor, which multiplies every filter: the messages of a padding edge
are exactly 0, and outputs and gradients are finite and independent of the
padding.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn

from ..ops import aggregate
from ..telemetry import scopes


# Every Dense of this family, the heads' included (models/base.py), multiplies
# in full float32. The configuration states float32 and the paper's
# implementation runs it; the TPU's default for float32 operands is ONE bf16
# pass, and no norm layer stands between the blocks or before the read-out:
# at F 128, 3 blocks, on outputs of O(0.3), that read 3.0e-3 and 3.6e-3 from
# the plain reference (two seeds), and 1.1e-3 to 3.4e-3 over eight seeds with
# the encoder alone at HIGHEST (my chip runs, PR 26): the heads' single pass
# over the pooled state was most of it. As it stands it reads 2.6e-7 to
# 6.0e-7, and the benchmark's limit for this family is 1e-4
# (graftbench/families/painn.py), so dropping HIGHEST here fails it. The matmuls
# are not where the time goes (model_flops_util 0.28%: the step moves
# [E, 384] rows). Under ``compute_dtype: bfloat16`` the operands are bf16 and
# this changes nothing.
PRECISION = jax.lax.Precision.HIGHEST
Dense = functools.partial(nn.Dense, precision=PRECISION)


class EdgeGeometry(NamedTuple):
    """What depends on positions alone, computed once a step in float32."""

    basis: jnp.ndarray  # [E, num_radial]  sin(nπd/r_c)/d
    cutoff: jnp.ndarray  # [E, 1]  ½(cos(πd/r_c)+1) inside r_c, 0 outside and on padding
    unit: jnp.ndarray  # [E, 3]  r_ij/d, r_ij = r_sender − r_receiver


def edge_geometry(positions, senders, receivers, edge_mask, radius, num_radial):
    with jax.named_scope(scopes.GEOM):
        positions = positions.astype(jnp.float32)
        with jax.named_scope(scopes.GATHER):
            r_j, r_i = positions[senders], positions[receivers]
        r_ij = r_j - r_i
        d2 = jnp.sum(r_ij * r_ij, axis=-1, keepdims=True)
        real = edge_mask[:, None] & (d2 > 0.0)
        d = jnp.sqrt(jnp.where(real, d2, 1.0))  # finite stand-in before dividing
        n = jnp.arange(1, num_radial + 1, dtype=jnp.float32)
        basis = jnp.sin(d * (n * (jnp.pi / radius))) / d
        cutoff = jnp.where(
            real & (d < radius), 0.5 * (jnp.cos(d * (jnp.pi / radius)) + 1.0), 0.0
        )
        return EdgeGeometry(basis, cutoff, r_ij / d)


class PaiNNBlock(nn.Module):
    """One message block and one update block (the paper's figure 2 b, c)."""

    features: int  # F
    axis_name: Optional[str] = None  # mesh axis of edge-sharded graph parallelism

    @nn.compact
    def __call__(self, s, v, geom, senders, receivers, row_ptr=None):
        f = self.features
        n = s.shape[0]

        # --- message ---
        x = Dense(3 * f, name="msg_1")(nn.silu(Dense(f, name="msg_0")(s)))
        # Each source is gathered once: 3F-wide rows both.
        with jax.named_scope(scopes.GATHER):
            x_j, v_j = x[senders], v[senders]
        with jax.named_scope(scopes.GEOM):
            w = Dense(3 * f, name="filter")(geom.basis) * geom.cutoff
        xw = x_j * w.astype(x_j.dtype)  # 0 on padding rows: the cutoff holds the mask
        a, b, c = xw[:, :f], xw[:, f : 2 * f], xw[:, 2 * f :]
        unit = geom.unit.astype(xw.dtype)
        dv = [
            b * v_j[:, k * f : (k + 1) * f] + c * unit[:, k : k + 1] for k in range(3)
        ]
        # ONE 4F-wide sum for both states: the sorted arm's prefix passes and
        # boundary reads are paid once (PERF.md §6, PR 26 has the measurement).
        agg = aggregate.fused_segment_sum(
            jnp.concatenate([a] + dv, axis=-1), receivers, n,
            axis_name=self.axis_name, row_ptr=row_ptr,
        ).astype(s.dtype)
        s = s + agg[:, :f]
        v = v + agg[:, f:]

        # --- update ---
        mix = Dense(2 * f, use_bias=False, name="vec")  # over the channel axis
        uv, vv = [], []
        for k in range(3):
            m = mix(v[:, k * f : (k + 1) * f])
            uv.append(m[:, :f])
            vv.append(m[:, f:])
        norm = jnp.sqrt(sum(t * t for t in vv) + 1e-8)
        g = Dense(3 * f, name="upd_1")(
            nn.silu(Dense(f, name="upd_0")(jnp.concatenate([s, norm], axis=-1)))
        )
        a_vv, a_sv, a_ss = g[:, :f], g[:, f : 2 * f], g[:, 2 * f :]
        v = v + jnp.concatenate([a_vv * t for t in uv], axis=-1)
        s = s + a_sv * sum(p * q for p, q in zip(uv, vv)) + a_ss
        return s, v
