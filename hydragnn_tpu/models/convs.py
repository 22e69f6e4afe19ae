"""Message-passing convolutions as XLA segment-op programs.

Each layer is the TPU-native equivalent of a PyTorch-Geometric conv used by the
reference model zoo (/root/reference/hydragnn/models/*Stack.py): gather source-node
rows, compute per-edge messages as dense (MXU-friendly) matmuls over the padded
edge array, and scatter-aggregate at the receivers with masked segment ops. No
dynamic shapes: padding edges connect padding nodes, so aggregation needs no
special-casing beyond the statistics masks.

Call convention (all convs):
    y = conv(x, senders, receivers, edge_attr, edge_mask, node_mask, train=...,
             row_ptr=None)
with x: [N_pad, F], senders/receivers: [E_pad], edge_attr: [E_pad, D] or None,
row_ptr: [N_pad + 1] CSR boundaries over the destination-sorted receivers (the
PR-7 batch contract, graphs/csr.py) or None — when present, every sorted-arm
aggregation consumes precomputed boundaries (zero in-step searchsorted) and
PNA's min and max come from the scan kernel (ops/aggregate.py has the table).

Every row a conv gathers, and so every row its backward scatter-adds, is
rank 2: [N_pad, width] -> [E_pad, width]. GATv2's heads included: its rows
are [N, h·f] and [E, h·f], never [·, h, f], because a [h, f] row pads to a
whole (8, 128) tile on the TPU and gathers and scatter-adds pay by the padded
row (tests/test_scopes.py and tests/test_tpu_compile.py hold the compiled
step to it).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from ..ops import aggregate
from ..telemetry import scopes

class SAGEConv(nn.Module):
    """GraphSAGE (mean aggregation): W_self·x_i + W_nbr·mean_j x_j.
    Reference: /root/reference/hydragnn/models/SAGEStack.py:24-31."""

    out_dim: int
    axis_name: Optional[str] = None  # mesh axis for edge-sharded graph parallelism

    @nn.compact
    def __call__(self, x, senders, receivers, edge_attr, edge_mask, node_mask, train=False, row_ptr=None):
        n = x.shape[0]
        with jax.named_scope(scopes.GATHER):
            x_j = x[senders]
        nbr = aggregate.fused_segment_mean(x_j, receivers, n, mask=edge_mask, axis_name=self.axis_name, row_ptr=row_ptr)
        return nn.Dense(self.out_dim, name="lin_nbr")(nbr) + nn.Dense(
            self.out_dim, name="lin_self"
        )(x)


class GINConv(nn.Module):
    """GIN with inner 2-layer MLP and trainable eps (init 100.0, matching the
    reference's unusually large eps — GINStack.py:24-33)."""

    out_dim: int
    eps_init: float = 100.0
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, x, senders, receivers, edge_attr, edge_mask, node_mask, train=False, row_ptr=None):
        n = x.shape[0]
        eps = self.param("eps", nn.initializers.constant(self.eps_init), ())
        with jax.named_scope(scopes.GATHER):
            x_j = x[senders]
        agg = aggregate.fused_segment_sum(x_j, receivers, n, mask=edge_mask, axis_name=self.axis_name, row_ptr=row_ptr)
        h = (1.0 + eps) * x + agg
        h = nn.Dense(self.out_dim, name="mlp_0")(h)
        h = nn.relu(h)
        return nn.Dense(self.out_dim, name="mlp_1")(h)


class MFCConv(nn.Module):
    """Molecular-fingerprint conv: degree-indexed weight pair
    W1[deg]·x_i + W2[deg]·Σ_j x_j, degree clamped to max_degree
    (reference MFCStack.py:24-36 → PyG MFConv). The per-node weight gather is a
    [N, F, F'] take — tiny at the hidden sizes this model family uses."""

    out_dim: int
    max_degree: int
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, x, senders, receivers, edge_attr, edge_mask, node_mask, train=False, row_ptr=None):
        n, f = x.shape
        d = self.max_degree + 1
        w_self = self.param(
            "w_self", nn.initializers.lecun_normal(), (d, f, self.out_dim)
        )
        w_nbr = self.param("w_nbr", nn.initializers.lecun_normal(), (d, f, self.out_dim))
        b = self.param("bias", nn.initializers.zeros, (d, self.out_dim))
        with jax.named_scope(scopes.GATHER):
            x_j = x[senders]
        agg, deg_f = aggregate.fused_segment_sum_count(
            x_j, receivers, n, mask=edge_mask, axis_name=self.axis_name,
            row_ptr=row_ptr,
        )
        deg = jnp.clip(deg_f.astype(jnp.int32), 0, self.max_degree)
        # The degree-indexed weights are row gathers too (backward: scatter-
        # adds into [d, f, o]).
        with jax.named_scope(scopes.GATHER):
            w_self_n, w_nbr_n, b_n = w_self[deg], w_nbr[deg], b[deg]
        out = jnp.einsum("nf,nfo->no", x, w_self_n) + jnp.einsum(
            "nf,nfo->no", agg, w_nbr_n
        )
        return out + b_n


def _head_blocks(w):
    """[h, f] -> [h·f, h], block-diagonal: column i holds ``w[i]`` in rows
    i·f … (i+1)·f. ``rows @ _head_blocks(w)`` is Σ_f rows[r, i, f]·w[i, f] and
    ``per_head @ _head_blocks(ones).T`` repeats a head's value over its f
    columns: the head axis as a 2-D matmul, so no [R, h, f] array exists."""
    h, f = w.shape
    return (w[:, :, None] * jnp.eye(h, dtype=w.dtype)[:, None, :]).reshape(h * f, h)


# The per-head contraction was a float32 multiply-and-sum (XLA lowered the
# einsum ``ehf,hf->eh`` to one); as a matmul it must not drop to the MXU's
# single bf16 pass.
_HEAD_PRECISION = jax.lax.Precision.HIGHEST


class GATv2Conv(nn.Module):
    """GATv2 multi-head attention over incoming edges, with implicit self-loops and
    masked segment softmax (reference GATStack.py:88-97; heads=6,
    negative_slope=0.05 hardcoded by create.py:112-114, attention dropout wired to
    the model's dropout rate).

    Self-loops are an EXPLICIT self-attention term, not the historical
    ``[edges; self-loops]`` concat: for node ``i`` the softmax runs over
    {incoming edges} ∪ {i itself}, with the self logit computed densely
    [N, h] and its exp added to the segment denominator. Mathematically
    identical to concatenating one identity edge per node (parity-locked in
    tests/test_csr_contract.py), but the edge array keeps collation's
    destination-sorted order — GAT rides the sorted/CSR aggregation path
    like every other family instead of being the one scatter-bound holdout.

    Layout: every row array is FLAT. ``x_src``, ``x_dst`` are [N, h·f] and
    ``x_j``, ``x_i``, ``pre``, ``msgs`` [E, h·f], from the gathers to the
    aggregation and in what the backward saves, because a [h, f] row pads to
    a whole (8, 128) tile on the TPU (1.5 KB in 4 KB at 6 × 64) and a
    gather's backward scatter-add pays by the padded row. The head axis
    exists only in the [·, h] arrays (logits, ``alpha``, the dropout mask);
    :func:`_head_blocks` carries it across as a matmul."""

    out_dim: int  # per-head output dim
    heads: int = 6
    negative_slope: float = 0.05
    concat: bool = True
    dropout: float = 0.25
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, x, senders, receivers, edge_attr, edge_mask, node_mask, train=False, row_ptr=None):
        from ..ops import segment as seg

        n = x.shape[0]
        h, f = self.heads, self.out_dim
        x_src = nn.Dense(h * f, name="lin_src")(x)  # [N, h·f]
        x_dst = nn.Dense(h * f, name="lin_dst")(x)

        att = self.param("att", nn.initializers.lecun_normal(), (h, f))
        att_blocks = _head_blocks(att)  # [h·f, h]
        repeat = _head_blocks(jnp.ones_like(att)).T  # [h, h·f], 0/1
        # Each source is gathered ONCE: x_j feeds the logits and the
        # messages, so its two cotangents add over [E, h·f] before the one
        # scatter-add of the backward. The receiver side's ids are sorted:
        # its backward is a sorted sum (aggregate.gather_sorted).
        with jax.named_scope(scopes.GATHER):
            x_j = x_src[senders]
        x_i = aggregate.gather_sorted(x_dst, receivers, row_ptr, self.axis_name)
        pre = nn.leaky_relu(x_j + x_i, self.negative_slope)  # [E, h·f]
        logits = jnp.dot(pre, att_blocks, precision=_HEAD_PRECISION)  # [E, h]
        # Self term: the diagonal of the attention matrix, computed densely
        # (x_src[i] + x_dst[i] — no gather, no extra edges).
        pre_self = nn.leaky_relu(x_src + x_dst, self.negative_slope)
        logit_self = jnp.dot(pre_self, att_blocks, precision=_HEAD_PRECISION)  # [N, h]

        # Stabilized softmax over edges ∪ self. The per-node shift is the
        # TRUE max of the contributing logits (stop_gradient like
        # seg.segment_softmax): edgeless segments fill with -1e9, not 0, so
        # an isolated node's shift is exactly its self logit and
        # alpha_self = 1 there for ANY magnitude (a 0 fill would underflow
        # exp(logit_self) for strongly negative self logits and silently
        # drop the self message the concat formulation kept). m stays
        # finite everywhere — logit_self is dense — so padding rows cannot
        # produce NaNs.
        edge_max = seg.segment_max(
            logits, receivers, n, mask=edge_mask, fill=-1e9,
            axis_name=self.axis_name,
        )  # [N, h]
        m = jax.lax.stop_gradient(jnp.maximum(edge_max, logit_self))
        with jax.named_scope(scopes.GATHER):
            m_e = m[receivers]
        exp_e = jnp.where(edge_mask[:, None], jnp.exp(logits - m_e), 0.0)  # [E, h]
        exp_self = jnp.where(
            node_mask[:, None], jnp.exp(logit_self - m), 0.0
        )  # [N, h]
        # The edge half of the denominator is globally reduced under graph
        # parallelism (psum inside fused_segment_sum); the self half is
        # identical on every shard (nodes replicated) and added AFTER the
        # reduction, so it is counted exactly once — the replacement for the
        # old shard-0-only self-loop mask.
        denom = aggregate.fused_segment_sum(
            exp_e, receivers, n, mask=edge_mask, axis_name=self.axis_name,
            row_ptr=row_ptr,
        ) + exp_self
        denom_e = aggregate.gather_sorted(denom, receivers, row_ptr, self.axis_name)
        alpha = exp_e / jnp.maximum(denom_e, 1e-16)  # [E, h]
        alpha_self = exp_self / jnp.maximum(denom, 1e-16)  # [N, h]
        if train and self.dropout > 0.0:
            rng = self.make_rng("dropout")
            keep = jax.random.bernoulli(
                rng, 1.0 - self.dropout, (n + alpha.shape[0],) + alpha.shape[1:]
            )
            alpha = jnp.where(
                keep[n:], alpha / (1.0 - self.dropout), 0.0
            )
            alpha_self = jnp.where(
                keep[:n], alpha_self / (1.0 - self.dropout), 0.0
            )
        msgs = x_j * jnp.dot(alpha, repeat, precision=_HEAD_PRECISION)  # [E, h·f]
        msgs = jnp.where(edge_mask[:, None], msgs, 0.0)
        out = aggregate.fused_segment_sum(
            msgs, receivers, n, axis_name=self.axis_name, row_ptr=row_ptr,
        )  # [N, h·f]
        # The self-loop message.
        out = out + x_src * jnp.dot(alpha_self, repeat, precision=_HEAD_PRECISION)
        if self.concat:
            bias = self.param("bias", nn.initializers.zeros, (h * f,))
        else:
            # The mean over heads, on the NODE-level result.
            out = out.reshape(n, h, f).mean(axis=1)
            bias = self.param("bias", nn.initializers.zeros, (f,))
        return out + bias


class CGConv(nn.Module):
    """Crystal-graph conv (channel-preserving, add-aggregated, gated):
    x_i + Σ_j σ(z·W_f)·softplus(z·W_s), z = [x_i, x_j, e_ij]
    (reference CGCNNStack.py:44-51 → PyG CGConv with aggr='add')."""

    edge_dim: int = 0
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, x, senders, receivers, edge_attr, edge_mask, node_mask, train=False, row_ptr=None):
        n, f = x.shape
        x_i = aggregate.gather_sorted(x, receivers, row_ptr, self.axis_name)
        with jax.named_scope(scopes.GATHER):
            z = [x_i, x[senders]]
        if self.edge_dim and edge_attr is not None:
            z.append(edge_attr)
        z = jnp.concatenate(z, axis=-1)
        gate = jax.nn.sigmoid(nn.Dense(f, name="lin_f")(z))
        core = jax.nn.softplus(nn.Dense(f, name="lin_s")(z))
        msgs = gate * core
        # Padding edges carry nonzero softplus output — mask before aggregation.
        msgs = jnp.where(edge_mask[:, None], msgs, 0.0)
        return x + aggregate.fused_segment_sum(msgs, receivers, n, axis_name=self.axis_name, row_ptr=row_ptr)


class PNAConv(nn.Module):
    """Principal Neighborhood Aggregation: 4 aggregators × 4 degree scalers with a
    pre-MLP on messages and a post-MLP on [x ‖ aggregated]
    (reference PNAStack.py:28-53 → PyG PNAConv, towers=1, pre_layers=1,
    post_layers=1, divide_input=False).

    ``deg_avg_log`` / ``deg_avg_lin`` are dataset statistics from the training
    degree histogram (reference calculate_PNA_degree, utils/model.py:81-86).
    """

    out_dim: int
    deg_avg_log: float
    deg_avg_lin: float
    edge_dim: Optional[int] = None
    axis_name: Optional[str] = None
    aggregators: Tuple[str, ...] = ("mean", "min", "max", "std")
    scalers: Tuple[str, ...] = ("identity", "amplification", "attenuation", "linear")

    @nn.compact
    def __call__(self, x, senders, receivers, edge_attr, edge_mask, node_mask, train=False, row_ptr=None):
        n, f = x.shape
        x_i = aggregate.gather_sorted(x, receivers, row_ptr, self.axis_name)
        with jax.named_scope(scopes.GATHER):
            z = [x_i, x[senders]]
        if self.edge_dim and edge_attr is not None:
            z.append(edge_attr)
        z = jnp.concatenate(z, axis=-1)
        msg = nn.Dense(f, name="pre_nn")(z)  # [E, f]

        # The sorted arm's stats bundle and extrema on the TPU, masked XLA
        # segment ops elsewhere — see ops/aggregate.py.
        agg, deg = aggregate.pna_aggregate(
            msg, receivers, n, self.aggregators,
            mask=edge_mask, axis_name=self.axis_name, row_ptr=row_ptr,
        )  # agg: [N, A, f]

        deg = jnp.maximum(deg, 1.0)
        log_deg = jnp.log(deg + 1.0)
        scales = []
        for s in self.scalers:
            if s == "identity":
                scales.append(jnp.ones_like(deg))
            elif s == "amplification":
                scales.append(log_deg / self.deg_avg_log)
            elif s == "attenuation":
                scales.append(self.deg_avg_log / log_deg)
            elif s == "linear":
                scales.append(deg / self.deg_avg_lin)
            else:
                raise ValueError(f"Unknown scaler {s}")
        scale = jnp.stack(scales, axis=1)  # [N, S]

        # [N, S, A, f] → flatten: every aggregator under every scaler.
        combined = agg[:, None, :, :] * scale[:, :, None, None]
        combined = combined.reshape(n, len(self.scalers) * len(self.aggregators) * f)
        out = jnp.concatenate([x, combined], axis=-1)
        out = nn.Dense(self.out_dim, name="post_nn")(out)
        # PyG applies a final linear after the tower post-MLPs (PNAConv.lin).
        return nn.Dense(self.out_dim, name="lin")(out)


def pna_degree_averages(deg_histogram: Sequence[float]) -> Tuple[float, float]:
    """avg(log(d+1)) and avg(d) over the training-set in-degree histogram, the two
    normalizers PNA scalers need. Averages use raw bin degrees (PyG clamps only
    the runtime degree, not the histogram average)."""
    import numpy as np

    hist = np.asarray(deg_histogram, dtype=np.float64)
    degrees = np.arange(len(hist))
    total = hist.sum()
    if total == 0:
        return 1.0, 1.0
    avg_log = float((hist * np.log(degrees + 1)).sum() / total)
    avg_lin = float((hist * degrees).sum() / total)
    return max(avg_log, 1e-6), max(avg_lin, 1e-6)
