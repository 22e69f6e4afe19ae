"""Masked segment ops — the XLA replacement for torch-scatter/-sparse kernels that
PyTorch-Geometric message passing leans on (reference conv calls:
/root/reference/hydragnn/models/Base.py:236-243, global_mean_pool at Base.py:250).

All ops take a static ``num_segments`` so shapes are compile-time constants, and an
optional boolean mask marking valid rows.

Graph parallelism (the long-context analog axis, SURVEY.md §5.7): every op accepts
an optional ``axis_name``. When set, the edge/data rows are assumed sharded across
that mesh axis (nodes replicated); each device reduces its local shard and the
partial segment results are combined with the matching XLA collective
(psum / pmax / pmin) over ICI. This turns large-graph message passing into
edge-partitioned SPMD with one collective per aggregation.
"""

from __future__ import annotations

import contextlib
from contextvars import ContextVar
from typing import Optional

import jax
import jax.numpy as jnp

from ..telemetry.scopes import agg_scope

_BIG = 1e30

# Platform the arm decision of ops/aggregate.py sees (the sorted arm's default,
# whether the extrema kernel is compiled or interpreted). jax.default_backend() is process-global and WRONG in
# mixed-platform environments (a TPU-attached host tracing a step for a CPU
# mesh): the gate must reflect the devices that will execute the op. Step
# builders pin it for the duration of tracing via platform_override().
# Defined here (the lowest-level ops module) so aggregate and segment_sorted
# share one source of truth without a circular import.
_PLATFORM_OVERRIDE: ContextVar[Optional[str]] = ContextVar(
    "hydragnn_execution_platform", default=None
)


@contextlib.contextmanager
def platform_override(platform: Optional[str]):
    token = _PLATFORM_OVERRIDE.set(platform)
    try:
        yield
    finally:
        _PLATFORM_OVERRIDE.reset(token)


def execution_platform() -> str:
    return _PLATFORM_OVERRIDE.get() or jax.default_backend()


def _pmax(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Differentiable cross-device max (lax.pmax has no VJP rule)."""
    return jnp.max(jax.lax.all_gather(x, axis_name), axis=0)


def _pmin(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    return jnp.min(jax.lax.all_gather(x, axis_name), axis=0)


def _expand(mask: jnp.ndarray, like: jnp.ndarray) -> jnp.ndarray:
    """Broadcast a [N] mask against [N, ...] data."""
    return mask.reshape(mask.shape + (1,) * (like.ndim - mask.ndim))


def segment_sum(
    data: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    mask: Optional[jnp.ndarray] = None,
    axis_name: Optional[str] = None,
) -> jnp.ndarray:
    with agg_scope("sum", "xla"):
        if mask is not None:
            data = jnp.where(_expand(mask, data), data, 0)
        out = jax.ops.segment_sum(data, segment_ids, num_segments=num_segments)
        if axis_name is not None:
            out = jax.lax.psum(out, axis_name)
        return out


def segment_count(
    segment_ids: jnp.ndarray,
    num_segments: int,
    mask: Optional[jnp.ndarray] = None,
    axis_name: Optional[str] = None,
) -> jnp.ndarray:
    with agg_scope("count", "xla"):
        ones = jnp.ones(segment_ids.shape[0], dtype=jnp.float32)
        if mask is not None:
            ones = jnp.where(mask, ones, 0.0)
        return segment_sum(ones, segment_ids, num_segments, axis_name=axis_name)


def segment_mean(
    data: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    mask: Optional[jnp.ndarray] = None,
    axis_name: Optional[str] = None,
) -> jnp.ndarray:
    with agg_scope("mean", "xla"):
        total = segment_sum(data, segment_ids, num_segments, mask, axis_name)
        count = segment_count(segment_ids, num_segments, mask, axis_name)
        return total / jnp.maximum(count, 1.0).reshape(
            count.shape + (1,) * (total.ndim - count.ndim)
        )


def segment_max(
    data: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    mask: Optional[jnp.ndarray] = None,
    fill: float = 0.0,
    axis_name: Optional[str] = None,
) -> jnp.ndarray:
    with agg_scope("extrema", "xla"):
        if mask is not None:
            data = jnp.where(_expand(mask, data), data, -_BIG)
        out = jax.ops.segment_max(data, segment_ids, num_segments=num_segments)
        if axis_name is not None:
            out = _pmax(out, axis_name)
        # Empty segments come back as -inf/-BIG: replace with `fill` so
        # downstream matmuls stay finite (isolated nodes have no incoming
        # messages).
        return jnp.where(out <= -_BIG / 2, fill, out)


def segment_min(
    data: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    mask: Optional[jnp.ndarray] = None,
    fill: float = 0.0,
    axis_name: Optional[str] = None,
) -> jnp.ndarray:
    with agg_scope("extrema", "xla"):
        if mask is not None:
            data = jnp.where(_expand(mask, data), data, _BIG)
        out = jax.ops.segment_min(data, segment_ids, num_segments=num_segments)
        if axis_name is not None:
            out = _pmin(out, axis_name)
        return jnp.where(out >= _BIG / 2, fill, out)


def segment_std(
    data: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    mask: Optional[jnp.ndarray] = None,
    eps: float = 1e-5,
    axis_name: Optional[str] = None,
) -> jnp.ndarray:
    """Per-segment standard deviation, sqrt(relu(E[x^2]-E[x]^2) + eps) like PyG's
    PNA 'std' aggregator (uses a small eps for a finite gradient at zero)."""
    with agg_scope("stats", "xla"):
        mean = segment_mean(data, segment_ids, num_segments, mask, axis_name)
        mean_sq = segment_mean(
            jnp.square(data), segment_ids, num_segments, mask, axis_name
        )
        var = jax.nn.relu(mean_sq - jnp.square(mean))
        return jnp.sqrt(var + eps)


def segment_softmax(
    logits: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    mask: Optional[jnp.ndarray] = None,
    axis_name: Optional[str] = None,
    sum_fn=None,
) -> jnp.ndarray:
    """Numerically-stable softmax normalized within each segment (GATv2 attention
    over incoming edges). Masked-out rows get weight 0. Under graph parallelism
    the per-segment max and denominator are reduced globally; the returned
    weights are for the LOCAL edge shard.

    ``sum_fn(data, ids, n, mask=, axis_name=)`` overrides the denominator's
    segment sum (must return the globally-reduced sum) — the hook
    ``aggregate.fused_segment_softmax`` plugs the sorted arm's sum into, so
    both arms share ONE stabilization body."""
    with agg_scope("softmax", "xla"):
        if mask is not None:
            logits = jnp.where(_expand(mask, logits), logits, -_BIG)
        seg_max = jax.ops.segment_max(logits, segment_ids, num_segments=num_segments)
        if axis_name is not None:
            seg_max = _pmax(seg_max, axis_name)
        seg_max = jnp.where(seg_max <= -_BIG / 2, 0.0, seg_max)
        # Softmax is shift-invariant, so the max is analytically a constant:
        # stop_gradient gives the identical gradient while skipping
        # segment_max's scatter-heavy TPU VJP (jax.nn.softmax does the same).
        seg_max = jax.lax.stop_gradient(seg_max)
        shifted = logits - seg_max[segment_ids]
        exp = jnp.exp(shifted)
        if mask is not None:
            exp = jnp.where(_expand(mask, exp), exp, 0.0)
        if sum_fn is not None:
            denom = sum_fn(
                exp, segment_ids, num_segments, mask=mask, axis_name=axis_name
            )
        else:
            denom = jax.ops.segment_sum(exp, segment_ids, num_segments=num_segments)
            if axis_name is not None:
                denom = jax.lax.psum(denom, axis_name)
        return exp / jnp.maximum(denom[segment_ids], 1e-16)


def masked_mean(data: jnp.ndarray, mask: jnp.ndarray, axis=None) -> jnp.ndarray:
    """Mean over rows where mask is True (for batch-norm statistics over padded
    node arrays)."""
    m = jnp.broadcast_to(_expand(mask, data), data.shape).astype(data.dtype)
    total = jnp.sum(data * m, axis=axis)
    count = jnp.sum(m, axis=axis)
    return total / jnp.maximum(count, 1.0)
