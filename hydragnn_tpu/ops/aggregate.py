"""The aggregation every conv family calls: one entry point a reduction, one
arm a condition the code can observe.

The arm is decided at trace time, here and nowhere else, from four things:

* the execution platform (``ops/segment.py`` ``execution_platform``): the
  sorted arm is the TPU's, the masked XLA segment ops the CPU's
  (``segment_sorted.sorted_enabled``; ``HYDRAGNN_SEGMENT_SORTED`` is the one
  override, and what puts the chip's arm under a CPU in the tests);
* whether the batch carries ``row_ptr`` (the CSR batch contract,
  ``graphs/csr.py``);
* whether an edge-sharded ``axis_name`` is set.

* a row's width, read off the shape (``segment_sorted.WIDE_ROW``, one lane
  tile): on the sorted arm a scatter-add costs the same a row at any width and
  the prefix sums cost by the lane tile, so wide rows take the first and
  narrow rows the second (PERF.md §6, PR 32).

| sorted arm | ``row_ptr`` | sums, means, PNA's stats: rows under ``WIDE_ROW`` columns | the same, wider rows      | min / max          |
|------------|-------------|------------------------------------------------------------|---------------------------|--------------------|
| on         | yes         | ``csr``: prefix sums, no search                            | ``scatter_sorted`` [2]    | ``pallas_csr`` [1] |
| on         | no          | ``sorted``: prefix sums, two searchsorted                  | ``scatter_sorted`` [2]    | ``xla``            |
| off        | either      | ``xla``: ``ops/segment.py``                                | ``xla``                   | ``xla``            |

[1] ``ops/extrema_scan.py``; under an ``axis_name`` a run is cut across shards
and the extrema are ``xla`` too.
[2] ONE XLA scatter-add over the raw ids with ``indices_are_sorted=True``; the
count still comes from the boundaries (``row_ptr``, or the two searches).

A conv's receiver-side row gather (:func:`gather_sorted`, PR 46) is decided by
the same rule, for its BACKWARD (its forward is ``table[ids]`` on every arm):

| sorted arm | ``row_ptr`` | backward of ``table[ids]``: rows under ``WIDE_ROW`` columns | the same, wider rows          |
|------------|-------------|--------------------------------------------------------------|-------------------------------|
| on         | yes         | prefix sums of the cotangent rows, no search                 | the scatter-add of [2]        |
| on         | no          | prefix sums, two searchsorted                                | the scatter-add of [2]        |
| off        | either      | autodiff's scatter-add (plain indexing, no ``custom_vjp``)   | the same                      |

It stays under ``hydragnn.gather``, forward and backward: no aggregation scope.

The names in quotes are the arms of ``telemetry/scopes.py``: every entry point
opens ``hydragnn.agg.<what>.<arm>``, so a trace says which arm ran.

**Precondition of every entry point (the batch contract).** ``segment_ids``
are non-decreasing, and a masked row sits in a padding segment's run (one
whose output nobody reads): collation guarantees both for receivers and
``node_graph``. On the sorted arm a masked row is zeroed and COUNTED in its
padding segment. ``HYDRAGNN_DEBUG_LAYOUT=1`` checks the order at run time.
Ids in any other order go to ``ops/segment.py``.

``std`` is computed from CENTERED values in a second pass,
``var = mean((x - mean[ids])^2)``: the uncentered ``E[x^2] - E[x]^2`` cancels
catastrophically in float32 on near-degenerate segments, in value and in
gradient (``tests/test_aggregate.py`` holds both against float64). That
second pass is a centered scatter-add at every width, told like the wide sums'
that the ids are sorted. No
backward here scatters in an order the compiler has to find: the sums' and the
stats' are gathers through the ids; the extrema's is gathers on the ``xla``
arm and, on ``pallas_csr``, a second streamed pass down the sorted rows that
gathers nothing either; the receiver-side gathers' is, on the sorted arm, the
forward sums' own two routes (a scatter-add told its ids are sorted, or none).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..telemetry import scopes
from . import segment as seg
from . import segment_sorted as srt
from .extrema_scan import _extrema_csr, _extrema_csr_bwd


def localize_row_ptr(row_ptr, axis_name, num_local_edges: int):
    """Global CSR boundaries → THIS edge shard's local boundaries (graftmesh
    halo/edge-cut contract, docs/DISTRIBUTED.md).

    Edge-sharded graph parallelism slices the destination-sorted edge list
    into equal contiguous shards (shard_map's even split over the edge axis),
    so shard ``s`` owns global rows ``[s*E_loc, (s+1)*E_loc)`` and a node's
    local run is the global run clamped into that window::

        local_row_ptr[n] = clip(global_row_ptr[n] - s*E_loc, 0, E_loc)

    Nodes whose edges live entirely on another shard get an empty local run
    (left == right), nodes cut by the shard boundary get exactly their local
    rows — the subsequent psum over ``axis_name`` is the halo exchange that
    sums each node's per-shard partial aggregates. Must be called INSIDE the
    sharded computation (``lax.axis_index`` needs the bound axis)."""
    start = jax.lax.axis_index(axis_name).astype(jnp.int32) * jnp.int32(
        num_local_edges
    )
    return jnp.clip(
        row_ptr.astype(jnp.int32) - start, 0, jnp.int32(num_local_edges)
    )


def _arm(row_ptr, width: int) -> str:
    """The ONE resolution of the route, as the scope names it
    (telemetry/scopes.py AGG_ARMS). ``width`` is a row's trailing size."""
    if not srt.sorted_enabled():
        return "xla"
    if srt.wide(width):
        return "scatter_sorted"
    return "csr" if row_ptr is not None else "sorted"


def _width(data) -> int:
    """Columns of ``data`` as :func:`_flatten_trailing` lays it out."""
    return math.prod(data.shape[1:])


def _shard_row_ptr(row_ptr, axis_name, segment_ids):
    """The boundaries of the rows this device holds: the batch's own, or under
    an edge-sharded axis this shard's (:func:`localize_row_ptr`)."""
    if row_ptr is None or axis_name is None:
        return row_ptr
    return localize_row_ptr(row_ptr, axis_name, segment_ids.shape[0])


def _flatten_trailing(data):
    """[E, ...] → ([E, F], unflatten)."""
    if data.ndim == 2:
        return data, lambda x: x
    shape = data.shape
    if data.ndim == 1:
        return data[:, None], lambda x: x[:, 0]
    return data.reshape(shape[0], -1), lambda x: x.reshape(
        (x.shape[0],) + shape[1:]
    )


# ------------------------------------------------------------ sum, count, mean
def fused_segment_sum(
    data, segment_ids, num_segments: int, mask=None, axis_name=None,
    row_ptr=None,
):
    """Masked ``segment_sum`` of any [E, ...] float data."""
    total, _ = _fused_sum_count(
        "sum", data, segment_ids, num_segments, mask, axis_name, row_ptr
    )
    return total


def fused_segment_sum_count(
    data, segment_ids, num_segments: int, mask=None, axis_name=None,
    row_ptr=None,
):
    """Masked (segment_sum, segment_count) in one pass, for callers that need
    both (MFC's degree lookup)."""
    return _fused_sum_count(
        "sum_count", data, segment_ids, num_segments, mask, axis_name, row_ptr
    )


def _fused_sum_count(
    what, data, segment_ids, num_segments, mask, axis_name, row_ptr
):
    """:func:`fused_segment_sum_count` under the scope of the entry point that
    was called (``what``: sum, sum_count)."""
    arm = _arm(row_ptr, _width(data))
    with scopes.agg_scope(what, arm):
        if arm == "xla":
            return (
                seg.segment_sum(
                    data, segment_ids, num_segments, mask=mask, axis_name=axis_name
                ),
                seg.segment_count(
                    segment_ids, num_segments, mask=mask, axis_name=axis_name
                ),
            )
        # Zero the masked rows and keep the RAW ids: a -1 marker would break
        # the order both routes need (the prefix sums' runs, the scatter-add's
        # ``indices_are_sorted``).
        srt.attach_layout_check(segment_ids)
        row_ptr = _shard_row_ptr(row_ptr, axis_name, segment_ids)
        flat, unflatten = _flatten_trailing(data)
        if mask is not None:
            flat = jnp.where(mask[:, None], flat, 0)
        total, count = srt.segment_sum_count_auto(
            flat.astype(jnp.float32), segment_ids.astype(jnp.int32),
            num_segments, row_ptr=row_ptr,
        )
        if axis_name is not None:
            total = jax.lax.psum(total, axis_name)
            count = jax.lax.psum(count, axis_name)
        return unflatten(total.astype(data.dtype)), count


def fused_segment_mean(
    data, segment_ids, num_segments: int, mask=None, axis_name=None,
    row_ptr=None,
):
    """Masked ``segment_mean`` (SAGE's neighbour mean, the global mean-pool
    read-out). Returns ``data.dtype`` on every arm."""
    arm = _arm(row_ptr, _width(data))
    with scopes.agg_scope("mean", arm):
        if arm == "xla":
            return seg.segment_mean(
                data, segment_ids, num_segments, mask=mask, axis_name=axis_name
            ).astype(data.dtype)
        total, count = fused_segment_sum_count(
            data, segment_ids, num_segments, mask=mask, axis_name=axis_name,
            row_ptr=row_ptr,
        )
        safe = jnp.maximum(count, 1.0).reshape(
            count.shape + (1,) * (total.ndim - count.ndim)
        )
        return (total / safe).astype(data.dtype)


def fused_segment_softmax(
    logits, segment_ids, num_segments: int, mask=None, axis_name=None,
    row_ptr=None,
):
    """Segment softmax over ``seg.segment_softmax``'s one stabilization body,
    with the denominator's sum on this module's arm. The per-segment max is
    XLA's ``segment_max`` under ``stop_gradient`` on every arm.

    ``GATv2Conv`` does not come through here: its softmax runs over
    {incoming edges} ∪ {self} and is built inline so that the dense self term
    joins the denominator (models/convs.py). This is the entry point for a
    plain edge-only segment softmax."""
    arm = _arm(row_ptr, _width(logits))
    sum_fn = None
    if arm != "xla":
        def sum_fn(d, i, n, mask=None, axis_name=None):
            return fused_segment_sum(
                d, i, n, mask=mask, axis_name=axis_name, row_ptr=row_ptr
            )
    with scopes.agg_scope("softmax", arm):
        return seg.segment_softmax(
            logits, segment_ids, num_segments, mask=mask, axis_name=axis_name,
            sum_fn=sum_fn,
        )


# ------------------------------------------------- the receiver-side gathers
def _gather(table, ids):
    with jax.named_scope(scopes.GATHER):
        return table[ids]


@jax.custom_vjp
def _gather_sorted(table, ids, row_ptr):
    # The scope is opened inside and again in the backward, as in
    # :func:`segment_extrema`: JAX traces each when it pleases.
    return _gather(table, ids)


def _gather_sorted_fwd(table, ids, row_ptr):
    # A zero-size carrier keeps the table's row count and dtype in the
    # residuals (neither is a JAX type).
    carrier = jnp.zeros((table.shape[0], 0), table.dtype)
    return _gather_sorted(table, ids, row_ptr), (ids, row_ptr, carrier)


def _gather_sorted_bwd(res, cot):
    """The sorted arm's segment sum of the cotangent rows, routed by their
    width as the forward sums are, under the GATHER scope and no
    ``hydragnn.agg.*`` one (``graftbench/flops.py`` counts these bytes as the
    gather's). No mask: a padded row's cotangent lands in the padding node's
    row, as autodiff's scatter-add lands it. No collective: see
    :func:`gather_sorted`."""
    ids, row_ptr, carrier = res
    with jax.named_scope(scopes.GATHER):
        flat, unflatten = _flatten_trailing(cot)
        total, _ = srt._sum_count_sorted(flat, ids, carrier.shape[0], row_ptr)
        return (
            unflatten(total.astype(carrier.dtype)),
            jnp.zeros(ids.shape, jax.dtypes.float0),
            None if row_ptr is None
            else jnp.zeros(row_ptr.shape, jax.dtypes.float0),
        )


_gather_sorted.defvjp(_gather_sorted_fwd, _gather_sorted_bwd)


def gather_sorted(table, ids, row_ptr=None, axis_name=None):
    """``table[ids]`` for ids under the batch contract (non-decreasing, the
    masked rows in the padding row's run): a conv's receiver-side gather.

    The forward is the gather on every arm. Off the sorted arm that is all
    there is: plain indexing, whose backward autodiff writes as a scatter-add.
    On it the backward is the segment sum of the cotangent rows over ``ids``
    on the forward sums' own routes: an undeclared scatter-add has its indices
    sorted and its ``[E, F]`` updates permuted by the chip's compiler first.

    Under an edge-sharded ``axis_name`` the backward is the LOCAL sum over
    this shard's rows, with this shard's boundaries: the transpose of the
    replicated table's use reduces it across shards, as it does for plain
    indexing, so a ``psum`` here would count it twice. ``axis_name`` is read
    for those boundaries and nothing else."""
    if not srt.sorted_enabled():
        return _gather(table, ids)
    srt.attach_layout_check(ids)
    return _gather_sorted(table, ids, _shard_row_ptr(row_ptr, axis_name, ids))


# ------------------------------------------------ sum, mean, std, count: stats
def _stats_forward(data, ids, num_segments, eps, axis_name, want_std, row_ptr):
    # Data arrives zeroed at masked rows and ids RAW (sorted; masked rows in a
    # padding segment's run). The centered second pass needs no mask: masked
    # rows hold 0 against a ~0 padding-segment mean, and nobody reads a
    # padding segment's output.
    total, count = srt.segment_sum_count_auto(
        data, ids, num_segments, row_ptr=row_ptr
    )
    if axis_name is not None:
        total = jax.lax.psum(total, axis_name)
        count = jax.lax.psum(count, axis_name)
    safe = jnp.maximum(count, 1.0)[:, None]
    mean = total / safe
    if not want_std:
        return total, mean, jnp.zeros_like(mean), count
    idx = jnp.clip(ids, 0, num_segments - 1)
    # sumsq via a CENTERED XLA scatter-add at every width, never the prefix
    # sums: squares are tiny exactly where 1/std^2 amplifies error
    # (near-degenerate segments), and prefix-difference noise (~1e-5 abs)
    # there costs ~5e-3 in the std GRADIENT. The centered scatter-add has no
    # cancellation (~1e-6 fwd, ~1e-5 grad). Told, like the wide sums', that
    # the ids are sorted (they are the same receivers).
    sumsq = jax.ops.segment_sum(
        jnp.square(data - mean[idx]), ids, num_segments=num_segments,
        indices_are_sorted=True,
    )
    if axis_name is not None:
        sumsq = jax.lax.psum(sumsq, axis_name)
    # Single-element segments have sumsq == 0 identically; pin them to
    # sqrt(eps) (the bwd already treats their dstd as 0).
    std = jnp.where(
        count[:, None] > 1.0,
        jnp.sqrt(sumsq / safe + eps),
        jnp.full_like(mean, jnp.sqrt(eps)),
    )
    return total, mean, std, count


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _stats(data, ids, num_segments, eps, axis_name, want_std, row_ptr=None):
    return _stats_forward(
        data, ids, num_segments, eps, axis_name, want_std, row_ptr
    )


def _stats_fwd(data, ids, num_segments, eps, axis_name, want_std, row_ptr=None):
    out = _stats_forward(
        data, ids, num_segments, eps, axis_name, want_std, row_ptr
    )
    total, mean, std, count = out
    return out, (data, ids, mean, std, count, row_ptr)


def _stats_bwd(num_segments, eps, axis_name, want_std, res, cots):
    """Analytic scatter-free backward. With s=Σx, μ=s/n, σ=sqrt(Σ(x-μ)²/n+eps):
    since Σ_e (x_e - μ) = 0 exactly, the μ-coupling inside σ vanishes and

        dx_e = ds̄[i] + dμ̄[i]/n[i] + dσ̄[i]·(x_e − μ[i])/(σ[i]·n[i]),  i=id(e)

    — pure gathers, no scatter (scatter is the slow op on TPU). Under graph
    parallelism the incoming cotangents are per-device shares of the global
    outputs, so they are psum'd first (VJP of the forward psum)."""
    data, ids, mean, std, count, row_ptr = res
    d_total, d_mean, d_std, d_count = cots
    del d_count  # no data dependence
    if axis_name is not None:
        d_total = jax.lax.psum(d_total, axis_name)
        d_mean = jax.lax.psum(d_mean, axis_name)
        d_std = jax.lax.psum(d_std, axis_name)
    safe = jnp.maximum(count, 1.0)[:, None]
    per_seg_lin = d_total + d_mean / safe  # [N, F]
    valid = (ids >= 0)[:, None]
    idx = jnp.clip(ids, 0, num_segments - 1)
    d_data = per_seg_lin[idx]
    if want_std:
        # Single-element segments have x ≡ μ, so dσ/dx is identically 0; guard
        # the 1/σ=1/sqrt(eps) amplification against residual rounding in x−μ.
        per_seg_quad = jnp.where(count[:, None] > 1.0, d_std / (std * safe), 0.0)
        d_data = d_data + per_seg_quad[idx] * (data - mean[idx])
    d_data = jnp.where(valid, d_data, 0.0)
    d_row_ptr = (
        None if row_ptr is None
        else jnp.zeros(row_ptr.shape, jax.dtypes.float0)
    )
    return (
        d_data.astype(data.dtype),
        jnp.zeros(ids.shape, jax.dtypes.float0),
        d_row_ptr,
    )


_stats.defvjp(_stats_fwd, _stats_bwd)


def fused_segment_stats(
    data: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    mask: Optional[jnp.ndarray] = None,
    eps: float = 1e-5,
    axis_name: Optional[str] = None,
    want_std: bool = True,
    row_ptr: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(sum, mean, std, count) per segment: PNA's sum/mean/std family from two
    passes (the sums, then the centered squares), with an analytic
    scatter-free backward. ``want_std=False`` skips the second pass (std comes
    back as zeros).

    Under an edge-sharded ``axis_name`` the partial sums are psum'd across the
    shards before mean and std are formed: two collectives in all. Off the
    sorted arm it is ``ops/segment.py``'s four ops (whose ``std`` is the
    uncentered one)."""
    ids = segment_ids.astype(jnp.int32)
    arm = _arm(row_ptr, _width(data))
    with scopes.agg_scope("stats", arm):
        if arm == "xla":
            total = seg.segment_sum(data, ids, num_segments, mask, axis_name)
            mean = seg.segment_mean(data, ids, num_segments, mask, axis_name)
            std = (
                seg.segment_std(data, ids, num_segments, mask, eps, axis_name)
                if want_std else jnp.zeros_like(mean)
            )
            count = seg.segment_count(ids, num_segments, mask, axis_name)
            return total, mean, std, count
        srt.attach_layout_check(ids)
        row_ptr = _shard_row_ptr(row_ptr, axis_name, ids)
        if mask is not None:
            data = jnp.where(mask[:, None], data, 0)
        return _stats(
            data.astype(jnp.float32), ids, num_segments, eps, axis_name,
            want_std, row_ptr,
        )


# --------------------------------------------------------------------- extrema
def _extrema_arm(row_ptr) -> str:
    return "xla" if row_ptr is None else "pallas_csr"


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def segment_extrema(
    data, ids, num_segments: int, axis_name: Optional[str] = None, row_ptr=None
):
    """(min, max) per segment with a scatter-free backward: the cotangent flows
    to every row equal to its segment's extremum (the standard subgradient;
    a row equal to both gets both), avoiding XLA's scatter-heavy
    segment_min/max VJP on TPU. Empty segments yield 0.

    With ``row_ptr`` (the CSR batch contract: ``ids`` RAW and non-decreasing,
    masked rows in the padding segments' runs, whose outputs nobody reads, and
    no ``axis_name``) the forward is :func:`extrema_scan._extrema_csr` and the
    backward :func:`extrema_scan._extrema_csr_bwd`: one streamed pass over the
    rows each, no scatter and no ``[E, F]`` gather. Without it ``ids`` < 0
    marks masked rows, the forward is XLA's two scatters and the backward four
    row gathers through the ids. The two backwards are equal to the bit."""
    # This IS the custom_vjp, so the scope is opened inside it and again in
    # its backward: JAX traces both when it pleases, under the caller's name
    # stack, and a scope round the call alone would be written twice wherever
    # the forward is traced after that scope has closed (the scan step).
    with scopes.agg_scope("extrema", _extrema_arm(row_ptr)):
        if row_ptr is not None:
            srt.attach_layout_check(ids)
            return _extrema_csr(
                data, ids, row_ptr, num_segments,
                seg.execution_platform() != "tpu",
            )
        mask = ids >= 0
        safe_ids = jnp.where(mask, ids, 0)
        mn = seg.segment_min(data, safe_ids, num_segments, mask=mask, axis_name=axis_name)
        mx = seg.segment_max(data, safe_ids, num_segments, mask=mask, axis_name=axis_name)
        return mn, mx


def _extrema_fwd(data, ids, num_segments, axis_name, row_ptr=None):
    mn, mx = segment_extrema(data, ids, num_segments, axis_name, row_ptr)
    return (mn, mx), (data, ids, mn, mx, row_ptr)


def _extrema_bwd(num_segments, axis_name, res, cots):
    data, ids, mn, mx, row_ptr = res
    d_mn, d_mx = cots
    with scopes.agg_scope("extrema", _extrema_arm(row_ptr)):
        if axis_name is not None:
            d_mn = jax.lax.psum(d_mn, axis_name)
            d_mx = jax.lax.psum(d_mx, axis_name)
        if row_ptr is not None:
            # The forward's route: the four node rows are constant along a
            # run, so one streamed pass carries them and no row is gathered.
            d_data = _extrema_csr_bwd(
                data, ids, row_ptr, mn, mx, d_mn, d_mx,
                seg.execution_platform() != "tpu",
            )
        else:
            valid = (ids >= 0)[:, None]
            idx = jnp.clip(ids, 0, num_segments - 1)
            d_data = jnp.where(valid & (data == mn[idx]), d_mn[idx], 0.0) + jnp.where(
                valid & (data == mx[idx]), d_mx[idx], 0.0
            )
        return (
            d_data.astype(data.dtype),
            jnp.zeros(ids.shape, jax.dtypes.float0),
            None if row_ptr is None
            else jnp.zeros(row_ptr.shape, jax.dtypes.float0),
        )


segment_extrema.defvjp(_extrema_fwd, _extrema_bwd)


# ------------------------------------------------------------------------- PNA
_XLA_AGGREGATORS = {
    "mean": seg.segment_mean, "sum": seg.segment_sum, "std": seg.segment_std,
    "min": seg.segment_min, "max": seg.segment_max,
}


def pna_aggregate(
    msg: jnp.ndarray,
    receivers: jnp.ndarray,
    num_segments: int,
    aggregators: Tuple[str, ...],
    mask: Optional[jnp.ndarray] = None,
    axis_name: Optional[str] = None,
    row_ptr=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """PNA's multi-aggregator bundle → (stacked [N, A, F] aggregates, count [N]).

    On the sorted arm the sum/mean/std family is :func:`fused_segment_stats`
    and min/max :func:`segment_extrema`: the scan kernel over receiver runs
    where the batch's ``row_ptr`` is there and no edge-sharded axis cuts a
    run, XLA's two scatters otherwise. Off the sorted arm every aggregator is
    its masked XLA segment op."""
    unknown = [a for a in aggregators if a not in _XLA_AGGREGATORS]
    if unknown:
        raise ValueError(f"Unknown aggregator {unknown[0]}")
    with jax.named_scope(scopes.AGG_PNA):
        n = num_segments
        if not srt.sorted_enabled():
            aggs = [
                _XLA_AGGREGATORS[a](msg, receivers, n, mask=mask, axis_name=axis_name)
                for a in aggregators
            ]
            count = seg.segment_count(receivers, n, mask=mask, axis_name=axis_name)
            return jnp.stack(aggs, axis=1), count
        fused = {}
        count = None
        if any(a in ("mean", "std", "sum") for a in aggregators):
            total, mean, std, count = fused_segment_stats(
                msg, receivers, n, mask=mask, axis_name=axis_name,
                want_std="std" in aggregators, row_ptr=row_ptr,
            )
            fused = {"mean": mean, "std": std, "sum": total}
        if "min" in aggregators or "max" in aggregators:
            # The scan kernel wants whole runs: an edge-sharded axis cuts them.
            extrema_ptr = row_ptr if axis_name is None else None
            ids = receivers.astype(jnp.int32)
            # CSR contract: RAW sorted ids, the masked edges' rows in the
            # padding node's run. The scatters take -1 for a masked row.
            if extrema_ptr is None and mask is not None:
                ids = jnp.where(mask, ids, -1)
            fused["min"], fused["max"] = segment_extrema(
                msg, ids, n, axis_name, extrema_ptr
            )
        if count is None:
            count = seg.segment_count(receivers, n, mask=mask, axis_name=axis_name)
        return jnp.stack([fused[a] for a in aggregators], axis=1), count
