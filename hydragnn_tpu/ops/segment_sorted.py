"""Segment sums for SORTED segment ids: two routes, chosen by a row's width.

Collation owns edge order (message passing is permutation-invariant over
edges), so GraphArena sorts each graph's edges by receiver once at arena
build; batch receivers are then globally non-decreasing (per-graph sorted
runs + ascending node offsets + padding edges at the top index).

**Wide rows** (``WIDE_ROW`` columns or more; PR 32): ONE XLA scatter-add told
``indices_are_sorted=True`` (``_sum_count_scatter``). It adds a run's rows one
after another onto a zero row, so a segment's error is that of a plain
sequential float32 sum of its own rows (~1e-6 at the cells' runs of <= 20
rows), with no cancellation against a prefix. On one TPU v5 lite chip it
costs 9-12 ns a row whatever the width where the prefix sums below cost 14 ns
at 128 columns and 55 at 512 (``benchmarks/sorted_sum_routes.py``; PERF.md §6,
PR 32). ``count`` still comes from the boundaries, exact.

**Narrow rows**: no scatter, pure prefix sums and gathers (a scatter pays by
the row, and below one lane tile the cumsum is cheaper: 3-7 ns a row at 6 to
64 columns against the scatter-add's 9.5):

    P[k]   = sum(data[:k])                       (compensated prefix, below)
    out[s] = P[right_s] - P[left_s]
    cnt[s] = right_s - left_s                    (EXACT, integer)

where left/right come from the batch's precomputed CSR ``row_ptr``
(graphs/csr.py — collation builds and validates it once per batch) or, when
no boundaries were provided, from two in-step ``searchsorted`` calls (the
pre-PR-7 derivation, kept for callers outside the batch contract and for
edge-sharded graph parallelism where global offsets don't apply).

Cost: one O(E·F) chunked cumsum (HBM-bound, log-depth on TPU), a short
TwoSum carry scan over chunk totals, two gathers [N, F] — and zero binary
searches when ``row_ptr`` rides along. Zero MXU work, zero scatter, no
O(N·E) one-hot.

Accuracy: a raw f32 prefix difference cancels against the magnitude of the
WHOLE prefix (worst ~1e-3 at E=16k), so the prefix is two-level: f32 cumsum
within chunks (error bounded by local magnitudes) and carries accumulated
across chunks as an UNEVALUATED hi+err pair via error-free TwoSum — no f64,
so no dependence on jax_enable_x64. The segment value is recovered as
(hi_r - hi_l) + (err_r - err_l) + (local_r - local_l): the hi cancellation
is exactly rounded and its accumulated rounding error lives in err.
Certified against an f64 ground truth (ops/certify.py).

The TPU's arm (``sorted_enabled``): ``ops/aggregate.py`` routes every conv
family's sums, means and PNA's stats here when it is on: wide rows under the
scope arm ``scatter_sorted``, narrow ones under ``csr`` with the batch's
``row_ptr`` where the batch carries one and under ``sorted`` with the two
searches where it does not. ``ops/certify.py`` holds all three to an f64
ground truth, forward and gradient. What the arms cost on the chip, alone and
inside a train step, is in PERF.md §5-6 and PERF_LEDGER.jsonl
(``agg.sum.scatter_sorted``, ``agg.stats.scatter_sorted``, ``agg.sum.csr``,
``agg.mean.csr``).
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

# Trace-time spy: number of searchsorted boundary derivations traced by this
# module. The CSR batch contract (graphs/csr.py) exists to drive this to ZERO
# in the compiled step — collation precomputes ``row_ptr`` once per batch and
# every sorted-path op consumes it. tests/test_csr_contract.py asserts a full
# model trace with row_ptr present increments this by 0.
SEARCHSORTED_CALLS = 0


def searchsorted_calls() -> int:
    return SEARCHSORTED_CALLS


def _host_assert_sorted(ids, what="segment ids"):
    """jax.debug.callback target: loud failure on a layout regression."""
    import numpy as np

    arr = np.asarray(ids)
    if len(arr) and (np.diff(arr) < 0).any():
        k = int(np.argmax(np.diff(arr) < 0))
        raise RuntimeError(
            f"sorted-layout contract violated: {what} decrease at row {k} "
            f"({int(arr[k])} -> {int(arr[k + 1])}) — a caller of "
            "ops/aggregate.py passed an unsorted layout "
            "(HYDRAGNN_DEBUG_LAYOUT check)"
        )


def attach_layout_check(ids: jnp.ndarray, what: str = "segment ids") -> None:
    """Debug-mode runtime assertion that ``ids`` really is non-decreasing.

    The entry points of ``ops/aggregate.py`` take sorted ids as their
    precondition; collation validates its own batches once per arena
    (graphs/csr.py), but a NEW caller with a broken layout would silently
    corrupt aggregation. Under
    ``HYDRAGNN_DEBUG_LAYOUT=1`` (read at trace time, like every other gate
    here) each sorted-path op embeds a host callback that raises on the first
    unsorted batch; default off — zero cost in production steps."""
    from ..graphs.csr import csr_debug_enabled

    if csr_debug_enabled():
        jax.debug.callback(functools.partial(_host_assert_sorted, what=what), ids)


def sorted_enabled() -> bool:
    """Whether the aggregation takes the sorted arm. Read at trace time.

    On where the step executes on a TPU (``ops/segment.py``
    ``execution_platform``, which the step builders pin to the mesh's
    platform), off elsewhere: a scatter is the TPU's slow operation and a
    CPU's cheap one, and the exact-gate reference-parity tests pin the XLA
    ops on the CPU. ``HYDRAGNN_SEGMENT_SORTED=1/0`` overrides either way: it
    is how the tests put the chip's arm under a CPU, and the only variable
    that selects an aggregation arm. On the chip the sorted arm's wide sums
    ARE XLA's scatter-add, with the flag that the ids are sorted (3.3 against
    5.9 ms unflagged and 14.5 for the prefix sums at ``[262144, 512]``), and
    its narrow sums beat it (0.8 against 2.5 ms at 6 columns): PERF.md §6,
    PR 32."""
    env = os.environ.get("HYDRAGNN_SEGMENT_SORTED")
    if env is not None:
        return env not in ("0", "false", "False")
    from . import segment as seg

    return seg.execution_platform() == "tpu"


def _chunk_rows(e: int) -> int:
    """Chunk size: >=128 (lane-friendly), sized so the carry scan stays short
    (<=512 sequential steps) while local f32 cumsum error stays bounded."""
    c = 128
    while e // c > 512:
        c *= 2
    return c


def _two_sum(a, b):
    """Error-free transformation: a + b = s + err exactly (Knuth)."""
    s = a + b
    bb = s - a
    err = (a - bb) + (b - (s - bb))
    return s, err


def _prefix_open(data32: jnp.ndarray):
    """Two-level inclusive prefix of [E, F] f32 data.

    Returns (local, hi, err, chunk): P[k] = hi[k // chunk] + err[k // chunk]
    + local[k], where (hi, err) is the compensated EXCLUSIVE sum of chunks
    before k's and local the f32 cumsum inside it."""
    e, f = data32.shape
    chunk = _chunk_rows(e)
    e_pad = (e + chunk - 1) // chunk * chunk
    padded = jnp.zeros((e_pad, f), jnp.float32).at[:e].set(data32)
    chunks = padded.reshape(e_pad // chunk, chunk, f)
    local = jnp.cumsum(chunks, axis=1)
    totals = local[:, -1, :]  # [C, F]

    def step(carry, t):
        s, err = carry
        s2, e2 = _two_sum(s, t)
        return (s2, err + e2), (s, err)  # emit EXCLUSIVE prefix

    zeros = jnp.zeros((f,), jnp.float32)
    _, (hi, err) = jax.lax.scan(step, (zeros, zeros), totals)
    return local.reshape(e_pad, f), hi, err, chunk


# W: rows at least this wide are summed by ONE XLA scatter-add told the ids
# are sorted, narrower ones by the prefix sums. One lane tile. Read off the
# shape, nothing else. Set from the kernel-alone table of
# ``benchmarks/sorted_sum_routes.py`` on one TPU v5 lite chip (PERF.md §6,
# PR 32): a scatter-add pays by the row whatever its width, the chunked cumsum
# by the row AND its width in lane tiles, and they cross below one tile.
WIDE_ROW = 128


def wide(width: int) -> bool:
    """Whether a sum of ``[E, width]`` rows takes the scatter-add
    (``ops/aggregate.py`` names the arm ``scatter_sorted`` from this)."""
    return width >= WIDE_ROW


def _boundaries(ids, num_segments: int, row_ptr):
    """(left, right) of every segment's run of rows."""
    if row_ptr is not None:
        # CSR batch contract: collation precomputed the boundaries once per
        # batch (graphs/csr.py). Identical values to the searchsorted
        # derivation below (validated at collation), so the two paths are
        # bit-exact — tests/test_csr_contract.py pins that.
        row_ptr = row_ptr.astype(jnp.int32)
        return row_ptr[:-1], row_ptr[1:]
    ids = ids.astype(jnp.int32)
    seg = jnp.arange(num_segments, dtype=jnp.int32)
    global SEARCHSORTED_CALLS
    SEARCHSORTED_CALLS += 1
    left = jnp.searchsorted(ids, seg, side="left").astype(jnp.int32)
    right = jnp.searchsorted(ids, seg, side="right").astype(jnp.int32)
    return left, right


def _sum_count_prefix(data32, ids, num_segments: int, row_ptr=None):
    """The narrow route: segment totals as differences of a compensated
    prefix sum (module docstring), the count from the boundaries."""
    # Mean-center before the prefix: a mean-shifted stream grows the prefix
    # linearly and the within-chunk f32 cumsum rounds at ulp(prefix) — ~5e-4
    # absolute at E=16k, 100x the scatter path. Centered, the prefix is a
    # random walk (~sqrt scale); the exact row count restores count*mu after
    # the difference (masked rows contribute -mu then get +mu back: net 0).
    mu = jnp.mean(data32, axis=0)
    local, hi, err, chunk = _prefix_open(data32 - mu)
    left, right = _boundaries(ids, num_segments, row_ptr)

    def parts(k):
        """(hi, err, local) components of P[k] = sum(data[:k]); k in [0, E]."""
        km1 = jnp.maximum(k - 1, 0)
        nz = (k > 0)[:, None]
        c = km1 // chunk
        return (
            jnp.where(nz, hi[c], 0.0),
            jnp.where(nz, err[c], 0.0),
            jnp.where(nz, local[km1], 0.0),
        )

    hi_r, err_r, loc_r = parts(right)
    hi_l, err_l, loc_l = parts(left)
    # hi_r - hi_l is exactly rounded; the carries' accumulated rounding error
    # is (err_r - err_l); within-chunk contributions cancel at local scale.
    count = (right - left).astype(jnp.float32)
    total = (
        (hi_r - hi_l) + (err_r - err_l) + (loc_r - loc_l)
        + count[:, None] * mu
    )
    return total, count


def _sum_count_scatter(data32, ids, num_segments: int, row_ptr=None):
    """The wide route: ONE XLA scatter-add over the RAW ids, told they are
    non-decreasing. A run's rows are added one after another onto a zero row,
    with no cancellation against a prefix: no centering, no carries. The
    count still comes from the boundaries: exact, and no second scatter.

    The rows are PINNED row-major on their way in. A scatter (like the row
    gather that fed the messages) moves whole rows, and the prefix route's
    chunked reshape used to hold the whole elementwise chain between gather
    and sum to that layout. Without it the chip's layout assignment follows
    whichever producer states a preference (PaiNN's 20-deep filter Dense wants
    its ``[E, 3F]`` output column-major) and pays a transposing copy of every
    ``[E, F]`` array at the gather and again here: 22 copies a PaiNN train
    step, 120.4 ms where the pinned step takes 91.2 (PERF.md §6, PR 32)."""
    rows = with_layout_constraint(data32, Layout(major_to_minor=(0, 1)))
    total = jax.ops.segment_sum(
        rows, ids.astype(jnp.int32), num_segments=num_segments,
        indices_are_sorted=True,
    )
    left, right = _boundaries(ids, num_segments, row_ptr)
    return total, (right - left).astype(jnp.float32)


def _sum_count_sorted(data, ids, num_segments: int, row_ptr=None):
    data32 = data.astype(jnp.float32)
    if data32.shape[0] == 0:
        # Drop-in parity with segment_sum on an empty edge set: exact zeros
        # (jnp.mean over the empty axis would otherwise inject NaN via mu).
        return (
            jnp.zeros((num_segments, data32.shape[1]), jnp.float32),
            jnp.zeros((num_segments,), jnp.float32),
        )
    route = _sum_count_scatter if wide(data32.shape[1]) else _sum_count_prefix
    return route(data32, ids, num_segments, row_ptr)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def segment_sum_count_sorted(data, ids, num_segments: int):
    """(segment_sum, segment_count) for non-decreasing ``ids`` — see module
    docstring. ``data`` [E, F] float; masked rows must already be zeroed and
    their ids kept sort-compatible (collation's padding contract)."""
    return _sum_count_sorted(data, ids, num_segments)


def _fwd(data, ids, num_segments):
    # Zero-size carrier keeps the input dtype in the residuals (a raw dtype
    # object is not a JAX type).
    carrier = jnp.zeros((0,), data.dtype)
    return _sum_count_sorted(data, ids, num_segments), (ids, carrier)


def _bwd(num_segments, res, cots):
    ids, carrier = res
    d_total, _ = cots  # count is effectively non-differentiable (integer)
    idx = jnp.clip(ids.astype(jnp.int32), 0, num_segments - 1)
    d_data = jnp.take(d_total, idx, axis=0).astype(carrier.dtype)
    return d_data, jnp.zeros(ids.shape, jax.dtypes.float0)


segment_sum_count_sorted.defvjp(_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def segment_sum_count_csr(data, row_ptr, ids, num_segments: int):
    """(segment_sum, segment_count) from PRECOMPUTED CSR boundaries — the
    zero-searchsorted twin of :func:`segment_sum_count_sorted`. ``row_ptr``
    [num_segments + 1] comes from collation (graphs/csr.py); ``ids`` feeds
    the gather backward and, for wide rows, the forward's scatter-add (narrow
    rows' forward never reads it)."""
    return _sum_count_sorted(data, ids, num_segments, row_ptr=row_ptr)


def _csr_fwd(data, row_ptr, ids, num_segments):
    carrier = jnp.zeros((0,), data.dtype)
    out = _sum_count_sorted(data, ids, num_segments, row_ptr=row_ptr)
    return out, (row_ptr, ids, carrier)


def _csr_bwd(num_segments, res, cots):
    row_ptr, ids, carrier = res
    d_total, _ = cots
    idx = jnp.clip(ids.astype(jnp.int32), 0, num_segments - 1)
    d_data = jnp.take(d_total, idx, axis=0).astype(carrier.dtype)
    return (
        d_data,
        jnp.zeros(row_ptr.shape, jax.dtypes.float0),
        jnp.zeros(ids.shape, jax.dtypes.float0),
    )


segment_sum_count_csr.defvjp(_csr_fwd, _csr_bwd)


def segment_sum_count_auto(data, ids, num_segments: int, row_ptr=None):
    """Dispatch between the precomputed-boundary and searchsorted variants —
    the single entry ``ops/aggregate.py`` routes sorted traffic through."""
    if row_ptr is not None:
        return segment_sum_count_csr(data, row_ptr, ids, num_segments)
    return segment_sum_count_sorted(data, ids, num_segments)


def segment_sum_sorted(
    data, ids, num_segments: int, mask: Optional[jnp.ndarray] = None,
    row_ptr=None,
):
    """Masked drop-in segment_sum for sorted ids ([E, ...] data)."""
    shape = data.shape
    flat = data.reshape(shape[0], -1) if data.ndim != 2 else data
    if mask is not None:
        flat = jnp.where(mask[:, None], flat, 0)
    total, _ = segment_sum_count_auto(flat, ids, num_segments, row_ptr=row_ptr)
    out = total.astype(data.dtype)
    if data.ndim != 2:
        out = out.reshape((num_segments,) + shape[1:])
    return out
