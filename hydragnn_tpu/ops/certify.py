"""Certification of the aggregation arms ``ops/aggregate.py`` can take on the
sorted arm, on whatever platform this process executes on: the arms' accuracy
against a float64 ground truth, never their speed (a speed is the
benchmark's to state: graftbench, PERF_LEDGER.jsonl).

* ``sorted`` and ``csr`` (PNA's stats bundle, ``fused_segment_stats`` without
  and with ``row_ptr``, at a width under ``segment_sorted.WIDE_ROW``: the
  prefix sums): sum, mean, std and count, forward and gradient,
  against numpy in float64. Forward gate ``KERNEL_CERT_GATE.fwd``
  (precision/tolerance.py). Gradient gate: no worse than the masked XLA ops of
  ``ops/segment.py`` on the same data, and never asked to beat the forward
  gate. The arms' ``std`` gradient inherits a ``1/std^2`` amplification of the
  sums' ~1e-5 noise at near-degenerate segments (~5e-3), where XLA's
  uncentered ``E[x^2] - E[x]^2`` carries ~1e-1.
* ``scatter_sorted`` (the same bundle at ``WIDE_ROW`` columns or more: one
  scatter-add told the ids are sorted; :func:`certify_wide_sum`): the same
  truth and the same gates over ``WIDE_CASES``, the layouts a batch can bring
  it. It adds a run's rows in row order onto a zero row, so its forward error
  is a plain sequential float32 sum's over the run (``WIDE_FWD_PIN``).
* the extrema scan kernels (``segment_extrema`` with ``row_ptr``): the
  forward bit-equal to ``jax.ops.segment_min`` / ``segment_max`` on every
  non-empty run, 0 on the empty ones; the gradient bit-equal to the XLA
  route's (``segment_extrema`` without ``row_ptr``: four row gathers).

Run by ``chip_smoke.py``'s kernels stage on the chip (where the scan kernel is
Mosaic's, not the interpreter's) and by the tier-1 tests under a CPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..precision.tolerance import KERNEL_CERT_GATE, max_abs_diff
from . import aggregate as agg
from . import segment as seg
from . import segment_sorted as srt

_EPS = 1e-5
# The cotangent of the scalar the gradient is taken of: all three
# differentiable outputs contribute.
_W_TOTAL, _W_MEAN, _W_STD = 0.3, 1.7, -0.9


def _scalar(bundle):
    def fn(d):
        total, mean, std, _ = bundle(d)
        return jnp.sum(total * _W_TOTAL + mean * _W_MEAN + std * _W_STD)

    return fn


def _truth(data, ids, n):
    """(sum, mean, std, count) and the gradient of :func:`_scalar`, float64.
    ``ids`` are sorted, so a segment's rows are one run (``reduceat``)."""
    d64 = np.asarray(data, np.float64)
    starts = np.searchsorted(ids, np.arange(n + 1))
    filled = np.flatnonzero(np.diff(starts))

    def run_sums(rows):
        out = np.zeros((n, rows.shape[1]))
        out[filled] = np.add.reduceat(rows, starts[filled], axis=0)
        return out

    total = run_sums(d64)
    count = np.diff(starts).astype(np.float64)
    safe = np.maximum(count, 1.0)[:, None]
    mean = total / safe
    centered = d64 - mean[ids]
    sumsq = run_sums(np.square(centered))
    std = np.sqrt(sumsq / safe + _EPS)
    # dstd at single-count segments is identically 0 (x ≡ mean there).
    quad = np.where(count[:, None] > 1.0, _W_STD / (std * safe), 0.0)
    grad = (_W_TOTAL + _W_MEAN / safe)[ids] + quad[ids] * centered
    return (total, mean, std, count), grad


def _require_sorted_arm(who: str) -> None:
    if not srt.sorted_enabled():
        raise RuntimeError(
            f"{who} runs the sorted arm: execute on a TPU, or set "
            "HYDRAGNN_SEGMENT_SORTED=1 to put it under this platform"
        )


def _errors(bundle, data, truth, grad_truth):
    """(forward, gradient) max |error| of a stats bundle against the truth."""
    outs = jax.jit(bundle)(data)
    grad = jax.jit(jax.grad(_scalar(bundle)))(data)
    return (
        max(max_abs_diff(o, t) for o, t in zip(outs, truth)),
        max_abs_diff(grad, grad_truth),
    )


def _xla_bundle(ids, n):
    return lambda d: (
        seg.segment_sum(d, ids, n), seg.segment_mean(d, ids, n),
        seg.segment_std(d, ids, n, eps=_EPS), seg.segment_count(ids, n),
    )


# The layouts a batch can bring the wide route, each held to the f64 truth:
# short runs everywhere; no ``row_ptr`` (the count's boundaries searched);
# two segments in three empty; the last quarter of the rows zeroed in the
# last segment's run (collation's padding contract: a long run of zeros,
# counted); a row count that is no multiple of 128; bfloat16 messages;
# an edge-sharded ``axis_name`` over the devices this process has (one on the
# chip: a loopback), each shard's scatter-add summed by the ``psum``.
WIDE_CASES = (
    "short_runs", "searched", "empty_runs", "padding_run", "ragged_rows",
    "bf16", "axis_name",
)
# What a sequential float32 sum of a run gives: (rows - 1) roundings of a
# partial sum, each half an ulp of it. Messages ~N(1, 2) in runs of 4-12 rows
# read 3.9e-6 to 9.8e-6 at [16384, 512] on a CPU; in runs of 16 (48 in the
# empty-runs case, sums near 64: ulp 7.6e-6) 1.6e-5 to 4.3e-5 at [262144, 512]
# on the chip; the cells' unit-variance rows 4.5e-6 there, where the prefix
# sums read 3.7e-4 (PERF.md §6, PR 32). A fifth of the certification gate.
WIDE_FWD_PIN = 1e-4
_BF16_EPS = 2.0 ** -8


def certify_wide_sum(
    case: str, e: int = 16384, f: int = 512, n: int = 4096, seed: int = 0
) -> dict:
    """Hold the ``scatter_sorted`` arm (``fused_segment_stats`` at ``f`` >=
    ``WIDE_ROW`` columns) to the float64 truth, forward and gradient, in one
    of ``WIDE_CASES``. Gates: forward ``WIDE_FWD_PIN`` (a fifth of
    ``KERNEL_CERT_GATE.fwd``); gradient no worse than the masked XLA ops on
    the same data and never under the forward gate (bfloat16 messages: plus
    the rounding of a bfloat16 gradient, 2^-8 of its largest entry)."""
    _require_sorted_arm("certify_wide_sum")
    if case not in WIDE_CASES or not srt.wide(f):
        raise ValueError(f"not a wide case: {case!r} at {f} columns")
    if case == "ragged_rows":
        e -= 37
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    data = jax.random.normal(k1, (e, f), jnp.float32) * 2.0 + 1.0
    ids_h = np.sort(np.asarray(jax.random.randint(k2, (e,), 0, n)))
    if case == "empty_runs":
        ids_h = ids_h // 3 * 3
    if case == "padding_run":
        pad = e // 4
        ids_h[e - pad:] = n - 1
        data = data.at[e - pad:].set(0.0)
    if case == "bf16":
        data = data.astype(jnp.bfloat16)
    ids = jnp.asarray(ids_h.astype(np.int32))
    row_ptr = jnp.asarray(
        np.searchsorted(ids_h, np.arange(n + 1)).astype(np.int32)
    )
    truth, grad_truth = _truth(data, ids_h, n)
    if case == "axis_name":
        from jax.sharding import Mesh, PartitionSpec as P

        devices = jax.devices()[: 2 if len(jax.devices()) > 1 and e % 2 == 0 else 1]
        mesh = Mesh(np.array(devices), ("graph",))

        def bundle(d):
            return jax.shard_map(
                lambda d_, i_, p_: agg.fused_segment_stats(
                    d_, i_, n, eps=_EPS, axis_name="graph", row_ptr=p_
                ),
                mesh=mesh, in_specs=(P("graph"), P("graph"), P()),
                out_specs=(P(), P(), P(), P()), check_vma=False,
            )(d, ids, row_ptr)
    else:
        ptr = None if case == "searched" else row_ptr

        def bundle(d):
            return agg.fused_segment_stats(d, ids, n, eps=_EPS, row_ptr=ptr)

    _, xla_grad = _errors(
        _xla_bundle(ids, n), data.astype(jnp.float32), truth, grad_truth
    )
    tol_grad = max(KERNEL_CERT_GATE.fwd, xla_grad)
    if case == "bf16":
        tol_grad += _BF16_EPS * float(np.abs(grad_truth).max())
    fwd, grad = _errors(bundle, data, truth, grad_truth)
    return {
        "case": case, "shape": {"e": e, "f": f, "n": n},
        "err_fwd": fwd, "err_grad": grad, "tol_grad": tol_grad,
        "ok": fwd < WIDE_FWD_PIN and grad <= tol_grad,
    }


def certify_aggregation(
    e: int = 16384, f: int = 64, n: int = 4096, seed: int = 0
) -> dict:
    """Hold the ``sorted``, ``csr`` and ``scatter_sorted`` arms and the
    extrema scan kernels to their gates at ``[e, f]`` messages over ``n``
    segments (sorted ids, no mask: the batch contract puts masked rows in
    padding segments nobody reads). The prefix arms run at ``f`` columns where
    that is narrow and at half of ``WIDE_ROW`` where it is not; the wide arm at
    ``f`` where that is wide and at four times ``WIDE_ROW`` where it is not
    (``shape`` says which). Returns the errors, the gates and ``ok`` for each
    and overall."""
    _require_sorted_arm("certify_aggregation")
    f_narrow = srt.WIDE_ROW // 2 if srt.wide(f) else f
    f_wide = f if srt.wide(f) else 4 * srt.WIDE_ROW
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    data = jax.random.normal(k1, (e, f), jnp.float32) * 2.0 + 1.0
    ids = jnp.sort(jax.random.randint(k2, (e,), 0, n))
    ids_h = np.asarray(ids)
    row_ptr = jnp.asarray(
        np.searchsorted(ids_h, np.arange(n + 1)).astype(np.int32)
    )
    narrow = data[:, :f_narrow]
    truth, grad_truth = _truth(narrow, ids_h, n)
    xla_fwd, xla_grad = _errors(_xla_bundle(ids, n), narrow, truth, grad_truth)
    tol_grad = max(KERNEL_CERT_GATE.fwd, xla_grad)
    arms = {}
    for arm, ptr in (("sorted", None), ("csr", row_ptr)):
        fwd, grad = _errors(
            lambda d: agg.fused_segment_stats(d, ids, n, eps=_EPS, row_ptr=ptr),
            narrow, truth, grad_truth,
        )
        arms[arm] = {
            "err_fwd": fwd, "err_grad": grad,
            "ok": fwd < KERNEL_CERT_GATE.fwd and grad <= tol_grad,
        }
    wide_cases = {
        case: certify_wide_sum(case, e, f_wide, n, seed) for case in WIDE_CASES
    }
    arms["scatter_sorted"] = {
        "err_fwd": max(c["err_fwd"] for c in wide_cases.values()),
        "err_grad": max(c["err_grad"] for c in wide_cases.values()),
        "cases": wide_cases,
        "ok": all(c["ok"] for c in wide_cases.values()),
    }

    mn, mx = jax.jit(
        lambda d: agg.segment_extrema(d, ids, n, None, row_ptr)
    )(data)
    filled = (np.bincount(ids_h, minlength=n) > 0)[:, None]
    want_mn = np.where(filled, jax.ops.segment_min(data, ids, num_segments=n), 0)
    want_mx = np.where(filled, jax.ops.segment_max(data, ids, num_segments=n), 0)
    bit_equal = bool(
        np.array_equal(np.asarray(mn), want_mn)
        and np.array_equal(np.asarray(mx), want_mx)
    )

    weights = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, n, f))

    def extrema_grad(ptr):
        def fn(d):
            lo, hi = agg.segment_extrema(d, ids, n, None, ptr)
            return jnp.sum(lo * weights[0] + hi * weights[1])

        return np.asarray(jax.jit(jax.grad(fn))(data))

    grad_bit_equal = bool(np.array_equal(extrema_grad(row_ptr), extrema_grad(None)))
    return {
        "backend": seg.execution_platform(),
        "shape": {"e": e, "f": f, "n": n, "f_narrow": f_narrow, "f_wide": f_wide},
        "tol": KERNEL_CERT_GATE.fwd,
        "tol_grad": tol_grad,
        "xla": {"err_fwd": xla_fwd, "err_grad": xla_grad},
        "arms": arms,
        "extrema_scan": {
            "bit_equal": bit_equal, "grad_bit_equal": grad_bit_equal,
            "ok": bit_equal and grad_bit_equal,
        },
        "ok": bit_equal and grad_bit_equal and all(a["ok"] for a in arms.values()),
    }
