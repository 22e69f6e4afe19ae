"""Certification of the aggregation arms ``ops/aggregate.py`` can take on the
sorted arm, on whatever platform this process executes on: the arms' accuracy
against a float64 ground truth, never their speed (a speed is the
benchmark's to state: graftbench, PERF_LEDGER.jsonl).

* ``sorted`` and ``csr`` (PNA's stats bundle, ``fused_segment_stats`` without
  and with ``row_ptr``): sum, mean, std and count, forward and gradient,
  against numpy in float64. Forward gate ``KERNEL_CERT_GATE.fwd``
  (precision/tolerance.py). Gradient gate: no worse than the masked XLA ops of
  ``ops/segment.py`` on the same data, and never asked to beat the forward
  gate. The arms' ``std`` gradient inherits a ``1/std^2`` amplification of the
  sums' ~1e-5 noise at near-degenerate segments (~5e-3), where XLA's
  uncentered ``E[x^2] - E[x]^2`` carries ~1e-1.
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
    """(sum, mean, std, count) and the gradient of :func:`_scalar`, float64."""
    d64 = np.asarray(data, np.float64)
    total = np.zeros((n, d64.shape[1]))
    np.add.at(total, ids, d64)
    count = np.bincount(ids, minlength=n).astype(np.float64)
    safe = np.maximum(count, 1.0)[:, None]
    mean = total / safe
    centered = d64 - mean[ids]
    sumsq = np.zeros_like(total)
    np.add.at(sumsq, ids, np.square(centered))
    std = np.sqrt(sumsq / safe + _EPS)
    # dstd at single-count segments is identically 0 (x ≡ mean there).
    quad = np.where(count[:, None] > 1.0, _W_STD / (std * safe), 0.0)
    grad = (_W_TOTAL + _W_MEAN / safe)[ids] + quad[ids] * centered
    return (total, mean, std, count), grad


def certify_aggregation(
    e: int = 16384, f: int = 64, n: int = 4096, seed: int = 0
) -> dict:
    """Hold the ``sorted`` and ``csr`` arms and the extrema scan kernels to
    their gates at ``[e, f]`` messages over ``n`` segments (sorted ids, no
    mask: the batch contract puts masked rows in padding segments nobody
    reads). Returns the errors, the gates and ``ok`` for each and overall."""
    if not srt.sorted_enabled():
        raise RuntimeError(
            "certify_aggregation runs the sorted arm: execute on a TPU, or set "
            "HYDRAGNN_SEGMENT_SORTED=1 to put it under this platform"
        )
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    data = jax.random.normal(k1, (e, f), jnp.float32) * 2.0 + 1.0
    ids = jnp.sort(jax.random.randint(k2, (e,), 0, n))
    ids_h = np.asarray(ids)
    row_ptr = jnp.asarray(
        np.searchsorted(ids_h, np.arange(n + 1)).astype(np.int32)
    )
    truth, grad_truth = _truth(data, ids_h, n)

    def errors(bundle):
        outs = jax.jit(bundle)(data)
        grad = jax.jit(jax.grad(_scalar(bundle)))(data)
        return (
            max(max_abs_diff(o, t) for o, t in zip(outs, truth)),
            max_abs_diff(grad, grad_truth),
        )

    xla_fwd, xla_grad = errors(lambda d: (
        seg.segment_sum(d, ids, n), seg.segment_mean(d, ids, n),
        seg.segment_std(d, ids, n, eps=_EPS), seg.segment_count(ids, n),
    ))
    tol_grad = max(KERNEL_CERT_GATE.fwd, xla_grad)
    arms = {}
    for arm, ptr in (("sorted", None), ("csr", row_ptr)):
        fwd, grad = errors(
            lambda d: agg.fused_segment_stats(d, ids, n, eps=_EPS, row_ptr=ptr)
        )
        arms[arm] = {
            "err_fwd": fwd, "err_grad": grad,
            "ok": fwd < KERNEL_CERT_GATE.fwd and grad <= tol_grad,
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
        "shape": {"e": e, "f": f, "n": n},
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
