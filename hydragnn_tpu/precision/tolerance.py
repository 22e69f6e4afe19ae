"""Shared numerical-tolerance machinery (docs/PRECISION.md "Tolerance gate").

ONE tolerance implementation for every place the stack compares a reduced- or
alternate-precision computation against a reference:

* the aggregation's certification (``ops/certify.py``) — its forward gate
  lives here as :data:`KERNEL_CERT_GATE`, so kernel certification and
  quantized serving can never drift apart on what "within tolerance" means;
* the serve engine's quantized arm (``serve/engine.py check_tolerance``) —
  the bit-exactness contract relaxes to :func:`tolerance_report` ONLY for
  ``--precision bf16|int8``;
* ``bench.py --precision`` — the step-matched convergence delta and the
  quantized-arm diff stats are computed through the same helpers.

Everything here is host-side numpy: no jax import, so the ops layer can
consume the gate constants without an import cycle.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence

import numpy as np


@dataclass(frozen=True)
class ToleranceGate:
    """A forward max-abs-error bound.

    ``check`` returns a verdict dict rather than raising: every consumer
    (certify artifact, serve gate, bench section) embeds the verdict in its
    own report and decides locally whether a failure is fatal."""

    fwd: float

    def check(self, fwd_err: float) -> Dict[str, Any]:
        return {
            "ok": float(fwd_err) < self.fwd,
            "fwd_err": float(fwd_err),
            "tol": self.fwd,
        }


# The aggregation arms' forward pin: 5e-4 against a float64 ground truth is
# kernel-grade strict. ops/certify.py consumes THIS constant; its gradient
# gate is relative (no worse than the XLA ops on the same data) and is
# stated there.
KERNEL_CERT_GATE = ToleranceGate(fwd=5e-4)


def max_abs_diff(a: Any, b: Any) -> float:
    """Max absolute elementwise difference, computed in f64 (the certify
    convention — the comparison must not round in the dtype under test)."""
    a64 = np.asarray(a, np.float64)
    b64 = np.asarray(b, np.float64)
    if a64.shape != b64.shape:
        raise ValueError(
            f"shape mismatch in tolerance comparison: {a64.shape} vs {b64.shape}"
        )
    if a64.size == 0:
        return 0.0
    return float(np.max(np.abs(a64 - b64)))


def tolerance_report(
    outputs: Sequence[Any],
    reference: Sequence[Any],
    bound: float,
    names: Optional[Sequence[str]] = None,
) -> Dict[str, Any]:
    """Per-head + overall max-abs-diff of ``outputs`` against ``reference``
    under one forward ``bound`` → the serve quantized-arm gate verdict.

    Also carries the reference dynamic range per head so a diff is readable
    as a relative error without re-running the reference."""
    if len(outputs) != len(reference):
        raise ValueError(
            f"{len(outputs)} outputs vs {len(reference)} reference heads"
        )
    heads: List[Dict[str, Any]] = []
    worst = 0.0
    for i, (out, ref) in enumerate(zip(outputs, reference)):
        diff = max_abs_diff(out, ref)
        ref64 = np.asarray(ref, np.float64)
        span = float(np.max(np.abs(ref64))) if ref64.size else 0.0
        heads.append(
            {
                "head": names[i] if names else f"head_{i}",
                "max_abs_diff": diff,
                "ref_max_abs": span,
                "rel_diff": diff / span if span > 0 else None,
            }
        )
        worst = max(worst, diff)
    gate = ToleranceGate(fwd=float(bound))
    verdict = gate.check(worst)
    verdict["per_head"] = heads
    return verdict
