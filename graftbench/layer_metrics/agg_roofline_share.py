"""The bytes a train step's segment reductions need, forward and backward
(``graftbench/flops.py``: the messages read once a pass the algorithm needs,
one result written a pass; a chip), over what the chip's HBM peak
(``peaks.json``) could move in ``agg_step_ms``. The sorted arm's prefix sums
carry no scope and land in ``optimizer_step_ms`` (PERF.md section 7), so this
divides by less than the aggregation's whole time until a ``tracing`` PR
scopes them: it reads high by that much. Not clamped. None where
``agg_step_ms`` is."""

from graftbench import xplane_scopes


def read(run):
    return xplane_scopes.roofline_share(run, "agg")
