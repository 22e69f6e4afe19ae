"""Device self time a train step of the operations under any
``hydragnn.agg.*`` scope (the segment reductions of ``ops/segment*.py`` and
``ops/pallas_segment.py``), forward and backward, from the operations' own
metadata in the trace (``graftbench/xplane_scopes.py``), mean over the chips.
None on a program that opens no such scope."""

from graftbench import xplane_scopes


def read(run):
    return xplane_scopes.step_ms(run, "agg")
