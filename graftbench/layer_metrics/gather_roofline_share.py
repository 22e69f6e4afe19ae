"""The bytes a train step's row gathers and their backward scatter-adds need
(``graftbench/flops.py``: float32, real rows, each row read and written
once, the index counted; a chip) over what the chip's HBM peak
(``peaks.json``) could move in ``gather_step_ms``. Not clamped. None where
``gather_step_ms`` is."""

from graftbench import xplane_scopes


def read(run):
    return xplane_scopes.roofline_share(run, "gather")
