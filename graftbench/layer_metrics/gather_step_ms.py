"""Device self time a train step of the operations under ``hydragnn.gather``:
the convs' node-to-edge row gathers and, in the backward pass, their
scatter-adds (``graftbench/xplane_scopes.py``), mean over the chips. None on
a program that opens no such scope."""

from graftbench import xplane_scopes


def read(run):
    return xplane_scopes.step_ms(run, "gather")
