"""Device self time a train step of the operations whose innermost scope is
``hydragnn.moe.route``: the router's matmul and sigmoid, the top-k, the sort
of the assignments by expert, the row gather into expert order, the weighting
and the scatter-add back to the nodes, forward and backward
(``graftbench/xplane_scopes.py``), mean over the chips. A part of
``model_dense_step_ms``. None on a program that opens no such scope."""

from graftbench.layer_metrics.moe_step_ms import scoped_ms

SCOPES = ("hydragnn.moe.route",)


def read(run):
    return scoped_ms(run, SCOPES)
