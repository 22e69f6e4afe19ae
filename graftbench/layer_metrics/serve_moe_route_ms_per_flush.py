"""Device milliseconds a flush under ``hydragnn.moe.route``: the router's
matmul and softmax, the top-k, the sort of the assignments by expert, the row
gather into expert order, the weighting and the scatter-add back to the
tokens, forward only, all routed layers together, read by leaf scope whatever
the root. None on a program that opens no such scope in a serving window."""

from graftbench.layer_metrics import serve_device_ms_per_flush


def read(run):
    return serve_device_ms_per_flush.scope_ms(run, "hydragnn.moe.route")
