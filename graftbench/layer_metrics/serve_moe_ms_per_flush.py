"""Device milliseconds a flush under ``hydragnn.moe.experts``: the routed
experts' grouped matmuls and the SwiGLU between them, forward only, all
routed layers together, read by leaf scope whatever the root. None on a
program that opens no such scope in a serving window."""

from graftbench.layer_metrics import serve_device_ms_per_flush


def read(run):
    return serve_device_ms_per_flush.scope_ms(run, "hydragnn.moe.experts")
