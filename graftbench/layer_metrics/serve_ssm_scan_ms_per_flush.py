"""Device milliseconds a flush under ``hydragnn.ssm.scan``: the selective
scan kernel's calls over the flush's packed documents (the state ``[d_inner,
d_state]`` carried in VMEM down the rung's rows, set to zero at each
document's first), all Mamba layers together, read by leaf scope whatever the
root. The gate's product is not in it (XLA fuses it into the ``z`` matmul's
output, whose time is the module's). None on a program that opens no such
scope in a serving window (a stack with no state-space layer; this PR's
parent)."""

from graftbench.layer_metrics import serve_device_ms_per_flush


def read(run):
    return serve_device_ms_per_flush.scope_ms(run, "hydragnn.ssm.scan")
