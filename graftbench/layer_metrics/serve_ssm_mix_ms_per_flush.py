"""Device milliseconds a flush under ``hydragnn.ssm.conv`` and
``hydragnn.ssm.dt``: what a Mamba mixer does round its scan that is no
projection of the module's own: the depthwise causal convolution of 4 taps
inside each document and its ``silu``; the split of ``u W_x``, the three
inner norms, ``W_dt`` and the softplus. All Mamba layers together, read by
leaf scope whatever the root. None on a program that opens neither scope."""

from graftbench.layer_metrics import serve_device_ms_per_flush


def read(run):
    parts = [
        serve_device_ms_per_flush.scope_ms(run, scope)
        for scope in ("hydragnn.ssm.conv", "hydragnn.ssm.dt")
    ]
    return sum(p for p in parts if p) or None
