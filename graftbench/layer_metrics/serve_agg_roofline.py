"""The bytes a flush's forward segment reductions need (``flops.forward``'s
``agg`` class) over what the chip's HBM peak could move in the device time
under any ``hydragnn.agg.*`` scope, in percent, read by leaf scope whatever
the root. Bytes bound it: a reduction has one operation an element. Not
clamped."""

from graftbench.layer_metrics import serve_device_ms_per_flush


def read(run):
    return serve_device_ms_per_flush.roofline(run, "hydragnn.agg.", "agg")
