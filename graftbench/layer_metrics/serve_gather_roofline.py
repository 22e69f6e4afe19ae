"""The bytes a flush's forward row gathers need (``flops.forward``'s
``gather`` class) over what the chip's HBM peak could move in the device time
under ``hydragnn.gather``, in percent, read by leaf scope whatever the root.
Bytes bound it: a gather has no operations. Not clamped."""

from graftbench.layer_metrics import serve_device_ms_per_flush


def read(run):
    return serve_device_ms_per_flush.roofline(run, "hydragnn.gather", "gather")
