"""The whole flush's share of the chip's peak: the operations ONE FORWARD of
the window's flushes needs (``graftbench/flops.py`` ``forward``: no backward,
no optimizer, real rows) over what the bf16 peak could do in
``serve_device_ms_per_flush``, in percent. It bounds any later gain claimed
for a kernel of this cell. None without a trace."""

from graftbench.layer_metrics import serve_device_ms_per_flush


def read(run):
    ms = serve_device_ms_per_flush.read(run)
    ops = run.facts.get("flush_ops")
    if not ms or not ops or not run.peaks:
        return None
    return 100.0 * ops / (ms * 1e-3 * run.peaks["flops_per_s_bf16"])
