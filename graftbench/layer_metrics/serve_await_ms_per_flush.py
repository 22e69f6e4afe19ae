"""Milliseconds a flush in which the engine waited for its requests:
``await`` (the flush's first request entered the queue that long after the
previous flush's replies were set: the engine held no request) + ``fill``
(first request queued -> the flush, by size or deadline), mean over the
window's ``serve/flush`` records. In a closed loop this is the CALLERS' turn:
reply set -> the flush's last request queued. None without such records."""

from graftbench.layer_metrics.serve_turnaround_ms_per_flush import mean_ms


def read(run):
    return mean_ms(run, "await", "fill")
