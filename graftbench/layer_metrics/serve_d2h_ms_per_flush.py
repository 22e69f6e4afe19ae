"""Host milliseconds a flush from the forward's ``block_until_ready`` to the
start of the demux: the outputs' copy to the host (``serve/d2h``), from the
``serve/flush`` record's marks (``d2h_end - ready``), mean over the window.
On the dispatcher's thread, in series with a closed loop's next flush. None
without such records."""

from graftbench.layer_metrics.serve_turnaround_ms_per_flush import mean_ms


def read(run):
    return mean_ms(run, "d2h")
