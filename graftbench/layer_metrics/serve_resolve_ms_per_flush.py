"""Host milliseconds a flush in ``InferenceEngine._resolve`` (the flush's
counters, each reply sliced and set, the ``serve/response`` events), from the
``serve/flush`` record's marks (``resolved - d2h_end``), mean over the
window. A reply's latency ends inside it; the next flush's launch waits for
all of it. None without such records."""

from graftbench.layer_metrics.serve_turnaround_ms_per_flush import mean_ms


def read(run):
    return mean_ms(run, "resolve")
