"""Host-to-device wire time a loader batch: the program's ``h2d`` spans
(``FeedStats.record_h2d``, one a transfer; a transfer is a stacked chunk of
batches on the scan path) under the window's train epochs, over the batches."""

from graftbench.layer_metrics import span_ms_per_batch


def read(run):
    return span_ms_per_batch(run, "h2d")
