"""Host seconds a loader batch took to collate: the program's graftel
``collate`` spans (one a pull on the feed's host thread) under the window's
train epochs, over the batches."""

from graftbench.layer_metrics import span_ms_per_batch


def read(run):
    return span_ms_per_batch(run, "collate")
