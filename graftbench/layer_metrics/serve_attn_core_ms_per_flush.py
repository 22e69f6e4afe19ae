"""Device milliseconds a flush under ``hydragnn.attn.full``: the causal
attention kernel's calls over the flush's documents (32 unshared heads of 128,
the shared rotary key already concatenated into each), all layers together,
read by leaf scope whatever the root. None on a program that opens no such
scope in a serving window."""

from graftbench.layer_metrics import serve_device_ms_per_flush


def read(run):
    return serve_device_ms_per_flush.scope_ms(run, "hydragnn.attn.full")
