"""Device milliseconds a flush under ``hydragnn.attn.window``: the band's
attention kernel (the splash kernel under a static ``LocalMask``) and the
rotary before it over the flush's documents, all window layers together, read
by leaf scope whatever the root. None on a program that opens no such scope in
a serving window (a stack whose every layer is full; this PR's parent)."""

from graftbench.layer_metrics import serve_device_ms_per_flush


def read(run):
    return serve_device_ms_per_flush.scope_ms(run, "hydragnn.attn.window")
