"""Device self time a train step of the operations whose innermost scope is
``hydragnn.attn.full``: the full-attention layers' rotary, the causal
kernel's calls and the gate's product, forward (the rematerialized one too)
and backward, all full layers together (``graftbench/xplane_scopes.py``),
mean over the chips. A part of ``model_dense_step_ms``; beside
``attn_window_step_ms`` it says what the band saves: a sliding layer has more
heads and should still cost less. None on a program that opens no such
scope."""

from graftbench.layer_metrics.moe_step_ms import scoped_ms

SCOPES = ("hydragnn.attn.full",)


def read(run):
    return scoped_ms(run, SCOPES)
