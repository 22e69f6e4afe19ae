"""Device self time a train step of the operations whose innermost scope is
``hydragnn.attn.window``: the sliding layers' query scaling, rotary, the band
kernel's calls and the gate's product, forward (the rematerialized one too)
and backward, all sliding layers together (``graftbench/xplane_scopes.py``),
mean over the chips. A part of ``model_dense_step_ms``; by layer in
``scopes.json`` (the ``module`` column). The projections are Dense layers
under their modules. None on a program that opens no such scope."""

from graftbench.layer_metrics.moe_step_ms import scoped_ms

SCOPES = ("hydragnn.attn.window",)


def read(run):
    return scoped_ms(run, SCOPES)
