"""Device self time a train step of the two token mixers' own work: under
``hydragnn.lfm2.conv`` (the gated short convolution's products and shifted
reads) and ``hydragnn.lfm2.attn`` (head norms, RoPE, the blockwise causal
softmax within each sequence), forward and backward
(``graftbench/xplane_scopes.py``), mean over the chips. Their projections are
Dense layers and stay with ``model_dense_step_ms``'s remainder. None on a
program that opens neither scope."""

from graftbench.layer_metrics.moe_step_ms import scoped_ms

SCOPES = ("hydragnn.lfm2.conv", "hydragnn.lfm2.attn")


def read(run):
    return scoped_ms(run, SCOPES)
