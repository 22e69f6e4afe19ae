"""Device milliseconds a flush under ``hydragnn.attn.latent``: latent
attention's two low-rank chains (``W_qa``, ``W_qb``, ``W_kva``, ``W_kvb``),
their two norms, the rotation of the rotary parts and the concatenation into
whole heads, all layers together, read by leaf scope whatever the root
(``serve_device_ms_per_flush.scope_ms``). The causal kernel is
``serve_attn_core_ms_per_flush``'s, the output projection the module's. None
on a program that opens no such scope."""

from graftbench.layer_metrics import serve_device_ms_per_flush


def read(run):
    return serve_device_ms_per_flush.scope_ms(run, "hydragnn.attn.latent")
