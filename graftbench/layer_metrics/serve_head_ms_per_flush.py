"""Device milliseconds a flush under ``hydragnn.head.logprob``: the class
head's reply in the engine's executable (``HydraGNN.score_tokens``): the
head's matmul over the vocabulary slice in row blocks, the log-softmax and
the pick of each next token's log-probability, read by leaf scope whatever
the root. None on a program that opens no such scope."""

from graftbench.layer_metrics import serve_device_ms_per_flush


def read(run):
    return serve_device_ms_per_flush.scope_ms(run, "hydragnn.head.logprob")
