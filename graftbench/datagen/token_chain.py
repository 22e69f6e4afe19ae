"""Seeded token sequences from a first-order Markov chain: a token is a node,
a sequence is a graph.

What a token family needs of its generator (``datasets.materialize`` does the
rest): one-column node features, here TWO of them -- the token id and the id
of the token that follows it, the target of the node head -- and positions
``(i, 0, 0)``, the token's place in its sequence, which the model reads for
RoPE and the loaders read for their radius graph (at radius 2.5 a line's is
the band i-2 .. i+2: carried, never read by the model). ``materialize``
min-max scales both columns; the model and the loss un-scale them exactly
from the dataset's table (hydragnn_tpu/models/lfm2.py ``token_ids``).

The chain: every token of the vocabulary (the rank's SLICE of the published
one: ids are drawn from the slice) has ``successors`` possible next tokens,
drawn once from the seed, and each step takes one of them uniformly. A
model that learns the table reaches a loss of ln(successors); an untrained
one reads ln(vocab). ``tokens + 1`` ids a sequence: inputs 0 .. tokens-1,
targets 1 .. tokens. The one graph feature is unused (the contract wants one).
"""

from __future__ import annotations

import numpy as np

DATASET = {
    "name": "graftbench_tokens",
    "format": "unit_test",
    "compositional_stratified_splitting": False,
    "rotational_invariance": False,
    "node_features": {"name": ["token", "next_token"], "dim": [1, 1],
                      "column_index": [0, 1]},
    "graph_features": {"name": ["unused"], "dim": [1], "column_index": [0]},
    # Buckets in multiples of 64: whole sequences of 1024 plus the one padding
    # node the batch contract reserves pad to 4160, not to 8192.
    "ladder_step": "mult64",
}


def generate(params: dict, seed: int):
    """``params``: graphs, tokens (a sequence), vocab, successors."""
    rng = np.random.default_rng([int(seed), 0x70C])
    number, tokens = int(params["graphs"]), int(params["tokens"])
    vocab, fan = int(params["vocab"]), int(params.get("successors", 4))
    table = rng.integers(0, vocab, (vocab, fan))
    ids = np.empty((number, tokens + 1), np.int64)
    ids[:, 0] = rng.integers(0, vocab, number)
    step = rng.integers(0, fan, (number, tokens))
    for t in range(tokens):
        ids[:, t + 1] = table[ids[:, t], step[:, t]]
    pos = np.zeros((tokens, 3), np.float32)
    pos[:, 0] = np.arange(tokens)
    return [
        (
            np.stack([ids[g, :-1], ids[g, 1:]], axis=1).astype(np.float64),
            pos.copy(), np.zeros(1, np.float32),
        )
        for g in range(number)
    ]
