"""What a later PR writes to add a model family: this file, copied by
``test_discovery.py`` to ``families/sage.py`` of its temporary copy. GraphSAGE
with mean aggregation (``models/convs.py`` ``SAGEConv``; reference
``SAGEStack.py``): ``W_self x_i + W_nbr mean_j x_j``, the mean of a node
without neighbours being 0."""

import jax.numpy as jnp

from graftbench import flops, reference


def conv(p, x, send, recv):
    n, f = x.shape
    deg = jnp.zeros((n,), jnp.float32).at[recv].add(1.0)
    total = jnp.zeros((n, f), jnp.float32).at[recv].add(x[send])
    nbr = total / jnp.maximum(deg, 1.0)[:, None]
    return reference.dense(p["lin_nbr"], nbr) + reference.dense(p["lin_self"], x)


def encode(model, params, stats, graph):
    return reference.conv_stack(
        params, stats, graph["x"],
        lambda p, x, li, depth: conv(p, x, graph["send"], graph["recv"]),
    )


def counts(arch: dict, nodes: int, edges: int):
    hidden = arch["hidden_dim"]
    widths = [arch["input_dim"]] + [hidden] * arch["num_conv_layers"]
    parts = []
    for li, (a, b) in enumerate(zip(widths[:-1], widths[1:])):
        parts += [
            flops.gather(nodes, edges, a, grad=li > 0),  # x_j
            flops.segment_reduce(edges, nodes, a, ops=edges * a, grad=li > 0),
            flops.part(nodes * a, flops.B * 2 * nodes * a),  # the division
            flops.dense(nodes, a, b),
            flops.dense(nodes, a, b),
            flops.batch_norm(nodes, b),
        ]
    return parts, hidden
