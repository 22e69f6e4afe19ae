"""GATv2 (``model_type: "GAT"``): the plain conv and its counts.

GATv2 conv (PyG GATv2Conv, self-loops added, heads concatenated except in
the last layer, where they are averaged):
  e_ij = a . leaky_relu(W_src x_j + W_dst x_i); alpha = softmax_j over
  N(i) + {i}; out_i = sum_j alpha_ij W_src x_j + bias
Departure from the reference's equations, as the program has it: the
attention's self loop is one more edge of the list here (PyG's formulation);
the program adds a dense self term instead.
"""

import jax
import jax.numpy as jnp

from graftbench import flops, reference

HEADS = 6  # fixed by the reference (create.py:112-114) and the program


def conv(p, x, send, recv, heads, slope, concat):
    n = x.shape[0]
    f = p["att"].shape[1]
    src = reference.dense(p["lin_src"], x).reshape(n, heads, f)
    dst = reference.dense(p["lin_dst"], x).reshape(n, heads, f)
    # One self loop a node, appended to the edge list as PyG does.
    loop = jnp.arange(n)
    send, recv = jnp.concatenate([send, loop]), jnp.concatenate([recv, loop])
    pre = jax.nn.leaky_relu(src[send] + dst[recv], slope)  # [E + n, h, f]
    logits = jnp.einsum("ehf,hf->eh", pre, p["att"])
    top = jnp.full((n, heads), -jnp.inf).at[recv].max(logits)
    weight = jnp.exp(logits - top[recv])
    alpha = weight / jnp.zeros((n, heads), jnp.float32).at[recv].add(weight)[recv]
    out = jnp.zeros((n, heads, f), jnp.float32).at[recv].add(alpha[..., None] * src[send])
    out = out.reshape(n, heads * f) if concat else out.mean(axis=1)
    return out + p["bias"]


def encode(model, params, stats, graph):
    return reference.conv_stack(
        params, stats, graph["x"],
        lambda p, x, li, depth: conv(
            p, x, graph["send"], graph["recv"], model.gat_heads,
            model.gat_negative_slope, concat=li < depth - 1,
        ),
    )


def conv_counts(nodes: int, edges: int, f_in: int, f_head: int, heads: int) -> list:
    """GATv2Conv forward with the self-loop term: two projections to
    heads x f_head; the gathers of the projected sources and destinations
    (each once) and of the softmax's shift and denominator; per edge (and per
    node, for the loop) add + leaky_relu + dot with the attention vector,
    softmax, weighted sum of sources. The self loop needs no gather and no
    segment pass (a node's own row), so those run over ``edges`` rows and the
    elementwise work over ``edges + nodes`` terms. The shift is under
    ``stop_gradient``: neither its pass nor its gather has a backward."""
    w = heads * f_head
    terms = edges + nodes  # incoming edges and the self loop
    return [
        flops.dense(nodes, f_in, w),
        flops.dense(nodes, f_in, w),
        flops.gather(nodes, edges, w),  # W_src x_j
        flops.gather(nodes, edges, w),  # W_dst x_i
        flops.gather(nodes, edges, heads, grad=False),  # the shift
        flops.gather(nodes, edges, heads),  # the denominator
        flops.segment_reduce(edges, nodes, heads, grad=False),  # logits' max
        flops.segment_reduce(edges, nodes, heads),  # denominators
        flops.segment_reduce(edges, nodes, w),  # weighted sum
        # add, leaky_relu, dot (2), then message multiply and accumulate, at
        # one read an operand and one write a result a pass: the logits from
        # the two gathered rows, alpha from logits, shift and denominator,
        # the messages from alpha and the gathered source.
        flops.part(
            terms * w * 6 + terms * heads * 5,
            flops.B * (4 * terms * w + 6 * terms * heads),
        ),
    ]


def counts(arch: dict, nodes: int, edges: int):
    hidden, depth = arch["hidden_dim"], arch["num_conv_layers"]
    parts = []
    f_in = arch["input_dim"]
    for li in range(depth):
        parts += conv_counts(nodes, edges, f_in, hidden, HEADS)
        f_in = hidden if li == depth - 1 else hidden * HEADS
        parts.append(flops.batch_norm(nodes, f_in))
    return parts, hidden
