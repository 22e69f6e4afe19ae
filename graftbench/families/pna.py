"""PNA (``model_type: "PNA"``): the plain conv and its counts.

PNA conv (PyG PNAConv, towers 1, pre/post layers 1):
  m_ij = W_pre [x_i, x_j (, e_ij)];  A = [mean, min, max, std]_j m_ij,
  std = sqrt(relu(E[m^2] - E[m]^2) + 1e-5); scalers identity,
  log(d+1)/avg_log, avg_log/log(d+1), d/avg_lin with d = max(deg, 1);
  out = W_lin W_post [x_i, scaler x aggregator block]
Departure from the reference's equations, as the program has it: min and max
of a node without neighbours are 0 (PyG gives 0 too).
"""

import jax
import jax.numpy as jnp

from graftbench import flops, reference


def conv(p, x, send, recv, edge_attr, avg_log, avg_lin):
    n, f = x.shape
    z = [x[recv], x[send]] + ([edge_attr] if edge_attr is not None else [])
    m = reference.dense(p["pre_nn"], jnp.concatenate(z, axis=-1))  # [E, f]
    deg = jnp.zeros((n,), jnp.float32).at[recv].add(1.0)
    d1 = jnp.maximum(deg, 1.0)[:, None]
    mean = jnp.zeros((n, f), jnp.float32).at[recv].add(m) / d1
    mean_sq = jnp.zeros((n, f), jnp.float32).at[recv].add(m * m) / d1
    std = jnp.sqrt(jax.nn.relu(mean_sq - mean * mean) + 1e-5)
    has = (deg > 0)[:, None]
    mn = jnp.where(has, jnp.full((n, f), jnp.inf).at[recv].min(m), 0.0)
    mx = jnp.where(has, jnp.full((n, f), -jnp.inf).at[recv].max(m), 0.0)
    agg = jnp.concatenate([mean, mn, mx, std], axis=-1)  # [n, 4f]
    log_d = jnp.log(d1 + 1.0)
    scaled = jnp.concatenate(
        [agg, agg * (log_d / avg_log), agg * (avg_log / log_d), agg * (d1 / avg_lin)],
        axis=-1,
    )
    out = reference.dense(p["post_nn"], jnp.concatenate([x, scaled], axis=-1))
    return reference.dense(p["lin"], out)


def encode(model, params, stats, graph):
    edge_attr = graph["edge_attr"] if model.use_edge_attr else None
    return reference.conv_stack(
        params, stats, graph["x"],
        lambda p, x, li, depth: conv(
            p, x, graph["send"], graph["recv"], edge_attr,
            model.pna_deg_avg_log, model.pna_deg_avg_lin,
        ),
    )


def conv_counts(nodes: int, edges: int, f_in: int, f_out: int, edge_dim: int = 0,
                aggregators: int = 4, scalers: int = 4,
                input_grad: bool = True) -> list:
    """PNAConv forward: the gathers of x_i and x_j; pre-MLP on [x_i, x_j,
    e_ij] -> f_in a message; mean, min, max and the squares for std over
    incoming messages, one pass each, the degree from the index alone;
    scalers; post-MLP on [x, scalers x aggregators x f_in]; final linear.
    ``input_grad`` is False for the first layer, whose gathers read the raw
    input and have no backward."""
    return [
        flops.gather(nodes, edges, f_in, grad=input_grad),  # x_i
        flops.gather(nodes, edges, f_in, grad=input_grad),  # x_j
        flops.dense(edges, 2 * f_in + edge_dim, f_in),
        # std takes the squares too: 5 passes of operations in all, 4 of bytes
        # (the squares are made on the way in).
        *(
            flops.segment_reduce(edges, nodes, f_in, ops=ops * edges * f_in)
            for ops in [1] * (aggregators - 1) + [2]
        ),
        flops.part(0, flops.B * (edges + nodes), 0, scope="agg"),  # the degree
        flops.part(  # every aggregator under every scaler
            scalers * aggregators * nodes * f_in,
            flops.B * (1 + scalers) * aggregators * nodes * f_in,
        ),
        flops.dense(nodes, (1 + scalers * aggregators) * f_in, f_out),
        flops.dense(nodes, f_out, f_out),
    ]


def counts(arch: dict, nodes: int, edges: int):
    hidden = arch["hidden_dim"]
    widths = [arch["input_dim"]] + [hidden] * arch["num_conv_layers"]
    parts = []
    for li, (a, b) in enumerate(zip(widths[:-1], widths[1:])):
        parts += conv_counts(
            nodes, edges, a, b, arch.get("edge_dim") or 0, input_grad=li > 0
        )
        parts.append(flops.batch_norm(nodes, b))
    return parts, hidden
