"""Operations and bytes the algorithm needs, from real shapes.

Counted from the REAL node, edge and graph counts of the batches a window
ran and the configuration's widths -- not from padded shapes and not from
XLA's ``cost_analysis``: work done on padding rows, recomputation and layout
copies are the system's choices and do not count as useful work. A multiply-
add is two operations. Elementwise work is counted at one operation an
element and pass. Bytes are the float32 traffic of a plain implementation
that reads each operand once and writes each result once; they bound the
memory roof from below and are used for nothing else yet.

The backward pass of a matmul costs twice its forward (a gradient for the
input and one for the weight), so a train step is three forwards; the first
conv layer's input needs no gradient, which is ignored (its width is the
input feature width, a handful).
"""

from __future__ import annotations

B = 4  # bytes a float32


def _dense(rows: int, fan_in: int, fan_out: int) -> dict:
    return {
        "ops": 2 * rows * fan_in * fan_out + rows * fan_out,
        "bytes": B * (rows * fan_in + fan_in * fan_out + rows * fan_out),
    }


def _add(*parts: dict) -> dict:
    return {
        "ops": sum(p["ops"] for p in parts),
        "bytes": sum(p["bytes"] for p in parts),
    }


def _mlp(rows: int, dims) -> dict:
    return _add(*(_dense(rows, a, b) for a, b in zip(dims[:-1], dims[1:])))


def pna_conv(nodes: int, edges: int, f_in: int, f_out: int, edge_dim: int = 0,
             aggregators: int = 4, scalers: int = 4) -> dict:
    """PNAConv forward: pre-MLP on [x_i, x_j, e_ij] -> f_in a message; mean,
    min, max, std over incoming messages; scalers; post-MLP on
    [x, scalers x aggregators x f_in]; final linear."""
    pre = _dense(edges, 2 * f_in + edge_dim, f_in)
    # gather of x_i and x_j (read 2 E f), then per aggregator one pass over
    # the messages (std takes the squares too: 5 passes in all).
    agg = {
        "ops": (aggregators + 1) * edges * f_in
        + scalers * aggregators * nodes * f_in,
        "bytes": B * (
            2 * edges * f_in + edges * f_in
            + (1 + scalers) * aggregators * nodes * f_in
        ),
    }
    post = _dense(nodes, (1 + scalers * aggregators) * f_in, f_out)
    lin = _dense(nodes, f_out, f_out)
    return _add(pre, agg, post, lin)


def gatv2_conv(nodes: int, edges: int, f_in: int, f_head: int, heads: int) -> dict:
    """GATv2Conv forward with the self-loop term: two projections to
    heads x f_head, per edge (and per node, for the loop) add + leaky_relu +
    dot with the attention vector, softmax, weighted sum of sources."""
    w = heads * f_head
    proj = _add(_dense(nodes, f_in, w), _dense(nodes, f_in, w))
    terms = edges + nodes  # incoming edges and the self loop
    attn = {
        # add, leaky_relu, dot (2), then message multiply and accumulate
        "ops": terms * w * 6 + terms * heads * 5,
        "bytes": B * (2 * terms * w + terms * w + 3 * terms * heads + nodes * w),
    }
    return _add(proj, attn)


def _batch_norm(rows: int, width: int) -> dict:
    return {"ops": 4 * rows * width, "bytes": B * 2 * rows * width}


def forward(arch: dict, nodes: int, edges: int, graphs: int) -> dict:
    """One forward pass of the stack ``arch`` (the completed ``Architecture``
    block) over ``nodes``/``edges``/``graphs`` real rows."""
    kind = arch["model_type"]
    hidden, depth = arch["hidden_dim"], arch["num_conv_layers"]
    parts = []
    if kind == "PNA":
        widths = [arch["input_dim"]] + [hidden] * depth
        for a, b in zip(widths[:-1], widths[1:]):
            parts += [
                pna_conv(nodes, edges, a, b, arch.get("edge_dim") or 0),
                _batch_norm(nodes, b),
            ]
        enc = hidden
    elif kind == "GAT":
        heads = 6  # fixed by the reference (create.py:112-114) and the program
        f_in = arch["input_dim"]
        for li in range(depth):
            last = li == depth - 1
            parts.append(gatv2_conv(nodes, edges, f_in, hidden, heads))
            out = hidden if last else hidden * heads
            parts.append(_batch_norm(nodes, out))
            f_in = out
        enc = hidden
    else:
        raise NotImplementedError(f"no operation count for {kind} yet")
    parts.append({"ops": nodes * enc, "bytes": B * (nodes * enc + graphs * enc)})
    heads_cfg = arch["output_heads"]
    for head_kind, dim in zip(arch["output_type"], arch["output_dim"]):
        if head_kind == "graph":
            g = heads_cfg["graph"]
            shared = [enc] + [g["dim_sharedlayers"]] * g["num_sharedlayers"]
            own = [shared[-1]] + list(g["dim_headlayers"][: g["num_headlayers"]]) + [dim]
            parts += [_mlp(graphs, shared), _mlp(graphs, own)]
        else:
            nd = heads_cfg["node"]
            parts.append(
                _mlp(nodes, [enc] + list(nd["dim_headlayers"][: nd["num_headlayers"]]) + [dim])
            )
    return _add(*parts)


def train_step(arch: dict, nodes: int, edges: int, graphs: int) -> dict:
    """Forward and backward: three forwards (see the module docstring)."""
    f = forward(arch, nodes, edges, graphs)
    return {"ops": 3 * f["ops"], "bytes": 3 * f["bytes"]}
