"""The plain reference: the forward pass of the PNA and GATv2 stacks with
their heads in straightforward ``jax.numpy``, float32, matmuls at
``jax.default_matmul_precision("highest")``, one graph at a time over its
edge list as the sample holds it (any order), aggregating with
``x.at[receivers].add/min/max``. No kernels, no padding, no sorted-edge
contract, no masks: nothing here imports the program's ``ops/`` or
``models/``. It reads the program's parameter tree (names as flax made them)
and the ``HydraGNN`` module's static fields, and nothing else of the program.

Equations (reference HydraGNN, ``hydragnn/models/Base.py`` forward):
  encoder   x <- relu(BN_eval(conv(x)))            per conv layer
  pool      g  = mean over the graph's nodes
  graph head  MLP_head(relu-MLP_shared(g));  node head  MLP(x) per node
PNA conv (PyG PNAConv, towers 1, pre/post layers 1, no edge features here):
  m_ij = W_pre [x_i, x_j (, e_ij)];  A = [mean, min, max, std]_j m_ij,
  std = sqrt(relu(E[m^2] - E[m]^2) + 1e-5); scalers identity,
  log(d+1)/avg_log, avg_log/log(d+1), d/avg_lin with d = max(deg, 1);
  out = W_lin W_post [x_i, scaler x aggregator block]
GATv2 conv (PyG GATv2Conv, self-loops added, heads concatenated except in
the last layer, where they are averaged):
  e_ij = a . leaky_relu(W_src x_j + W_dst x_i); alpha = softmax_j over
  N(i) + {i}; out_i = sum_j alpha_ij W_src x_j + bias
Departures from the reference's equations, each as the program has them:
  * BatchNorm in evaluation mode (running mean and variance).
  * Min and max of a node without neighbours are 0 (PyG gives 0 too).
  * The attention's self loop is one more edge of the list here (PyG's
    formulation); the program adds a dense self term instead.
  * The graph-shared MLP has a ReLU after every layer ("framework" layout;
    the reference's Sequential has none between its Linears).
  * No dropout: the forward is the evaluation forward.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Program against reference, per output element: |a - b| <= ATOL + RTOL |b|.
# The program multiplies float32 operands in bf16 on the TPU (2^-8 relative an
# operand, three conv layers deep, contractions up to 4,352 wide) and sums
# messages in another order. Measured on the chip (my chip runs, PR 22): 1.9e-5
# to 5.3e-4 over 34 runs of the three train cells, seeds 3 and 10-15; up to
# 2.8e-3 on lattices of 288-768 atoms (12 runs of a serving rehearsal that is
# no cell). A mis-wired head, a missing scaler or the wrong aggregator is off by O(0.1-1)
# on outputs of O(1); activations kept in bf16 instead of float32 are off by
# about 1e-2. The tolerance is ten times the worst seen in a train cell.
ATOL = RTOL = 5e-3


def _dense(p, x):
    return x @ p["kernel"] + p["bias"]


def _mlp(p, x, final_relu=False):
    n = len(p)
    for i in range(n):
        x = _dense(p[f"dense_{i}"], x)
        if i < n - 1 or final_relu:
            x = jax.nn.relu(x)
    return x


def _bn_eval(p, stats, x, eps=1e-5):
    return (x - stats["mean"]) / jnp.sqrt(stats["var"] + eps) * p["scale"] + p["bias"]


def _pna_conv(p, x, send, recv, edge_attr, avg_log, avg_lin):
    n, f = x.shape
    z = [x[recv], x[send]] + ([edge_attr] if edge_attr is not None else [])
    m = _dense(p["pre_nn"], jnp.concatenate(z, axis=-1))  # [E, f]
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
    out = _dense(p["post_nn"], jnp.concatenate([x, scaled], axis=-1))
    return _dense(p["lin"], out)


def _gatv2_conv(p, x, send, recv, heads, slope, concat):
    n = x.shape[0]
    f = p["att"].shape[1]
    src = _dense(p["lin_src"], x).reshape(n, heads, f)
    dst = _dense(p["lin_dst"], x).reshape(n, heads, f)
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


def _host_device():
    """The reference is tiny (one graph at a time): run it on the host's CPU
    backend where there is one, so it costs the chip no compile."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return jax.devices()[0]


def forward(model, variables, samples):
    """Per graph, the list of per-head outputs ([dim] for a graph head,
    [n, dim] for a node head) of ``model`` with ``variables`` on ``samples``
    (host ``GraphSample``s as the program's loaders hold them)."""
    kind = model.conv_type
    if kind not in ("PNA", "GAT"):
        raise NotImplementedError(f"no plain reference for {kind} yet")
    layers = sorted(
        (k for k in variables["params"] if k.startswith("conv_")),
        key=lambda k: int(k[5:]),
    )

    def graph_forward(params, stats, x, send, recv, edge_attr):
        for li, name in enumerate(layers):
            if kind == "PNA":
                c = _pna_conv(
                    params[name], x, send, recv, edge_attr,
                    model.pna_deg_avg_log, model.pna_deg_avg_lin,
                )
            else:
                c = _gatv2_conv(
                    params[name], x, send, recv, model.gat_heads,
                    model.gat_negative_slope, concat=li < len(layers) - 1,
                )
            x = jax.nn.relu(_bn_eval(params[f"bn_{li}"], stats[f"bn_{li}"], c))
        pooled = x.mean(axis=0)
        outs = []
        for h, head_kind in enumerate(model.output_type):
            if head_kind == "graph":
                shared = _mlp(params["graph_shared"], pooled, final_relu=True)
                outs.append(_mlp(params[f"head_{h}"], shared))
            else:
                outs.append(_mlp(params[f"head_{h}"]["mlp"], x))
        return outs

    results = []
    with jax.default_device(_host_device()), jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: jnp.asarray(np.asarray(a)), dict(variables["params"])
        )
        stats = jax.tree_util.tree_map(
            lambda a: jnp.asarray(np.asarray(a)),
            dict(variables.get("batch_stats", {})),
        )
        run = jax.jit(graph_forward)  # one small compile a graph shape
        for s in samples:
            send, recv = np.asarray(s.edge_index, np.int32)
            edge_attr = None
            if kind == "PNA" and model.use_edge_attr:
                edge_attr = np.asarray(s.edge_attr, np.float32)
            outs = run(
                params, stats, np.asarray(s.x, np.float32), send, recv, edge_attr
            )
            results.append([np.asarray(o) for o in outs])
    return results
