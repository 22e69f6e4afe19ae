"""The plain reference: the forward pass of a message-passing stack with its
heads in straightforward ``jax.numpy``, float32, matmuls at
``jax.default_matmul_precision("highest")``, one graph at a time over its
edge list as the sample holds it (any order), aggregating with
``x.at[receivers].add/min/max``. No kernels, no padding, no sorted-edge
contract, no masks: nothing here imports the program's ``ops/`` or
``models/``. It reads the program's parameter tree (names as flax made them)
and the ``HydraGNN`` module's static fields, and nothing else of the program.

The encoder of a model family is ``graftbench/families/<model_type>.py``'s
``encode`` (its conv's equations and departures are written there); what
every family shares is here, as ``models/base.py`` shares it.

Equations (reference HydraGNN, ``hydragnn/models/Base.py`` forward):
  encoder   x <- relu(BN_eval(conv(x)))            per conv layer
            (``conv_stack``; a family whose state is more than one array
            writes its own loop)
  pool      g  = mean over the graph's nodes
  graph head  MLP_head(relu-MLP_shared(g));  node head  MLP(x) per node
Departures from the reference's equations, each as the program has them:
  * BatchNorm in evaluation mode (running mean and variance).
  * The graph-shared MLP has a ReLU after every layer ("framework" layout;
    the reference's Sequential has none between its Linears).
  * No dropout: the forward is the evaluation forward.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from graftbench import families

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


def dense(p, x):
    return x @ p["kernel"] + p["bias"]


def mlp(p, x, final_relu=False):
    n = len(p)
    for i in range(n):
        x = dense(p[f"dense_{i}"], x)
        if i < n - 1 or final_relu:
            x = jax.nn.relu(x)
    return x


def bn_eval(p, stats, x, eps=1e-5):
    return (x - stats["mean"]) / jnp.sqrt(stats["var"] + eps) * p["scale"] + p["bias"]


def conv_stack(params, stats, x, conv):
    """The loop the classic families share: ``x <- relu(BN_eval(conv(x)))``
    over the ``conv_<i>`` layers, ``conv(p, x, layer index, depth)``."""
    depth = sum(k.startswith("conv_") for k in params)
    for li in range(depth):
        c = conv(params[f"conv_{li}"], x, li, depth)
        x = jax.nn.relu(bn_eval(params[f"bn_{li}"], stats[f"bn_{li}"], c))
    return x


def tolerance(model_type: str):
    """(ATOL, RTOL) that decide ``correct`` for a family: its own where its
    file gives them with their reason, else the ones above."""
    family = families.load(model_type)
    return getattr(family, "ATOL", ATOL), getattr(family, "RTOL", RTOL)


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
    family = families.load(model.conv_type)

    def graph_forward(params, stats, graph):
        x = family.encode(model, params, stats, graph)
        pooled = x.mean(axis=0)
        outs = []
        for h, head_kind in enumerate(model.output_type):
            if head_kind == "graph":
                shared = mlp(params["graph_shared"], pooled, final_relu=True)
                outs.append(mlp(params[f"head_{h}"], shared))
            else:
                outs.append(mlp(params[f"head_{h}"]["mlp"], x))
        return outs

    def f32(a):
        return None if a is None else np.asarray(a, np.float32)

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
            graph = {
                "x": f32(s.x), "pos": f32(s.pos), "send": send, "recv": recv,
                "edge_attr": f32(s.edge_attr),
            }
            results.append([np.asarray(o) for o in run(params, stats, graph)])
    return results
