"""CSR batch contract (PR 7): collation-computed row pointers end to end.

Covers: row_ptr/graph_ptr emission + validation (graphs/csr.py), bit-exact
precomputed-boundary vs searchsorted segment ops, the packed+shuffled+
quarantined loader property (receivers always non-decreasing, row_ptr always
consistent), zero in-step searchsorted via the trace spy, GAT's
self-loop-as-self-term parity against the reference concat formulation, the
debug-mode layout assertion hook,
and the check_config sorted-family / CSR-shape rejections."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import flax.linen as nn
import jax
import jax.numpy as jnp

from hydragnn_tpu.graphs.collate import GraphArena, collate_graphs
from hydragnn_tpu.graphs.csr import build_row_ptr, validate_csr
from hydragnn_tpu.graphs.sample import GraphSample
from hydragnn_tpu.ops import aggregate as agg
from hydragnn_tpu.ops import segment as seg
from hydragnn_tpu.ops import segment_sorted as srt
from tests.conftest import forward


def _random_graphs(rng, count=6, fdim=3, edge_dim=None, target=True):
    graphs = []
    for _ in range(count):
        n = int(rng.integers(3, 9))
        e = int(rng.integers(4, 14))
        ei = np.stack([
            rng.integers(0, n, e).astype(np.int64),
            rng.integers(0, n, e).astype(np.int64),
        ])
        x = rng.normal(size=(n, fdim)).astype(np.float32)
        graphs.append(
            GraphSample(
                x=x,
                pos=np.zeros((n, 3), np.float32),
                y=np.asarray([x.sum()], np.float32) if target else None,
                y_loc=np.array([0, 1], np.int64) if target else None,
                edge_index=ei,
                edge_attr=rng.normal(size=(e, edge_dim)).astype(np.float32)
                if edge_dim
                else None,
            )
        )
    return graphs


# ----------------------------------------------------------------- emission
def pytest_collate_emits_valid_csr():
    rng = np.random.default_rng(0)
    batch = collate_graphs(_random_graphs(rng), ["graph"], [1])
    assert batch.row_ptr is not None and batch.graph_ptr is not None
    assert batch.row_ptr.shape == (batch.num_nodes_pad + 1,)
    assert batch.graph_ptr.shape == (batch.num_graphs_pad + 1,)
    validate_csr(
        np.asarray(batch.receivers), np.asarray(batch.row_ptr),
        batch.num_nodes_pad,
    )
    validate_csr(
        np.asarray(batch.node_graph), np.asarray(batch.graph_ptr),
        batch.num_graphs_pad, what="node_graph",
    )
    # The pointers ARE the searchsorted boundaries (bit-exact consumption
    # depends on this identity).
    np.testing.assert_array_equal(
        np.asarray(batch.row_ptr),
        np.searchsorted(
            np.asarray(batch.receivers), np.arange(batch.num_nodes_pad + 1)
        ),
    )


def pytest_validate_csr_rejects_broken_layouts():
    ids = np.array([0, 0, 1, 3], np.int32)
    rp = build_row_ptr(ids, 5)
    validate_csr(ids, rp, 5)  # sanity: the good case passes
    with pytest.raises(ValueError, match="shape"):
        validate_csr(ids, rp[:-1], 5)
    with pytest.raises(ValueError, match="endpoints"):
        validate_csr(ids, rp + 1, 5)
    bad = rp.copy()
    bad[2] = 0  # break agreement (still monotone-ish edge case caught)
    with pytest.raises(ValueError):
        validate_csr(ids, bad, 5)
    with pytest.raises(ValueError, match="not sorted"):
        unsorted = np.array([1, 0, 2, 3], np.int32)
        validate_csr(unsorted, build_row_ptr(np.sort(unsorted), 5), 5)


# ------------------------------------------------------------- bit-exactness
def pytest_precomputed_boundaries_bit_exact_vs_searchsorted():
    """segment_sum_count_csr (collation's row_ptr) must be BIT-IDENTICAL to
    segment_sum_count_sorted (in-step searchsorted) — same math after the
    boundary derivation, so promoting the contract cannot move a single
    ulp anywhere in training."""
    rng = np.random.default_rng(1)
    e, n, f = 900, 200, 7
    ids = np.sort(rng.integers(0, n - 1, e)).astype(np.int32)
    ids[-80:] = n - 1  # padding tail targeting the top segment
    data = np.where(
        np.arange(e)[:, None] < e - 80,
        (rng.normal(size=(e, f)) * 2 + 1).astype(np.float32),
        0.0,
    ).astype(np.float32)
    row_ptr = jnp.asarray(build_row_ptr(ids, n))
    t_ss, c_ss = jax.jit(
        lambda d, i: srt.segment_sum_count_sorted(d, i, n)
    )(jnp.asarray(data), jnp.asarray(ids))
    t_rp, c_rp = jax.jit(
        lambda d, rp, i: srt.segment_sum_count_csr(d, rp, i, n)
    )(jnp.asarray(data), row_ptr, jnp.asarray(ids))
    np.testing.assert_array_equal(np.asarray(t_ss), np.asarray(t_rp))
    np.testing.assert_array_equal(np.asarray(c_ss), np.asarray(c_rp))

    # Gradients ride the same gather backward.
    g_ss = jax.grad(
        lambda d: srt.segment_sum_count_sorted(d, jnp.asarray(ids), n)[0].sum()
    )(jnp.asarray(data))
    g_rp = jax.grad(
        lambda d: srt.segment_sum_count_csr(
            d, row_ptr, jnp.asarray(ids), n
        )[0].sum()
    )(jnp.asarray(data))
    np.testing.assert_array_equal(np.asarray(g_ss), np.asarray(g_rp))


def pytest_model_forward_bit_exact_with_and_without_row_ptr(monkeypatch):
    """A full PNA forward on a collated batch: sorted path with the CSR
    boundaries == sorted path with in-step searchsorted, bit-exact."""
    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    from hydragnn_tpu.models.create import create_model, init_model_variables

    rng = np.random.default_rng(2)
    batch = collate_graphs(
        _random_graphs(rng, edge_dim=2), ["graph"], [1], edge_dim=2
    )
    model = create_model(
        model_type="PNA", input_dim=3, hidden_dim=8, output_dim=[1],
        output_type=["graph"],
        output_heads={"graph": {"num_sharedlayers": 1, "dim_sharedlayers": 8,
                                "num_headlayers": 1, "dim_headlayers": [8]}},
        task_weights=[1.0], num_conv_layers=2, pna_deg=[0, 1, 2, 1],
        edge_dim=2,
    )
    variables = init_model_variables(model, batch)
    with_ptr = forward(model, variables, batch)
    without_ptr = forward(model, variables, batch.replace(row_ptr=None, graph_ptr=None))
    # Op-level the two variants are bit-exact (previous test); whole-program
    # XLA fusion may differ between the traces, so allow ulp-level noise.
    for a, b in zip(with_ptr, without_ptr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6
        )


# ------------------------------------------------------------------ trace spy
def pytest_compiled_step_runs_zero_searchsorted(monkeypatch):
    """Acceptance gate: with row_ptr present, tracing the full guarded train
    step under the sorted path performs ZERO searchsorted boundary
    derivations (the module-level trace spy counts them)."""
    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    import optax

    from hydragnn_tpu.models.create import create_model, init_model_variables
    from hydragnn_tpu.train.trainer import create_train_state, make_train_step

    rng = np.random.default_rng(3)
    batch = collate_graphs(_random_graphs(rng, count=8), ["graph"], [1])
    model = create_model(
        model_type="SAGE", input_dim=3, hidden_dim=8, output_dim=[1],
        output_type=["graph"],
        output_heads={"graph": {"num_sharedlayers": 1, "dim_sharedlayers": 8,
                                "num_headlayers": 1, "dim_headlayers": [8]}},
        task_weights=[1.0], num_conv_layers=2,
    )
    variables = init_model_variables(model, batch)
    state = create_train_state(model, variables, optax.adamw(1e-3))
    step = make_train_step(model, optax.adamw(1e-3), donate=False)
    before = srt.searchsorted_calls()
    state, metrics = step(state, batch, jax.random.PRNGKey(0))
    jax.block_until_ready(metrics["loss"])
    assert srt.searchsorted_calls() == before, (
        "compiled step still derives segment boundaries with searchsorted "
        "despite row_ptr being present"
    )
    # Control: the spy DOES fire when the boundaries are absent.
    step(state, batch.replace(row_ptr=None, graph_ptr=None),
         jax.random.PRNGKey(0))
    assert srt.searchsorted_calls() > before


# ------------------------------------------------- loader composition property
def pytest_packed_shuffled_quarantined_streams_keep_csr_contract():
    """Property: packing x shuffling x quarantine never breaks the layout —
    every yielded batch has non-decreasing receivers and row_ptr equal to
    the searchsorted boundaries (the composition of packing.py's FFD bins
    with the arena's per-graph edge sort)."""
    from hydragnn_tpu.preprocess.dataloader import GraphDataLoader

    rng = np.random.default_rng(4)
    graphs = _random_graphs(rng, count=40)
    # Poison a few samples: the quarantine path must not disturb the layout.
    graphs[7].x = graphs[7].x.copy()
    graphs[7].x[0, 0] = np.nan
    graphs[23].edge_index = np.array([[0, 99], [0, 0]], np.int64)
    loader = GraphDataLoader(
        graphs, batch_size=4, shuffle=True, seed=11, head_types=["graph"],
        head_dims=[1], packing=True, ladder_step="mult64", skip_budget=4,
        num_buckets=2,
    )
    assert len(loader.quarantined) == 2
    seen = 0
    for epoch in range(2):
        loader.set_epoch(epoch)
        for batch in loader:
            recv = np.asarray(batch.receivers)
            assert (np.diff(recv) >= 0).all()
            validate_csr(recv, np.asarray(batch.row_ptr), batch.num_nodes_pad)
            validate_csr(
                np.asarray(batch.node_graph), np.asarray(batch.graph_ptr),
                batch.num_graphs_pad, what="node_graph",
            )
            seen += 1
    assert seen > 4


# -------------------------------------------------------------- GAT self-term
def pytest_gat_self_term_parity_vs_reference_concat(monkeypatch):
    """GATv2 with self-loops as an explicit self-attention term must match
    the reference formulation (concatenate one identity edge per node, run
    the masked segment softmax over the widened edge array) on real rows —
    same parameters, train=False."""
    from hydragnn_tpu.models.convs import GATv2Conv

    rng = np.random.default_rng(5)
    batch = collate_graphs(_random_graphs(rng), ["graph"], [1])
    heads, f = 4, 6
    conv = GATv2Conv(out_dim=f, heads=heads, negative_slope=0.05)
    graph = (batch.senders, batch.receivers, None, batch.edge_mask, batch.node_mask)
    x = jnp.asarray(batch.node_features)
    n = x.shape[0]
    # Each side ONE program (the initializer, the conv, the reference).
    variables = jax.jit(
        lambda x: conv.init(jax.random.PRNGKey(0), x, *graph, train=False)
    )(x)
    out_new = np.asarray(jax.jit(
        lambda v, x: conv.apply(v, x, *graph, train=False, row_ptr=batch.row_ptr)
    )(variables, x))

    def reference(p, x):
        """The concat formulation, from the SAME parameters."""
        x_src = (x @ p["lin_src"]["kernel"] + p["lin_src"]["bias"]).reshape(
            n, heads, f
        )
        x_dst = (x @ p["lin_dst"]["kernel"] + p["lin_dst"]["bias"]).reshape(
            n, heads, f
        )
        s = jnp.concatenate([batch.senders, jnp.arange(n, dtype=jnp.int32)])
        r = jnp.concatenate([batch.receivers, jnp.arange(n, dtype=jnp.int32)])
        m = jnp.concatenate([batch.edge_mask, batch.node_mask])
        pre = nn.leaky_relu(x_src[s] + x_dst[r], 0.05)
        logits = jnp.einsum("ehf,hf->eh", pre, p["att"])
        alpha = seg.segment_softmax(logits, r, n, mask=m)
        msgs = jnp.where(m[:, None, None], x_src[s] * alpha[..., None], 0.0)
        return seg.segment_sum(msgs, r, n).reshape(n, heads * f) + p["bias"]

    out_ref = np.asarray(jax.jit(reference)(variables["params"], x))
    real = np.asarray(batch.node_mask)
    np.testing.assert_allclose(
        out_new[real], out_ref[real], rtol=2e-5, atol=2e-5
    )


def pytest_gat_isolated_node_keeps_self_attention():
    """An isolated node (zero unmasked incoming edges) must keep
    alpha_self == 1 for ANY self-logit magnitude — the concat formulation's
    behavior. Regression: a 0.0 empty-segment fill in the softmax shift made
    exp(logit_self) underflow for strongly negative self logits and silently
    dropped the self message. Features are scaled so some heads' self
    logits land far below the f32 exp underflow threshold (~-88)."""
    from hydragnn_tpu.models.convs import GATv2Conv

    rng = np.random.default_rng(12)
    n_pad, e_pad, heads, f = 4, 8, 4, 5
    x = jnp.asarray(rng.normal(size=(n_pad, 3)).astype(np.float32) * 1e4)
    senders = jnp.full((e_pad,), n_pad - 1, jnp.int32)
    receivers = jnp.full((e_pad,), n_pad - 1, jnp.int32)
    edge_mask = jnp.zeros((e_pad,), bool)
    node_mask = jnp.asarray([True, True, False, False])

    conv = GATv2Conv(out_dim=f, heads=heads, negative_slope=0.05)
    graph = (senders, receivers, None, edge_mask, node_mask)
    variables = jax.jit(
        lambda x: conv.init(jax.random.PRNGKey(1), x, *graph, train=False)
    )(x)

    def by_hand(p, x):
        """(every node's self logit, its source row + bias: what a node whose
        ``alpha_self`` is 1 keeps)."""
        x_src = (x @ p["lin_src"]["kernel"] + p["lin_src"]["bias"]).reshape(
            n_pad, heads, f
        )
        x_dst = (x @ p["lin_dst"]["kernel"] + p["lin_dst"]["bias"]).reshape(
            n_pad, heads, f
        )
        logit_self = jnp.einsum(
            "nhf,hf->nh", nn.leaky_relu(x_src + x_dst, 0.05), p["att"]
        )
        return logit_self, x_src.reshape(n_pad, heads * f) + p["bias"]

    logit_self, want = jax.jit(by_hand)(variables["params"], x)
    # The scenario must actually cover the underflow regime on a real node.
    assert float(np.asarray(logit_self)[:2].min()) < -100.0

    out = np.asarray(
        jax.jit(lambda v, x: conv.apply(v, x, *graph, train=False))(variables, x)
    )
    # alpha_self == 1 everywhere real ⇒ out = x_src (flattened) + bias.
    np.testing.assert_allclose(out[:2], np.asarray(want)[:2], rtol=1e-6, atol=1e-6)


class _Rank3GATv2Conv(nn.Module):
    """The formulation ``GATv2Conv`` had up to PR 23, as a module of the same
    parameter tree: rows as ``[·, h, f]`` arrays, the logits an einsum, and
    ``x_src[senders]`` gathered a second time for the messages. The flat
    ``GATv2Conv`` is held to it below."""

    out_dim: int
    heads: int = 6
    negative_slope: float = 0.05
    concat: bool = True
    dropout: float = 0.25

    @nn.compact
    def __call__(self, x, senders, receivers, edge_attr, edge_mask, node_mask, train=False, row_ptr=None):
        n = x.shape[0]
        h, f = self.heads, self.out_dim
        x_src = nn.Dense(h * f, name="lin_src")(x).reshape(n, h, f)
        x_dst = nn.Dense(h * f, name="lin_dst")(x).reshape(n, h, f)
        att = self.param("att", nn.initializers.lecun_normal(), (h, f))
        x_j, x_i = x_src[senders], x_dst[receivers]
        pre = nn.leaky_relu(x_j + x_i, self.negative_slope)
        logits = jnp.einsum("ehf,hf->eh", pre, att)
        pre_self = nn.leaky_relu(x_src + x_dst, self.negative_slope)
        logit_self = jnp.einsum("nhf,hf->nh", pre_self, att)
        edge_max = seg.segment_max(logits, receivers, n, mask=edge_mask, fill=-1e9)
        m = jax.lax.stop_gradient(jnp.maximum(edge_max, logit_self))
        exp_e = jnp.where(edge_mask[:, None], jnp.exp(logits - m[receivers]), 0.0)
        exp_self = jnp.where(node_mask[:, None], jnp.exp(logit_self - m), 0.0)
        denom = agg.fused_segment_sum(
            exp_e, receivers, n, mask=edge_mask, row_ptr=row_ptr,
        ) + exp_self
        alpha = exp_e / jnp.maximum(denom[receivers], 1e-16)
        alpha_self = exp_self / jnp.maximum(denom, 1e-16)
        if train and self.dropout > 0.0:
            keep = jax.random.bernoulli(
                self.make_rng("dropout"), 1.0 - self.dropout,
                (n + alpha.shape[0],) + alpha.shape[1:],
            )
            alpha = jnp.where(keep[n:], alpha / (1.0 - self.dropout), 0.0)
            alpha_self = jnp.where(keep[:n], alpha_self / (1.0 - self.dropout), 0.0)
        x_j = x_src[senders]
        msgs = jnp.where(edge_mask[:, None, None], x_j * alpha[..., None], 0.0)
        out = agg.fused_segment_sum(msgs, receivers, n, row_ptr=row_ptr)
        out = out + x_src * alpha_self[..., None]
        if self.concat:
            out = out.reshape(n, h * f)
            bias = self.param("bias", nn.initializers.zeros, (h * f,))
        else:
            out = out.mean(axis=1)
            bias = self.param("bias", nn.initializers.zeros, (f,))
        return out + bias


@pytest.mark.parametrize("train", [False, True], ids=["eval", "dropout"])
@pytest.mark.parametrize("concat", [True, False], ids=["concat", "headmean"])
@pytest.mark.parametrize("heads,f", [(6, 64), (6, 5)])
def pytest_gat_flat_rows_match_rank3_formulation(heads, f, concat, train):
    """``GATv2Conv`` keeps every row array flat ([N, h·f], [E, h·f]) and
    gathers each source once; the rank-3 formulation it replaced, from the
    SAME parameters and the same dropout key (the mask's shape (n + E, h) is
    unchanged, so the masks are identical), gives the same output and the
    same gradients with respect to ``x`` and every parameter, at lane-wide
    rows (6 × 64) and at the CI toys' narrow ones (6 × 5), on seeded graphs
    with isolated nodes and padding rows. float32 on the CPU, 1e-5."""
    from hydragnn_tpu.models.convs import GATv2Conv

    rng = np.random.default_rng(5)
    batch = collate_graphs(_random_graphs(rng, fdim=7), ["graph"], [1])
    real_n, real_e = np.asarray(batch.node_mask), np.asarray(batch.edge_mask)
    indegree = np.bincount(
        np.asarray(batch.receivers)[real_e], minlength=len(real_n)
    )
    assert (indegree[real_n] == 0).any(), "no isolated real node in the data"
    assert (~real_n).any() and (~real_e).any(), "no padding rows in the data"

    kw = dict(out_dim=f, heads=heads, negative_slope=0.05, concat=concat)
    flat, rank3 = GATv2Conv(**kw), _Rank3GATv2Conv(**kw)
    x = jnp.asarray(batch.node_features)
    graph = (batch.senders, batch.receivers, None, batch.edge_mask, batch.node_mask)
    # The initializers and each formulation's loss and gradients: one
    # program each.
    params = jax.jit(
        lambda x: flat.init(jax.random.PRNGKey(0), x, *graph, train=False)
    )(x)["params"]
    # A zero bias and its zero gradient would compare as equal whatever ran.
    params = dict(params, bias=jnp.asarray(
        rng.normal(size=params["bias"].shape).astype(np.float32)
    ))
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(
        jax.eval_shape(
            lambda x: rank3.init(jax.random.PRNGKey(0), x, *graph, train=False), x
        )["params"]
    )
    weight = jnp.asarray(
        rng.normal(size=(x.shape[0], heads * f if concat else f)).astype(np.float32)
    ) * real_n[:, None]

    def run(conv):
        def loss(params, x):
            out = conv.apply(
                {"params": params}, x, *graph, train=train,
                row_ptr=batch.row_ptr, rngs={"dropout": jax.random.PRNGKey(7)},
            )
            return (out * weight).sum(), out

        (_, out), grads = jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
        )(params, x)
        return out, grads

    out, (g_params, g_x) = run(flat)
    out_ref, (g_params_ref, g_x_ref) = run(rank3)

    def close(got, want, what):
        got, want = np.asarray(got), np.asarray(want)
        np.testing.assert_allclose(
            got, want, rtol=1e-5, atol=1e-5 * np.abs(want).max(), err_msg=what
        )

    close(out[real_n], out_ref[real_n], "output")
    close(g_x, g_x_ref, "d/dx")
    assert float(jnp.abs(g_x_ref).max()) > 0.0
    for name in ("lin_src", "lin_dst"):
        for leaf in ("kernel", "bias"):
            close(g_params[name][leaf], g_params_ref[name][leaf], f"d/d{name}.{leaf}")
    close(g_params["att"], g_params_ref["att"], "d/datt")
    close(g_params["bias"], g_params_ref["bias"], "d/dbias")


def pytest_gat_rides_sorted_path_with_zero_searchsorted(monkeypatch):
    """GAT (the historical sortedness breaker) now traces through the sorted
    path with precomputed boundaries: zero searchsorted derivations AND
    bit-identical outputs with/without row_ptr under the sorted gate."""
    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    from hydragnn_tpu.models.create import create_model, init_model_variables

    rng = np.random.default_rng(6)
    batch = collate_graphs(_random_graphs(rng), ["graph"], [1])
    model = create_model(
        model_type="GAT", input_dim=3, hidden_dim=4, output_dim=[1],
        output_type=["graph"],
        output_heads={"graph": {"num_sharedlayers": 1, "dim_sharedlayers": 8,
                                "num_headlayers": 1, "dim_headlayers": [8]}},
        task_weights=[1.0], num_conv_layers=2,
    )
    variables = init_model_variables(model, batch)
    before = srt.searchsorted_calls()
    out = forward(model, variables, batch)
    jax.block_until_ready(out)
    assert srt.searchsorted_calls() == before
    out_stripped = forward(model, variables, batch.replace(row_ptr=None, graph_ptr=None))
    # The segment op itself is bit-exact either way (the op-level test
    # above); at whole-program level XLA may fuse the two traces differently
    # (searchsorted present vs absent), so the model comparison allows ulp
    # noise.
    for a, b in zip(out, out_stripped):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6
        )


# ------------------------------------------------------------ layout assertion
def pytest_debug_layout_hook_fails_loudly_on_unsorted_ids(monkeypatch):
    """An actually-unsorted layout handed to ops/aggregate.py's sorted arm
    must fail loudly under HYDRAGNN_DEBUG_LAYOUT=1 instead of silently
    corrupting aggregation (and must stay silent on a valid layout)."""
    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    monkeypatch.setenv("HYDRAGNN_DEBUG_LAYOUT", "1")
    rng = np.random.default_rng(9)
    data = jnp.asarray(rng.normal(size=(64, 4)).astype(np.float32))
    good = jnp.asarray(np.sort(rng.integers(0, 10, 64)).astype(np.int32))
    bad = jnp.asarray(rng.permutation(np.asarray(good)).astype(np.int32))

    total = jax.jit(lambda d, ids: agg.fused_segment_sum(d, ids, 10))
    jax.block_until_ready(total(data, good))  # valid layout: no error

    with pytest.raises(Exception, match="sorted-layout contract"):
        jax.block_until_ready(total(data, bad))


def pytest_debug_layout_hook_off_by_default(monkeypatch):
    monkeypatch.delenv("HYDRAGNN_DEBUG_LAYOUT", raising=False)
    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    rng = np.random.default_rng(10)
    data = jnp.asarray(rng.normal(size=(32, 3)).astype(np.float32))
    bad = jnp.asarray(rng.integers(0, 8, 32).astype(np.int32))
    # Off by default: garbage in, garbage out, but NO runtime callback cost.
    out = jax.jit(lambda d, ids: agg.fused_segment_sum(d, ids, 8))(data, bad)
    jax.block_until_ready(out)


# ------------------------------------------------------------------ contracts
def pytest_check_config_rejects_unregistered_sorted_family(monkeypatch):
    """A conv family outside SORTED_PATH_FAMILIES would silently fall back
    to the unsorted scatter path on TPU — check_config rejects it up front
    (unless the sorted path is explicitly pinned off)."""
    import json

    from hydragnn_tpu.analysis.contracts import (
        ConfigContractError,
        check_config,
    )
    from hydragnn_tpu.models import families

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "tests/inputs/ci.json")) as fh:
        config = json.load(fh)
    config["NeuralNetwork"]["Architecture"]["model_type"] = "SAGE"
    check_config(config, deep=False)  # registered family: fine

    monkeypatch.setattr(
        families, "SORTED_PATH_FAMILIES", frozenset({"GIN"}), raising=True
    )
    monkeypatch.delenv("HYDRAGNN_SEGMENT_SORTED", raising=False)
    with pytest.raises(ConfigContractError, match="SORTED_PATH_FAMILIES"):
        check_config(config, deep=False)
    # Explicit opt-out: scatter path is intended, config passes.
    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "0")
    check_config(config, deep=False)


def pytest_example_batch_csr_validated_in_eval_shape_gate(monkeypatch):
    """The eval_shape gate validates the example batch's CSR arrays — a
    layout regression in collation fails check-config, not a training run."""
    import json

    from hydragnn_tpu.analysis import contracts
    from hydragnn_tpu.analysis.contracts import (
        ConfigContractError,
        check_config,
    )
    from hydragnn_tpu.models import create as mcreate

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "tests/inputs/ci.json")) as fh:
        config = json.load(fh)
    orig = mcreate.make_example_batch

    def broken(*args, **kwargs):
        b = orig(*args, **kwargs)
        rp = np.asarray(b.row_ptr).copy()
        rp[1] = rp[-1] + 5  # non-monotone, disagrees with receivers
        return b.replace(row_ptr=jnp.asarray(rp))

    monkeypatch.setattr(mcreate, "make_example_batch", broken)
    contracts._SHAPE_CACHE.clear()
    try:
        with pytest.raises(ConfigContractError, match="CSR contract"):
            check_config(config)
    finally:
        contracts._SHAPE_CACHE.clear()
