"""Numerical parity of the torch-checkpoint importer, per conv family.

The round-trip test (test_torch_import.py) checks placement and that the
imported model RUNS; it cannot catch a wrong assumption about PyG's tensor
semantics (GATv2 lin_l/lin_r src-vs-dst roles, PNA scaler-major concat order,
MFC lins_l-vs-lins_r bias carrier, a missed transpose). This file can: each
test implements the REFERENCE conv's forward in plain torch/numpy directly
from PyG's documented semantics (the modules the reference stacks build —
PNAStack.py:28-53, GATStack.py:35-46, SAGEStack/GINStack/MFCStack/CGCNNStack
→ PyG PNAConv/GATv2Conv/SAGEConv/GINConv/MFConv/CGConv; no torch_geometric
import needed), runs it on the synthesized state_dict's own tensors, maps the
same tensors through ``_map_conv``, and asserts the flax conv reproduces the
torch forward to fp32 tolerance.
"""

import numpy as np
import pytest

torch = pytest.importorskip("torch")

import jax

from hydragnn_tpu.models.convs import (
    CGConv,
    GATv2Conv,
    GINConv,
    MFCConv,
    PNAConv,
    SAGEConv,
    pna_degree_averages,
)
from hydragnn_tpu.utils.torch_import import _map_conv

from test_torch_import import EDGE, _family_conv_sd, _lin
from tests.conftest import forward

N, F_IN, F_OUT, HEADS, MAX_DEG = 7, 3, 8, 6, 3

# Fixed edge list: every node has >= 2 incoming edges (degree-0/1 corner
# semantics differ across PyG versions and are not what this file locks).
SENDERS = np.array([1, 2, 0, 3, 0, 4, 1, 5, 2, 6, 3, 0, 4, 1, 5, 6, 6, 2], np.int32)
RECEIVERS = np.array([0, 0, 1, 1, 2, 2, 3, 3, 4, 4, 5, 5, 6, 6, 0, 1, 3, 5], np.int32)
E = len(SENDERS)


def _graph(gen):
    x = gen.normal(size=(N, F_IN)).astype(np.float32)
    e = gen.normal(size=(E, EDGE)).astype(np.float32)
    return x, e


def _pfx(sd):
    """_map_conv addresses tensors as f"{tprefix}.{name}"."""
    return {f"c.{k}": v for k, v in sd.items()}


def _np_sd(sd):
    return {k: np.asarray(v.detach().numpy(), np.float32) for k, v in sd.items()}


def _apply_flax(conv, mapped, x, edge_attr):
    masks = (np.ones(E, bool), np.ones(N, bool))
    return np.asarray(
        conv.apply(
            {"params": mapped},
            x,
            SENDERS,
            RECEIVERS,
            edge_attr,
            *masks,
            train=False,
        )
    )


def _template(conv, x, edge_attr):
    v = conv.init(
        jax.random.PRNGKey(0),
        x,
        SENDERS,
        RECEIVERS,
        edge_attr,
        np.ones(E, bool),
        np.ones(N, bool),
        train=False,
    )
    return jax.tree_util.tree_map(np.asarray, dict(v["params"]))


def _scatter_sum(src, index, n):
    out = torch.zeros((n,) + src.shape[1:], dtype=src.dtype)
    return out.index_add(0, index, src)


def _degree(index, n):
    return _scatter_sum(torch.ones(len(index), 1), index, n)[:, 0]


def _lin_t(sd, name, x):
    y = x @ torch.tensor(sd[f"{name}.weight"]).T
    if f"{name}.bias" in sd:
        y = y + torch.tensor(sd[f"{name}.bias"])
    return y


def _check(family, torch_out, flax_out):
    np.testing.assert_allclose(
        flax_out,
        torch_out.numpy(),
        rtol=2e-4,
        atol=2e-4,
        err_msg=f"{family}: flax forward diverges from the PyG-semantics "
        "torch forward on the imported weights",
    )


def pytest_numeric_parity_sage():
    gen = np.random.default_rng(11)
    x_np, _ = _graph(gen)
    sd = _np_sd(_family_conv_sd(gen, "SAGE", F_IN, F_OUT))

    # PyG SAGEConv(aggr='mean'): lin_l(mean_{j in N(i)} x_j) + lin_r(x_i).
    x = torch.tensor(x_np)
    deg = _degree(torch.tensor(RECEIVERS, dtype=torch.long), N).clamp(min=1.0)
    mean = _scatter_sum(x[SENDERS], torch.tensor(RECEIVERS, dtype=torch.long), N) / deg[:, None]
    ref = _lin_t(sd, "lin_l", mean) + _lin_t(sd, "lin_r", x)

    conv = SAGEConv(out_dim=F_OUT)
    mapped = _map_conv("SAGE", _pfx(sd), "c", _template(conv, x_np, None), set())
    _check("SAGE", ref, _apply_flax(conv, mapped, x_np, None))


def pytest_numeric_parity_gin():
    gen = np.random.default_rng(12)
    x_np, _ = _graph(gen)
    sd = _np_sd(_family_conv_sd(gen, "GIN", F_IN, F_OUT))
    # GIN needs square in/out on the skip term only when f_in == f_out in the
    # nn; the synthesized sd has nn.0: [F_OUT, F_IN], which is fine: the skip
    # (1+eps)x + sum happens in F_IN before the MLP.
    x = torch.tensor(x_np)
    agg = _scatter_sum(x[SENDERS], torch.tensor(RECEIVERS, dtype=torch.long), N)
    h = (1.0 + float(sd["eps"][0])) * x + agg
    ref = _lin_t(sd, "nn.2", torch.relu(_lin_t(sd, "nn.0", h)))

    conv = GINConv(out_dim=F_OUT)
    mapped = _map_conv("GIN", _pfx(sd), "c", _template(conv, x_np, None), set())
    _check("GIN", ref, _apply_flax(conv, mapped, x_np, None))


def pytest_numeric_parity_mfc():
    gen = np.random.default_rng(13)
    x_np, _ = _graph(gen)
    sd = _np_sd(_family_conv_sd(gen, "MFC", F_IN, F_OUT, max_deg=MAX_DEG))

    # PyG MFConv: deg-indexed Linear pair, lins_l (bias) on the neighbor SUM,
    # lins_r (bias=False) on the root; degree clamped to max_degree.
    x = torch.tensor(x_np)
    recv = torch.tensor(RECEIVERS, dtype=torch.long)
    agg = _scatter_sum(x[SENDERS], recv, N)
    deg = _degree(recv, N).long().clamp(max=MAX_DEG)
    ref = torch.stack(
        [
            _lin_t(sd, f"lins_l.{int(d)}", agg[i]) + _lin_t(sd, f"lins_r.{int(d)}", x[i])
            for i, d in enumerate(deg)
        ]
    )

    conv = MFCConv(out_dim=F_OUT, max_degree=MAX_DEG)
    mapped = _map_conv("MFC", _pfx(sd), "c", _template(conv, x_np, None), set())
    _check("MFC", ref, _apply_flax(conv, mapped, x_np, None))


def pytest_numeric_parity_gat():
    gen = np.random.default_rng(14)
    x_np, _ = _graph(gen)
    sd = _np_sd(_family_conv_sd(gen, "GAT", F_IN, F_OUT, heads=HEADS))

    # PyG GATv2Conv(add_self_loops=True, concat=True, negative_slope=0.05):
    # lin_l transforms the SOURCE (message carrier), lin_r the TARGET;
    # e_ij = att . leaky_relu(lin_l x_j + lin_r x_i); alpha = softmax over
    # incoming edges incl. the self-loop; out_i = sum_j alpha_ij (lin_l x_j).
    x = torch.tensor(x_np)
    xl = _lin_t(sd, "lin_l", x).view(N, HEADS, F_OUT)
    xr = _lin_t(sd, "lin_r", x).view(N, HEADS, F_OUT)
    s = torch.tensor(np.concatenate([SENDERS, np.arange(N)]), dtype=torch.long)
    r = torch.tensor(np.concatenate([RECEIVERS, np.arange(N)]), dtype=torch.long)
    pre = torch.nn.functional.leaky_relu(xl[s] + xr[r], 0.05)
    logits = (pre * torch.tensor(sd["att"])[0]).sum(-1)  # [E', H]
    ex = torch.exp(logits - logits.max())
    denom = _scatter_sum(ex, r, N)[r]
    alpha = ex / denom
    out = _scatter_sum(xl[s] * alpha[..., None], r, N).reshape(N, HEADS * F_OUT)
    ref = out + torch.tensor(sd["bias"])

    conv = GATv2Conv(out_dim=F_OUT, heads=HEADS, concat=True, dropout=0.0)
    mapped = _map_conv("GAT", _pfx(sd), "c", _template(conv, x_np, None), set())
    _check("GAT", ref, _apply_flax(conv, mapped, x_np, None))


def pytest_numeric_parity_cgcnn():
    gen = np.random.default_rng(15)
    x_np, e_np = _graph(gen)
    sd = _np_sd(_family_conv_sd(gen, "CGCNN", F_IN, F_IN))

    # PyG CGConv(aggr='add'): z = [x_i | x_j | e_ij];
    # out = x + sum_j sigmoid(lin_f z) * softplus(lin_s z).
    x, e = torch.tensor(x_np), torch.tensor(e_np)
    z = torch.cat([x[RECEIVERS], x[SENDERS], e], dim=-1)
    msg = torch.sigmoid(_lin_t(sd, "lin_f", z)) * torch.nn.functional.softplus(
        _lin_t(sd, "lin_s", z)
    )
    ref = x + _scatter_sum(msg, torch.tensor(RECEIVERS, dtype=torch.long), N)

    conv = CGConv(edge_dim=EDGE)
    mapped = _map_conv("CGCNN", _pfx(sd), "c", _template(conv, x_np, e_np), set())
    _check("CGCNN", ref, _apply_flax(conv, mapped, x_np, e_np))


def pytest_numeric_parity_pna():
    gen = np.random.default_rng(16)
    x_np, e_np = _graph(gen)
    AGG_SCALE = 16
    sd = {}
    for prefix, (o, i) in {
        "pre_nns.0.0": (F_IN, 3 * F_IN),
        "edge_encoder": (F_IN, EDGE),
        "post_nns.0.0": (F_OUT, (AGG_SCALE + 1) * F_IN),
        "lin": (F_OUT, F_OUT),
    }.items():
        for k, v in _lin(gen, o, i).items():
            sd[f"{prefix}.{k}"] = v
    sd = _np_sd(sd)

    # PyG PNAConv(towers=1, pre/post_layers=1, divide_input=False):
    # m_ij = pre_nn([x_i | x_j | edge_encoder(e_ij)]); aggregators
    # [mean|min|max|std] concat, then scalers [identity|amplification|
    # attenuation|linear] scaler-major; update = lin(post_nn([x_i | agg])).
    x, e = torch.tensor(x_np), torch.tensor(e_np)
    recv = torch.tensor(RECEIVERS, dtype=torch.long)
    z = torch.cat([x[RECEIVERS], x[SENDERS], _lin_t(sd, "edge_encoder", e)], -1)
    m = _lin_t(sd, "pre_nns.0.0", z)  # [E, F_IN]
    deg = _degree(recv, N)
    mean = _scatter_sum(m, recv, N) / deg.clamp(min=1.0)[:, None]
    mn = torch.full((N, F_IN), torch.inf).scatter_reduce(
        0, recv[:, None].expand(-1, F_IN), m, "amin", include_self=False
    )
    mx = torch.full((N, F_IN), -torch.inf).scatter_reduce(
        0, recv[:, None].expand(-1, F_IN), m, "amax", include_self=False
    )
    var = _scatter_sum(m * m, recv, N) / deg.clamp(min=1.0)[:, None] - mean**2
    std = torch.sqrt(torch.relu(var) + 1e-5)
    aggs = torch.cat([mean, mn, mx, std], -1)  # [N, 4*F_IN]

    hist = np.bincount(RECEIVERS, minlength=N)
    avg_log, avg_lin = pna_degree_averages(np.bincount(hist))
    d = deg.clamp(min=1.0)[:, None]
    scaled = torch.cat(
        [
            aggs,
            aggs * (torch.log(d + 1.0) / avg_log),
            aggs * (avg_log / torch.log(d + 1.0)),
            aggs * (d / avg_lin),
        ],
        -1,
    )  # [N, 16*F_IN], scaler-major
    ref = _lin_t(sd, "lin", _lin_t(sd, "post_nns.0.0", torch.cat([x, scaled], -1)))

    conv = PNAConv(
        out_dim=F_OUT, deg_avg_log=avg_log, deg_avg_lin=avg_lin, edge_dim=EDGE
    )
    mapped = _map_conv("PNA", _pfx(sd), "c", _template(conv, x_np, e_np), set())
    _check("PNA", ref, _apply_flax(conv, mapped, x_np, e_np))


# ---------------------------------------------------------------------------
# Full-model parity for num_sharedlayers=2 (ISSUE 2 satellite): the reference
# shared-MLP Sequential is [ReLU, Linear, Linear, ReLU] (Base.py:155-162) —
# no ReLU between the shared Linears. With the model built in the
# reference-grammar layout (output_heads.graph.shared_layout="reference"),
# the imported checkpoint must reproduce the torch forward END TO END
# (2 PNA convs + eval BatchNorms + mean pool + shared MLP + graph head) at
# fp32 tolerance and with an empty caveat list.
# ---------------------------------------------------------------------------

SHARED2, HEADH2 = 5, 7


def _pna_layer_sd(gen, f_in, f_out, agg_scale=16):
    sd = {}
    for prefix, (o, i) in {
        "pre_nns.0.0": (f_in, 3 * f_in),
        "edge_encoder": (f_in, EDGE),
        "post_nns.0.0": (f_out, (agg_scale + 1) * f_in),
        "lin": (f_out, f_out),
    }.items():
        for k, v in _lin(gen, o, i).items():
            sd[f"{prefix}.{k}"] = v
    return sd


def _bn_sd(gen, width):
    return {
        "module.weight": torch.tensor(
            gen.uniform(0.5, 1.5, width).astype(np.float32)
        ),
        "module.bias": torch.tensor(gen.normal(size=width).astype(np.float32)),
        "module.running_mean": torch.tensor(
            gen.normal(size=width).astype(np.float32)
        ),
        "module.running_var": torch.tensor(
            gen.uniform(0.5, 2.0, width).astype(np.float32)
        ),
        "module.num_batches_tracked": torch.tensor(3),
    }


def _torch_pna_conv(sd, prefix, x, e, avg_log, avg_lin):
    """One reference PNAConv forward (same semantics as
    pytest_numeric_parity_pna, parameterized by layer prefix)."""
    f_in = x.shape[1]
    recv = torch.tensor(RECEIVERS, dtype=torch.long)
    z = torch.cat(
        [x[RECEIVERS], x[SENDERS], _lin_t(sd, f"{prefix}.edge_encoder", e)], -1
    )
    m = _lin_t(sd, f"{prefix}.pre_nns.0.0", z)
    deg = _degree(recv, N)
    mean = _scatter_sum(m, recv, N) / deg.clamp(min=1.0)[:, None]
    mn = torch.full((N, f_in), torch.inf).scatter_reduce(
        0, recv[:, None].expand(-1, f_in), m, "amin", include_self=False
    )
    mx = torch.full((N, f_in), -torch.inf).scatter_reduce(
        0, recv[:, None].expand(-1, f_in), m, "amax", include_self=False
    )
    var = _scatter_sum(m * m, recv, N) / deg.clamp(min=1.0)[:, None] - mean**2
    std = torch.sqrt(torch.relu(var) + 1e-5)
    aggs = torch.cat([mean, mn, mx, std], -1)
    d = deg.clamp(min=1.0)[:, None]
    scaled = torch.cat(
        [
            aggs,
            aggs * (torch.log(d + 1.0) / avg_log),
            aggs * (avg_log / torch.log(d + 1.0)),
            aggs * (d / avg_lin),
        ],
        -1,
    )
    return _lin_t(
        sd,
        f"{prefix}.lin",
        _lin_t(sd, f"{prefix}.post_nns.0.0", torch.cat([x, scaled], -1)),
    )


def _torch_bn_eval(sd, prefix, x):
    w = torch.tensor(sd[f"{prefix}.module.weight"])
    b = torch.tensor(sd[f"{prefix}.module.bias"])
    rm = torch.tensor(sd[f"{prefix}.module.running_mean"])
    rv = torch.tensor(sd[f"{prefix}.module.running_var"])
    return (x - rm) / torch.sqrt(rv + 1e-5) * w + b


def _shared2_state_dict(gen):
    sd = {}
    for i, f_in in enumerate((F_IN, F_OUT)):
        for k, v in _pna_layer_sd(gen, f_in, F_OUT).items():
            sd[f"convs.{i}.{k}"] = v
        for k, v in _bn_sd(gen, F_OUT).items():
            sd[f"batch_norms.{i}.{k}"] = v
    # num_sharedlayers=2: Sequential(ReLU@0, Linear@1, Linear@2, ReLU@3).
    for k, v in _lin(gen, SHARED2, F_OUT).items():
        sd[f"graph_shared.1.{k}"] = v
    for k, v in _lin(gen, SHARED2, SHARED2).items():
        sd[f"graph_shared.2.{k}"] = v
    # Graph head Sequential(Linear@0, ReLU, Linear@2, ReLU, Linear@4).
    for idx, (o, i) in zip(
        (0, 2, 4), ((HEADH2, SHARED2), (HEADH2, HEADH2), (1, HEADH2))
    ):
        for k, v in _lin(gen, o, i).items():
            sd[f"heads_NN.0.{idx}.{k}"] = v
    return _np_sd(sd)


def _shared2_model(shared_layout):
    from hydragnn_tpu.models.create import create_model

    deg_per_node = np.bincount(RECEIVERS, minlength=N)
    output_heads = {
        "graph": {
            "num_sharedlayers": 2,
            "dim_sharedlayers": SHARED2,
            "num_headlayers": 2,
            "dim_headlayers": [HEADH2, HEADH2],
        }
    }
    if shared_layout is not None:
        output_heads["graph"]["shared_layout"] = shared_layout
    return create_model(
        model_type="PNA",
        input_dim=F_IN,
        hidden_dim=F_OUT,
        output_dim=[1],
        output_type=["graph"],
        output_heads=output_heads,
        task_weights=[1.0],
        num_conv_layers=2,
        edge_dim=EDGE,
        pna_deg=np.bincount(deg_per_node),
    ), pna_degree_averages(np.bincount(deg_per_node))


def _shared2_batch(x_np, e_np):
    from hydragnn_tpu.graphs.collate import GraphSample, collate_graphs

    sample = GraphSample(
        x=x_np,
        pos=np.zeros((N, 3), np.float32),
        y=np.zeros(1, np.float32),
        y_loc=np.array([[0, 1]], np.int64),
        edge_index=np.stack([SENDERS, RECEIVERS]),
        edge_attr=e_np,
    )
    return collate_graphs(
        [sample], head_types=["graph"], head_dims=[1], edge_dim=EDGE
    )


def pytest_numeric_parity_num_sharedlayers2_reference_layout(tmp_path):
    from hydragnn_tpu.models.create import init_model_variables
    from hydragnn_tpu.utils.torch_import import import_torch_checkpoint

    gen = np.random.default_rng(17)
    x_np, e_np = _graph(gen)
    sd = _shared2_state_dict(gen)
    path = tmp_path / "shared2.pk"
    torch.save({"model_state_dict": {k: torch.tensor(v) for k, v in sd.items()}}, str(path))

    model, (avg_log, avg_lin) = _shared2_model("reference")
    batch = _shared2_batch(x_np, e_np)
    variables = init_model_variables(model, batch, seed=0)
    new_vars, report = import_torch_checkpoint(str(path), model, variables)
    assert report["caveats"] == [], report["caveats"]
    assert report["ignored"] == [], report["ignored"]

    # Reference torch forward, straight from the module grammar.
    x, e = torch.tensor(x_np), torch.tensor(e_np)
    for i in range(2):
        x = _torch_pna_conv(sd, f"convs.{i}", x, e, avg_log, avg_lin)
        x = torch.relu(_torch_bn_eval(sd, f"batch_norms.{i}", x))
    xg = x.mean(dim=0, keepdim=True)  # global mean pool, one graph
    # graph_shared = Sequential(ReLU, Linear, Linear, ReLU): NO inner ReLU.
    xs = torch.relu(
        _lin_t(sd, "graph_shared.2", _lin_t(sd, "graph_shared.1", torch.relu(xg)))
    )
    ref = _lin_t(
        sd,
        "heads_NN.0.4",
        torch.relu(
            _lin_t(sd, "heads_NN.0.2", torch.relu(_lin_t(sd, "heads_NN.0.0", xs)))
        ),
    )

    out = np.asarray(forward(model, new_vars, batch)[0])[:1]
    np.testing.assert_allclose(
        out,
        ref.numpy(),
        rtol=2e-4,
        atol=2e-4,
        err_msg="num_sharedlayers=2 reference-layout import diverges from "
        "the reference torch forward",
    )


def pytest_num_sharedlayers2_framework_layout_still_caveats(tmp_path):
    """The default (framework) layout applies an inner ReLU the reference
    lacks — the importer must keep flagging that divergence."""
    from hydragnn_tpu.models.create import init_model_variables
    from hydragnn_tpu.utils.torch_import import import_torch_checkpoint

    gen = np.random.default_rng(18)
    sd = _shared2_state_dict(gen)
    path = tmp_path / "shared2_fw.pk"
    torch.save({"model_state_dict": {k: torch.tensor(v) for k, v in sd.items()}}, str(path))

    model, _ = _shared2_model(None)  # default framework layout
    x_np, e_np = _graph(gen)
    batch = _shared2_batch(x_np, e_np)
    variables = init_model_variables(model, batch, seed=0)
    _, report = import_torch_checkpoint(str(path), model, variables)
    assert any("shared_layout" in c for c in report["caveats"]), report
