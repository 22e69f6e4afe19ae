"""Round-trip test for the reference-checkpoint importer
(hydragnn_tpu/utils/torch_import.py): build a state_dict with the EXACT key
grammar the reference's torch module tree emits (Base.py:99-223, PNAStack /
PyG PNAConv towers=1 — tensors only, no torch_geometric import needed), save
it with torch.save the way save_model does
(/root/reference/hydragnn/utils/model.py:35-47), import, and verify placement,
the edge-encoder fold (functional check in numpy), and a full forward pass."""

import collections
import os

import numpy as np
import pytest

torch = pytest.importorskip("torch")

from hydragnn_tpu.graphs.collate import GraphSample, collate_graphs
from hydragnn_tpu.models.create import create_model, init_model_variables
from hydragnn_tpu.utils.torch_import import import_torch_checkpoint
from tests.conftest import forward

IN, HID, EDGE, SHARED, HEADH = 3, 8, 2, 5, 7
AGG_SCALE = 16  # 4 aggregators x 4 scalers


def _lin(gen, n_out, n_in, bias=True):
    d = {"weight": torch.tensor(gen.normal(size=(n_out, n_in)).astype(np.float32))}
    if bias:
        d["bias"] = torch.tensor(gen.normal(size=(n_out,)).astype(np.float32))
    return d


def _reference_pna_state_dict(gen, num_nodes_mlp=None):
    """Key grammar of PNAStack(2 conv layers) + 1 graph head (+ optional node
    'mlp' head) as the reference's state_dict() would produce it."""
    sd = collections.OrderedDict()

    def put(prefix, tensors):
        for k, v in tensors.items():
            sd[f"{prefix}.{k}"] = v

    for i, f_in in enumerate((IN, HID)):
        c = f"convs.{i}"
        put(f"{c}.pre_nns.0.0", _lin(gen, f_in, 3 * f_in))
        put(f"{c}.edge_encoder", _lin(gen, f_in, EDGE))
        put(f"{c}.post_nns.0.0", _lin(gen, HID, (AGG_SCALE + 1) * f_in))
        put(f"{c}.lin", _lin(gen, HID, HID))
        b = f"batch_norms.{i}.module"
        sd[f"{b}.weight"] = torch.tensor(
            gen.uniform(0.5, 1.5, HID).astype(np.float32)
        )
        sd[f"{b}.bias"] = torch.tensor(gen.normal(size=HID).astype(np.float32))
        sd[f"{b}.running_mean"] = torch.tensor(
            gen.normal(size=HID).astype(np.float32)
        )
        sd[f"{b}.running_var"] = torch.tensor(
            gen.uniform(0.5, 2.0, HID).astype(np.float32)
        )
        sd[f"{b}.num_batches_tracked"] = torch.tensor(7)

    # graph_shared = Sequential(ReLU@0, Linear@1) for num_sharedlayers=1
    put("graph_shared.1", _lin(gen, SHARED, HID))
    # graph head = Sequential(Linear@0, ReLU@1, Linear@2, ReLU@3, Linear@4)
    put("heads_NN.0.0", _lin(gen, HEADH, SHARED))
    put("heads_NN.0.2", _lin(gen, HEADH, HEADH))
    put("heads_NN.0.4", _lin(gen, 1, HEADH))

    if num_nodes_mlp:
        # node 'mlp' head: reference MLPNode builds num_nodes Sequentials but
        # forward uses only mlp.0 (Base.py:330-366)
        for inode in range(num_nodes_mlp):
            put(f"heads_NN.1.mlp.{inode}.0", _lin(gen, HEADH, HID))
            put(f"heads_NN.1.mlp.{inode}.2", _lin(gen, 1, HEADH))
    return sd


def _make_model(node_head=False):
    output_heads = {
        "graph": {
            "num_sharedlayers": 1,
            "dim_sharedlayers": SHARED,
            "num_headlayers": 2,
            "dim_headlayers": [HEADH, HEADH],
        }
    }
    out_dim, out_type, weights = [1], ["graph"], [1.0]
    if node_head:
        output_heads["node"] = {
            "type": "mlp",
            "num_headlayers": 1,
            "dim_headlayers": [HEADH],
        }
        out_dim, out_type, weights = [1, 1], ["graph", "node"], [1.0, 1.0]
    return create_model(
        model_type="PNA",
        input_dim=IN,
        hidden_dim=HID,
        output_dim=out_dim,
        output_type=out_type,
        output_heads=output_heads,
        task_weights=weights,
        num_conv_layers=2,
        edge_dim=EDGE,
        num_nodes=4,
        pna_deg=np.array([0.0, 0.0, 1.0], np.float32),
    )


def _example_batch(gen, n_heads=1):
    graphs = []
    for _ in range(3):
        nn_ = int(gen.integers(3, 6))
        x = gen.normal(size=(nn_, IN)).astype(np.float32)
        src = np.arange(nn_)
        dst = (src + 1) % nn_
        ei = np.stack(
            [np.concatenate([src, dst]), np.concatenate([dst, src])]
        ).astype(np.int32)
        ea = gen.normal(size=(ei.shape[1], EDGE)).astype(np.float32)
        if n_heads == 1:
            y = np.array([x.sum()], np.float32)
            y_loc = np.array([0, 1], np.int32)
        else:
            y = np.concatenate([[x.sum()], x[:, 0]]).astype(np.float32)
            y_loc = np.array([0, 1, 1 + nn_], np.int32)
        graphs.append(
            GraphSample(x=x, pos=x, y=y, y_loc=y_loc, edge_index=ei, edge_attr=ea)
        )
    head_types = ["graph"] if n_heads == 1 else ["graph", "node"]
    head_dims = [1] if n_heads == 1 else [1, 1]
    return collate_graphs(graphs, head_types=head_types, head_dims=head_dims, edge_dim=EDGE)


def pytest_torch_import_roundtrip_pna(tmp_path):
    gen = np.random.default_rng(0)
    sd = _reference_pna_state_dict(gen)
    path = tmp_path / "ref_model.pk"
    torch.save({"model_state_dict": sd, "optimizer_state_dict": {}}, str(path))

    model = _make_model()
    batch = _example_batch(np.random.default_rng(1))
    variables = init_model_variables(model, batch, seed=0)
    new_vars, report = import_torch_checkpoint(str(path), model, variables)
    assert report["ignored"] == [], report["ignored"]
    assert report["caveats"] == []

    p = new_vars["params"]
    # Linear transpose: flax kernel [in, out] == torch weight.T
    np.testing.assert_array_equal(
        p["graph_shared"]["dense_0"]["kernel"],
        sd["graph_shared.1.weight"].numpy().T,
    )
    np.testing.assert_array_equal(
        p["head_0"]["dense_2"]["kernel"], sd["heads_NN.0.4.weight"].numpy().T
    )
    # BatchNorm running stats land in batch_stats
    np.testing.assert_array_equal(
        new_vars["batch_stats"]["bn_1"]["mean"],
        sd["batch_norms.1.module.running_mean"].numpy(),
    )
    np.testing.assert_array_equal(
        p["bn_0"]["scale"], sd["batch_norms.0.module.weight"].numpy()
    )

    # Edge-encoder fold: our fused pre_nn([xi, xj, e_raw]) must equal the
    # reference composition pre(cat([xi, xj, enc(e_raw)])) for any input.
    xi = gen.normal(size=(5, IN)).astype(np.float32)
    xj = gen.normal(size=(5, IN)).astype(np.float32)
    er = gen.normal(size=(5, EDGE)).astype(np.float32)
    enc_w = sd["convs.0.edge_encoder.weight"].numpy()
    enc_b = sd["convs.0.edge_encoder.bias"].numpy()
    pre_w = sd["convs.0.pre_nns.0.0.weight"].numpy()
    pre_b = sd["convs.0.pre_nns.0.0.bias"].numpy()
    ref_out = (
        np.concatenate([xi, xj, er @ enc_w.T + enc_b], axis=1) @ pre_w.T + pre_b
    )
    ours = p["conv_0"]["pre_nn"]
    our_out = (
        np.concatenate([xi, xj, er], axis=1) @ np.asarray(ours["kernel"])
        + np.asarray(ours["bias"])
    )
    np.testing.assert_allclose(our_out, ref_out, rtol=1e-5, atol=1e-5)

    # Full forward with imported weights runs and is finite.
    out = forward(model, new_vars, batch)
    assert np.all(np.isfinite(np.asarray(out[0])))


def pytest_torch_import_node_mlp_head(tmp_path):
    gen = np.random.default_rng(2)
    sd = _reference_pna_state_dict(gen, num_nodes_mlp=4)
    path = tmp_path / "ref_model.pk"
    torch.save({"model_state_dict": sd}, str(path))

    model = _make_model(node_head=True)
    batch = _example_batch(np.random.default_rng(3), n_heads=2)
    variables = init_model_variables(model, batch, seed=0)
    new_vars, report = import_torch_checkpoint(str(path), model, variables)
    # mlp.1..3 are the reference's unused per-node duplicates ('mlp' forward
    # only calls mlp[0], Base.py:363-366)
    assert all(".mlp." in k for k in report["ignored"]), report["ignored"]
    np.testing.assert_array_equal(
        new_vars["params"]["head_1"]["mlp"]["dense_0"]["kernel"],
        sd["heads_NN.1.mlp.0.0.weight"].numpy().T,
    )
    out = forward(model, new_vars, batch)
    assert np.all(np.isfinite(np.asarray(out[1])))


def pytest_torch_import_shape_mismatch_raises(tmp_path):
    gen = np.random.default_rng(4)
    sd = _reference_pna_state_dict(gen)
    sd["graph_shared.1.weight"] = torch.zeros(SHARED + 1, HID)
    sd["graph_shared.1.bias"] = torch.zeros(SHARED + 1)
    path = tmp_path / "bad.pk"
    torch.save({"model_state_dict": sd}, str(path))
    model = _make_model()
    batch = _example_batch(np.random.default_rng(5))
    variables = init_model_variables(model, batch, seed=0)
    with pytest.raises(ValueError, match="shape mismatch|architecture"):
        import_torch_checkpoint(str(path), model, variables)


def _family_conv_sd(gen, family, f_in, f_out, heads=6, max_deg=3):
    """Reference (PyG) conv state tensors for one layer, keyed per family."""
    sd = {}

    def put(prefix, tensors):
        for k, v in tensors.items():
            sd[f"{prefix}.{k}"] = v

    if family == "GIN":
        put("nn.0", _lin(gen, f_out, f_in))
        put("nn.2", _lin(gen, f_out, f_out))
        sd["eps"] = torch.tensor([3.0])
    elif family == "SAGE":
        put("lin_l", _lin(gen, f_out, f_in))
        put("lin_r", _lin(gen, f_out, f_in, bias=False))
    elif family == "MFC":
        for d in range(max_deg + 1):
            put(f"lins_l.{d}", _lin(gen, f_out, f_in))
            put(f"lins_r.{d}", _lin(gen, f_out, f_in, bias=False))
    elif family == "GAT":
        put("lin_l", _lin(gen, heads * f_out, f_in))
        put("lin_r", _lin(gen, heads * f_out, f_in))
        sd["att"] = torch.tensor(
            gen.normal(size=(1, heads, f_out)).astype(np.float32)
        )
        sd["bias"] = torch.tensor(
            gen.normal(size=(heads * f_out,)).astype(np.float32)
        )
    elif family == "CGCNN":
        put("lin_f", _lin(gen, f_in, 2 * f_in + EDGE))
        put("lin_s", _lin(gen, f_in, 2 * f_in + EDGE))
    return sd


@pytest.mark.parametrize("family", ["GIN", "SAGE", "MFC", "GAT", "CGCNN"])
def pytest_torch_import_other_families(family, tmp_path):
    gen = np.random.default_rng(6)
    heads, max_deg = 6, 3
    sd = collections.OrderedDict()

    if family == "GAT":
        # GATStack widths: conv_0 in->hid (concat), conv_1 hid*heads->hid
        # (concat=False, bias width hid) — GATStack.py:35-46
        layer0 = _family_conv_sd(gen, family, IN, HID, heads)
        layer1 = _family_conv_sd(gen, family, heads * HID, HID, heads)
        layer1["bias"] = torch.tensor(gen.normal(size=(HID,)).astype(np.float32))
        widths = (heads * HID, HID)
        layers = (layer0, layer1)
    elif family == "CGCNN":
        layers = tuple(
            _family_conv_sd(gen, family, IN, IN) for _ in range(2)
        )
        widths = (IN, IN)
    else:
        layers = (
            _family_conv_sd(gen, family, IN, HID, heads, max_deg),
            _family_conv_sd(gen, family, HID, HID, heads, max_deg),
        )
        widths = (HID, HID)

    for i, layer in enumerate(layers):
        for k, v in layer.items():
            sd[f"convs.{i}.{k}"] = v
        b = f"batch_norms.{i}.module"
        w = widths[i]
        sd[f"{b}.weight"] = torch.ones(w)
        sd[f"{b}.bias"] = torch.zeros(w)
        sd[f"{b}.running_mean"] = torch.zeros(w)
        sd[f"{b}.running_var"] = torch.ones(w)
        sd[f"{b}.num_batches_tracked"] = torch.tensor(1)

    enc_out = IN if family == "CGCNN" else HID
    sd.update({f"graph_shared.1.{k}": v for k, v in _lin(gen, SHARED, enc_out).items()})
    for idx, (o, i_) in zip((0, 2, 4), ((HEADH, SHARED), (HEADH, HEADH), (1, HEADH))):
        sd.update({f"heads_NN.0.{idx}.{k}": v for k, v in _lin(gen, o, i_).items()})

    path = tmp_path / "ref.pk"
    torch.save({"model_state_dict": sd}, str(path))

    model = create_model(
        model_type=family,
        input_dim=IN,
        hidden_dim=HID,
        output_dim=[1],
        output_type=["graph"],
        output_heads={
            "graph": {
                "num_sharedlayers": 1,
                "dim_sharedlayers": SHARED,
                "num_headlayers": 2,
                "dim_headlayers": [HEADH, HEADH],
            }
        },
        task_weights=[1.0],
        num_conv_layers=2,
        edge_dim=EDGE if family == "CGCNN" else None,
        max_neighbours=max_deg,
    )
    batch = _example_batch(np.random.default_rng(7))
    variables = init_model_variables(model, batch, seed=0)
    new_vars, report = import_torch_checkpoint(str(path), model, variables)
    assert report["ignored"] == [], (family, report["ignored"])
    out = forward(model, new_vars, batch)
    assert np.all(np.isfinite(np.asarray(out[0])))


def pytest_torch_import_conv_node_head(tmp_path):
    """'conv' node heads: tensors live under convs_node_* / batch_norms_node_*
    and are ALSO aliased under heads_NN.{i}.{j} (the reference appends the
    same module objects, Base.py:209-216) — aliases must read as consumed."""
    gen = np.random.default_rng(8)
    h0, h1 = 6, 5
    sd = collections.OrderedDict()

    def put(prefix, tensors):
        for k, v in tensors.items():
            sd[f"{prefix}.{k}"] = v

    def bn(prefix, w):
        sd[f"{prefix}.module.weight"] = torch.ones(w)
        sd[f"{prefix}.module.bias"] = torch.zeros(w)
        sd[f"{prefix}.module.running_mean"] = torch.zeros(w)
        sd[f"{prefix}.module.running_var"] = torch.ones(w)
        sd[f"{prefix}.module.num_batches_tracked"] = torch.tensor(1)

    def gin(prefix, f_in, f_out):
        put(f"{prefix}.nn.0", _lin(gen, f_out, f_in))
        put(f"{prefix}.nn.2", _lin(gen, f_out, f_out))
        sd[f"{prefix}.eps"] = torch.tensor([3.0])

    # encoder: 2 GIN convs
    gin("convs.0", IN, HID)
    bn("batch_norms.0", HID)
    gin("convs.1", HID, HID)
    bn("batch_norms.1", HID)
    # node-conv chain: 2 hidden + 1 output conv (+ bns)
    gin("convs_node_hidden.0", HID, h0)
    bn("batch_norms_node_hidden.0", h0)
    gin("convs_node_hidden.1", h0, h1)
    bn("batch_norms_node_hidden.1", h1)
    gin("convs_node_output.0", h1, 1)
    bn("batch_norms_node_output.0", 1)
    # graph head + shared
    sd.update({f"graph_shared.1.{k}": v for k, v in _lin(gen, SHARED, HID).items()})
    for idx, (o, i_) in zip((0, 2, 4), ((HEADH, SHARED), (HEADH, HEADH), (1, HEADH))):
        sd.update({f"heads_NN.0.{idx}.{k}": v for k, v in _lin(gen, o, i_).items()})
    # heads_NN.1 = ModuleList aliasing the SAME node-chain modules
    for j, src in enumerate(
        (
            "convs_node_hidden.0",
            "batch_norms_node_hidden.0",
            "convs_node_hidden.1",
            "batch_norms_node_hidden.1",
            "convs_node_output.0",
            "batch_norms_node_output.0",
        )
    ):
        for k in list(sd):
            if k.startswith(src + "."):
                sd[f"heads_NN.1.{j}" + k[len(src):]] = sd[k]

    model = create_model(
        model_type="GIN",
        input_dim=IN,
        hidden_dim=HID,
        output_dim=[1, 1],
        output_type=["graph", "node"],
        output_heads={
            "graph": {
                "num_sharedlayers": 1,
                "dim_sharedlayers": SHARED,
                "num_headlayers": 2,
                "dim_headlayers": [HEADH, HEADH],
            },
            "node": {
                "type": "conv",
                "num_headlayers": 2,
                "dim_headlayers": [h0, h1],
            },
        },
        task_weights=[1.0, 1.0],
        num_conv_layers=2,
    )
    batch = _example_batch(np.random.default_rng(9), n_heads=2)
    variables = init_model_variables(model, batch, seed=0)
    path = tmp_path / "ref.pk"
    torch.save({"model_state_dict": sd}, str(path))
    new_vars, report = import_torch_checkpoint(str(path), model, variables)
    assert report["ignored"] == [], report["ignored"]
    np.testing.assert_array_equal(
        new_vars["params"]["node_conv_1"]["mlp_0"]["kernel"],
        sd["convs_node_hidden.1.nn.0.weight"].numpy().T,
    )
    np.testing.assert_array_equal(
        new_vars["batch_stats"]["node_out_bn_0"]["var"],
        sd["batch_norms_node_output.0.module.running_var"].numpy(),
    )
    out = forward(model, new_vars, batch)
    assert np.all(np.isfinite(np.asarray(out[1])))


def pytest_torch_import_mlp_per_node_head(tmp_path):
    """'mlp_per_node': the reference keeps one Sequential PER node slot; they
    stack into our [num_nodes, in, out] weight arrays."""
    gen = np.random.default_rng(10)
    num_nodes = 4
    sd = _reference_pna_state_dict(gen, num_nodes_mlp=num_nodes)

    output_heads = {
        "graph": {
            "num_sharedlayers": 1,
            "dim_sharedlayers": SHARED,
            "num_headlayers": 2,
            "dim_headlayers": [HEADH, HEADH],
        },
        "node": {
            "type": "mlp_per_node",
            "num_headlayers": 1,
            "dim_headlayers": [HEADH],
        },
    }
    model = create_model(
        model_type="PNA",
        input_dim=IN,
        hidden_dim=HID,
        output_dim=[1, 1],
        output_type=["graph", "node"],
        output_heads=output_heads,
        task_weights=[1.0, 1.0],
        num_conv_layers=2,
        edge_dim=EDGE,
        num_nodes=num_nodes,
        pna_deg=np.array([0.0, 0.0, 1.0], np.float32),
    )
    batch = _example_batch(np.random.default_rng(11), n_heads=2)
    variables = init_model_variables(model, batch, seed=0)
    path = tmp_path / "ref.pk"
    torch.save({"model_state_dict": sd}, str(path))
    new_vars, report = import_torch_checkpoint(str(path), model, variables)
    assert report["ignored"] == [], report["ignored"]
    p = new_vars["params"]["head_1"]
    assert p["w_0"].shape == (num_nodes, HID, HEADH)
    # node slot 2, layer 1 == heads_NN.1.mlp.2.2 transposed
    np.testing.assert_array_equal(
        p["w_1"][2], sd["heads_NN.1.mlp.2.2.weight"].numpy().T
    )
    np.testing.assert_array_equal(
        p["b_0"][3], sd["heads_NN.1.mlp.3.0.bias"].numpy()
    )
    out = forward(model, new_vars, batch)
    assert np.all(np.isfinite(np.asarray(out[1])))


def pytest_torch_import_ddp_prefixed_checkpoint(tmp_path):
    """Reference checkpoints saved from a DDP-wrapped model carry 'module.'
    on every key (utils/model.py:70-76 strips them on load; our importer must
    too)."""
    gen = np.random.default_rng(12)
    sd = _reference_pna_state_dict(gen)
    ddp_sd = collections.OrderedDict(("module." + k, v) for k, v in sd.items())
    path = tmp_path / "ddp.pk"
    torch.save({"model_state_dict": ddp_sd}, str(path))

    model = _make_model()
    batch = _example_batch(np.random.default_rng(13))
    variables = init_model_variables(model, batch, seed=0)
    new_vars, report = import_torch_checkpoint(str(path), model, variables)
    assert report["ignored"] == [], report["ignored"]
    np.testing.assert_array_equal(
        new_vars["params"]["graph_shared"]["dense_0"]["kernel"],
        sd["graph_shared.1.weight"].numpy().T,
    )
