"""The named-scope vocabulary (hydragnn_tpu/telemetry/scopes.py) in the
compiled programs: every conv family's train step, and the mesh step on four
virtual devices, are lowered and compiled here and held, from the ``op_name``
of each HLO instruction, to

(a) every gather / scatter / reduce-window / sort / custom-call sitting under
    ``hydragnn.gather``, ``hydragnn.agg.*`` or ``hydragnn.pool``, forward and
    ``transpose(`` alike, the ``custom_vjp`` arms included;
(b) the arm in the name being the arm the routing chose;
(c) the scopes being metadata only (same optimized HLO with them switched
    off);
(d) every name used being in the vocabulary.

CPU, tiny sizes: op names and counts, never a time."""

import contextlib
import os
import re

import jax
import numpy as np
import pytest

from hydragnn_tpu.graphs import GraphSample, collate_graphs
from hydragnn_tpu.models import create_model, init_model_variables
from hydragnn_tpu.ops import aggregate as agg
from hydragnn_tpu.ops import segment_sorted as srt
from hydragnn_tpu.telemetry import scopes
from hydragnn_tpu.train.trainer import (
    create_train_state,
    make_eval_step,
    make_train_epoch_scan,
    make_train_step,
    make_train_step_dp,
    stack_batches,
)
from hydragnn_tpu.utils.optimizer import select_optimizer

FAMILIES = ("SAGE", "GIN", "MFC", "GAT", "CGCNN", "PNA")
# route -> (environment, keep the batch's CSR pointers, arm of the sums)
ROUTES = {
    "xla": ({"HYDRAGNN_SEGMENT_SORTED": "0"}, True, "xla"),
    "sorted": ({"HYDRAGNN_SEGMENT_SORTED": "1"}, False, "sorted"),
    "csr": ({"HYDRAGNN_SEGMENT_SORTED": "1"}, True, "csr"),
}
_DATA_MOVERS = re.compile(r"\s(gather|scatter|reduce-window|sort|custom-call)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HYDRAGNN = re.compile(r"hydragnn\.[\w.]+")


def _heads(node_type="mlp"):
    return {
        "graph": {
            "num_sharedlayers": 1, "dim_sharedlayers": 4,
            "num_headlayers": 1, "dim_headlayers": [4],
        },
        "node": {"num_headlayers": 1, "dim_headlayers": [4], "type": node_type},
    }


def _batch(csr=True):
    rng = np.random.default_rng(0)
    graphs = []
    for _ in range(4):
        n = 6
        x = rng.normal(size=(n, 1)).astype(np.float32)
        ei = np.stack([np.arange(n), (np.arange(n) + 1) % n]).astype(np.int32)
        ei = np.concatenate([ei, ei[::-1]], axis=1)
        ea = rng.random((ei.shape[1], 1)).astype(np.float32) + 0.1
        y = np.concatenate([[x.sum()], x[:, 0]]).astype(np.float32)
        graphs.append(GraphSample(
            x=x, pos=np.zeros((n, 3), np.float32), y=y,
            y_loc=np.array([[0, 1, 1 + n]], dtype=np.int64),
            edge_index=ei, edge_attr=ea,
        ))
    batch = collate_graphs(graphs, ("graph", "node"), (1, 1), edge_dim=1)
    return batch if csr else batch.replace(row_ptr=None, graph_ptr=None)


def _model(conv, node_type="mlp", hidden=8, **kw):
    kw.setdefault("edge_dim", 1)
    if conv == "PNA":
        kw["pna_deg"] = [0, 1, 2, 4, 2, 1]
    if conv == "MFC":
        kw["max_neighbours"] = 8
    if node_type == "mlp_per_node":
        kw["num_nodes"] = 6
    if conv == "PAINN":
        kw.update(radius=1.5, num_radial=4)
    return create_model(
        conv, 1, hidden, (1, 1), ("graph", "node"), _heads(node_type), [1.0, 1.0],
        2, **kw,
    )


def _state_shapes(model, batch, opt):
    """The train state ``batch`` would initialize, as shapes: a step is
    lowered and compiled here, never run, so no weight is drawn."""
    return jax.eval_shape(
        lambda: create_train_state(model, init_model_variables(model, batch), opt)
    )


_TEXTS = {}  # what _compiled_text compiled, by everything its trace reads


def _shapes(tree):
    leaves, structure = jax.tree_util.tree_flatten(tree)
    return structure, tuple((np.shape(a), str(np.asarray(a).dtype)) for a in leaves)


def _compiled_text(conv, batch, build=make_train_step, stacked=None, **model_kw):
    """Optimized HLO of ``build``'s step; ``stacked`` is what the scanned
    epoch is lowered for (``batch`` still shapes the model). A step several
    tests read is compiled once: the key holds the model's arguments, the
    shapes, and the two switches this file's tests turn before they trace
    (the sorted arm's override and ``jax.named_scope`` itself)."""
    key = (
        conv, build, tuple(sorted(model_kw.items())), _shapes(batch), _shapes(stacked),
        os.environ.get("HYDRAGNN_SEGMENT_SORTED"), jax.named_scope,
    )
    if key not in _TEXTS:
        _TEXTS[key] = _compile_text(conv, batch, build, stacked, **model_kw)
    return _TEXTS[key]


def _compile_text(conv, batch, build, stacked, **model_kw):
    model = _model(conv, **model_kw)
    opt = select_optimizer("AdamW", 1e-3)
    state = _state_shapes(model, batch, opt)
    step = build(model, opt, donate=False)
    if stacked is None:
        args = (state, batch, jax.random.PRNGKey(0))
    else:  # the scanned epoch: every stacked batch real
        slots = jax.tree_util.tree_leaves(stacked)[0].shape[0]
        args = (state, stacked, np.asarray(slots, np.int32), jax.random.PRNGKey(0))
    return step.lower(*args).compile().as_text()


def _op_names(text):
    """[(the data-moving opcode or None, op_name)] of every instruction."""
    out = []
    for line in text.splitlines():
        name = _OP_NAME.search(line)
        if name:
            mover = _DATA_MOVERS.search(line.split("metadata=")[0])
            out.append((mover.group(1) if mover else None, name.group(1)))
    return out


def _check_movers_scoped(names, root):
    movers = [(op, n) for op, n in names if op]
    assert movers, "no gather or scatter compiled: nothing was checked"
    for op, name in movers:
        assert root in name, f"{op} outside the root scope: {name}"
        assert (
            scopes.GATHER in name or "hydragnn.agg." in name
            or scopes.POOL in name
        ), f"{op} under no gather/agg/pool scope: {name}"
    # The backward pass is held too, not only the forward.
    assert any("transpose(" in n for _, n in movers)


def _used(names):
    return {m for _, n in names for m in _HYDRAGNN.findall(n)}


def _arms(names):
    """{what: {arms}} over the ``hydragnn.agg.<what>.<arm>`` names used."""
    out = {}
    for name in _used(names):
        parts = name.split(".")
        if parts[:2] == ["hydragnn", "agg"] and len(parts) == 4:
            out.setdefault(parts[2], set()).add(parts[3])
    return out


# ------------------------------------------------------------- (a), (b), (d)
@pytest.mark.parametrize("route", list(ROUTES))
@pytest.mark.parametrize("conv", FAMILIES)
def pytest_train_step_movers_scoped_and_arm_named(conv, route, monkeypatch):
    env, csr, arm = ROUTES[route]
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    names = _op_names(_compiled_text(conv, _batch(csr)))
    _check_movers_scoped(names, scopes.TRAIN_STEP)
    used = _used(names)
    assert used <= scopes.VOCABULARY, used - scopes.VOCABULARY  # (d)
    assert {scopes.TRAIN_STEP, scopes.GATHER, scopes.POOL, scopes.LOSS,
            scopes.OPTIMIZER} <= used
    # (b): what the routing chose is what the names say.
    assert srt.sorted_enabled() is (route != "xla")
    arms = _arms(names)
    # PNA's extrema: the scan kernel over receiver runs where the sorted arm
    # has the batch's row pointers, XLA's segment_max/min on the other routes.
    # GAT's logits' max is XLA's segment_max on every route.
    extrema = "pallas_csr" if (conv, route) == ("PNA", "csr") else "xla"
    for what, got in arms.items():
        assert got == ({extrema} if what == "extrema" else {arm}), (what, got)
    expected = {
        "SAGE": {"mean"}, "GIN": {"sum", "mean"}, "MFC": {"sum_count", "mean"},
        "GAT": {"sum", "extrema", "mean"}, "CGCNN": {"sum", "mean"},
        "PNA": {"mean", "stats", "extrema"},
    }[conv]
    assert set(arms) == expected, (set(arms), expected)
    if conv == "PNA":
        assert scopes.AGG_PNA in used


# family -> (hidden width, {what: widths it reduces at that hidden width}):
# sums on both sides of ``segment_sorted.WIDE_ROW`` (128) in one program.
_WIDE = {
    # 6 heads of 32: the weighted sum 192 wide, the denominators 6, the pool 32
    "GAT": (32, {"sum": (192, 6), "mean": (32,)}),
    # F 32: the block's one sum 4F = 128 wide, the pool 32
    "PAINN": (32, {"sum": (128,), "mean": (32,)}),
    # hidden 128: conv_0's messages one column, conv_1's 128, the pool 128
    "PNA": (128, {"stats": (1, 128), "mean": (128,)}),
}


@pytest.mark.parametrize("route", list(ROUTES))
@pytest.mark.parametrize("conv", list(_WIDE))
def pytest_wide_sums_are_named_scatter_sorted(conv, route, monkeypatch):
    """The engagement counter of PR 32's mechanism, held to the compiled train
    step. On the sorted arm a reduction of rows ``WIDE_ROW`` columns wide or
    more is named ``scatter_sorted`` with or without ``row_ptr`` and a
    narrower one keeps ``csr`` / ``sorted``, side by side in one program
    (GATv2's 192-wide sum and its 6-wide denominators; PNA's 128-wide hidden
    layer and its one-column input layer); a CPU's default arm names every
    width ``xla``. Under ``scatter_sorted`` the forward holds a scatter and
    none of the prefix route's row fetches (a sum's or a mean's forward
    gathers nothing; the chip's ``reduce-window`` is held in
    tests/test_tpu_compile.py, this CPU lowers a cumsum otherwise); under the
    narrow arms the forward fetches rows and scatters nothing but PNA's
    centered squares; and every scatter under an aggregation scope of the
    sorted arm is told its indices are sorted."""
    env, csr, arm = ROUTES[route]
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    hidden, reduced = _WIDE[conv]
    batch = _painn_batch(csr) if conv == "PAINN" else _batch(csr)
    kw = {"edge_dim": None} if conv == "PAINN" else {}
    text = _compiled_text(conv, batch, hidden=hidden, **kw)
    names = _op_names(text)
    _check_movers_scoped(names, scopes.TRAIN_STEP)
    assert _used(names) <= scopes.VOCABULARY
    want = {
        what: {
            arm if route == "xla" or w < srt.WIDE_ROW else "scatter_sorted"
            for w in widths
        }
        for what, widths in reduced.items()
    }
    got = {what: arms for what, arms in _arms(names).items() if what != "extrema"}
    assert got == want, (got, want)
    if route == "xla":
        return
    forward = lambda scope, op: [  # noqa: E731
        n for o, n in names if o == op and scope in n and "transpose(" not in n
    ]
    for what, arms in want.items():
        if "scatter_sorted" in arms:
            wide = scopes.agg(what, "scatter_sorted")
            assert forward(wide, "scatter"), f"no scatter under {wide}"
            # stats gathers the mean back for the centered squares
            # (without ``row_ptr`` the count's two searches gather ids)
            fetched = [n for n in forward(wide, "gather") if "searchsorted" not in n]
            assert what == "stats" or not fetched, fetched
            # The backward is the gathers it was: no scatter there.
            assert not [n for o, n in names
                        if o == "scatter" and wide in n and "transpose(" in n]
        if arm in arms:
            narrow = scopes.agg(what, arm)
            assert forward(narrow, "gather"), f"no prefix fetch under {narrow}"
            # PNA's std keeps its centered scatter-add at every width.
            assert bool(forward(narrow, "scatter")) == (what == "stats")
    scatters = [
        line for line in text.splitlines()
        if re.search(r"\sscatter\(", line.split("metadata=")[0])
        and re.search(r"hydragnn\.agg\.(sum|stats|mean)\.", line)
        and "transpose(" not in _OP_NAME.search(line).group(1)
    ]
    assert scatters and all("indices_are_sorted=true" in s for s in scatters), scatters


def pytest_custom_vjp_backward_carries_the_scope(monkeypatch):
    """``segment_sum_count_csr``, ``_stats``, ``segment_extrema`` and
    ``gather_sorted`` trace their ``_bwd`` apart from the call site: the
    backward gathers carry the forward's scope all the same, and so does the
    extrema's backward kernel, which gathers nothing (interpreted here: a
    loop over its grid)."""
    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")

    def check(names, *held, op="gather"):
        for scope in held:
            bwd = [n for o, n in names if (op is None or o == op) and scope in n
                   and "transpose(" in n]
            assert bwd, f"no backward {op or 'operation'} under {scope}"
            # Written once, not once by the call site and again by the function.
            assert all(n.count(scope) == 1 for _, n in names if scope in n)

    names = _op_names(_compiled_text("PNA", _batch()))
    check(names, scopes.agg("stats", "csr"), scopes.agg("mean", "csr"))
    # PR 46: ``aggregate.gather_sorted``'s backward is a sorted sum (eight
    # columns here: the prefix sums' row fetches, the only GATHERS a gather's
    # backward can hold) booked to the gather it is the backward of, written
    # once, under no aggregation scope.
    check(names, scopes.GATHER)
    back = [n for o, n in names if o and scopes.GATHER in n and "transpose(" in n]
    assert not [n for n in back if "hydragnn.agg." in n], back
    # The kernels' forward (its row fetches here) and backward both carry the
    # arm's name, and the other arm is gone.
    kernel = scopes.agg("extrema", "pallas_csr")
    check(names, kernel, op=None)
    assert any(kernel in n and "transpose(" not in n for _, n in names)
    assert scopes.agg("extrema", "xla") not in _used(names)
    names = _op_names(_compiled_text("PNA", _batch(csr=False)))
    check(names, scopes.agg("stats", "sorted"), scopes.agg("extrema", "xla"))
    names = _op_names(_compiled_text("GIN", _batch(csr=False)))
    scope = scopes.agg("sum", "sorted")
    assert any(op == "gather" and scope in n and "transpose(" in n
               for op, n in names)


def pytest_module_path_and_scopes_survive_remat_and_scan(monkeypatch):
    """flax writes ``conv_0`` into the op name under ``nn.remat`` too, and the
    scanned epoch carries the same names under its own root."""
    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    names = _op_names(_compiled_text("PNA", _batch(), remat=True))
    recomputed = [n for _, n in names if "rematted_computation" in n]
    assert any("/conv_0/" + scopes.GATHER in n for n in recomputed)
    assert any("/conv_1/" + scopes.AGG_PNA in n for n in recomputed)
    assert any("/conv_0/pre_nn/" in n for n in recomputed)
    batch = _batch()
    stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), batch, batch)
    names = _op_names(_compiled_text(
        "PNA", batch, build=make_train_epoch_scan, stacked=stacked
    ))
    _check_movers_scoped(names, scopes.TRAIN_EPOCH_SCAN)
    assert scopes.TRAIN_STEP not in _used(names)
    assert _used(names) <= scopes.VOCABULARY


def pytest_eval_step_root_and_per_node_head(monkeypatch):
    """The evaluation step is told from the train step by its root, and the
    per-node head's position gathers are ``hydragnn.pool`` / ``gather``."""
    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "0")
    batch = _batch()
    model = _model("SAGE", node_type="mlp_per_node")
    opt = select_optimizer("AdamW", 1e-3)
    state = _state_shapes(model, batch, opt)
    names = _op_names(
        make_eval_step(model).lower(state, batch).compile().as_text()
    )
    used = _used(names)
    assert scopes.EVAL_STEP in used and scopes.TRAIN_STEP not in used
    assert {scopes.POOL, scopes.LOSS, scopes.GATHER} <= used
    assert used <= scopes.VOCABULARY
    for op, name in names:
        if op:
            assert scopes.EVAL_STEP in name and (
                scopes.GATHER in name or "hydragnn.agg." in name
                or scopes.POOL in name
            ), name
    assert any("/head_1/" + scopes.POOL in n for _, n in names)


# ------------------------------------------------------- GATv2's row gathers
_SHAPE = re.compile(r"= \(?f32\[([\d,]*)\]")


def _row_dims(line):
    """The result shape of an instruction without its unit dimensions (the
    CPU's gather writes ``[E, 1, width]``)."""
    return tuple(
        int(d) for d in _SHAPE.search(line).group(1).split(",") if d != "1"
    )


@pytest.mark.parametrize("route", ["xla", "csr"])
def pytest_gat_gathers_flat_rows_once_a_layer(route, monkeypatch):
    """The counter of PR 24's mechanism. In the optimized GATv2 train step
    every gather and scatter under ``hydragnn.gather`` moves rank-2 rows; a
    conv layer has exactly two row gathers of width h·f forward
    (``x_src[senders]``, ``x_dst[receivers]``) and two scatter-adds into
    ``[N_pad, h·f]`` backward (one on the sorted arm since PR 46: the
    senders'); and NO instruction anywhere, inside a fusion or
    out, has the shape ``[E_pad, h, f]``: a ``[h, f]`` row pads to a whole
    (8, 128) tile on the TPU, and a reshape that brings it back fails here
    (stricter than "no fusion outputs one": the CPU compiler this runs on
    would keep such a reshape inside a fusion where the TPU's writes it out).
    """
    env, csr, _ = ROUTES[route]
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    batch = _batch(csr)
    n_pad, e_pad = batch.node_features.shape[0], batch.senders.shape[0]
    heads, f = 6, 8  # create_model's GAT: six heads of hidden_dim
    text = _compiled_text("GAT", batch)
    rows = {}  # (conv layer, backward?, opcode) -> [result dims]
    for line in text.splitlines():
        head = line.split("metadata=")[0]
        mover, name = _DATA_MOVERS.search(head), _OP_NAME.search(line)
        if not (mover and name and scopes.GATHER in name.group(1)):
            continue
        layer = re.search(r"/(conv_\d+)/", name.group(1)).group(1)
        dims = _row_dims(head)
        assert len(dims) == 2, f"rank-{len(dims)} rows under hydragnn.gather: {line}"
        rows.setdefault(
            (layer, "transpose(" in name.group(1), mover.group(1)), []
        ).append(dims)
    assert {k[0] for k in rows} == {"conv_0", "conv_1"}
    for layer in ("conv_0", "conv_1"):
        forward = rows[(layer, False, "gather")]
        assert sorted(forward) == sorted(
            [(e_pad, heads * f)] * 2 + [(e_pad, heads)] * 2
        ), (layer, forward)  # x_j, x_i; the softmax's shift and denominator
        backward = rows[(layer, True, "scatter")]
        # The shift carries no gradient. On the sorted arm the receiver side
        # (``x_dst``, the denominators) goes back as sorted sums, narrow here
        # (``aggregate.gather_sorted``): the senders' scatter-add is alone.
        assert sorted(backward) == sorted(
            [(n_pad, heads * f)] * 2 + [(n_pad, heads)] if route == "xla"
            else [(n_pad, heads * f)]
        ), (layer, backward)
        assert (layer, False, "scatter") not in rows
    rank3 = re.compile(rf"f32\[{e_pad},(1,)?{heads},(1,)?{f}\]")
    assert not rank3.search(text), rank3.search(text).group(0)


# ------------------------------------------------- PaiNN: geometry, flat rows
def _painn_batch(csr=True):
    """``_batch``'s rings with coordinates: neighbours a unit apart."""
    rng = np.random.default_rng(0)
    graphs = []
    for _ in range(4):
        n = 6
        angle = 2 * np.pi * np.arange(n) / n
        pos = np.stack([np.cos(angle), np.sin(angle), 0.1 * rng.normal(size=n)], 1)
        ei = np.stack([np.arange(n), (np.arange(n) + 1) % n]).astype(np.int32)
        x = rng.normal(size=(n, 1)).astype(np.float32)
        graphs.append(GraphSample(
            x=x, pos=pos.astype(np.float32),
            y=np.concatenate([[x.sum()], x[:, 0]]).astype(np.float32),
            y_loc=np.array([[0, 1, 1 + n]], dtype=np.int64),
            edge_index=np.concatenate([ei, ei[::-1]], axis=1),
        ))
    batch = collate_graphs(graphs, ("graph", "node"), (1, 1), with_positions=True)
    return batch if csr else batch.replace(row_ptr=None, graph_ptr=None)


@pytest.mark.parametrize("route", list(ROUTES))
def pytest_painn_step_geometry_scoped_and_edge_rows_flat(route, monkeypatch):
    """PaiNN's train step on every route: the movers scoped as for the other
    families; the edge geometry and each block's filter Dense under
    ``hydragnn.geom``, its two position gathers under ``hydragnn.gather``
    inside it; a block gathers two 3F-wide rows and scatter-adds as many (the
    first block's ``v`` is zero: XLA drops that gather's backward); and NO
    instruction has the shape ``[E_pad, 3, F]``: ``v`` is flat."""
    env, csr, arm = ROUTES[route]
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    batch = _painn_batch(csr)
    n_pad, e_pad, f = batch.node_features.shape[0], batch.senders.shape[0], 8
    text = _compiled_text("PAINN", batch, edge_dim=None)
    names = _op_names(text)
    _check_movers_scoped(names, scopes.TRAIN_STEP)
    used = _used(names)
    assert used <= scopes.VOCABULARY, used - scopes.VOCABULARY
    assert {scopes.TRAIN_STEP, scopes.GATHER, scopes.GEOM, scopes.POOL,
            scopes.LOSS, scopes.OPTIMIZER} <= used
    assert _arms(names) == {"sum": {arm}, "mean": {arm}}
    positions = [n for op, n in names
                 if op == "gather" and f"{scopes.GEOM}/{scopes.GATHER}" in n]
    assert positions and all("/conv_" not in n for n in positions)
    assert any(f"/conv_1/{scopes.GEOM}/filter/" in n for _, n in names)
    assert any(f"/conv_0/{scopes.GEOM}/filter/" in n and "transpose(" in n
               for _, n in names)
    rows = {}  # (block, backward?, opcode) -> [result dims]
    for line in text.splitlines():
        head = line.split("metadata=")[0]
        mover, name = _DATA_MOVERS.search(head), _OP_NAME.search(line)
        if not (mover and name and scopes.GATHER in name.group(1)):
            continue
        block = re.search(r"/(conv_\d+)/", name.group(1))
        if block is None:
            continue  # the position gathers
        dims = _row_dims(head)
        assert len(dims) == 2, f"rank-{len(dims)} rows under hydragnn.gather: {line}"
        rows.setdefault(
            (block.group(1), "transpose(" in name.group(1), mover.group(1)), []
        ).append(dims)
    for block in ("conv_0", "conv_1"):
        assert (e_pad, 3 * f) in rows[(block, False, "gather")], rows
        assert set(rows[(block, True, "scatter")]) == {(n_pad, 3 * f)}, rows
    assert len(rows[("conv_1", True, "scatter")]) == 2  # x and v
    rank3 = re.compile(rf"f32\[{e_pad},(1,)?3,(1,)?{f}\]")
    assert not rank3.search(text), rank3.search(text).group(0)


def pytest_painn_eval_and_scan_roots_carry_the_geometry(monkeypatch):
    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    batch = _painn_batch()
    model = _model("PAINN", edge_dim=None)
    opt = select_optimizer("AdamW", 1e-3)
    state = _state_shapes(model, batch, opt)
    assert state.batch_stats == {}  # no batch norm anywhere in this family
    names = _op_names(make_eval_step(model).lower(state, batch).compile().as_text())
    used = _used(names)
    assert {scopes.EVAL_STEP, scopes.GEOM, scopes.GATHER, scopes.POOL} <= used
    assert scopes.TRAIN_STEP not in used and used <= scopes.VOCABULARY
    stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), batch, batch)
    names = _op_names(_compiled_text(
        "PAINN", batch, build=make_train_epoch_scan, stacked=stacked, edge_dim=None
    ))
    _check_movers_scoped(names, scopes.TRAIN_EPOCH_SCAN)
    assert scopes.GEOM in _used(names) and _used(names) <= scopes.VOCABULARY


# ------------------------------------------------------------------- the mesh
def pytest_mesh_step_on_four_devices_is_rooted_and_scoped(monkeypatch):
    """The program of the four-chip cell: it had no scope at all."""
    from hydragnn_tpu.parallel import make_mesh

    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    devices = jax.devices()[:4]
    assert len(devices) == 4
    mesh = make_mesh(devices=devices)
    batch = _batch()
    model = _model("PNA")
    opt = select_optimizer("AdamW", 1e-3)
    state = _state_shapes(model, batch, opt)
    step = make_train_step_dp(model, opt, mesh, donate=False)
    text = step.lower(
        state, stack_batches([batch] * 4, 4), jax.random.PRNGKey(0)
    ).compile().as_text()
    names = _op_names(text)
    _check_movers_scoped(names, scopes.TRAIN_STEP)
    used = _used(names)
    assert used <= scopes.VOCABULARY
    assert {scopes.GRAD_SYNC, scopes.OPTIMIZER, scopes.LOSS} <= used
    reduces = [
        _OP_NAME.search(line).group(1) for line in text.splitlines()
        if re.search(r"\sall-reduce(-start)?\(", line.split("metadata=")[0])
        and _OP_NAME.search(line)
    ]
    assert reduces, "no all-reduce compiled"
    assert all(scopes.GRAD_SYNC in n for n in reduces), reduces


# ------------------------------------------------------------------------ (c)
def _stripped(text):
    """Optimized HLO without what a scope may change: instruction metadata
    and the source tables it points into."""
    body = text[text.index("\n\n", text.index("StackFrames")):] \
        if "StackFrames" in text else text
    return re.sub(r",? ?metadata=\{[^}]*\}", "", body)


@pytest.mark.parametrize("conv", ["PNA", "GAT"])
def pytest_scopes_are_metadata_only(conv, monkeypatch):
    """Same optimized HLO, text for text, with every scope of the vocabulary
    switched off: a scope emits no instruction and moves no fusion."""
    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    batch = _batch()
    with_scopes = _compiled_text(conv, batch)
    assert "hydragnn.agg." in with_scopes
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    without = _compiled_text(conv, batch)
    assert "hydragnn." not in without
    assert _stripped(with_scopes) == _stripped(without)


# ------------------------------------------------------------- the vocabulary
def pytest_vocabulary_table():
    assert scopes.agg("stats", "csr") == "hydragnn.agg.stats.csr"
    # PR 32: a name added, none changed, so the version stands.
    assert scopes.AGG_ARMS == ("xla", "sorted", "csr", "pallas_csr", "scatter_sorted")
    assert scopes.agg("sum", "scatter_sorted") == "hydragnn.agg.sum.scatter_sorted"
    assert scopes.agg("stats", "scatter_sorted") in scopes.VOCABULARY
    assert scopes.VERSION == 1
    assert all(n.startswith("hydragnn.") for n in scopes.VOCABULARY)
    assert set(scopes.ROOTS) < scopes.VOCABULARY
    with pytest.raises(ValueError):
        scopes.agg("stats", "fast")
    with pytest.raises(ValueError):
        scopes.agg("variance", "xla")


def pytest_pr39_adds_three_names_and_changes_none():
    """PR 39's leaves (latent attention's chains, the shared expert of that
    block, the class head's reply in the engine's executable): added to the
    vocabulary, every earlier name still there, so the version stands and no
    cached executable is served under a changed meaning."""
    added = {"hydragnn.attn.latent", "hydragnn.moe.shared", "hydragnn.head.logprob"}
    assert {scopes.ATTN_LATENT, scopes.MOE_SHARED, scopes.HEAD_LOGPROB} == added
    assert added <= scopes.VOCABULARY and scopes.VERSION == 1
    before = {
        "hydragnn.train_step", "hydragnn.train_epoch_scan", "hydragnn.eval_step",
        "hydragnn.gather", "hydragnn.pool", "hydragnn.geom", "hydragnn.lfm2.conv",
        "hydragnn.lfm2.attn", "hydragnn.attn.full", "hydragnn.attn.window",
        "hydragnn.moe.route", "hydragnn.moe.experts", "hydragnn.loss",
        "hydragnn.optimizer", "hydragnn.grad_sync", "hydragnn.agg.pna",
    } | {scopes.agg(w, a) for w in scopes.AGG_WHATS for a in scopes.AGG_ARMS}
    # PR 45 added the three leaves of a state-space layer the same way.
    later = {"hydragnn.ssm.conv", "hydragnn.ssm.dt", "hydragnn.ssm.scan"}
    assert scopes.VOCABULARY == before | added | later
    # A regression family's served program opens none of them.
    from hydragnn_tpu.serve import InferenceEngine

    model = create_model("PNA", 1, 8, (1, 1), ("graph", "node"), _heads(), [1.0, 1.0], 2,
                         pna_deg=[0, 2, 4, 2, 1], max_neighbours=4)
    batch = _batch()
    variables = init_model_variables(model, batch)
    engine = InferenceEngine(model, variables, autostart=False)
    text = engine._jit.lower(
        variables["params"], variables.get("batch_stats", {}), batch
    ).as_text(debug_info=True)
    engine.close()
    assert not any(name in text for name in added) and scopes.POOL in text


def pytest_pr41_serves_a_band_under_the_names_the_table_has():
    """PR 41 adds no name: Mellum's engine executable, the first served
    program with ``hydragnn.attn.window`` in it, carries the band's core, the
    triangle's, the router, the experts and the reply under names the table
    already held, each kind of core under its own layers, and no
    ``hydragnn.`` name outside the vocabulary."""
    from hydragnn_tpu.serve import InferenceEngine

    # 19 names at PR 41; PR 45's three leaves came after.
    assert len(scopes.VOCABULARY) == 19 + 3 + len(scopes.AGG_WHATS) * len(scopes.AGG_ARMS)
    v = 16
    rope = {"full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                               "original_max_position_embeddings": 8},
            "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
    model = create_model(
        "MELLUM", 1, 8, (v,), ("node",),
        {"node": {"num_headlayers": 0, "dim_headlayers": [], "type": "mlp"}}, [1.0], 2,
        token_arch=dict(
            layer_types=["sliding_attention", "full_attention"], mlp_layer_types=["sparse"] * 2,
            num_attention_heads=2, num_key_value_heads=1, head_dim=4, sliding_window=3,
            rope_parameters=rope, moe_intermediate_size=8, num_experts=4,
            num_experts_per_tok=2, vocab_size=v, token_minmax=[0.0, v - 1.0],
        ), head_loss=("cross_entropy",), class_minmax=([0.0, v - 1.0],),
    )
    from hydragnn_tpu.models.create import make_example_batch

    variables = init_model_variables(
        model, make_example_batch(1, [1], ["node"], edge_dim=None, num_nodes=4, with_positions=True)
    )
    engine = InferenceEngine(model, variables, autostart=False)
    text = engine._jit.lower(
        variables["params"], variables.get("batch_stats", {}), engine._dummy_batch(16, 8)
    ).as_text(debug_info=True)
    engine.close()
    used = set(_HYDRAGNN.findall(text))
    assert used <= scopes.VOCABULARY, used - scopes.VOCABULARY
    assert {scopes.ATTN_WINDOW, scopes.ATTN_FULL, scopes.MOE_ROUTE, scopes.MOE_EXPERTS,
            scopes.HEAD_LOGPROB} <= used
    assert f"conv_0/self_attn/{scopes.ATTN_WINDOW}" in text
    assert f"conv_1/self_attn/{scopes.ATTN_FULL}" in text
    assert f"conv_0/self_attn/{scopes.ATTN_FULL}" not in text
    assert scopes.VERSION == 1


def pytest_pr45_adds_three_names_for_a_state_space_layer_and_changes_none():
    """PR 45's leaves (the Mamba mixer's convolution, its dt chain, its scan):
    added to the vocabulary, every earlier name still there, so the version
    stands. The JAMBA engine's executable carries them in its Mamba layers
    alone and the causal core in its attention layer alone; a stack with no
    such layer (Mellum's, above) opens none of them; the TRAIN step of the
    family carries them under the train root, forward and backward."""
    from hydragnn_tpu.models.create import make_example_batch
    from hydragnn_tpu.serve import InferenceEngine

    added = {"hydragnn.ssm.conv", "hydragnn.ssm.dt", "hydragnn.ssm.scan"}
    assert {scopes.SSM_CONV, scopes.SSM_DT, scopes.SSM_SCAN} == added
    assert added <= scopes.VOCABULARY and scopes.VERSION == 1
    v = 16
    model = create_model(
        "JAMBA", 1, 8, (v,), ("node",),
        {"node": {"num_headlayers": 0, "dim_headlayers": [], "type": "mlp"}}, [1.0], 2,
        token_arch=dict(
            attn_layer_period=2, attn_layer_offset=1, intermediate_size=16,
            num_attention_heads=2, num_key_value_heads=1, mamba_d_state=4, mamba_d_conv=4,
            mamba_dt_rank=2, mamba_expand=2, vocab_size=v, token_minmax=[0.0, v - 1.0],
        ), head_loss=("cross_entropy",), class_minmax=([0.0, v - 1.0],),
    )
    variables = init_model_variables(
        model, make_example_batch(1, [1], ["node"], edge_dim=None, num_nodes=4, with_positions=True)
    )
    assert not [k for k in variables["params"] if k.startswith("head")]  # the tied head
    engine = InferenceEngine(model, variables, autostart=False)
    text = engine._jit.lower(
        variables["params"], variables.get("batch_stats", {}), engine._dummy_batch(16, 8)
    ).as_text(debug_info=True)
    engine.close()
    used = set(_HYDRAGNN.findall(text))
    assert used <= scopes.VOCABULARY, used - scopes.VOCABULARY
    assert added | {scopes.ATTN_FULL, scopes.HEAD_LOGPROB} <= used
    assert not used & {scopes.MOE_ROUTE, scopes.MOE_EXPERTS, scopes.ATTN_WINDOW}
    for name in added:
        assert f"conv_0/mamba/{name}" in text and f"conv_1/mamba/{name}" not in text
    assert f"conv_1/self_attn/{scopes.ATTN_FULL}" in text
    assert f"conv_0/self_attn" not in text


def pytest_outermost_entry_point_names_the_operation():
    """``fused_segment_sum`` is ``..._sum_count``'s first output and
    ``segment_mean`` a sum over a count: one name an operation, the entry
    point's that was called."""
    import jax.numpy as jnp

    from hydragnn_tpu.ops import segment as seg

    data = jnp.ones((8, 2))
    ids = jnp.array([0, 0, 1, 1, 2, 2, 3, 3])

    def names_of(fn):
        text = jax.jit(fn).lower(data).compile().as_text()
        return {m for _, n in _op_names(text) for m in _HYDRAGNN.findall(n)}

    assert names_of(lambda d: seg.segment_mean(d, ids, 4)) == {
        scopes.agg("mean", "xla")
    }
    assert names_of(lambda d: seg.segment_std(d, ids, 4)) == {
        scopes.agg("stats", "xla")
    }
    assert names_of(lambda d: agg.fused_segment_sum(d, ids, 4)) == {
        scopes.agg("sum", "xla")
    }
    assert names_of(lambda d: agg.fused_segment_softmax(d[:, 0], ids, 4)) == {
        scopes.agg("softmax", "xla")
    }


def pytest_compile_cache_keys_carry_the_vocabulary_version(monkeypatch):
    """JAX leaves operation metadata out of its persistent cache's key, so an
    executable compiled under other scope names would be served with them
    (seen on the chip, PERF.md §6 PR 23): ``place_jax_cache`` folds
    ``scopes.VERSION`` into JAX's key through JAX's own hook, graftcache into
    its environment fingerprint."""
    from jax._src import cache_key

    from hydragnn_tpu.cache import jaxcache, store

    monkeypatch.setattr(cache_key, "custom_hook", lambda: "")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/set/by/the/machine")
    assert jaxcache.place_jax_cache() == "/set/by/the/machine"
    jaxcache.place_jax_cache()  # idempotent: the tag goes in once
    assert cache_key.custom_hook() == f"hydragnn-scopes-v{scopes.VERSION}"
    before = store.environment_fingerprint()["topology"]
    monkeypatch.setattr(scopes, "VERSION", scopes.VERSION + 1)
    assert store.environment_fingerprint()["topology"] != before
    jaxcache.place_jax_cache()
    assert cache_key.custom_hook().endswith(f"-v{scopes.VERSION}")
