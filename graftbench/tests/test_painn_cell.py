"""PaiNN's family file and its cell: the counts against cases worked out by
hand (two nodes, two edges, F 4, 3 basis functions), the two ``geom_*``
readers on recorded traces and on a made-up table, ``BENCHMARK.json`` with
four cells, and a tiny rehearsal of the new cell on the CPU through
``main(argv, allow_cpu=True)``. Nothing here is a device number."""

import json
import os
import types

import pytest

import tiny
from graftbench import flops, xplane_scopes
from graftbench.families import painn
from graftbench.layer_metrics import geom_roofline_share, geom_step_ms

REPO = tiny.REPO
DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "testdata")
CELL = "painn_f128.train_b512"
ARCH = {"model_type": "PAINN", "hidden_dim": 4, "num_radial": 3, "num_conv_layers": 2,
        "input_dim": 1}


def pytest_painn_block_two_nodes_by_hand():
    # nodes 2, edges 2, F 4 (rows 12 wide), 3 basis functions.
    got = flops.total(painn.block_counts(nodes=2, edges=2, f=4, radial=3))
    # message: Dense 4->4 (2*2*4*4 + 8 = 72), SiLU (32), Dense 4->12 (216),
    # filter Dense 3->12 on 2 edges with the cutoff (2*2*3*12 + 2*2*12 = 192),
    # x_j*W and b*v_j + c(x)u (24 + 72 = 96), the one 16-wide sum (32),
    # the residuals (32). update: the channel mix of v, no bias (2*6*4*8 =
    # 384), the norm (64), Dense 8->4 (136), SiLU (32), Dense 4->12 (216),
    # the gated residuals (112).
    assert got["ops"] == 72 + 32 + 216 + 192 + 96 + 32 + 32 + 384 + 64 + 136 + 32 + 216 + 112
    # Two gathers [2, 12] -> [2, 12]: 24 + 24 words and 2 indices each; their
    # scatter-adds read 24 and 2 indices and write 24.
    assert got["bytes"]["gather"] == {"fwd": 4 * 2 * 50, "bwd": 4 * 2 * 50}
    # ONE sum of [2, 16] rows, as the program runs it: 32 + 2 + 32 words.
    assert got["bytes"]["agg"] == {"fwd": 4 * 66, "bwd": 4 * 66}
    # The first block: v is zero, no gradient flows into v_j, one scatter-add.
    first = flops.total(painn.block_counts(nodes=2, edges=2, f=4, radial=3, first=True))
    assert first["bytes"]["gather"] == {"fwd": 4 * 2 * 50, "bwd": 4 * 50}
    assert first["ops"] == got["ops"] and first["bytes"]["agg"] == got["bytes"]["agg"]


def pytest_painn_stack_and_geom_bytes_by_hand():
    parts, enc = painn.counts(ARCH, nodes=2, edges=2)
    got = flops.total(parts)
    assert enc == 4
    # Once a step: two gathers of [2, 3] position rows (6 + 6 words and 2
    # indices each), no backward; then two blocks, the first of them with no
    # scatter-add for v_j: three in all, not four.
    assert got["bytes"]["gather"] == {
        "fwd": 4 * (2 * 14 + 2 * 2 * 50), "bwd": 4 * 3 * 50,
    }
    assert got["bytes"]["agg"]["fwd"] == 2 * 4 * 66
    # Under hydragnn.geom: the geometry once (reads two [2, 3], writes u [2, 3],
    # the cutoff [2] and the basis [2, 3]: 26 words, no backward) and a
    # filter a block (basis 6 + weights 36 + bias 12 + cutoff 2 + [2, 12]
    # written = 80 words; backward the weights' gradient alone, 80 again).
    assert painn.geom_bytes(ARCH, nodes=2, edges=2) == {
        "fwd": 4 * (26 + 2 * 80), "bwd": 4 * 2 * 80,
    }
    # The geometry's operations: 15 + 3 a basis function an edge.
    s0 = 2 * 2 * 1 * 4 + 2 * 4
    assert got["ops"] == s0 + 2 * (15 + 9) + 2 * 1616
    assert flops.train_step(
        dict(ARCH, output_heads={}, output_type=[], output_dim=[]), 2, 2, 1
    )["ops"] == 3 * (got["ops"] + 2 * 4)  # + the pool


def _run(steps=2, edges=100.0, nodes=10.0):
    cell = types.SimpleNamespace(
        trace_dir=None, out_dir=None,
        config={"NeuralNetwork": {"Architecture": dict(ARCH)}},
    )
    return types.SimpleNamespace(
        cell=cell, peaks={"hbm_bytes_per_s": 819e9},
        facts={"steps": steps, "chips": 1, "real_nodes": nodes * steps,
               "real_edges": edges * steps},
    )


def pytest_geom_readers_on_a_table(monkeypatch):
    rows = [
        dict(root="train", rooted=True, direction="fwd", module="(model)",
             scope="hydragnn.geom", seconds=0.002),
        dict(root="train", rooted=True, direction="bwd", module="conv_1",
             scope="hydragnn.geom", seconds=0.004),
        dict(root="eval", rooted=True, direction="fwd", module="(model)",
             scope="hydragnn.geom", seconds=1.0),  # not the train root's
        dict(root="train", rooted=True, direction="fwd", module="(model)",
             scope="hydragnn.gather", seconds=1.0),  # the position gathers
    ]
    run = _run()
    monkeypatch.setattr(xplane_scopes, "table", lambda _run: {"rows": rows})
    assert geom_step_ms.read(run) == pytest.approx(3.0)
    assert all(xplane_scopes.bucket(r) == "model_dense" for r in rows[:2])
    counted = painn.geom_bytes(ARCH, 10.0, 100.0)
    assert geom_roofline_share.read(run) == pytest.approx(
        100.0 * (counted["fwd"] + counted["bwd"]) / (3.0e-3 * 819e9)
    )


def pytest_geom_readers_return_nothing_on_a_program_without_the_scope(monkeypatch):
    """The recorded traces are of programs that open no ``hydragnn.geom`` (as
    the parent of this PR does not), and a family with no ``geom_bytes``
    counts none: nothing is returned and nothing raises."""
    for name in ("scoped_v5e.xplane.pb", "small_v5e.xplane.pb"):
        table = xplane_scopes.by_scope(os.path.join(DATA, name))
        run = _run()
        monkeypatch.setattr(xplane_scopes, "table", lambda _run, t=table: t)
        assert geom_step_ms.read(run) is None
        assert geom_roofline_share.read(run) is None
    monkeypatch.setattr(xplane_scopes, "table", lambda _run: None)  # no trace
    assert geom_step_ms.read(run) is None and geom_roofline_share.read(run) is None
    rows = [dict(root="train", rooted=True, direction="fwd", module="(model)",
                 scope="hydragnn.geom", seconds=0.002)]
    run = _run()
    run.cell.config["NeuralNetwork"]["Architecture"]["model_type"] = "GAT"
    monkeypatch.setattr(xplane_scopes, "table", lambda _run: {"rows": rows})
    assert geom_roofline_share.read(run) is None


def pytest_benchmark_json_holds_the_cell_and_the_four_chip_cap():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = bench["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    (entry,) = [w for w in cells if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "painn_f128", "train_md17like_b512", 1
    )
    (config,) = [c for c in bench["configs"] if c["name"] == "painn_f128"]
    assert config["reduced"] == []
    with open(os.path.join(REPO, config["file"])) as f:
        arch = json.load(f)["NeuralNetwork"]["Architecture"]
    assert (arch["model_type"], arch["hidden_dim"], arch["num_conv_layers"],
            arch["num_radial"], arch["radius"]) == ("PAINN", 128, 3, 20, 5.0)
    reported = {
        m["name"] for kind in ("end_to_end", "per_layer") for m in bench[kind]
        if "workloads" not in m or CELL in m["workloads"]
    }
    sibling = {
        m["name"] for kind in ("end_to_end", "per_layer") for m in bench[kind]
        if "workloads" not in m or "gatv2_h64x6_md17like.train_b512" in m["workloads"]
    }
    assert reported == sibling | {"geom_step_ms", "geom_roofline_share"}
    for name in reported - {"train_graphs_per_s", "setup_s"}:
        assert os.path.exists(
            os.path.join(REPO, "graftbench", "layer_metrics", name + ".py")
        ), name


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """``tiny.make_copy`` names a tiny cell by its traffic file, and this
    cell shares one with the GATv2 cell: give it a name of its own."""
    root = tiny.make_copy(str(tmp_path_factory.mktemp("graftbench_tiny_painn")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["config"] == "tiny_painn_f128")
    shared, entry["name"] = entry["name"], "tiny.painn"
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if shared in m.get("workloads", ()):
                m["workloads"].append("tiny.painn")
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def pytest_painn_cell_rehearsal_on_the_cpu(root):
    rc, line, text = tiny.run_cell(root, "tiny.painn", seconds=0.5, trace=1)
    assert rc == 0 and line["correct"], text[-3000:]
    assert "cell=tiny.painn" in text
    assert "program vs plain float32 reference on 8 graphs" in text
    with open(os.path.join(root, "graftbench", "out", "tiny.painn", "last_run.json")) as f:
        run = json.load(f)
    arch = run["extra"]["hydragnn_config"]["NeuralNetwork"]["Architecture"]
    assert arch["model_type"] == "PAINN" and arch["num_radial"] == 20
    assert run["facts"]["step_bytes"]["gather"]["bwd"] > 0
    # On a CPU there is no device plane: the trace's readers, the two new
    # ones among them, find nothing and are left out; nothing raises.
    assert {"setup_compile_s", "collate_ms_per_batch", "program_temp_gb"} <= set(
        line["metrics"]
    )
    assert not {"geom_step_ms", "geom_roofline_share", "gather_step_ms"} & set(
        line["metrics"]
    )
    rc, line, text = tiny.run_cell(root, "tiny.painn", seconds=0.5, seed=2_409_270_026)
    assert rc == 0 and line["correct"], text[-3000:]
    assert set(line["metrics"]) == {"train_graphs_per_s", "setup_s"}


def pytest_the_limit_tells_float32_from_the_precision_below(monkeypatch):
    """The control of ``painn.ATOL``/``RTOL``, at the configuration's widths
    (F 128, 3 blocks, 20 basis functions, cutoff 5, its heads) on 8 of the
    traffic generator's 21-atom molecules: the program in float32 is ``correct`` against the
    reference, and the reference with ONE bf16 pass (operands rounded,
    float32 accumulation) in the shared and head MLPs alone -- the smallest
    step below the stated float32, the one that read 1.1e-3 to 3.4e-3 on the
    chip -- comes out NOT correct. Under ``reference.py``'s 5e-3 it would
    pass. A CPU emulation: it decides nothing about a device number."""
    import jax.numpy as jnp
    import numpy as np

    from graftbench import reference
    from graftbench.datagen import md17_like
    from graftbench.drivers.train_epochs import check_against_reference, shaken
    from hydragnn_tpu.graphs import GraphSample, collate_graphs
    from hydragnn_tpu.models import create_model, init_model_variables

    with open(os.path.join(REPO, "graftbench", "configs", "painn_f128.json")) as f:
        arch = json.load(f)["NeuralNetwork"]["Architecture"]
    graphs = []
    for x, pos, _ in md17_like.generate({"graphs": 8, "noise": 0.08}, 2_609_270_031):
        d = np.linalg.norm(pos[:, None] - pos[None], axis=-1)
        send, recv = np.nonzero((d < arch["radius"]) & (d > 0))
        graphs.append(GraphSample(
            x=(x - 1.0) / 7.0, pos=pos,  # the atomic number, min-max scaled
            y=np.zeros(1, np.float32), y_loc=np.array([[0, 1]], dtype=np.int64),
            edge_index=np.stack([send, recv]).astype(np.int32),
        ))
    model = create_model(
        "PAINN", 1, arch["hidden_dim"], (1,), ("graph",), arch["output_heads"],
        [1.0], arch["num_conv_layers"], radius=arch["radius"],
        num_radial=arch["num_radial"],
    )
    batch = collate_graphs(graphs, ("graph",), (1,), with_positions=True)
    variables = shaken(init_model_variables(model, batch), 26)
    atol, rtol = reference.tolerance("PAINN")
    assert (atol, rtol) == (painn.ATOL, painn.RTOL) == (1e-4, 1e-4)

    def program(samples):
        out = model.apply(variables, batch, train=False)[0]
        return [[np.asarray(out)[g]] for g in range(len(samples))]

    worst, fail = check_against_reference(model, graphs, program, variables, atol, rtol)
    assert fail is None and worst < 1e-5, (worst, fail)

    def one_bf16_pass(p, x):
        r = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
        return r(x) @ r(p["kernel"]) + p["bias"]

    def heads_below(samples):
        with monkeypatch.context() as m:
            exact = reference.dense
            # reference.mlp (the shared and head MLPs) looks ``dense`` up in
            # its module; the family's encoder keeps the exact one.
            m.setattr(painn.reference, "dense", one_bf16_pass)
            m.setattr(painn, "reference", types.SimpleNamespace(dense=exact))
            return reference.forward(model, variables, samples)

    worst_below, fail_below = check_against_reference(
        model, graphs, heads_below, variables, atol, rtol
    )
    assert fail_below is not None and worst_below > 3e-4, (worst_below, fail_below)
    _, at_5e3 = check_against_reference(
        model, graphs, heads_below, variables, reference.ATOL, reference.RTOL
    )
    assert at_5e3 is None or worst_below > 5e-3  # what the kept limit let through
