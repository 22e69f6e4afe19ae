"""The LFM2 family's files and its cell: discovery of the family, the driver
and the generator by name; the counts against cases worked out by hand; the
five new readers on a made-up table and on recorded traces of programs
without the scopes (the parent's); ``BENCHMARK.json`` and the configuration's
file against the source's widths; the generator; a tiny rehearsal of the cell
on the CPU through ``main(argv, allow_cpu=True)``; and the control of the
family's limits: the precision below the stated one comes out NOT correct.
Nothing here is a device number."""

import importlib
import json
import os
import types

import numpy as np
import pytest

import tiny
from graftbench import families, flops, xplane_scopes
from graftbench.families import lfm2
from graftbench.layer_metrics import (
    moe_load_max_over_mean, moe_roofline_share, moe_route_step_ms, moe_step_ms,
    seqmix_step_ms,
)

REPO = tiny.REPO
DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "testdata")
CELL = "lfm2_8b_a1b_ep4.train_seq1k_b4"
SIBLING = "painn_f128.train_b512"
NEW = {"moe_step_ms", "moe_roofline_share", "moe_route_step_ms", "seqmix_step_ms",
       "moe_load_max_over_mean"}
# Small widths of the same shape of stack: conv, conv, attention, conv; one
# leading dense layer; 8 experts, 2 a token, 4 held from expert 2.
SMALL = dict(
    hidden_dim=32, num_conv_layers=4,
    layer_types=["conv", "conv", "full_attention", "conv"], num_dense_layers=1,
    intermediate_size=48, moe_intermediate_size=24, num_experts=8,
    num_experts_per_tok=2, num_experts_held=4, experts_offset=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8, vocab_size=64,
)


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _config():
    with open(os.path.join(REPO, "graftbench", "configs", "lfm2_8b_a1b_ep4.json")) as f:
        return json.load(f)


def pytest_family_driver_and_generator_are_found_by_name():
    family = families.load("LFM2")
    assert family is lfm2 and callable(family.encode) and callable(family.counts)
    assert callable(family.logits) and callable(family.compare) and callable(family.moe_counts)
    assert 0 < family.ROUTER_EPS < family.ROUTE_EPS < 0.1 and 0 < family.REL_L2 < 0.1
    with open(os.path.join(REPO, "graftbench", "traffic", "train_seq1k_b4.json")) as f:
        traffic = json.load(f)
    driver = importlib.import_module(f"graftbench.drivers.{traffic['driver']}")
    generator = importlib.import_module(f"graftbench.datagen.{traffic['graphs']['generator']}")
    assert callable(driver.run) and callable(generator.generate)
    assert set(generator.DATASET["node_features"]["dim"]) == {1}  # what materialize scales


def pytest_counts_by_hand():
    arch = dict(SMALL, model_type="LFM2")
    d, f = 32, 24
    # 10 rows routed to held experts: three projections a row, SwiGLU's
    # product; bytes: 3 routed layers x 4 held experts x three matrices read
    # once, and a row's input, two hidden halves, their product and its output.
    moe = lfm2.moe_counts(arch, 10)
    assert moe["ops"] == 2 * 10 * 3 * d * f + 2 * 10 * f
    assert moe["bytes"] == 4 * (3 * 4 * 3 * d * f + 10 * (2 * d + 3 * f + d))
    # Uniform routing sends K * held / experts = 1 row a token and layer.
    nodes, edges = 48, 4 * 48 - 6 * 2  # two sequences of 24 on a line
    uniform, width = lfm2.counts(arch, nodes, edges)
    counted, _ = lfm2.counts(arch, nodes, edges, routed_rows=3 * nodes)
    assert width == d
    assert flops.total(uniform)["ops"] == flops.total(counted)["ops"]
    fewer, _ = lfm2.counts(arch, nodes, edges, routed_rows=nodes)
    saved = flops.total(counted)["ops"] - flops.total(fewer)["ops"]
    assert saved == lfm2.moe_counts(arch, 3 * nodes)["ops"] - lfm2.moe_counts(arch, nodes)["ops"]
    # Attention: each token scores half its 24-token sequence on average;
    # q k and p v are 2 x 2 x heads x head_dim a score, the softmax 5 a score.
    attention = [p for p in uniform if p["ops"] == int(
        4 * nodes * 12.5 * 4 * 8 + 5 * nodes * 12.5 * 4
    )]
    assert len(attention) == 1
    # Nothing is booked to the gather or aggregation classes: no edge is read.
    assert flops.total(uniform)["bytes"]["gather"] == {"fwd": 0, "bwd": 0}
    assert flops.total(uniform)["bytes"]["agg"] == {"fwd": 0, "bwd": 0}


def _run(steps=2, rows=4096.0, load_max=700.0):
    cell = types.SimpleNamespace(
        trace_dir=None, out_dir=None,
        config={"NeuralNetwork": {"Architecture": _config()["NeuralNetwork"]["Architecture"]}},
    )
    return types.SimpleNamespace(
        cell=cell, peaks={"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12},
        facts={"steps": steps, "chips": 1, "moe_rows_held": rows * steps * 4,
               "moe_load_max": load_max * steps * 4, "moe_load_min": 300.0 * steps * 4},
    )


def pytest_readers_on_a_table(monkeypatch):
    def row(scope, seconds, root="train", rooted=True, direction="fwd"):
        return dict(root=root, rooted=rooted, direction=direction, module="conv_3",
                    scope=scope, seconds=seconds)

    rows = [
        row("hydragnn.moe.experts", 0.020), row("hydragnn.moe.experts", 0.040, direction="bwd"),
        row("hydragnn.moe.route", 0.006), row("hydragnn.lfm2.conv", 0.003),
        row("hydragnn.lfm2.attn", 0.005, direction="bwd"),
        row("hydragnn.moe.experts", 1.0, root="eval"),  # not the train root's
        row("(model)", 1.0),  # the Dense layers: model_dense's remainder
    ]
    run = _run()
    monkeypatch.setattr(xplane_scopes, "table", lambda _run: {"rows": rows})
    assert moe_step_ms.read(run) == pytest.approx(30.0)
    assert moe_route_step_ms.read(run) == pytest.approx(3.0)
    assert seqmix_step_ms.read(run) == pytest.approx(4.0)
    assert all(xplane_scopes.bucket(r) == "model_dense" for r in rows)
    arch = run.cell.config["NeuralNetwork"]["Architecture"]
    counted = lfm2.moe_counts(arch, 4 * 4096.0)  # a step's rows, 4 routed layers
    least = max(3 * counted["ops"] / 197e12, 3 * counted["bytes"] / 819e9)
    assert moe_roofline_share.read(run) == pytest.approx(100.0 * least / 30e-3)
    assert 0 < moe_roofline_share.read(run) < 100
    # 700 rows on the fullest of 8 held experts against a mean of 512.
    assert moe_load_max_over_mean.read(run) == pytest.approx(700.0 / 512.0)


def pytest_readers_return_nothing_on_a_program_without_the_scopes(monkeypatch):
    """The recorded traces are of programs that open none of the new scopes
    and count no routed rows (as the parent of PR 31 does not): nothing is
    returned and nothing raises."""
    readers = (moe_step_ms, moe_route_step_ms, seqmix_step_ms, moe_roofline_share)
    for name in ("scoped_v5e.xplane.pb", "small_v5e.xplane.pb"):
        table = xplane_scopes.by_scope(os.path.join(DATA, name))
        run = _run()
        monkeypatch.setattr(xplane_scopes, "table", lambda _run, t=table: t)
        assert all(r.read(run) is None for r in readers)
    monkeypatch.setattr(xplane_scopes, "table", lambda _run: None)  # no trace
    assert all(r.read(_run()) is None for r in readers)
    bare = _run()
    bare.facts = {"steps": 2, "chips": 1}  # a driver without the counters
    assert moe_load_max_over_mean.read(bare) is None
    rows = [dict(root="train", rooted=True, direction="fwd", module="conv_3",
                 scope="hydragnn.moe.experts", seconds=0.02)]
    monkeypatch.setattr(xplane_scopes, "table", lambda _run: {"rows": rows})
    assert moe_roofline_share.read(bare) is None
    other = _run()
    other.cell.config["NeuralNetwork"]["Architecture"] = {"model_type": "GAT"}
    assert moe_roofline_share.read(other) is None  # a family that counts no experts


def pytest_benchmark_json_holds_the_cell():
    bench = _bench()
    cells = bench["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    (entry,) = [w for w in cells if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "lfm2_8b_a1b_ep4", "train_seq1k_b4", 1
    )
    (config,) = [c for c in bench["configs"] if c["name"] == "lfm2_8b_a1b_ep4"]
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]

    def reported(cell):
        return {
            m["name"] for kind in ("end_to_end", "per_layer") for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]
        }

    # What the sibling reports, less what reads an edge list, plus the five.
    edges = {"agg_step_ms", "gather_step_ms", "agg_roofline_share",
             "gather_roofline_share", "geom_step_ms", "geom_roofline_share"}
    assert reported(CELL) == (reported(SIBLING) - edges) | NEW
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert CELL in m["workloads"] and m["moves"] == "train_graphs_per_s"
    for name in reported(CELL) - {"train_graphs_per_s", "setup_s"}:
        assert os.path.exists(
            os.path.join(REPO, "graftbench", "layer_metrics", name + ".py")
        ), name


def pytest_the_configuration_keeps_every_published_width():
    """The file's top level is the source's ``config.json`` as run: every
    number but the three under ``reduced`` as the catalog's row has it, and
    the program's ``Architecture`` says the same under its own keys."""
    catalog = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1792, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2,
        "num_experts": 32, "num_experts_per_tok": 4, "num_hidden_layers": 24,
        "num_key_value_heads": 8, "rope_theta": 1000000, "routed_scaling_factor": 1,
        "use_expert_bias": True, "vocab_size": 65536,
    }
    config = _config()
    assert set(config["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    for key, value in catalog.items():
        if key in config["reduced"]:
            assert config["published"][key] == value and config[key] != value, key
        else:
            assert config[key] == value, key
    assert len(config["layer_types"]) == 24
    assert config["layer_types"].count("full_attention") == 6
    assert not any(
        k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size" for k in config["reduced"]
    )
    (entry,) = [c for c in _bench()["configs"] if c["name"] == "lfm2_8b_a1b_ep4"]
    assert config["source"] == entry["source"]
    arch = config["NeuralNetwork"]["Architecture"]
    assert arch["model_type"] == "LFM2"
    assert arch["hidden_dim"] == config["hidden_size"]
    assert arch["num_conv_layers"] == config["num_hidden_layers"] == 6
    assert arch["layer_types"] == config["layer_types"]
    assert arch["layer_types"][:6] == ["conv", "conv", "full_attention", "conv", "conv", "conv"]
    assert (arch["num_experts"], arch["num_experts_held"], arch["experts_offset"]) == (32, 8, 0)
    assert arch["num_experts_held"] == config["num_experts"]
    assert arch["vocab_size"] == config["vocab_size"] == 16384
    assert arch["head_dim"] * arch["num_attention_heads"] == arch["hidden_dim"]
    for key in ("intermediate_size", "moe_intermediate_size", "num_experts_per_tok",
                "num_attention_heads", "num_key_value_heads", "num_dense_layers",
                "conv_L_cache", "norm_eps", "rope_theta", "norm_topk_prob",
                "use_expert_bias", "routed_scaling_factor"):
        assert arch[key] == config[key], key
    voi = config["NeuralNetwork"]["Variables_of_interest"]
    assert voi["loss"] == ["cross_entropy"] and voi["num_classes"] == [arch["vocab_size"]]
    # The parameters the file states: 602.2M + the embedding norm and biases.
    d, f, e = 2048, 1792, 8
    conv, attn = 4 * d * d + 3 * d, (32 + 8 + 8 + 32) * 64 * d + 128
    dense, routed = 3 * d * 7168, e * 3 * d * f + d * 32 + 32
    total = 2 * (conv + dense) + (attn + routed) + 3 * (conv + routed) + 13 * d \
        + 2 * 16384 * d + 16384
    assert abs(total - 602.2e6) < 0.1e6


def pytest_token_chain_is_seeded_and_learnable():
    from graftbench.datagen import token_chain

    params = {"graphs": 6, "tokens": 40, "vocab": 128, "successors": 4}
    first = token_chain.generate(params, 3_109_280_001)
    again = token_chain.generate(params, 3_109_280_001)
    other = token_chain.generate(params, 3_109_280_002)
    assert all(np.array_equal(a[0], b[0]) for a, b in zip(first, again))
    assert any(not np.array_equal(a[0], b[0]) for a, b in zip(first, other))
    x, pos, y = first[0]
    assert x.shape == (40, 2) and pos.shape == (40, 3) and y.shape == (1,)
    assert np.array_equal(x[1:, 0], x[:-1, 1])  # the target is the next token
    assert np.array_equal(pos[:, 0], np.arange(40)) and not pos[:, 1:].any()
    assert x.min() >= 0 and x.max() < 128 and np.array_equal(x, np.round(x))
    # First order, 4 successors a token: over many steps no token is followed
    # by more than 4 different ones.
    long = token_chain.generate({"graphs": 8, "tokens": 4000, "vocab": 16, "successors": 4}, 5)
    follows = {}
    for x, _, _ in long:
        for a, b in x.astype(int):
            follows.setdefault(a, set()).add(b)
    assert max(len(v) for v in follows.values()) <= 4


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """``tiny.make_copy`` shrinks ``hidden_dim`` and the heads alone; this
    family's other widths and its traffic are shrunk here."""
    root = tiny.make_copy(str(tmp_path_factory.mktemp("graftbench_tiny_lfm2")))
    path = os.path.join(root, "graftbench", "configs", "tiny_lfm2_8b_a1b_ep4.json")
    with open(path) as f:
        config = json.load(f)
    config["NeuralNetwork"]["Architecture"].update(SMALL)
    config["NeuralNetwork"]["Variables_of_interest"]["num_classes"] = [SMALL["vocab_size"]]
    with open(path, "w") as f:
        json.dump(config, f)
    path = os.path.join(root, "graftbench", "traffic", "tiny_train_seq1k_b4.json")
    with open(path) as f:
        traffic = json.load(f)
    traffic["graphs"].update(graphs=40, tokens=24, vocab=SMALL["vocab_size"])
    traffic["batch_size"] = 4
    with open(path, "w") as f:
        json.dump(traffic, f)
    return root


def pytest_cell_rehearsal_on_the_cpu(root):
    rc, line, text = tiny.run_cell(root, "tiny.train_seq1k_b4", seconds=0.5, trace=1,
                                   seed=3_109_280_031)
    assert rc == 0 and line["correct"], text[-3000:]
    assert "program vs plain float32 reference on 2 sequences at full width" in text
    assert "routing margin" in text and "router margin" in text
    with open(os.path.join(root, "graftbench", "out", "tiny.train_seq1k_b4", "last_run.json")) as f:
        run = json.load(f)
    arch = run["extra"]["hydragnn_config"]["NeuralNetwork"]["Architecture"]
    assert arch["model_type"] == "LFM2" and arch["num_experts_held"] == 4
    facts = run["facts"]
    assert facts["moe_rows_held"] > 0 and facts["moe_load_max"] >= facts["moe_load_min"]
    assert facts["step_ops"] > 0 and facts["reference"]["rel_l2"] < 1e-5
    # 28 train sequences of 24 tokens, 4 a step: one shape of 128 nodes.
    assert facts["pad_nodes"] == facts["steps"] * 128
    # On a CPU there is no device plane: the trace's readers find nothing and
    # are left out; the counter's reader needs no trace.
    assert {"setup_compile_s", "collate_ms_per_batch", "program_temp_gb",
            "moe_load_max_over_mean"} <= set(line["metrics"])
    assert not (NEW - {"moe_load_max_over_mean"}) & set(line["metrics"])
    assert not {"gather_step_ms", "agg_step_ms"} & set(line["metrics"])
    rc, line, text = tiny.run_cell(root, "tiny.train_seq1k_b4", seconds=0.5,
                                   seed=2_147_483_659)
    assert rc == 0 and line["correct"], text[-3000:]
    assert set(line["metrics"]) == {"train_graphs_per_s", "setup_s"}


def pytest_the_limits_tell_the_stated_precision_from_the_one_below():
    """The control of ``lfm2.REL_L2`` and ``lfm2.ROUTER_EPS`` AT THE
    CONFIGURATION'S WIDTHS (the two readings lie 28% apart, and only there
    do they lie where the family file says), one sequence of 256 tokens, on
    the CPU: this file's reference computed with matmul OPERANDS rounded to
    bf16 (what the configuration states, and the program runs) is
    ``correct`` against the float32 reference; computed with what it keeps
    between operations rounded to bf16 as well -- the residual stream,
    activations, the softmax's probabilities -- it is NOT, by the relative
    L2 limit; and a router with bf16 operands fails the router margin. A CPU
    emulation (~2 min, 5 GB): it decides nothing about a device number."""
    import jax
    import jax.numpy as jnp

    from graftbench.drivers.train_epochs import shaken
    from hydragnn_tpu.graphs import GraphSample, collate_graphs
    from hydragnn_tpu.models.create import create_model_config, init_model_variables

    arch = dict(
        _config()["NeuralNetwork"]["Architecture"], input_dim=1, output_dim=[16384],
        output_type=["node"], token_minmax=[0.0, 16383.0],
        head_loss=["cross_entropy"], class_minmax=[[0.0, 16383.0]],
    )
    model = create_model_config(arch)
    n = 256
    rng = np.random.default_rng(31)
    pos = np.zeros((n, 3), np.float32)
    pos[:, 0] = np.arange(n)
    tiny_batch = collate_graphs(
        [GraphSample(x=np.zeros((4, 1), np.float32), pos=pos[:4], y=np.zeros(4, np.float32),
                     y_loc=np.array([[0, 4]], np.int64), edge_index=np.zeros((2, 0), np.int32))],
        ("node",), (1,), with_positions=True,
    )
    variables = shaken(init_model_variables(model, tiny_batch), 31)
    params = jax.tree_util.tree_map(jnp.asarray, variables["params"])
    graph = {"x": (rng.integers(0, 16384, (n, 1)) / 16383.0).astype(np.float32), "pos": pos}

    class Operands(lfm2.Exact):
        mm = staticmethod(lambda a, w: lfm2.bf16(a) @ lfm2.bf16(w))

    class Below(Operands):
        keep = staticmethod(lfm2.bf16)

    readings = {}
    for plain in (Operands, Below):
        got, report = lfm2.logits(model, params, graph, None, plain)
        routing = jax.tree_util.tree_map(np.asarray, report["routing"])
        want, margins = lfm2.logits(model, params, graph, routing)
        readings[plain.__name__] = lfm2.compare(got, want) + (margins,)
    worst, rel, fail, margins = readings["Operands"]
    assert fail is None and 1.6e-2 < rel < 1.8e-2, readings["Operands"]
    assert margins["route_margin"] < lfm2.ROUTE_EPS
    assert margins["router_margin"] < lfm2.ROUTER_EPS  # its router is float32
    worst, rel, fail, margins = readings["Below"]
    assert fail is not None and "relative L2" in fail and rel > 2.1e-2, readings["Below"]
    # A router whose matmul rounds its operands to bf16 picks, somewhere among
    # these rows, an expert that float32 on the SAME input would not.
    router_in = routing["conv_3"]["router_in"]
    f = params["conv_3"]["feed_forward"]
    with jax.default_matmul_precision("highest"):
        rounded = jax.nn.sigmoid(lfm2.bf16(router_in) @ lfm2.bf16(f["gate"])) + f["expert_bias"]
        exact = np.asarray(jax.nn.sigmoid(router_in @ f["gate"]) + f["expert_bias"])
    chosen = np.asarray(jax.lax.top_k(rounded, 4)[1])
    assert lfm2.top_k_margin(rounded, chosen, 4) == 0.0
    assert lfm2.top_k_margin(exact, chosen, 4) > lfm2.ROUTER_EPS
