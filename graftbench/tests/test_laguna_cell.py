"""The LAGUNA family's files and its cell: discovery by name; ``attn_counts``
against bands counted by hand (a sequence shorter than the window, one equal
to it, one of three windows) and the other counts; the three new readers on a
made-up scope table and on recorded traces of programs without the scopes
(the parent's); ``BENCHMARK.json`` and the configuration's file against the
catalog's row; a tiny rehearsal of the cell on the CPU through
``main(argv, allow_cpu=True)``; and the control of the family's limits: the
precision below the stated one comes out NOT correct. Nothing here is a
device number."""

import importlib
import json
import os
import types

import numpy as np
import pytest

import tiny
from graftbench import families, flops, xplane_scopes
from graftbench.families import laguna
from graftbench.layer_metrics import (
    attn_full_step_ms, attn_window_roofline_share, attn_window_step_ms,
    moe_load_max_over_mean, moe_roofline_share, moe_step_ms,
)

REPO = tiny.REPO
DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "testdata")
CELL = "laguna_xs2_ep8.train_seq4k_b1"
SIBLING = "lfm2_8b_a1b_ep4.train_seq1k_b4"
NEW = {"attn_window_step_ms", "attn_full_step_ms", "attn_window_roofline_share"}
# Small widths of the same shape of stack (the per-layer lists stay the
# published ones): 5 layers, 4 / 6 query heads over 2 key-value heads of 8, a
# window of 8, 16 experts, 2 a token, 4 held from expert 2, a shared expert.
SMALL = dict(
    hidden_dim=32, num_conv_layers=5, num_attention_heads_per_layer=[4, 6, 6, 6] * 10,
    num_key_value_heads=2, head_dim=8, intermediate_size=48, moe_intermediate_size=24,
    shared_expert_intermediate_size=16, num_experts=16, num_experts_per_tok=2,
    num_experts_held=4, experts_offset=2, sliding_window=8, vocab_size=64,
)


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _config():
    with open(os.path.join(REPO, "graftbench", "configs", "laguna_xs2_ep8.json")) as f:
        return json.load(f)


def pytest_family_driver_and_generator_are_found_by_name():
    family = families.load("LAGUNA")
    assert family is laguna
    for name in ("encode", "counts", "logits", "compare", "moe_counts", "attn_counts"):
        assert callable(getattr(family, name)), name
    assert 0 < family.ROUTER_EPS < family.ROUTE_EPS < 0.1 and 0 < family.REL_L2 < 0.1
    with open(os.path.join(REPO, "graftbench", "traffic", "train_seq4k_b1.json")) as f:
        traffic = json.load(f)
    assert traffic == {
        "driver": "train_tokens", "chips": 1, "layout": "single",
        "graphs": {"generator": "token_chain", "graphs": 48, "tokens": 4096,
                   "vocab": 12544, "successors": 4},
        "batch_size": 1, "num_buckets": 1, "check_sequences": 1,
    }
    driver = importlib.import_module(f"graftbench.drivers.{traffic['driver']}")
    generator = importlib.import_module(f"graftbench.datagen.{traffic['graphs']['generator']}")
    assert callable(driver.run) and callable(generator.generate)


def _pairs_by_hand(n, window):
    """Count the (i, j) one by one."""
    return sum(1 for i in range(n) for j in range(n) if 0 <= i - j and (
        window is None or i - j < window
    ))


def pytest_attn_counts_against_bands_counted_by_hand():
    arch = _config()["NeuralNetwork"]["Architecture"]
    w = arch["sliding_window"]
    assert w == 512
    # Shorter than the window, equal to it, three windows: the band is the
    # triangle up to 512 tokens and 512 pairs a token after them.
    assert laguna.pairs(300, w) == _pairs_by_hand(300, w) == 300 * 301 // 2
    assert laguna.pairs(512, w) == _pairs_by_hand(512, w) == 512 * 513 // 2
    assert laguna.pairs(1536, w) == _pairs_by_hand(1536, w) == 512 * 513 // 2 + 1024 * 512
    assert laguna.pairs(1536) == 1536 * 1537 // 2
    # The cell's sequence: the band holds a quarter of the causal pairs.
    assert laguna.pairs(4096, w) / laguna.pairs(4096) == pytest.approx(0.2343, abs=1e-4)
    for lengths in ([300], [512], [1536], [300, 1536]):
        cores = laguna.attn_counts(arch, lengths)
        band = sum(_pairs_by_hand(n, w) for n in lengths)
        full = sum(_pairs_by_hand(n, None) for n in lengths)
        tokens = sum(lengths)
        # Layers 1-3 slide with 64 heads, layers 0 and 4 are full with 48.
        assert cores["window"]["layers"] == 3 and cores["full"]["layers"] == 2
        assert cores["window"]["pairs"] == 3 * band and cores["full"]["pairs"] == 2 * full
        assert cores["window"]["ops"] == 3 * (4 * band * 64 * 128 + 5 * band * 64)
        assert cores["full"]["ops"] == 2 * (4 * full * 48 * 128 + 5 * full * 48)
        assert cores["window"]["bytes"] == 3 * 4 * tokens * (2 * 64 + 2 * 8) * 128
    short = laguna.attn_counts(arch, [300])
    assert short["window"]["ops"] * 2 * 48 == short["full"]["ops"] * 3 * 64  # no band yet


def pytest_counts_by_hand():
    arch = dict(_config()["NeuralNetwork"]["Architecture"], **SMALL)
    d, f = 32, 24
    moe = laguna.moe_counts(arch, 10)
    assert moe["ops"] == 2 * 10 * 3 * d * f + 2 * 10 * f
    # 4 routed layers x 4 held experts x three matrices read once.
    assert moe["bytes"] == 4 * (4 * 4 * 3 * d * f + 10 * (2 * d + 3 * f + d))
    nodes, edges = 48, 4 * 48 - 6 * 2  # two sequences of 24 on a line
    uniform, width = laguna.counts(arch, nodes, edges)
    # Uniform routing sends K * held / experts = 1/2 row a token and layer.
    counted, _ = laguna.counts(arch, nodes, edges, routed_rows=4 * nodes / 2)
    assert width == d and flops.total(uniform)["ops"] == flops.total(counted)["ops"]
    cores = laguna.attn_counts(arch, [24, 24])
    for kind in ("window", "full"):
        assert sum(p["ops"] == int(cores[kind]["ops"]) for p in uniform) == 1
    # 24 tokens under a window of 8: 36 + 16 x 8 pairs a sequence and layer.
    assert cores["window"]["pairs"] == 3 * 2 * (36 + 16 * 8)
    assert cores["full"]["pairs"] == 2 * 2 * 300
    assert flops.total(uniform)["bytes"]["gather"] == {"fwd": 0, "bwd": 0}
    assert flops.total(uniform)["bytes"]["agg"] == {"fwd": 0, "bwd": 0}
    # The step's count follows the routed rows the program reports.
    fewer, _ = laguna.counts(arch, nodes, edges, routed_rows=nodes)
    saved = flops.total(counted)["ops"] - flops.total(fewer)["ops"]
    assert saved == laguna.moe_counts(arch, 2 * nodes)["ops"] - laguna.moe_counts(arch, nodes)["ops"]
    assert flops.train_step(
        dict(arch, output_type=["node"], output_dim=[64]), nodes, edges, 2
    )["ops"] > 3 * flops.total(uniform)["ops"]  # + pool and head


def _run(steps=33, remat=True):
    arch = dict(_config()["NeuralNetwork"]["Architecture"], remat=remat)
    cell = types.SimpleNamespace(
        trace_dir=None, out_dir=None, config={"NeuralNetwork": {"Architecture": arch}},
    )
    return types.SimpleNamespace(
        cell=cell, peaks={"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12},
        facts={"steps": steps, "chips": 1, "real_graphs": steps, "real_nodes": 4096 * steps,
               "moe_rows_held": 4096.0 * steps * 4, "moe_load_max": 200.0 * steps * 4,
               "moe_load_min": 90.0 * steps * 4},
    )


def pytest_readers_on_a_table(monkeypatch):
    def row(scope, seconds, module="conv_1", root="train", rooted=True, direction="fwd"):
        return dict(root=root, rooted=rooted, direction=direction, module=module,
                    scope=scope, seconds=seconds)

    rows = [
        row("hydragnn.attn.window", 0.33), row("hydragnn.attn.window", 0.66, direction="bwd"),
        row("hydragnn.attn.full", 0.99, module="conv_0"),
        row("hydragnn.attn.full", 0.66, module="conv_4", direction="bwd"),
        row("hydragnn.moe.experts", 0.33),
        row("hydragnn.attn.window", 5.0, root="eval"),  # not the train root's
        row("hydragnn.lfm2.attn", 1.0),  # another family's scope
        row("(model)", 1.0),  # the Dense layers: model_dense's remainder
    ]
    run = _run()
    monkeypatch.setattr(xplane_scopes, "table", lambda _run: {"rows": rows})
    assert attn_window_step_ms.read(run) == pytest.approx(30.0)
    assert attn_full_step_ms.read(run) == pytest.approx(50.0)
    assert moe_step_ms.read(run) == pytest.approx(10.0)
    assert all(xplane_scopes.bucket(r) == "model_dense" for r in rows)
    arch = run.cell.config["NeuralNetwork"]["Architecture"]
    band = 512 * 513 // 2 + 3584 * 512  # one sequence of 4096, a layer
    ops = 3 * (4 * band * 64 * 128 + 5 * band * 64)
    assert laguna.attn_counts(arch, [4096.0])["window"]["ops"] == ops
    # Three forwards a train step and the rematerialized one.
    assert attn_window_roofline_share.read(run) == pytest.approx(
        100.0 * 4 * ops / 197e12 / 30e-3
    )
    assert 0 < attn_window_roofline_share.read(run) < 100
    assert attn_window_roofline_share.read(_run(remat=False)) == pytest.approx(
        100.0 * 3 * ops / 197e12 / 30e-3
    )
    # The accepted readers find this family's counts by its model_type.
    counted = laguna.moe_counts(arch, 4 * 4096.0)
    least = max(3 * counted["ops"] / 197e12, 3 * counted["bytes"] / 819e9)
    assert moe_roofline_share.read(run) == pytest.approx(100.0 * least / 10e-3)
    # 200 rows on the fullest of 32 held experts against a mean of 128.
    assert moe_load_max_over_mean.read(run) == pytest.approx(200.0 / 128.0)


def pytest_readers_return_nothing_on_a_program_without_the_scopes(monkeypatch):
    """The recorded traces are of programs that open neither attention scope
    (as this PR's parent does not): nothing is returned and nothing raises."""
    readers = (attn_window_step_ms, attn_full_step_ms, attn_window_roofline_share)
    for name in ("scoped_v5e.xplane.pb", "small_v5e.xplane.pb"):
        table = xplane_scopes.by_scope(os.path.join(DATA, name))
        run = _run()
        monkeypatch.setattr(xplane_scopes, "table", lambda _run, t=table: t)
        assert all(r.read(run) is None for r in readers)
    monkeypatch.setattr(xplane_scopes, "table", lambda _run: None)  # no trace
    assert all(r.read(_run()) is None for r in readers)
    rows = [dict(root="train", rooted=True, direction="fwd", module="conv_1",
                 scope="hydragnn.attn.window", seconds=0.02)]
    monkeypatch.setattr(xplane_scopes, "table", lambda _run: {"rows": rows})
    bare = _run()
    bare.facts = {"steps": 2, "chips": 1}  # a driver without the padding counts
    assert attn_window_roofline_share.read(bare) is None
    other = _run()
    other.cell.config["NeuralNetwork"]["Architecture"] = {"model_type": "LFM2"}
    assert attn_window_roofline_share.read(other) is None  # a family without attn_counts


def pytest_benchmark_json_holds_the_cell():
    bench = _bench()
    cells = bench["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    (entry,) = [w for w in cells if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "laguna_xs2_ep8", "train_seq4k_b1", 1
    )
    assert len(entry["why"]) <= 200 and "1/8" in entry["why"]
    (config,) = [c for c in bench["configs"] if c["name"] == "laguna_xs2_ep8"]
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert config["source"] == _config()["source"]

    def reported(cell):
        return {
            m["name"] for kind in ("end_to_end", "per_layer") for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]
        }

    # What the sibling token cell reports, less its own token mixers' time,
    # plus the three.
    assert reported(CELL) == (reported(SIBLING) - {"seqmix_step_ms"}) | NEW
    own = [m for m in bench["per_layer"] if m["name"] in (
        "attn_window_step_ms", "attn_full_step_ms", "attn_window_roofline_share"
    )]
    assert len(own) == 3
    for m in own:
        assert m["workloads"] == [CELL] and m["moves"] == "train_graphs_per_s"
        assert m["layer"] == "model" and m["source"] == "device_trace"
    for name in reported(CELL) - {"train_graphs_per_s", "setup_s"}:
        assert os.path.exists(
            os.path.join(REPO, "graftbench", "layer_metrics", name + ".py")
        ), name


def pytest_the_configuration_keeps_every_published_width():
    """The file's top level is the catalog row's ``config`` as run: every
    number but the three under ``reduced``, the nested ``rope_parameters``
    and the three per-layer lists whole; the program's ``Architecture`` says
    the same under its own keys."""
    catalog = {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
        "intermediate_size": 8192, "num_hidden_layers": 40, "num_attention_heads": 48,
        "num_key_value_heads": 8, "head_dim": 128, "max_position_embeddings": 262144,
        "attention_bias": False, "rms_norm_eps": 1e-06, "num_experts": 256,
        "num_experts_per_tok": 8, "moe_intermediate_size": 512,
        "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
        "gating": True, "sliding_window": 512, "moe_apply_router_weight_on_input": False,
        "partial_rotary_factor": 0.5, "moe_routed_scaling_factor": 2.5,
    }
    config = _config()
    assert set(config["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    for key, value in catalog.items():
        if key in config["reduced"]:
            assert config["published"][key] == value and config[key] != value, key
        else:
            assert config[key] == value, key
    assert config["rope_parameters"] == {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1, "beta_fast": 64,
            "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5,
        },
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1,
        },
        "original_max_position_embeddings": 4096,
    }
    period = ["full_attention"] + ["sliding_attention"] * 3
    assert config["layer_types"] == period * 10
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 39
    assert config["num_attention_heads_per_layer"] == [48, 64, 64, 64] * 10
    assert not any(
        k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size" for k in config["reduced"]
    )
    for key in ("stands_for", "assumed", "departures", "parameters", "published"):
        assert config[key], key
    assert "8" in config["stands_for"] and "expert parallelism" in config["stands_for"]
    arch = config["NeuralNetwork"]["Architecture"]
    assert arch["model_type"] == "LAGUNA" and arch["remat"] is True
    assert arch["hidden_dim"] == config["hidden_size"]
    assert arch["num_conv_layers"] == config["num_hidden_layers"] == 5
    for key in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer"):
        assert arch[key] == config[key], key
    assert arch["layer_types"][:5] == period + ["full_attention"]
    assert (arch["num_experts"], arch["num_experts_held"], arch["experts_offset"]) == (256, 32, 0)
    assert arch["num_experts_held"] == config["num_experts"]
    assert arch["vocab_size"] == config["vocab_size"] == 12544 == 100352 // 8
    for kind in ("full_attention", "sliding_attention"):
        assert arch["rope_parameters"][kind] == config["rope_parameters"][kind]
    for key in ("intermediate_size", "moe_intermediate_size", "shared_expert_intermediate_size",
                "num_experts_per_tok", "num_key_value_heads", "head_dim", "sliding_window",
                "rms_norm_eps", "moe_routed_scaling_factor", "gating"):
        assert arch[key] == config[key], key
    voi = config["NeuralNetwork"]["Variables_of_interest"]
    assert voi["loss"] == ["cross_entropy"] and voi["num_classes"] == [arch["vocab_size"]]
    # The parameters the file states.
    d = 2048
    full = 2 * d * 48 * 128 + 2 * d * 8 * 128 + d * 48
    sliding = 2 * d * 64 * 128 + 2 * d * 8 * 128 + d * 64
    dense, routed = 3 * d * 8192, 32 * 3 * d * 512 + 3 * d * 512 + d * 256
    total = (full + dense) + 3 * (sliding + routed) + (full + routed) + 11 * d \
        + 2 * 12544 * d + 12544
    assert total == 691_636_480 and "691.6M" in config["parameters"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """``tiny.make_copy`` shrinks ``hidden_dim``, the depth and the heads
    alone; this family's other widths and its traffic are shrunk here."""
    root = tiny.make_copy(str(tmp_path_factory.mktemp("graftbench_tiny_laguna")))
    path = os.path.join(root, "graftbench", "configs", "tiny_laguna_xs2_ep8.json")
    with open(path) as f:
        config = json.load(f)
    config["NeuralNetwork"]["Architecture"].update(SMALL)
    config["NeuralNetwork"]["Variables_of_interest"]["num_classes"] = [SMALL["vocab_size"]]
    with open(path, "w") as f:
        json.dump(config, f)
    path = os.path.join(root, "graftbench", "traffic", "tiny_train_seq4k_b1.json")
    with open(path) as f:
        traffic = json.load(f)
    traffic["graphs"].update(graphs=40, tokens=24, vocab=SMALL["vocab_size"])
    traffic["batch_size"] = 2
    with open(path, "w") as f:
        json.dump(traffic, f)
    return root


def pytest_cell_rehearsal_on_the_cpu(root):
    rc, line, text = tiny.run_cell(root, "tiny.train_seq4k_b1", seconds=0.5, trace=1,
                                   seed=3_309_280_031)
    assert rc == 0 and line["correct"], text[-3000:]
    assert "program vs plain float32 reference on 1 sequences at full width" in text
    assert "routing margin" in text and "router margin" in text
    with open(os.path.join(root, "graftbench", "out", "tiny.train_seq4k_b1", "last_run.json")) as f:
        run = json.load(f)
    arch = run["extra"]["hydragnn_config"]["NeuralNetwork"]["Architecture"]
    assert arch["model_type"] == "LAGUNA" and arch["num_experts_held"] == 4
    assert arch["remat"] is True and arch["sliding_window"] == 8
    facts = run["facts"]
    assert facts["moe_rows_held"] > 0 and facts["moe_load_max"] >= facts["moe_load_min"]
    assert facts["step_ops"] > 0 and facts["reference"]["rel_l2"] < 1e-5
    # 28 train sequences of 24 tokens, 2 a step: one shape of 64 nodes.
    assert facts["pad_nodes"] == facts["steps"] * 64
    # On a CPU there is no device plane: the trace's readers find nothing and
    # are left out; the counter's reader needs no trace.
    assert {"setup_compile_s", "collate_ms_per_batch", "program_temp_gb",
            "moe_load_max_over_mean"} <= set(line["metrics"])
    assert not NEW & set(line["metrics"])
    assert not {"gather_step_ms", "agg_step_ms", "seqmix_step_ms"} & set(line["metrics"])
    rc, line, text = tiny.run_cell(root, "tiny.train_seq4k_b1", seconds=0.5,
                                   seed=2_147_483_659)
    assert rc == 0 and line["correct"], text[-3000:]
    assert set(line["metrics"]) == {"train_graphs_per_s", "setup_s"}


def pytest_the_limits_tell_the_stated_precision_from_the_one_below():
    """The control of ``laguna.REL_L2`` and ``laguna.ROUTER_EPS`` AT THE
    CONFIGURATION'S WIDTHS, one sequence of 256 tokens, on the CPU: this
    file's reference computed with matmul OPERANDS rounded to bf16 (what the
    configuration states, and the program runs) is ``correct`` against the
    float32 reference; computed with what it keeps between operations
    rounded to bf16 as well -- the residual stream, activations, the
    softmax's probabilities -- it is NOT, by the relative L2 limit (the two
    readings lie 8% apart here, 1.96e-2 and 2.13e-2, and the limit between
    them); and a router with bf16 operands fails the router margin. A CPU
    emulation (~1.5 min, 7 GB): it decides nothing about a device number."""
    import jax
    import jax.numpy as jnp

    from graftbench.drivers.train_epochs import shaken
    from hydragnn_tpu.graphs import GraphSample, collate_graphs
    from hydragnn_tpu.models.create import create_model_config, init_model_variables

    arch = dict(
        _config()["NeuralNetwork"]["Architecture"], input_dim=1, output_dim=[12544],
        output_type=["node"], token_minmax=[0.0, 12543.0],
        head_loss=["cross_entropy"], class_minmax=[[0.0, 12543.0]],
    )
    model = create_model_config(arch)
    n = 256
    rng = np.random.default_rng(33)
    pos = np.zeros((n, 3), np.float32)
    pos[:, 0] = np.arange(n)
    tiny_batch = collate_graphs(
        [GraphSample(x=np.zeros((4, 1), np.float32), pos=pos[:4], y=np.zeros(4, np.float32),
                     y_loc=np.array([[0, 4]], np.int64), edge_index=np.zeros((2, 0), np.int32))],
        ("node",), (1,), with_positions=True,
    )
    variables = shaken(init_model_variables(model, tiny_batch), 33)
    params = jax.tree_util.tree_map(jnp.asarray, variables["params"])
    graph = {"x": (rng.integers(0, 12544, (n, 1)) / 12543.0).astype(np.float32), "pos": pos}

    class Operands(laguna.Exact):
        mm = staticmethod(lambda a, w: laguna.bf16(a) @ laguna.bf16(w))

    class Below(Operands):
        keep = staticmethod(laguna.bf16)

    readings = {}
    for plain in (Operands, Below):
        got, report = laguna.logits(model, params, graph, None, plain)
        routing = jax.tree_util.tree_map(np.asarray, report["routing"])
        want, margins = laguna.logits(model, params, graph, routing)
        readings[plain.__name__] = laguna.compare(got, want) + (margins,)
    worst, rel, fail, margins = readings["Operands"]
    assert fail is None and 1.9e-2 < rel < 2.0e-2, readings["Operands"]
    assert margins["route_margin"] < laguna.ROUTE_EPS
    assert margins["router_margin"] < laguna.ROUTER_EPS  # its router is float32
    worst, rel, fail, margins = readings["Below"]
    assert fail is not None and "relative L2" in fail and rel > 2.1e-2, readings["Below"]
    # A router whose matmul rounds its operands to bf16 picks, somewhere among
    # these rows, an expert that float32 on the SAME input would not.
    router_in = routing["conv_3"]["router_in"]
    gate = params["conv_3"]["feed_forward"]["gate"]
    with jax.default_matmul_precision("highest"):
        rounded = jax.nn.sigmoid(laguna.bf16(router_in) @ laguna.bf16(gate))
        exact = np.asarray(jax.nn.sigmoid(router_in @ gate))
    chosen = np.asarray(jax.lax.top_k(rounded, 8)[1])
    assert laguna.top_k_margin(rounded, chosen, 8) == 0.0
    assert laguna.top_k_margin(exact, chosen, 8) > laguna.ROUTER_EPS
