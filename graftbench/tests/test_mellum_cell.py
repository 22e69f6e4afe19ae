"""The MELLUM family's files and its serving cell: discovery by name; the
traffic's parameters; the counts against hand numbers; the two new readers on
a made-up scope table and on recorded traces of programs without the scope
(the parent's); ``BENCHMARK.json`` by MEMBERSHIP (what the cell, the
configuration and each reader hold, wherever they stand in their lists: a
later cell appended after this one must not fail this file) and the
configuration's file against the catalog's row; a tiny rehearsal of the cell
on the CPU through ``main(argv, allow_cpu=True)``; whole runs that must come
out NOT correct (a reply altered in the engine, one expert's weights perturbed
in the engine alone, a window layer run as a full one in the engine alone:
the fault this family can have that a stack of full layers cannot); and the
control of the family's limits at the published widths: the precision below
the stated one comes out NOT correct. Nothing here is a device number."""

import importlib
import json
import os
import types

import numpy as np
import pytest

import tiny
from graftbench import families, flops, xplane_scopes
from graftbench.families import mellum
from graftbench.layer_metrics import (
    serve_attn_core_roofline, serve_attn_window_ms_per_flush, serve_attn_window_roofline,
    serve_moe_load_max_over_mean, serve_moe_roofline,
)

REPO = tiny.REPO
DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "testdata")
CELL = "mellum2_12b_l4.serve_score_docs_c4_v98k"
SIBLING = "mistral_small4_ep8.serve_score_docs_c4"
NEW = {"serve_attn_window_ms_per_flush", "serve_attn_window_roofline"}
# Small widths with the published PATTERN: three band layers of a window of 4
# to one full layer, 4 query heads on 2 key-value heads, 8 experts all held,
# 2 a token.
SMALL = dict(
    hidden_dim=32, num_conv_layers=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=8, sliding_window=4, moe_intermediate_size=24, num_experts=8,
    num_experts_per_tok=2, num_experts_held=8, experts_offset=0, vocab_size=64,
)


def _json(*path):
    with open(os.path.join(REPO, *path)) as f:
        return json.load(f)


def _bench():
    return _json("BENCHMARK.json")


def _config():
    return _json("graftbench", "configs", "mellum2_12b_l4.json")


def _traffic():
    return _json("graftbench", "traffic", "serve_score_docs_c4_v98k.json")


def pytest_family_driver_and_generator_are_found_by_name():
    family = families.load("MELLUM")
    assert family is mellum
    for name in ("encode", "logits", "logprobs", "compare", "rel_l2_limit", "counts",
                 "attn_counts", "moe_counts", "head_counts", "Operands", "Below"):
        assert callable(getattr(family, name)), name
    assert 0 < family.REL_L2 < 0.1 and 0 < family.ROUTE_EPS < 0.5 and family.ATOL == 0.2
    traffic = _traffic()
    assert traffic["driver"] == "serve_tokens" and traffic["chips"] == 1
    assert traffic["clients"] == traffic["engine"]["max_batch_graphs"] == 4
    assert traffic["engine"] == {
        "max_batch_graphs": 4, "max_delay_ms": 1000.0, "queue_limit": 8,
        "precision": "f32", "packing": False,
    }
    assert "matmul_precision" not in traffic and traffic["check_replies"] == 2
    graphs = traffic["graphs"]
    assert (graphs["generator"], graphs["vocab"], graphs["successors"]) == ("token_chain", 98304, 4)
    assert graphs["documents"] == [[2048, 8], [3072, 6], [4096, 4], [6144, 3]]
    assert traffic["bucket_ladder"] == [[12288, 8], [15872, 8], [19968, 8], [25088, 8]]
    # The Mistral cell's traffic with the vocabulary whole, and nothing else.
    sibling = _json("graftbench", "traffic", "serve_score_docs_c4.json")
    for key in ("driver", "chips", "clients", "engine", "bucket_ladder", "check_replies"):
        assert traffic[key] == sibling[key], key
    assert dict(graphs, vocab=16384, note="") == dict(sibling["graphs"], note="")
    # Every document is under YaRN's trained context and over the window.
    assert all(1024 < t < 8192 for t, _ in graphs["documents"])
    driver = importlib.import_module(f"graftbench.drivers.{traffic['driver']}")
    generator = importlib.import_module(f"graftbench.datagen.{graphs['generator']}")
    assert callable(driver.run) and callable(generator.generate)


def pytest_counts_by_hand():
    arch = _config()["NeuralNetwork"]["Architecture"]
    w = 1024
    # The band's REAL pairs: a token, its own place and the 1023 before it;
    # the first 1024 tokens see a triangle.
    band = {n: sum(min(i + 1, w) for i in range(n)) for n in (2048, 6144)}
    assert band == {2048: 1_573_376, 6144: 5_767_680}
    assert all(band[n] == w * n - w * (w - 1) // 2 for n in band)
    for lengths in ([2048], [6144], [2048, 3072, 4096, 6144]):
        cores = mellum.attn_counts(arch, lengths)
        tri = sum(n * (n + 1) // 2 for n in lengths)
        bnd = sum(w * n - w * (w - 1) // 2 for n in lengths)
        assert (cores["full"]["layers"], cores["window"]["layers"]) == (1, 3)
        assert cores["full"]["pairs"] == tri and cores["window"]["pairs"] == 3 * bnd
        # 4 x 128 operations a pair and head over 32 heads, and the softmax's 5.
        assert cores["full"]["ops"] == 4 * 128 * tri * 32 + 5 * tri * 32
        assert cores["window"]["ops"] == 3 * (4 * 128 * bnd * 32 + 5 * bnd * 32)
        # q and the output over 32 heads, k and v over 4, float32, once a layer.
        assert cores["full"]["bytes"] == 4 * sum(lengths) * (2 * 32 + 2 * 4) * 128
        assert cores["window"]["bytes"] == 3 * cores["full"]["bytes"]
    # A document under the window is a triangle on every layer.
    short = mellum.attn_counts(arch, [700])
    assert short["window"]["pairs"] == 3 * short["full"]["pairs"] == 3 * 700 * 701 // 2
    # The mean document: a window layer's real pairs are 45% of a full layer's.
    docs = [t for t, n in _traffic()["graphs"]["documents"] for _ in range(n)]
    mean = mellum.attn_counts(arch, docs)
    assert mean["window"]["pairs"] / 3 / mean["full"]["pairs"] == pytest.approx(0.446, abs=0.003)
    assert mean["full"]["pairs"] / 21 == pytest.approx(6.44e6, rel=2e-3)
    assert mean["window"]["pairs"] / 3 / 21 == pytest.approx(2.87e6, rel=2e-3)
    # The grouped matmuls: three projections a row; 64 held experts' three
    # float32 matrices read once a layer, 4 layers.
    moe = mellum.moe_counts(arch, 1000)
    assert moe["ops"] == 2 * 1000 * 3 * 2304 * 896 + 2 * 1000 * 896
    assert moe["bytes"] == 4 * (4 * 64 * 3 * 2304 * 896 + 1000 * (2 * 2304 + 3 * 896 + 2304))
    # At the 1,658 rows an expert of a mean flush the operations take 3.4
    # times the WEIGHTS' bytes (ISSUE 41's reckoning), and as long as all the
    # bytes of the convention (float32 row arrays in and out of each grouped
    # matmul, 38 kB a row): neither arm leads by more than a few percent, where
    # the share cells' bytes lead by 2-4 times.
    rows = 4 * 13263 * 8
    mean = mellum.moe_counts(arch, rows)
    assert rows / 4 / 64 == pytest.approx(1658, abs=1)
    weights = 4 * 4 * 64 * 3 * 2304 * 896
    assert mean["ops"] / 197e12 / (weights / 819e9) == pytest.approx(3.45, abs=0.05)
    assert mean["ops"] / 197e12 / (mean["bytes"] / 819e9) == pytest.approx(0.97, abs=0.02)
    # A token's operations outside the cores: 42.5 MFLOP of projections, 0.3
    # the router, 99 the experts a layer; 453 the head.
    nodes = 1000
    parts, width = mellum.counts(arch, nodes, 0, lengths=[nodes])
    assert width == 2304
    attn = 2 * (2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304)
    assert round(attn / 1e6, 1) == 42.5 and round(8 * 2 * 3 * 2304 * 896 / 1e6) == 99
    cores = mellum.attn_counts(arch, [nodes])
    dense_ops = sum(p["ops"] for p in parts) - int(cores["full"]["ops"]) - int(
        cores["window"]["ops"]
    ) - int(mellum.moe_counts(arch, 4 * nodes * 8)["ops"])
    assert 1.00 < dense_ops / nodes / 4 / (attn + 2 * 2304 * 64) < 1.02
    head = flops.total(mellum.head_counts(arch, nodes, 98304))["ops"] / nodes
    assert round(head / 1e6) == 454  # 2 x 2304 x 98304 = 453.0 and the log-softmax's 6 a logit
    fewer, _ = mellum.counts(arch, nodes, 0, routed_rows=100, lengths=[nodes])
    saved = flops.total(parts)["ops"] - flops.total(fewer)["ops"]
    assert saved == int(mellum.moe_counts(arch, 32000)["ops"]) - int(
        mellum.moe_counts(arch, 100)["ops"]
    )
    # flops.py's shared count (pool and heads) finds the family by its type.
    whole = flops.forward(
        dict(arch, output_type=["node"], output_dim=[98304]), nodes, 0, 1
    )["ops"]
    assert whole > flops.total(parts)["ops"] + nodes * 2 * 2304 * 98304


def _run(flushes=10):
    arch = _config()["NeuralNetwork"]["Architecture"]
    cell = types.SimpleNamespace(
        trace_dir=None, out_dir=None, config={"NeuralNetwork": {"Architecture": arch}},
    )
    lengths = [2048, 3072, 4096, 6144] * flushes
    return types.SimpleNamespace(
        cell=cell, peaks={"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12},
        facts={"flushes": flushes, "steps": flushes, "chips": 1, "doc_lengths": lengths,
               "moe_rows_held": 15360.0 * 8 * 4 * flushes, "moe_load_max": 2200.0 * 4 * flushes,
               "moe_fallback_layers": 0},
    )


def pytest_readers_on_a_table(monkeypatch):
    def row(scope, seconds, module="conv_1"):
        return dict(root="other", rooted=False, direction="fwd", module=module,
                    scope=scope, seconds=seconds)

    rows = [
        row("hydragnn.attn.window", 0.10, module="conv_0"), row("hydragnn.attn.window", 0.12),
        row("hydragnn.attn.window", 0.08, module="conv_2"),
        row("hydragnn.attn.full", 0.20, module="conv_3"),
        row("hydragnn.moe.experts", 0.90), row("hydragnn.moe.route", 0.25),
        row("hydragnn.head.logprob", 0.40, module="(model)"), row("(model)", 0.2),
    ]
    run = _run()
    monkeypatch.setattr(xplane_scopes, "table", lambda _run: {"rows": rows})
    assert serve_attn_window_ms_per_flush.read(run) == pytest.approx(30.0)
    w = 1024
    bnd = sum(w * n - w * (w - 1) // 2 for n in (2048, 3072, 4096, 6144))
    ops = 3 * (4 * 128 * bnd * 32 + 5 * bnd * 32)  # one flush, three layers
    assert serve_attn_window_roofline.read(run) == pytest.approx(100.0 * ops / 197e12 / 30e-3)
    assert 0 < serve_attn_window_roofline.read(run) < 100
    # The accepted readers find this family's counts by its type: the full
    # layer's triangle, and the experts by the larger of their two arms.
    tri = sum(n * (n + 1) // 2 for n in (2048, 3072, 4096, 6144))
    assert serve_attn_core_roofline.read(run) == pytest.approx(
        100.0 * (4 * 128 * tri * 32 + 5 * tri * 32) / 197e12 / 20e-3
    )
    arch = run.cell.config["NeuralNetwork"]["Architecture"]
    counted = mellum.moe_counts(arch, 15360.0 * 8 * 4)
    least = max(counted["ops"] / 197e12, counted["bytes"] / 819e9)
    assert serve_moe_roofline.read(run) == pytest.approx(100.0 * least / 90e-3)
    assert 0 < serve_moe_roofline.read(run) < 100
    # 2200 rows on the fullest of 64 held experts against a mean of 15360 x 8 / 64.
    assert serve_moe_load_max_over_mean.read(run) == pytest.approx(2200.0 / 1920.0)


def pytest_readers_return_nothing_on_a_program_without_the_scope(monkeypatch):
    """The recorded traces are of programs that open no ``hydragnn.attn.window``
    in a serving window (as this PR's parent cannot): nothing is returned and
    nothing raises. Nor for a family whose ``attn_counts`` has no ``window``
    (the Mistral cell's), nor for one without ``attn_counts``."""
    readers = (serve_attn_window_ms_per_flush, serve_attn_window_roofline)
    for name in ("scoped_v5e.xplane.pb", "small_v5e.xplane.pb"):
        table = xplane_scopes.by_scope(os.path.join(DATA, name))
        monkeypatch.setattr(xplane_scopes, "table", lambda _run, t=table: t)
        assert all(r.read(_run()) is None for r in readers)
    monkeypatch.setattr(xplane_scopes, "table", lambda _run: None)  # no trace
    assert all(r.read(_run()) is None for r in readers)
    rows = [dict(root="other", rooted=False, direction="fwd", module="conv_1",
                 scope="hydragnn.attn.window", seconds=0.02)]
    monkeypatch.setattr(xplane_scopes, "table", lambda _run: {"rows": rows})
    bare = _run()
    bare.facts = {"flushes": 2, "chips": 1}  # a driver without the documents' lengths
    assert serve_attn_window_ms_per_flush.read(bare) == pytest.approx(10.0)
    assert serve_attn_window_roofline.read(bare) is None
    for other in (
        _json("graftbench", "configs", "mistral_small4_ep8.json")["NeuralNetwork"]["Architecture"],
        {"model_type": "PNA"},
    ):
        run = _run()
        run.cell.config["NeuralNetwork"]["Architecture"] = other
        assert serve_attn_window_roofline.read(run) is None


def pytest_benchmark_json_holds_the_cell_by_membership():
    """What the entries hold, not where they stand: a cell appended later
    leaves this test alone (``test_mistral4_cell.py``'s and
    ``test_serve_cell.py``'s assertions on LAST entries are one ``benchmark``
    PR's to turn into these; PERF.md section 7)."""
    bench = _bench()
    cells = bench["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    (entry,) = [w for w in cells if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "mellum2_12b_l4", "serve_score_docs_c4_v98k", 1
    )
    assert len(entry["why"]) <= 200 and "1,658 rows an expert" in entry["why"]
    (config,) = [c for c in bench["configs"] if c["name"] == "mellum2_12b_l4"]
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["source"] == _config()["source"] and len(config["why"]) <= 200
    assert config["file"] == "graftbench/configs/mellum2_12b_l4.json"
    assert [c["config"] for c in cells].count("mellum2_12b_l4") == 1  # one cell, no second

    def reported(cell):
        return {
            m["name"] for kind in ("end_to_end", "per_layer") for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]
        }

    # What the Mistral serving cell reports, less its latent chains, plus the two.
    assert reported(CELL) == (reported(SIBLING) - {"serve_attn_latent_ms_per_flush"}) | NEW
    assert {"serve_graphs_per_s", "serve_p50_ms", "serve_p95_ms", "setup_s", "serve_mfu",
            "serve_attn_core_roofline", "serve_moe_roofline"} <= reported(CELL)
    own = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    assert set(own) == NEW
    for m in own.values():
        assert CELL in m["workloads"] and m["moves"] == "serve_graphs_per_s"
        assert m["layer"] == "model" and m["source"] == "device_trace"
        assert (m["unit"] == "%") == m["name"].endswith("_roofline")
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    for name in reported(CELL) - {"serve_graphs_per_s", "serve_p50_ms", "serve_p95_ms", "setup_s"}:
        assert os.path.exists(
            os.path.join(REPO, "graftbench", "layer_metrics", name + ".py")
        ), name
    # Every bound is what it was.
    assert {m["name"]: m["bound"] for m in bench["end_to_end"]} == {
        "train_graphs_per_s": 0.025, "serve_graphs_per_s": 0.1, "serve_p50_ms": 0.1,
        "serve_p95_ms": 0.1, "setup_s": 0.1,
    }
    assert bench["run_seconds"] == 25


def pytest_the_configuration_keeps_every_published_width():
    """The file's top level is the catalog row's ``config`` as run: every key
    but ``num_hidden_layers``, the lists and ``rope_parameters`` whole; the
    program's ``Architecture`` says the same under its own keys."""
    period = ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"]
    catalog = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2304,
        "intermediate_size": 7168, "layer_types": period * 7, "mlp_layer_types": ["sparse"] * 28,
        "max_position_embeddings": 131072, "max_window_layers": 0, "model_type": "mellum",
        "moe_intermediate_size": 896, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 64, "num_experts_per_tok": 8, "num_hidden_layers": 28,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "sliding_window": 1024,
        "tie_word_embeddings": False, "vocab_size": 98304, "use_sliding_window": True,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
                "attention_factor": 1.2772588722239782,
            },
            "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
        },
    }
    config = _config()
    assert list(config["reduced"]) == ["num_hidden_layers"] == list(config["published"])
    for key, value in catalog.items():
        assert config[key] == (4 if key == "num_hidden_layers" else value), key
    assert config["published"]["num_hidden_layers"] == 28
    arch = config["NeuralNetwork"]["Architecture"]
    assert arch["model_type"] == "MELLUM" and arch["hidden_dim"] == catalog["hidden_size"]
    assert arch["num_conv_layers"] == 4  # the floor, and one whole period
    assert arch["layer_types"][:4] == period and arch["mlp_layer_types"][:4] == ["sparse"] * 4
    assert (arch["num_experts"], arch["num_experts_held"], arch["experts_offset"]) == (64, 64, 0)
    assert arch["vocab_size"] == catalog["vocab_size"]
    assert config["NeuralNetwork"]["Variables_of_interest"]["num_classes"] == [98304]
    for key in ("layer_types", "mlp_layer_types", "num_attention_heads", "num_key_value_heads",
                "head_dim", "sliding_window", "rope_parameters", "moe_intermediate_size",
                "num_experts", "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps"):
        assert arch[key] == catalog[key], key
    for key in ("router", "no norm on q / k", "window", "yarn truncate", "eps", "output head",
                "precision"):
        assert key in config["assumed"], key
    assert "Qwen3" in config["assumed"]["no norm on q / k"]
    for key in ("the MTP head is not built", "no auxiliary balance loss", "head bias", "data"):
        assert key in config["departures"], key
    assert "8.50 GB" in config["parameters"] and "EVERY one of its 64 experts" in config["stands_for"]
    assert "~7 times" in config["reduced"]["num_hidden_layers"]


# --------------------------------------------------------------- whole runs
def _tiny_cell(root):
    """``tiny.make_copy`` shrinks ``hidden_dim``, the depth and the serving
    mix's clients and ladder alone; this family's other widths, its depth of
    one whole period, its vocabulary, its documents (both sides of a window of
    4) and a ladder that holds four of the longest are set here, in the
    copy's files."""
    path = os.path.join(root, "graftbench", "configs", "tiny_mellum2_12b_l4.json")
    with open(path) as f:
        config = json.load(f)
    config["NeuralNetwork"]["Architecture"].update(SMALL)
    config["NeuralNetwork"]["Variables_of_interest"]["num_classes"] = [SMALL["vocab_size"]]
    with open(path, "w") as f:
        json.dump(config, f)
    path = os.path.join(root, "graftbench", "traffic", "tiny_serve_score_docs_c4_v98k.json")
    with open(path) as f:
        traffic = json.load(f)
    assert traffic["clients"] == 4
    traffic["graphs"].update(vocab=SMALL["vocab_size"], documents=[[3, 4], [9, 3], [14, 2]])
    traffic["bucket_ladder"] = [[32, 8], [64, 8]]
    with open(path, "w") as f:
        json.dump(traffic, f)
    return tiny.cell(root, "serve_tokens", model="MELLUM")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_copy(str(tmp_path_factory.mktemp("graftbench_tiny_mellum")))


def pytest_tiny_cell_runs_correct_and_traced_prints_its_counters(root):
    name = _tiny_cell(root)
    rc, last, text = tiny.run_cell(root, name, seconds=1.0, seed=4_100_000_019)
    assert rc == 0 and last["correct"], text[-3000:]
    assert last["failed"] == 0 and last["attempted"] >= 8
    assert set(last["metrics"]) == {"serve_graphs_per_s", "serve_p50_ms", "serve_p95_ms", "setup_s"}
    compared = last["compared"]
    assert set(compared) == {"reply_rel_l2", "reply_max_diff", "route_margin"}
    assert compared["reply_rel_l2"]["value"] < 1e-5 < compared["reply_rel_l2"]["limit"]
    assert compared["route_margin"]["value"] < 1e-5
    assert "ladder rungs warmed: [(32, 8), (64, 8)]" in text
    assert "0 short of full" in text and "0 off the ladder" in text
    assert "layers past the capacity 0" in text
    rc, last, text = tiny.run_cell(root, name, seconds=1.0, trace=1, seed=7)
    assert rc == 0 and last["correct"], text[-3000:]
    got = set(last["metrics"])
    # On the CPU no device operation is traced: the by-scope readers return
    # nothing; the counters' readers and the three without a list do.
    assert {"serve_moe_load_max_over_mean", "serve_batch_occupancy", "serve_queue_wait_ms",
            "serve_padding_waste_nodes", "setup_init_s", "setup_compile_s"} <= got
    assert not got & (NEW | {"serve_attn_core_roofline", "serve_moe_roofline", "serve_mfu"})
    assert last["metrics"]["serve_batch_occupancy"]["value"] == 1.0
    assert last["metrics"]["serve_moe_load_max_over_mean"]["value"] >= 1.0


ALTERED = """
from hydragnn_tpu.serve import engine as _e
_plain = _e.InferenceEngine._denormalize
_e.InferenceEngine._denormalize = lambda self, ihead, value: _plain(self, ihead, value) + 0.5
"""
PERTURBED = """
import jax
from graftbench.drivers import serve_tokens as _d
_start = _d.start_engine
def _other(model, weights, traffic, **control):
    params = jax.tree_util.tree_map(lambda a: a, dict(weights["params"]))
    # ONE expert, in the engine alone; in every layer, so that whichever
    # replies the run checks some token of theirs was routed to it (in one
    # layer a 14-token document misses it once in fifty).
    for name in [k for k in params if k.startswith("conv_") and "feed_forward" in params[k]]:
        layer = dict(params[name]); ffn = dict(layer["feed_forward"])
        ffn["w2"] = ffn["w2"].at[0].multiply(3.0)
        layer["feed_forward"] = ffn; params[name] = layer
    return _start(model, dict(weights, params=params), traffic, **control)
_d.start_engine = _other
"""
WINDOW_AS_FULL = """
from hydragnn_tpu.models import mellum as _m
_core = _m.segment_causal_attention
def _no_window(q, k, v, node_graph, window=None, **kw):
    return _core(q, k, v, node_graph, window=None, **kw)  # every layer the triangle
_m.segment_causal_attention = _no_window
"""


@pytest.mark.parametrize("prelude,why", [
    (ALTERED, "beyond atol"), (PERTURBED, "beyond"), (WINDOW_AS_FULL, "beyond"),
], ids=["a reply altered", "one expert perturbed", "a window layer run as full"])
def pytest_a_broken_engine_comes_out_not_correct(root, prelude, why):
    name = _tiny_cell(root)
    rc, last, text = tiny.run_cell(root, name, seconds=1.0, seed=11, prelude=prelude)
    assert rc == 0 and last is not None, text[-3000:]
    assert last["correct"] is False and last["failed"] == 0
    assert "NOT CORRECT" in text and why in text, text[-2000:]
    # The family's own reference takes nothing from the program's model code.
    compared = last["compared"]
    assert (
        compared["reply_max_diff"]["value"] > compared["reply_max_diff"]["atol"]
        or compared["reply_rel_l2"]["value"] > compared["reply_rel_l2"]["limit"]
    )


def pytest_the_precision_below_the_stated_one_is_not_correct():
    """The control of the limits, at the PUBLISHED widths (4 layers, 64 of 64
    experts, 98,304 classes: 8.50 GB of float32 weights on the host) on one
    document of 2048 tokens, the cell's shortest: the family's reference with
    operands rounded to bf16 (the stated precision, emulated) passes
    ``compare`` against the float32 reference; with the residual stream, the
    kept activations and the probabilities rounded too (the precision below)
    it does not. About five minutes and 25 GB of host memory."""
    import jax

    from graftbench.drivers import serve_tokens as drv

    model, template, _ = drv.init_model(drv.completed_arch(_config()))
    host, params = drv.reference_params(drv.seeded_weights(template, 41))
    graphs = dict(_traffic()["graphs"], documents=[[2048, 1]])
    doc = drv.make_pool(graphs, 41)[0]
    graph = {"x": doc.x, "pos": doc.pos}
    with jax.default_device(host):
        want, report = mellum.logprobs(model, params, graph)
        routing = np.concatenate(report["chosen"], axis=1)
        assert routing.shape == (2048, 4 * 8) and report["rows_held"] == 2048 * 8 * 4
        stated, _ = mellum.logprobs(model, params, graph, routing, plain=mellum.Operands)
        below, again = mellum.logprobs(model, params, graph, routing, plain=mellum.Below)
    # Routed as the float32 reference routes; its own router logits, reached
    # through rounded activations, still hold those choices within the margin.
    assert 0.0 < again["route_margin"] < mellum.ROUTE_EPS
    worst, rel, fail = mellum.compare(stated, want)
    assert fail is None and rel < mellum.rel_l2_limit(2048) == mellum.REL_L2, (worst, rel, fail)
    worst_below, rel_below, fail_below = mellum.compare(below, want)
    assert fail_below is not None and "relative L2" in fail_below, (rel_below, fail_below)
    assert rel_below > 1.15 * mellum.REL_L2 and rel < 0.87 * mellum.REL_L2
    assert mellum.rel_l2_limit(6144) < mellum.rel_l2_limit(4096) < mellum.REL_L2
    print(f"stated {rel:.3e}; below {rel_below:.3e}; limit {mellum.REL_L2:.3e}")
