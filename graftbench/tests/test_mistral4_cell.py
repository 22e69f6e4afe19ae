"""The MISTRAL4 family's files and its serving cell: discovery by name; the
counts against hand numbers; the eight new readers on a made-up scope table
and on recorded traces of programs without the scopes (the parent's);
``BENCHMARK.json`` and the configuration's file against the catalog's row; a
tiny rehearsal of the cell on the CPU through ``main(argv, allow_cpu=True)``;
whole runs that must come out NOT correct (a reply altered in the engine, one
expert's weights perturbed in the engine alone); and the control of the
family's limits at the published widths: the precision below the stated one
comes out NOT correct. Nothing here is a device number."""

import importlib
import json
import os
import types

import numpy as np
import pytest

import tiny
from graftbench import families, flops, xplane_scopes
from graftbench.families import mistral4
from graftbench.layer_metrics import (
    serve_attn_core_ms_per_flush, serve_attn_core_roofline,
    serve_attn_latent_ms_per_flush, serve_head_ms_per_flush,
    serve_moe_load_max_over_mean, serve_moe_ms_per_flush, serve_moe_roofline,
    serve_moe_route_ms_per_flush,
)

REPO = tiny.REPO
DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "testdata")
CELL = "mistral_small4_ep8.serve_score_docs_c4"
SIBLING = "pna_multihead_h256.serve_closed_lattice"
NEW = {
    "serve_attn_latent_ms_per_flush", "serve_attn_core_ms_per_flush",
    "serve_attn_core_roofline", "serve_moe_ms_per_flush", "serve_moe_roofline",
    "serve_moe_route_ms_per_flush", "serve_moe_load_max_over_mean",
    "serve_head_ms_per_flush",
}
# Small widths with the published RATIOS: three head widths that differ, two
# ranks that differ, 8 experts, 2 a token, 4 held from expert 2.
SMALL = dict(
    hidden_dim=32, num_conv_layers=3, num_attention_heads=4, q_lora_rank=24,
    kv_lora_rank=12, qk_nope_head_dim=6, qk_rope_head_dim=4, v_head_dim=8,
    moe_intermediate_size=24, n_routed_experts=8, num_experts_per_tok=2,
    num_experts_held=4, experts_offset=2, vocab_size=64,
)


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _config():
    with open(os.path.join(REPO, "graftbench", "configs", "mistral_small4_ep8.json")) as f:
        return json.load(f)


def _traffic():
    with open(os.path.join(REPO, "graftbench", "traffic", "serve_score_docs_c4.json")) as f:
        return json.load(f)


def pytest_family_driver_and_generator_are_found_by_name():
    family = families.load("MISTRAL4")
    assert family is mistral4
    for name in ("encode", "logits", "logprobs", "compare", "counts", "attn_counts",
                 "moe_counts", "head_counts"):
        assert callable(getattr(family, name)), name
    assert 0 < family.REL_L2 < 0.1 and 0 < family.ROUTE_EPS < 0.5
    traffic = _traffic()
    assert traffic["driver"] == "serve_tokens" and traffic["chips"] == 1
    assert traffic["clients"] == traffic["engine"]["max_batch_graphs"] == 4
    assert traffic["engine"] == {
        "max_batch_graphs": 4, "max_delay_ms": 1000.0, "queue_limit": 8,
        "precision": "f32", "packing": False,
    }
    assert "matmul_precision" not in traffic and traffic["check_replies"] == 2
    docs = traffic["graphs"]["documents"]
    assert docs == [[2048, 8], [3072, 6], [4096, 4], [6144, 3]]
    assert sum(n for _, n in docs) == 21
    assert round(sum(t * n for t, n in docs) / 21) == 3316
    ladder = traffic["bucket_ladder"]
    assert ladder == [[12288, 8], [15872, 8], [19968, 8], [25088, 8]]
    assert all(n % 512 == 0 for n, _ in ladder) and ladder[-1][0] == 4 * 6144 + 512
    driver = importlib.import_module(f"graftbench.drivers.{traffic['driver']}")
    generator = importlib.import_module(f"graftbench.datagen.{traffic['graphs']['generator']}")
    assert callable(driver.run) and callable(generator.generate)


def pytest_the_ladders_note_is_the_simulation():
    """The shares and the padding the traffic file states, simulated again:
    4 seeded client orders over the 21 lengths, a flush one document of each."""
    from graftbench.drivers.serve_closed import client_orders

    traffic = _traffic()
    sizes = np.array([t for t, n in traffic["graphs"]["documents"] for _ in range(n)])
    rungs = np.array([n for n, _ in traffic["bucket_ladder"]])
    sums = []
    for seed in range(40):
        orders = client_orders(len(sizes), 4, seed)
        sums.append(sum(sizes[o[:1000]] for o in orders))
    sums = np.concatenate(sums)
    assert sums.min() == 8192 and sums.max() == 24576 and (sums % 1024 == 0).all()
    assert sums.mean() == pytest.approx(13263, abs=60) and sums.std() == pytest.approx(2753, abs=60)
    rung = np.searchsorted(rungs, sums, side="right")  # the first rung OVER the flush
    share = np.bincount(rung, minlength=4) / len(sums)
    assert share == pytest.approx([0.325, 0.502, 0.158, 0.015], abs=0.012)
    assert 1 - sums.sum() / rungs[rung].sum() == pytest.approx(0.144, abs=0.006)
    # No rung's edge lies near the 50th or the 95th percentile of a window's
    # requests: ISSUE 39's rungs (15,360 / 18,432) left the guard 6% and put
    # a window's p95 on its flushes in some seeds and under them in others.
    edges = np.cumsum(share)[:-1]
    assert min(abs(edges - 0.50).min(), abs(edges - 0.95).min()) > 0.03
    named = np.bincount(np.searchsorted([12288, 15360, 18432, 25088], sums, side="right"),
                        minlength=4) / len(sums)
    assert abs(np.cumsum(named)[2] - 0.95) < 0.015


def pytest_counts_by_hand():
    arch = _config()["NeuralNetwork"]["Architecture"]
    # The attention cores: 4 x 128 operations a pair and head, 32 heads, and
    # the softmax's 5; 5 layers.
    for lengths in ([2048], [6144], [2048, 3072, 4096, 6144]):
        core = mistral4.attn_counts(arch, lengths)["full"]
        n_pairs = sum(n * (n + 1) // 2 for n in lengths)
        assert core["pairs"] == 5 * n_pairs and core["layers"] == 5
        assert core["ops"] == 5 * (4 * 128 * n_pairs * 32 + 5 * n_pairs * 32)
        assert core["bytes"] == 5 * 4 * sum(lengths) * 32 * (2 * 128 + 2 * 128)
    assert mistral4.pairs(3) == 6
    # The grouped matmuls: three projections a row; 16 held experts' three
    # float32 matrices read once a layer.
    moe = mistral4.moe_counts(arch, 1000)
    assert moe["ops"] == 2 * 1000 * 3 * 4096 * 2048 + 2 * 1000 * 2048
    assert moe["bytes"] == 4 * (5 * 16 * 3 * 4096 * 2048 + 1000 * (2 * 4096 + 3 * 2048 + 4096))
    # At the ~415 rows an expert of a mean flush the weights' bytes bound it.
    rows = 5 * 13264 * 4 * 16 / 128
    mean = mistral4.moe_counts(arch, rows)
    assert mean["bytes"] / 819e9 > mean["ops"] / 197e12
    # A token's operations: ISSUE 39's arithmetic (56 latent projections + 50
    # shared + ~25 routed here a layer, 134 the head; MFLOP).
    nodes = 1000
    parts, width = mistral4.counts(arch, nodes, 0, lengths=[nodes])
    assert width == 4096
    latent = 2 * (4096 * 1024 + 1024 * 4096 + 4096 * 320 + 256 * 6144 + 4096 * 4096)
    assert round(latent / 1e6) == 56 and round(2 * 3 * 4096 * 2048 / 1e6) == 50
    dense_ops = sum(p["ops"] for p in parts) - int(
        mistral4.attn_counts(arch, [nodes])["full"]["ops"]
    ) - int(mistral4.moe_counts(arch, 5 * nodes * 4 * 16 / 128)["ops"])
    per_token = dense_ops / nodes / 5
    assert 1.00 < per_token / (latent + 2 * 3 * 4096 * 2048 + 2 * 4096 * 128) < 1.01
    head = flops.total(mistral4.head_counts(arch, nodes, 16384))["ops"] / nodes
    assert round(head / 1e6) == 134
    fewer, _ = mistral4.counts(arch, nodes, 0, routed_rows=100, lengths=[nodes])
    saved = flops.total(parts)["ops"] - flops.total(fewer)["ops"]
    assert saved == int(mistral4.moe_counts(arch, 2500)["ops"]) - int(
        mistral4.moe_counts(arch, 100)["ops"]
    )
    # flops.py's shared count (pool and heads) finds the family by its type.
    whole = flops.forward(
        dict(arch, output_type=["node"], output_dim=[16384]), nodes, 0, 1
    )["ops"]
    assert whole > flops.total(parts)["ops"] + nodes * 2 * 4096 * 16384


def _run(flushes=10):
    arch = _config()["NeuralNetwork"]["Architecture"]
    cell = types.SimpleNamespace(
        trace_dir=None, out_dir=None, config={"NeuralNetwork": {"Architecture": arch}},
    )
    lengths = [2048, 3072, 4096, 6144] * flushes
    return types.SimpleNamespace(
        cell=cell, peaks={"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12},
        facts={"flushes": flushes, "steps": flushes, "chips": 1, "doc_lengths": lengths,
               "moe_rows_held": 8000.0 * flushes, "moe_load_max": 700.0 * 5 * flushes,
               "moe_fallback_layers": 0},
    )


def pytest_readers_on_a_table(monkeypatch):
    def row(scope, seconds, module="conv_1", root="other", rooted=False):
        return dict(root=root, rooted=rooted, direction="fwd", module=module,
                    scope=scope, seconds=seconds)

    rows = [
        row("hydragnn.attn.latent", 0.40), row("hydragnn.attn.full", 0.30),
        row("hydragnn.attn.full", 0.20, module="conv_4"),
        row("hydragnn.moe.experts", 0.25), row("hydragnn.moe.route", 0.05),
        row("hydragnn.moe.shared", 0.15), row("hydragnn.head.logprob", 0.10, module="(model)"),
        row("(model)", 0.2),  # o_proj, the norms: the module's own
    ]
    run = _run()
    monkeypatch.setattr(xplane_scopes, "table", lambda _run: {"rows": rows})
    assert serve_attn_latent_ms_per_flush.read(run) == pytest.approx(40.0)
    assert serve_attn_core_ms_per_flush.read(run) == pytest.approx(50.0)
    assert serve_moe_ms_per_flush.read(run) == pytest.approx(25.0)
    assert serve_moe_route_ms_per_flush.read(run) == pytest.approx(5.0)
    assert serve_head_ms_per_flush.read(run) == pytest.approx(10.0)
    arch = run.cell.config["NeuralNetwork"]["Architecture"]
    n_pairs = sum(n * (n + 1) // 2 for n in (2048, 3072, 4096, 6144))
    ops = 5 * (4 * 128 * n_pairs * 32 + 5 * n_pairs * 32)  # one flush
    assert serve_attn_core_roofline.read(run) == pytest.approx(100.0 * ops / 197e12 / 50e-3)
    assert 0 < serve_attn_core_roofline.read(run) < 100
    counted = mistral4.moe_counts(arch, 8000.0)
    least = max(counted["ops"] / 197e12, counted["bytes"] / 819e9)
    assert serve_moe_roofline.read(run) == pytest.approx(100.0 * least / 25e-3)
    assert 0 < serve_moe_roofline.read(run) < 100
    # 700 rows on the fullest of 16 held experts against a mean of 8000 / 5 / 16.
    assert serve_moe_load_max_over_mean.read(run) == pytest.approx(700.0 / 100.0)


def pytest_readers_return_nothing_on_a_program_without_the_scopes(monkeypatch):
    """The recorded traces are of programs that open none of the scopes (as
    this PR's parent does not): nothing is returned and nothing raises."""
    readers = (
        serve_attn_latent_ms_per_flush, serve_attn_core_ms_per_flush,
        serve_attn_core_roofline, serve_moe_ms_per_flush, serve_moe_roofline,
        serve_moe_route_ms_per_flush, serve_head_ms_per_flush,
    )
    for name in ("scoped_v5e.xplane.pb", "small_v5e.xplane.pb"):
        table = xplane_scopes.by_scope(os.path.join(DATA, name))
        monkeypatch.setattr(xplane_scopes, "table", lambda _run, t=table: t)
        assert all(r.read(_run()) is None for r in readers)
    monkeypatch.setattr(xplane_scopes, "table", lambda _run: None)  # no trace
    assert all(r.read(_run()) is None for r in readers)
    rows = [dict(root="other", rooted=False, direction="fwd", module="conv_1",
                 scope=s, seconds=0.02) for s in ("hydragnn.attn.full", "hydragnn.moe.experts")]
    monkeypatch.setattr(xplane_scopes, "table", lambda _run: {"rows": rows})
    bare = _run()
    bare.facts = {"flushes": 2, "chips": 1}  # a driver without the counts (the lattice cell's)
    assert serve_attn_core_roofline.read(bare) is None
    assert serve_moe_roofline.read(bare) is None
    assert serve_moe_load_max_over_mean.read(bare) is None
    other = _run()
    other.cell.config["NeuralNetwork"]["Architecture"] = {"model_type": "PNA"}
    assert serve_attn_core_roofline.read(other) is None  # a family without attn_counts
    assert serve_moe_roofline.read(other) is None


def pytest_benchmark_json_holds_the_cell():
    bench = _bench()
    cells = bench["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    (entry,) = [w for w in cells if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "mistral_small4_ep8", "serve_score_docs_c4", 1
    )
    assert len(entry["why"]) <= 200 and "1/8" in entry["why"]
    (config,) = [c for c in bench["configs"] if c["name"] == "mistral_small4_ep8"]
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["source"] == _config()["source"]
    assert cells[-1] is entry and bench["configs"][-1] is config  # appended, nothing moved

    def reported(cell):
        return {
            m["name"] for kind in ("end_to_end", "per_layer") for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]
        }

    # What the lattice serving cell reports, less its two aggregation shares,
    # plus the eight.
    assert reported(CELL) == (
        reported(SIBLING) - {"serve_gather_roofline", "serve_agg_roofline"}
    ) | NEW
    own = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in bench["per_layer"][-8:]] == [m["name"] for m in own]
    for m in own:
        assert m["workloads"] == [CELL] and m["moves"] == "serve_graphs_per_s"
        assert m["layer"] == "model"
        assert m["source"] == (
            "program_counter" if m["name"] == "serve_moe_load_max_over_mean" else "device_trace"
        )
        assert (m["unit"] == "%") == m["name"].endswith("_roofline")
    for name in reported(CELL) - {"serve_graphs_per_s", "serve_p50_ms", "serve_p95_ms", "setup_s"}:
        assert os.path.exists(
            os.path.join(REPO, "graftbench", "layer_metrics", name + ".py")
        ), name


def pytest_the_configuration_keeps_every_published_width():
    """The file's top level is the catalog row's ``config`` as run: every key
    but the three under ``reduced``, ``rope_parameters`` whole; the program's
    ``Architecture`` says the same under its own keys."""
    catalog = {
        "attention_bias": False, "first_k_dense_replace": 0, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 12288,
        "kv_lora_rank": 256, "max_position_embeddings": 1048576, "mlp_bias": False,
        "model_type": "mistral4", "moe_intermediate_size": 2048, "n_group": 1,
        "n_routed_experts": 128, "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4, "num_hidden_layers": 36,
        "num_key_value_heads": 32, "q_lora_rank": 1024, "qk_head_dim": 128,
        "qk_nope_head_dim": 64, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "routed_scaling_factor": 1, "sliding_window": None,
        "tie_word_embeddings": False, "topk_group": 1, "v_head_dim": 128,
        "vocab_size": 131072,
        "rope_parameters": {
            "beta_fast": 32, "beta_slow": 1, "factor": 128, "llama_4_scaling_beta": 0.1,
            "mscale": 1, "mscale_all_dim": 1, "original_max_position_embeddings": 8192,
            "rope_theta": 10000, "rope_type": "yarn", "type": "yarn",
        },
    }
    config = _config()
    reduced = {"num_hidden_layers": 5, "n_routed_experts": 16, "vocab_size": 16384}
    assert set(config["reduced"]) == set(reduced) == set(config["published"])
    for key, value in catalog.items():
        assert config[key] == reduced.get(key, value), key
        if key in reduced:
            assert config["published"][key] == value
    arch = config["NeuralNetwork"]["Architecture"]
    assert arch["model_type"] == "MISTRAL4" and arch["hidden_dim"] == catalog["hidden_size"]
    assert arch["num_conv_layers"] == 5 >= 4  # the floor: never fewer than 4 layers
    assert (arch["n_routed_experts"], arch["num_experts_held"], arch["experts_offset"]) == (128, 16, 0)
    assert arch["num_experts_held"] >= 8 and arch["vocab_size"] * 8 == catalog["vocab_size"]
    for key in ("num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "intermediate_size", "moe_intermediate_size",
                "first_k_dense_replace", "n_shared_experts", "num_experts_per_tok", "n_group",
                "topk_group", "norm_topk_prob", "routed_scaling_factor", "rope_interleave",
                "rope_parameters", "rms_norm_eps"):
        assert arch[key] == catalog[key], key
    for key in ("router score function", "no correction bias", "mscale conventions",
                "llama_4_scaling_beta", "eps", "precision"):
        assert key in config["assumed"], key
    for key in ("the vision tower is not built", "no auxiliary balance loss", "head bias", "data"):
        assert key in config["departures"], key
    assert "9.66 GB" in config["parameters"] and "one rank of 8" in config["stands_for"]


# --------------------------------------------------------------- whole runs
def _tiny_cell(root):
    """``tiny.make_copy`` shrinks ``hidden_dim``, the depth and the serving
    mix's clients and ladder alone; this family's other widths, its vocabulary
    and its documents are shrunk here, in the copy's files."""
    path = os.path.join(root, "graftbench", "configs", "tiny_mistral_small4_ep8.json")
    with open(path) as f:
        config = json.load(f)
    config["NeuralNetwork"]["Architecture"].update(SMALL)
    config["NeuralNetwork"]["Variables_of_interest"]["num_classes"] = [SMALL["vocab_size"]]
    with open(path, "w") as f:
        json.dump(config, f)
    path = os.path.join(root, "graftbench", "traffic", "tiny_serve_score_docs_c4.json")
    with open(path) as f:
        traffic = json.load(f)
    assert traffic["bucket_ladder"] == [[16, 40], [32, 64]] and traffic["clients"] == 4
    traffic["graphs"].update(vocab=SMALL["vocab_size"], documents=[[3, 4], [5, 3], [7, 2]])
    with open(path, "w") as f:
        json.dump(traffic, f)
    return tiny.cell(root, "serve_tokens", model="MISTRAL4")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_copy(str(tmp_path_factory.mktemp("graftbench_tiny_mistral4")))


def pytest_tiny_cell_runs_correct_and_traced_prints_its_counters(root):
    name = _tiny_cell(root)
    rc, last, text = tiny.run_cell(root, name, seconds=1.0, seed=3_000_000_019)
    assert rc == 0 and last["correct"], text[-3000:]
    assert last["failed"] == 0 and last["attempted"] >= 8
    assert set(last["metrics"]) == {"serve_graphs_per_s", "serve_p50_ms", "serve_p95_ms", "setup_s"}
    compared = last["compared"]
    assert set(compared) == {"reply_rel_l2", "reply_max_diff", "route_margin"}
    assert compared["reply_rel_l2"]["value"] < 1e-5 < compared["reply_rel_l2"]["limit"]
    assert compared["route_margin"]["value"] < 1e-5
    # The engine gives every rung of a token family its 8 padding edges.
    assert "ladder rungs warmed: [(16, 8), (32, 8)]" in text
    assert "0 short of full" in text and "0 off the ladder" in text
    rc, last, text = tiny.run_cell(root, name, seconds=1.0, trace=1, seed=7)
    assert rc == 0 and last["correct"], text[-3000:]
    got = set(last["metrics"])
    # On the CPU no device operation is traced: the by-scope readers return
    # nothing; the counters' readers and the three without a list do.
    assert {"serve_moe_load_max_over_mean", "serve_batch_occupancy", "serve_queue_wait_ms",
            "serve_padding_waste_nodes", "setup_init_s", "setup_compile_s"} <= got
    assert not got & {"serve_attn_core_roofline", "serve_moe_roofline", "serve_mfu"}
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
    layer = dict(params["conv_1"]); ffn = dict(layer["feed_forward"])
    ffn["w2"] = ffn["w2"].at[0].multiply(3.0)  # ONE held expert, in the engine alone
    layer["feed_forward"] = ffn; params["conv_1"] = layer
    return _start(model, dict(weights, params=params), traffic, **control)
_d.start_engine = _other
"""


@pytest.mark.parametrize("prelude,why", [
    (ALTERED, "beyond atol"), (PERTURBED, "relative L2 distance"),
], ids=["a reply altered", "one expert perturbed"])
def pytest_a_broken_engine_comes_out_not_correct(root, prelude, why):
    name = _tiny_cell(root)
    rc, last, text = tiny.run_cell(root, name, seconds=1.0, seed=11, prelude=prelude)
    assert rc == 0 and last is not None, text[-3000:]
    assert last["correct"] is False and last["failed"] == 0
    assert "NOT CORRECT" in text and why in text, text[-2000:]


def pytest_the_precision_below_the_stated_one_is_not_correct():
    """The control of the limits, at the PUBLISHED widths (5 layers, 16 of
    128 experts, 16384 classes: 9.66 GB of float32 weights on the host) on one
    document of 2048 tokens, the cell's shortest (the distances fall with a
    document's length: 512 tokens read a fifth higher): the family's reference with operands rounded to
    bf16 (the stated precision, emulated) passes ``compare`` against the
    float32 reference; with the residual stream, the kept activations and the
    probabilities rounded too (the precision below) it does not. About five
    minutes and 25 GB of host memory."""
    import jax

    from graftbench.drivers import serve_tokens as drv

    model, template, _ = drv.init_model(drv.completed_arch(_config()))
    host, params = drv.reference_params(drv.seeded_weights(template, 39))
    graphs = dict(_traffic()["graphs"], documents=[[2048, 1]])
    doc = drv.make_pool(graphs, 39)[0]
    graph = {"x": doc.x, "pos": doc.pos}
    with jax.default_device(host):
        want, report = mistral4.logprobs(model, params, graph)
        routing = np.concatenate(report["chosen"], axis=1)
        stated, _ = mistral4.logprobs(model, params, graph, routing, plain=mistral4.Operands)
        below, again = mistral4.logprobs(model, params, graph, routing, plain=mistral4.Below)
    # Routed as the float32 reference routes; its own router logits, reached
    # through rounded activations, still hold those choices within the margin.
    assert 0.0 < again["route_margin"] < mistral4.ROUTE_EPS
    worst, rel, fail = mistral4.compare(stated, want)
    assert fail is None and rel < mistral4.rel_l2_limit(2048) == mistral4.REL_L2, (worst, rel, fail)
    worst_below, rel_below, fail_below = mistral4.compare(below, want)
    assert fail_below is not None and "relative L2" in fail_below, (rel_below, fail_below)
    assert rel_below > 1.08 * mistral4.REL_L2 and rel < 0.92 * mistral4.REL_L2
    # The limit follows the document's length (readings beside REL_L2).
    assert mistral4.rel_l2_limit(6144) == pytest.approx(0.898e-3, rel=2e-3)
    assert mistral4.rel_l2_limit(4096) == pytest.approx(0.945e-3, rel=2e-3)
    print(f"stated {rel:.3e}; below {rel_below:.3e}; limit {mistral4.REL_L2:.3e}")
