"""The JAMBA family's files and its serving cell: discovery by name; the
traffic's parameters; the counts against hand numbers (104.16M and 76.68M a
layer, 3,029.3M whole, 81,920 state elements a token and layer); the three new
readers on a made-up scope table and on a program without the scopes;
``BENCHMARK.json`` by MEMBERSHIP (what the cell, the configuration and each
reader hold, wherever they stand in their lists: a later cell appended after
this one must not fail this file) and the configuration's file against the
catalog's row; a tiny rehearsal of the cell on the CPU through ``main(argv,
allow_cpu=True)``; whole runs that must come out NOT correct (a reply altered
in the engine, a state reset dropped in the engine alone: the fault this
family can have that a stack of attention layers cannot); and the control of
the family's limits at the published widths: the state rounded to bf16 at
every step comes out NOT correct. Nothing here is a device number."""

import json
import os
import types

import numpy as np
import pytest

import tiny
from graftbench import families, flops, xplane_scopes
from graftbench.families import jamba
from graftbench.layer_metrics import (
    serve_attn_core_roofline, serve_ssm_mix_ms_per_flush, serve_ssm_scan_ms_per_flush,
    serve_ssm_scan_roofline,
)

REPO = tiny.REPO
CELL = "jamba2_3b.serve_score_pages_c4"
NEW = {"serve_ssm_scan_ms_per_flush", "serve_ssm_scan_roofline", "serve_ssm_mix_ms_per_flush"}
# Small widths with the published PATTERN: a period of 4 with attention at
# layer 2 (three Mamba layers to one), 4 query heads on ONE key-value head.
SMALL = dict(
    hidden_dim=32, num_conv_layers=4, attn_layer_period=4, attn_layer_offset=2,
    intermediate_size=48, num_attention_heads=4, num_key_value_heads=1,
    mamba_d_state=4, mamba_dt_rank=6, vocab_size=64,
)


def _json(*path):
    with open(os.path.join(REPO, *path)) as f:
        return json.load(f)


def _bench():
    return _json("BENCHMARK.json")


def _config():
    return _json("graftbench", "configs", "jamba2_3b.json")


def _traffic():
    return _json("graftbench", "traffic", "serve_score_pages_c4.json")


def _arch():
    return dict(_config()["NeuralNetwork"]["Architecture"])


def pytest_family_driver_and_generator_are_found_by_name():
    import importlib

    family = families.load("JAMBA")
    assert family is jamba
    for name in ("logprobs", "compare", "rel_l2_limit", "counts", "attn_counts",
                 "scan_counts", "head_counts", "ROUTE_EPS", "ATOL", "RTOL", "Operands", "Below"):
        assert hasattr(family, name), name
    traffic = _traffic()
    importlib.import_module(f"graftbench.drivers.{traffic['driver']}")
    importlib.import_module(f"graftbench.datagen.{traffic['graphs']['generator']}")
    assert traffic["clients"] == traffic["engine"]["max_batch_graphs"] == 4
    assert traffic["engine"]["queue_limit"] == 8 and traffic["engine"]["precision"] == "f32"
    assert traffic["engine"]["max_delay_ms"] == 1000.0 and traffic["engine"]["packing"] is False
    assert "matmul_precision" not in traffic and traffic["check_replies"] == 2
    assert traffic["graphs"]["documents"] == [[1024, 8], [2048, 6], [3072, 4], [4096, 3]]
    assert traffic["graphs"]["vocab"] == 65536
    ladder = [r[0] for r in traffic["bucket_ladder"]]
    assert ladder[-1] == 4 * 4096 + 512 == 16896 and ladder == sorted(ladder)
    assert all(r % 512 == 0 for r in ladder)
    # The family file takes nothing from the program's model code.
    with open(jamba.__file__) as f:
        assert "hydragnn_tpu" not in f.read().replace("``hydragnn_tpu/models/", "")


def pytest_counts_by_hand():
    arch = _arch()
    p = jamba.parameters(arch)
    mamba = (
        2560 * 10240 + 5120 * 4 + 5120 + 5120 * 192 + 160 * 5120 + 5120
        + 5120 * 16 + 5120 + 192 + 5120 * 2560
    )
    ffn = 3 * 2560 * 8192 + 2 * 2560
    assert p["mamba_layer"] == mamba + ffn and round(p["mamba_layer"] / 1e6, 2) == 104.16
    assert round(mamba / 1e6, 2) == 41.24
    assert p["attention_layer"] == 2 * 2560 * 2560 + 2 * 2560 * 128 + ffn
    assert round(p["attention_layer"] / 1e6, 2) == 76.68
    assert p["embedding"] == 65536 * 2560
    assert p["whole"] == 26 * p["mamba_layer"] + 2 * p["attention_layer"] + p["embedding"] + 2560
    assert round(p["whole"] / 1e6, 1) == 3029.3 and round(4 * p["whole"] / 1e9, 2) == 12.12
    one_period = dict(arch, num_conv_layers=14)
    assert round(jamba.parameters(one_period)["whole"] / 1e6, 1) == 1598.6
    # The scan: 5120 x 16 state elements a token and layer, 26 layers.
    scan = jamba.scan_counts(arch, 1000.0)
    assert scan["layers"] == 26 and scan["state_elements"] == 26 * 1000 * 81920
    assert scan["ops"] == 26 * 1000 * (81920 * 7 + 5120 * 3)
    assert scan["bytes"] == 26 * 4 * (1000 * (3 * 5120 + 32) + 5120 * 16 + 5120)
    # The two attention layers over real causal pairs.
    lengths = [1024, 4096]
    pairs = 1024 * 1025 / 2 + 4096 * 4097 / 2
    core = jamba.attn_counts(arch, lengths)["full"]
    assert core["layers"] == 2 and core["pairs"] == 2 * pairs
    assert core["ops"] == 2 * (4 * pairs * 20 * 128 + 5 * pairs * 20)
    assert core["bytes"] == 2 * 4 * 5120 * (2 * 20 + 2 * 1) * 128
    # A token's matmuls: 2 x the parameters of the layers, the head apart.
    parts, width = jamba.counts(arch, 1.0, 0, None, [1])
    dense = 2 * (26 * (mamba - 5120 * 4 - 5120 - 5120 - 5120 * 16 - 5120 - 192)
                 + 2 * (2 * 2560 * 2560 + 2 * 2560 * 128) + 28 * 3 * 2560 * 8192)
    total = flops.total(parts)["ops"]
    assert width == 2560 and dense < total < 1.03 * dense
    head = flops.total(jamba.head_counts(arch, 1.0, 65536))["ops"]
    assert round(head / 1e6, 1) == 336.0  # 2 x 2560 x 65536 = 335.5M and the log-softmax's 6 a class
    assert 0.05 < head / (head + total) < 0.06  # the model's own share


def _run(flushes=10):
    cell = types.SimpleNamespace(config=_config(), trace_dir="/nowhere", out_dir="/nowhere")
    return types.SimpleNamespace(
        cell=cell, facts={"flushes": flushes, "doc_lengths": [1024, 2048] * 20, "steps": flushes},
        peaks={"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}, trace={},
    )


def pytest_readers_on_a_table(monkeypatch):
    def row(scope, seconds, module="conv_1"):
        return {"root": "other", "direction": "forward", "module": module, "scope": scope,
                "seconds": seconds}

    rows = [
        row("hydragnn.ssm.scan", 0.5), row("hydragnn.ssm.scan", 0.1, "conv_3"),
        row("hydragnn.ssm.conv", 0.04), row("hydragnn.ssm.dt", 0.06),
        row("hydragnn.attn.full", 0.02, "conv_7"), row("(model)", 1.0),
    ]
    monkeypatch.setattr(xplane_scopes, "table", lambda _run: {"rows": rows})
    run = _run()
    assert serve_ssm_scan_ms_per_flush.read(run) == pytest.approx(60.0)
    assert serve_ssm_mix_ms_per_flush.read(run) == pytest.approx(10.0)
    tokens = 20 * (1024 + 2048)
    counted = jamba.scan_counts(_arch(), float(tokens))
    # The bytes bound it: the operations against the matrix unit's peak are less.
    assert counted["bytes"] / 819e9 > counted["ops"] / 197e12
    want = 100.0 * counted["bytes"] / 819e9 / 10 / 0.060
    assert serve_ssm_scan_roofline.read(run) == pytest.approx(want)
    assert 0 < want < 100
    assert serve_attn_core_roofline.read(run) > 0  # the accepted reader finds attn_counts


def pytest_readers_return_nothing_on_a_program_without_the_scopes(monkeypatch):
    rows = [{"root": "other", "direction": "forward", "module": "conv_0",
             "scope": "hydragnn.attn.full", "seconds": 0.3}]
    for table in ({"rows": rows}, {"rows": []}, None):
        monkeypatch.setattr(xplane_scopes, "table", lambda _run, t=table: t)
        run = _run()
        for reader in (serve_ssm_scan_ms_per_flush, serve_ssm_scan_roofline,
                       serve_ssm_mix_ms_per_flush):
            assert reader.read(run) is None
    # A family without scan_counts (the siblings'): nothing, and no error.
    monkeypatch.setattr(xplane_scopes, "table", lambda _run: {
        "rows": [dict(rows[0], scope="hydragnn.ssm.scan")]})
    run = _run()
    run.cell.config = _json("graftbench", "configs", "mellum2_12b_l4.json")
    assert serve_ssm_scan_roofline.read(run) is None


def pytest_benchmark_json_holds_the_cell_by_membership():
    bench = _bench()
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    assert cells[CELL] == dict(cells[CELL], config="jamba2_3b", traffic="serve_score_pages_c4", chips=1)
    assert len(cells[CELL]["why"]) <= 200
    entry = configs["jamba2_3b"]
    assert entry["file"] == "graftbench/configs/jamba2_3b.json" and entry["reduced"] == []
    assert entry["source"] == _config()["source"] and len(entry["why"]) <= 200

    def reported(cell):
        return {m["name"] for kind in ("end_to_end", "per_layer") for m in bench[kind]
                if "workloads" not in m or cell in m["workloads"]}

    got = reported(CELL)
    assert NEW <= got
    assert {"serve_graphs_per_s", "serve_p50_ms", "serve_p95_ms", "setup_s", "setup_compile_s",
            "setup_cache_hits", "setup_init_s", "serve_queue_wait_ms", "serve_batch_occupancy",
            "serve_collate_ms_per_flush", "serve_h2d_ms_per_flush", "serve_padding_waste_nodes",
            "serve_device_ms_per_flush", "serve_mfu", "serve_device_idle_share",
            "serve_peak_hbm_gb", "serve_attn_core_ms_per_flush", "serve_attn_core_roofline",
            "serve_head_ms_per_flush"} <= got
    assert not {m for m in got if "moe" in m or "window" in m or "latent" in m}
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["layer"] == "model"
            assert m["moves"] == "serve_graphs_per_s" and m["source"] == "device_trace"
            assert os.path.exists(os.path.join(REPO, "graftbench", "layer_metrics", m["name"] + ".py"))


def pytest_the_configuration_keeps_every_published_key():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["name"] == "AI21-Jamba2-3B")
    config = _config()
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert config[key] == value, key
    assert config["reduced"] == {} and config["published"]["num_hidden_layers"] == 28
    arch = config["NeuralNetwork"]["Architecture"]
    assert arch["hidden_dim"] == config["hidden_size"] and arch["num_conv_layers"] == 28
    for key in ("attn_layer_period", "attn_layer_offset", "intermediate_size",
                "num_attention_heads", "num_key_value_heads", "mamba_d_state", "mamba_d_conv",
                "mamba_dt_rank", "mamba_expand", "mamba_conv_bias", "mamba_proj_bias",
                "num_experts", "rms_norm_eps", "tie_word_embeddings", "vocab_size"):
        assert arch[key] == config[key], key
    for key in ("layer order", "inner norms", "initializer of the scan's parameters", "precision"):
        assert key in config["assumed"], key
    assert "3,029.3M" in config["parameters"] and "12.12 GB" in config["parameters"]
    assert config["NeuralNetwork"]["Variables_of_interest"]["num_classes"] == [65536]


# --------------------------------------------------------------- whole runs
def _tiny_cell(root):
    """``tiny.make_copy`` shrinks ``hidden_dim``, the depth and the serving
    mix's clients and ladder alone; this family's other widths, its depth of
    one whole period, its vocabulary, its pages and a ladder that holds four
    of the longest are set here, in the copy's files."""
    path = os.path.join(root, "graftbench", "configs", "tiny_jamba2_3b.json")
    with open(path) as f:
        config = json.load(f)
    config["NeuralNetwork"]["Architecture"].update(SMALL)
    config["NeuralNetwork"]["Variables_of_interest"]["num_classes"] = [SMALL["vocab_size"]]
    with open(path, "w") as f:
        json.dump(config, f)
    path = os.path.join(root, "graftbench", "traffic", "tiny_serve_score_pages_c4.json")
    with open(path) as f:
        traffic = json.load(f)
    assert traffic["clients"] == 4
    traffic["graphs"].update(vocab=SMALL["vocab_size"], documents=[[5, 4], [9, 3], [14, 2]])
    traffic["bucket_ladder"] = [[32, 8], [64, 8]]
    # Six replies checked where the cell checks two: the FIRST page of a flush
    # has no page before it, so a dropped reset cannot show in it, and one
    # checked reply in four is a first page.
    traffic["check_replies"] = 6
    with open(path, "w") as f:
        json.dump(traffic, f)
    return tiny.cell(root, "serve_tokens", model="JAMBA")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_copy(str(tmp_path_factory.mktemp("graftbench_tiny_jamba")))


def pytest_tiny_cell_runs_correct_and_traced_prints_its_counters(root):
    name = _tiny_cell(root)
    rc, last, text = tiny.run_cell(root, name, seconds=1.0, seed=4_500_000_019)
    assert rc == 0 and last["correct"], text[-3000:]
    assert last["failed"] == 0 and last["attempted"] >= 8
    assert set(last["metrics"]) == {"serve_graphs_per_s", "serve_p50_ms", "serve_p95_ms", "setup_s"}
    compared = last["compared"]
    assert set(compared) == {"reply_rel_l2", "reply_max_diff", "route_margin"}
    assert compared["reply_rel_l2"]["value"] < 1e-5 < compared["reply_rel_l2"]["limit"]
    assert compared["route_margin"]["value"] == 0.0  # nothing is routed
    assert "ladder rungs warmed: [(32, 8), (64, 8)]" in text
    assert "0 short of full" in text and "0 off the ladder" in text
    assert "rows to held experts a flush 0" in text
    rc, last, text = tiny.run_cell(root, name, seconds=1.0, trace=1, seed=7)
    assert rc == 0 and last["correct"], text[-3000:]
    got = set(last["metrics"])
    # On the CPU no device operation is traced: the by-scope readers return
    # nothing; the counters' readers and the three without a list do.
    assert {"serve_batch_occupancy", "serve_queue_wait_ms", "serve_padding_waste_nodes",
            "setup_init_s", "setup_compile_s"} <= got
    assert not got & (NEW | {"serve_attn_core_roofline", "serve_mfu"})
    assert last["metrics"]["serve_batch_occupancy"]["value"] == 1.0


ALTERED = """
from hydragnn_tpu.serve import engine as _e
_plain = _e.InferenceEngine._denormalize
_e.InferenceEngine._denormalize = lambda self, ihead, value: _plain(self, ihead, value) + 0.5
"""
# The scan told that the whole flush is ONE run: the state of a page flows
# into the next. In the engine alone (the reference is one page at a time and
# cannot have the fault).
NO_RESET = """
import jax.numpy as jnp
from hydragnn_tpu.models import jamba as _m
_scan = _m.selective_scan
def _one_run(u, dt, a, b, c, skip, node_graph):
    return _scan(u, dt, a, b, c, skip, jnp.zeros_like(node_graph))
_m.selective_scan = _one_run
"""


@pytest.mark.parametrize("prelude,why", [(ALTERED, "beyond"), (NO_RESET, "beyond")],
                         ids=["a reply altered", "a state reset dropped"])
def pytest_a_broken_engine_comes_out_not_correct(root, prelude, why):
    name = _tiny_cell(root)
    rc, last, text = tiny.run_cell(root, name, seconds=1.0, seed=11, prelude=prelude)
    assert rc == 0 and last is not None, text[-3000:]
    assert last["correct"] is False and last["failed"] == 0
    assert "NOT CORRECT" in text and why in text, text[-2000:]
    compared = last["compared"]
    assert (
        compared["reply_max_diff"]["value"] > compared["reply_max_diff"]["atol"]
        or compared["reply_rel_l2"]["value"] > compared["reply_rel_l2"]["limit"]
    )


def pytest_the_state_rounded_to_bf16_each_step_is_not_correct():
    """The control of the limits at the PUBLISHED widths and depth (28 layers,
    65,536 classes, the head tied: 12.12 GB of float32 weights on the host) on
    one page of 1,024 tokens, the cell's shortest and the length at which the
    two readings lie nearest: the family's reference with operands rounded to
    bf16 (the stated precision, emulated) passes ``compare`` against the
    float32 reference; with dt, the decay and the state rounded to bf16 at
    every step of the recurrence too (the precision below) it does not, by
    the relative L2 and not by each limit. About ten minutes and 30 GB of host
    memory."""
    import jax

    from graftbench.drivers import serve_tokens as drv

    model, template, _ = drv.init_model(drv.completed_arch(_config()))
    host, params = drv.reference_params(drv.seeded_weights(template, 45))
    graphs = dict(_traffic()["graphs"], documents=[[1024, 1]])
    doc = drv.make_pool(graphs, 45)[0]
    graph = {"x": doc.x, "pos": doc.pos}
    with jax.default_device(host):
        want, report = jamba.logprobs(model, params, graph)
        assert report["route_margin"] == 0.0 and report["rows_held"] == 0
        stated, _ = jamba.logprobs(model, params, graph, plain=jamba.Operands)
        below, _ = jamba.logprobs(model, params, graph, plain=jamba.Below)
    limit = jamba.rel_l2_limit(1024)
    worst, rel, fail = jamba.compare(stated, want)
    assert fail is None and rel < limit, (worst, rel, fail)
    worst_below, rel_below, fail_below = jamba.compare(below, want)
    assert fail_below is not None and "relative L2" in fail_below, (worst_below, rel_below)
    assert worst_below < jamba.ATOL  # by one of the limits, not by each
    assert rel_below > 1.05 * limit and rel < 0.95 * limit
    print(f"stated {rel:.3e} / {worst:.3f}; below {rel_below:.3e} / {worst_below:.3f}; "
          f"limit {limit:.3e}")
