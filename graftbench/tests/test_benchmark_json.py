"""``BENCHMARK.json`` against the contract's limits, and against the files
the harness will look for."""

import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH_DIR)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# Keys a ``reduced`` list may never hold, beside any that ends in ``_dim`` or
# ``_rank``: the widths of the configurations' sources.
WIDTHS = {
    "hidden_size", "hidden_dim", "intermediate_size", "moe_intermediate_size",
    "shared_expert_intermediate_size", "head_dim", "num_experts_per_tok",
    "conv_L_cache", "sliding_window",
}


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def pytest_keys_names_units_and_lengths():
    b = _bench()
    assert set(b) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    assert b["paths"] == ["graftbench"]
    assert len(b["command"]) <= 32 and all(_line(w) for w in b["command"])
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        assert not any(
            k.endswith(("_dim", "_rank")) or k in WIDTHS for k in c["reduced"]
        ), "reduced may never name a width"
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and _line(w["why"])
    for kind, keys in (
        ("end_to_end", {"name", "unit", "better", "bound", "source"}),
        ("per_layer", {"name", "unit", "better", "source", "layer", "moves"}),
    ):
        for m in b[kind]:
            assert set(m) - {"workloads"} == keys, m
            assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert _line(m["layer"])
    every = [m["name"] for k in ("end_to_end", "per_layer") for m in b[k]]
    assert len(every) == len(set(every))
    for kind in ("configs", "workloads"):
        names = [x["name"] for x in b[kind]]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))


def pytest_cells_find_their_files_and_report_what_they_must():
    b = _bench()
    configs = {c["name"]: c for c in b["configs"]}
    four = [w for w in b["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(b["workloads"]) // 4)
    assert {w["config"] for w in b["workloads"]} == set(configs)
    files = [c["file"] for c in b["configs"]]
    assert len(files) == len(set(files))
    for w in b["workloads"]:
        cfg = configs[w["config"]]
        assert cfg["file"].startswith("graftbench/")
        with open(os.path.join(REPO, cfg["file"])) as f:
            config = json.load(f)
        assert set(cfg["reduced"]) == set(config["reduced"])
        assert "assumed" in config and "NeuralNetwork" in config
        with open(os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        assert traffic["chips"] == w["chips"]
        for sub, name in (("drivers", traffic["driver"]),
                          ("datagen", traffic["graphs"]["generator"])):
            assert os.path.exists(os.path.join(BENCH_DIR, sub, name + ".py"))

        def reported(kind):
            return [m for m in b[kind]
                    if "workloads" not in m or w["name"] in m["workloads"]]

        e2e = {m["name"] for m in reported("end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        layers = reported("per_layer")
        assert layers, w["name"]
        for m in layers:
            assert m["moves"] in e2e, (w["name"], m["name"], m["moves"])
            assert os.path.exists(
                os.path.join(BENCH_DIR, "layer_metrics", m["name"] + ".py")
            ), m["name"]
    cells = {w["name"] for w in b["workloads"]}
    for kind in ("end_to_end", "per_layer"):
        for m in b[kind]:
            assert set(m.get("workloads", ())) <= cells, m["name"]


def pytest_every_configuration_has_its_family_file():
    """The plain reference and the counts of a configuration are found by
    its ``model_type`` (``graftbench/families/<model_type>.py``)."""
    from graftbench import families

    for c in _bench()["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            kind = json.load(f)["NeuralNetwork"]["Architecture"]["model_type"]
        assert os.path.exists(
            os.path.join(BENCH_DIR, "families", kind.lower() + ".py")
        ), (c["name"], kind)
        family = families.load(kind)
        assert callable(family.encode) and callable(family.counts), kind


def pytest_peaks_table_names_its_sources():
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["flops_per_s_bf16"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert all(p["source"] for p in peaks.values())
