"""A later PR adds a configuration, a traffic mix and a per-layer metric as
files of their own plus entries in ``BENCHMARK.json``, editing no file that
is there. Shown on a temporary copy: three new files, three appended
entries, one run. So with a model family the program has and the benchmark
lacks: a family file, a configuration, two entries, one run."""

import hashlib
import json
import os
import shutil

import tiny


def _digests(root):
    out = {}
    for base, _dirs, files in os.walk(os.path.join(root, "graftbench")):
        if "out" in base.split(os.sep) or ".cache" in base.split(os.sep):
            continue
        for name in files:
            if name.endswith(".pyc"):
                continue
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[path] = hashlib.sha256(f.read()).hexdigest()
    return out


def pytest_new_cell_config_traffic_and_metric_as_files_only(tmp_path):
    root = tiny.make_copy(str(tmp_path))
    before = _digests(root)
    bench_dir = os.path.join(root, "graftbench")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    old = next(w for w in bench["workloads"] if w["name"] == tiny.cell(root, "train_epochs"))
    with open(os.path.join(bench_dir, "configs", old["config"] + ".json")) as f:
        config = json.load(f)
    config["NeuralNetwork"]["Architecture"]["hidden_dim"] = 4
    with open(os.path.join(bench_dir, "configs", "later_config.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench_dir, "traffic", old["traffic"] + ".json")) as f:
        traffic = json.load(f)
    traffic["batch_size"] = 8
    with open(os.path.join(bench_dir, "traffic", "later_mix.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(bench_dir, "layer_metrics", "later_metric.py"), "w") as f:
        f.write("def read(run):\n    return run.facts['epochs']\n")

    path = os.path.join(root, "BENCHMARK.json")
    bench["configs"].append(dict(
        name="later_config", source="a later PR", why="discovery self-test",
        file="graftbench/configs/later_config.json", reduced=["num_conv_layers"],
    ))
    bench["workloads"].append(dict(
        name="later.cell", config="later_config", traffic="later_mix", chips=1,
        why="discovery self-test",
    ))
    for m in bench["end_to_end"]:
        if m["name"] == "train_graphs_per_s":
            m["workloads"].append("later.cell")
    bench["per_layer"].append(dict(
        name="later_metric", unit="count", better="higher",
        source="program_counter", layer="step", moves="train_graphs_per_s",
        workloads=["later.cell"],
    ))
    with open(path, "w") as f:
        json.dump(bench, f)

    rc, line, text = tiny.run_cell(root, "later.cell", seconds=0.5, trace=1)
    assert rc == 0 and line["correct"], text[-3000:]
    assert line["metrics"]["later_metric"]["value"] >= 1
    assert line["metrics"]["later_metric"]["unit"] == "count"
    # The cell reports the metrics that apply to every cell, and not those
    # that list other cells.
    assert "setup_compile_s" in line["metrics"]
    assert "padding_waste_nodes" not in line["metrics"]
    after = _digests(root)
    assert {p: d for p, d in after.items() if p in before} == before
    assert len(after) == len(before) + 3


def pytest_new_model_family_as_files_only(tmp_path):
    """SAGE: the program has it (``models/convs.py``), the benchmark has no
    ``families/sage.py``. Its plain reference and its counts arrive as ONE
    file (``later_family_sage.py`` here), its cell as a configuration file
    and two entries."""
    root = tiny.make_copy(str(tmp_path))
    before = _digests(root)
    bench_dir = os.path.join(root, "graftbench")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    old = next(w for w in bench["workloads"] if w["name"] == tiny.cell(root, "train_epochs"))
    with open(os.path.join(bench_dir, "configs", old["config"] + ".json")) as f:
        config = json.load(f)
    config["NeuralNetwork"]["Architecture"]["model_type"] = "SAGE"
    with open(os.path.join(bench_dir, "configs", "later_sage.json"), "w") as f:
        json.dump(config, f)
    bench["configs"].append(dict(
        name="later_sage", source="a later PR", why="discovery self-test",
        file="graftbench/configs/later_sage.json", reduced=["num_conv_layers"],
    ))
    bench["workloads"].append(dict(
        name="later_sage.cell", config="later_sage", traffic=old["traffic"],
        chips=1, why="discovery self-test",
    ))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if old["name"] in m.get("workloads", ()):
            m["workloads"].append("later_sage.cell")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    # Without its file the family fails with the name of the file to add.
    rc, line, text = tiny.run_cell(root, "later_sage.cell", seconds=0.5)
    assert rc != 0 and line is None
    assert "add graftbench/families/sage.py" in text, text[-3000:]

    shutil.copy(
        os.path.join(tiny.HERE, "later_family_sage.py"),
        os.path.join(bench_dir, "families", "sage.py"),
    )
    rc, line, text = tiny.run_cell(root, "later_sage.cell", seconds=0.5, trace=1)
    assert rc == 0, text[-3000:]
    assert "program vs plain float32 reference on 8 graphs" in text
    assert not [l for l in text.splitlines() if "NOT CORRECT: reference:" in l], text[-3000:]
    with open(os.path.join(bench_dir, "out", "later_sage.cell", "last_run.json")) as f:
        facts = json.load(f)["facts"]
    assert facts["step_ops"] > 0
    assert all(v > 0 for v in facts["step_bytes"]["gather"].values())
    assert all(v > 0 for v in facts["step_bytes"]["agg"].values())
    after = _digests(root)
    assert {p: d for p, d in after.items() if p in before} == before
    assert len(after) == len(before) + 2
