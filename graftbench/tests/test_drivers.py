"""The train driver at tiny sizes on the CPU, each run a process of its own:
against ``run_training``, a traced run's line, the mesh cell on four virtual
devices, a forced learning-rate drop. Nothing here is a device number."""

import json
import os
import subprocess
import sys

import pytest

import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_copy(str(tmp_path_factory.mktemp("graftbench_tiny")))


def _last_run(root, cell):
    with open(os.path.join(root, "graftbench", "out", cell, "last_run.json")) as f:
        return json.load(f)


def pytest_train_driver_matches_run_training(root, tmp_path):
    # The shortest window: one warm-up epoch and one measured epoch.
    name = tiny.cell(root, "train_epochs")
    rc, line, text = tiny.run_cell(root, name, seconds=0.001)
    assert rc == 0 and line["correct"], text[-3000:]
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line["metrics"]) == {"train_graphs_per_s", "setup_s"}
    extra = _last_run(root, name)["extra"]
    config = extra["hydragnn_config"]
    config["NeuralNetwork"]["Training"]["num_epoch"] = 2
    code = (
        "import json, sys, hydragnn_tpu; "
        "h = hydragnn_tpu.run_training(json.load(open(sys.argv[1]))); "
        "print('LOSSES', json.dumps([float(v) for v in h['total_loss_train']]))"
    )
    with open(tmp_path / "config.json", "w") as f:
        json.dump(config, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=tiny.REPO,
               SERIALIZED_DATA_PATH=str(tmp_path),
               JAX_ENABLE_COMPILATION_CACHE="false")
    proc = subprocess.run(
        [sys.executable, "-c", code, str(tmp_path / "config.json")],
        cwd=tmp_path, env=env, text=True, capture_output=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    theirs = json.loads(
        [l for l in proc.stdout.splitlines() if l.startswith("LOSSES")][-1][7:]
    )
    # Same loaders, same seeds, same step programs: the same numbers.
    assert extra["losses"][:2] == pytest.approx(theirs, rel=1e-6, abs=0.0)


def pytest_gatv2_cell_agrees_with_its_reference_and_traced_line(root):
    name = tiny.cell(root, "train_epochs", model="GAT")
    rc, line, text = tiny.run_cell(root, name, seconds=0.5, trace=1)
    assert rc == 0 and line["correct"], text[-3000:]
    assert "program vs plain float32 reference" in text
    assert set(line) == {
        "correct", "attempted", "failed", "metrics", "device", "breakdown",
    }
    # A traced line carries per-layer metrics only; on a CPU there is no
    # device plane, so the trace's readers find nothing and are left out.
    assert {"setup_compile_s", "setup_init_s", "collate_ms_per_batch",
            "padding_waste_nodes", "program_temp_gb"} <= set(line["metrics"])
    assert not {"train_graphs_per_s", "device_idle_share", "device_step_ms"} & set(
        line["metrics"]
    )
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"memory_peak_bytes", "allocator_peak_bytes", "program_temp_bytes",
            "busy_s", "window_s"} <= set(line["device"])


def pytest_mesh_cell_on_four_virtual_devices(root):
    name = tiny.cell(root, "train_epochs", chips=4)
    rc, line, text = tiny.run_cell(root, name, seconds=0.5, devices=4)
    assert rc == 0 and line["correct"], text[-3000:]
    assert line["device"]["count"] == 4
    # Fewer devices than the cell asks for: no result.
    rc, line, text = tiny.run_cell(root, name, seconds=0.5, devices=1)
    assert rc != 0 and line is None


def pytest_learning_rate_drop_compiles_nothing_on_one_chip(root):
    # The one-chip cells run the program's own plateau scheduler. Here a
    # traffic FILE asks for patience 0, a drop at every epoch that does not
    # improve: no XLA compile may follow one. (On a mesh one does: PERF.md.)
    name = tiny.cell(root, "train_epochs")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == name)
    traffic_dir = os.path.join(root, "graftbench", "traffic")
    with open(os.path.join(traffic_dir, entry["traffic"] + ".json")) as f:
        traffic = json.load(f)
    with open(os.path.join(traffic_dir, "tiny_lr_drops.json"), "w") as f:
        json.dump(dict(traffic, plateau_patience=0), f)
    bench["workloads"].append(dict(entry, name="tiny.lr_drops", traffic="tiny_lr_drops"))
    for m in bench["end_to_end"]:
        if name in m.get("workloads", ()):
            m["workloads"].append("tiny.lr_drops")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    rc, line, text = tiny.run_cell(root, "tiny.lr_drops", seconds=1.0)
    assert rc == 0 and line["correct"], text[-3000:]
    run = _last_run(root, "tiny.lr_drops")
    start = run["extra"]["hydragnn_config"]["NeuralNetwork"]["Training"]["learning_rate"]
    assert run["extra"]["learning_rate"] < 0.6 * start, text[-3000:]


def pytest_no_result_without_a_tpu_or_without_the_program(root, tmp_path):
    # The command itself, as the driver runs it, on this CPU-only machine.
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=tiny.REPO)
    cmd = [sys.executable, "-m", "graftbench.run", "--workload",
           tiny.cell(root, "train_epochs"), "--seed", "0", "--seconds", "1",
           "--trace", "0"]
    proc = subprocess.run(cmd, cwd=root, env=env, text=True, capture_output=True,
                          timeout=300)
    assert proc.returncode != 0 and "{" not in proc.stdout
    # Alone in a directory: BENCHMARK.json and graftbench/ and nothing else.
    env.pop("PYTHONPATH")
    proc = subprocess.run(cmd, cwd=root, env=env, text=True, capture_output=True,
                          timeout=300)
    assert proc.returncode != 0 and "{" not in proc.stdout
