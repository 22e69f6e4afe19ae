"""A tiny copy of the benchmark for CPU rehearsals: ``graftbench/`` as it is,
plus tiny configuration and traffic FILES and a ``BENCHMARK.json`` whose
cells name them. Used by the self-tests; nothing here is a cell of the real
benchmark and no number from it is a device number."""

from __future__ import annotations

import copy
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH_DIR)


def _shrink(config: dict, hidden: int, head: int) -> dict:
    config = copy.deepcopy(config)
    arch = config["NeuralNetwork"]["Architecture"]
    arch["hidden_dim"] = hidden
    arch["num_conv_layers"] = 2
    # The pinned histogram is the full-size lattices'; tiny ones take their
    # own from the data, as run_training does (the parity test needs that).
    arch.pop("pna_deg", None)
    for block in arch["output_heads"].values():
        block["dim_headlayers"] = [head, head]
        if "dim_sharedlayers" in block:
            block["dim_sharedlayers"] = head
    config["NeuralNetwork"]["Training"]["learning_rate"] = 0.01
    return config


def make_copy(dst: str) -> str:
    """Copies graftbench/ under ``dst`` and adds the tiny cells. Returns dst."""
    shutil.copytree(
        BENCH_DIR, os.path.join(dst, "graftbench"),
        ignore=shutil.ignore_patterns(".cache", "out", "__pycache__"),
    )
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    root = os.path.join(dst, "graftbench")
    cells = []
    for entry in bench["workloads"]:
        with open(os.path.join(root, "traffic", entry["traffic"] + ".json")) as f:
            traffic = json.load(f)
        # Enough steps an epoch that every shape passes with the state laid
        # out on the mesh inside the one warm-up epoch, as at full size.
        traffic["graphs"]["graphs"] = 160 * entry["chips"]
        if "cell_x" in traffic["graphs"]:  # 2 or 4 atoms, two types: the
            # stratified split needs fewer compositions than test graphs
            traffic["graphs"].update(
                cell_x=[1, 3], cell_y=[1, 2], cell_z=[1, 2], number_types=2
            )
        traffic["batch_size"] = 16
        if "bucket_ladder" in traffic:  # a serving mix: 4 clients of 2 or 4
            # atoms, a rung for most flushes and the guard for 4 of the largest
            traffic["clients"] = traffic["engine"]["max_batch_graphs"] = 4
            traffic["engine"]["queue_limit"] = 8
            traffic["bucket_ladder"] = [[16, 40], [32, 64]]
        with open(os.path.join(root, "traffic", "tiny_" + entry["traffic"] + ".json"), "w") as f:
            json.dump(traffic, f)
        cells.append(dict(entry, name="tiny." + entry["traffic"],
                          config="tiny_" + entry["config"],
                          traffic="tiny_" + entry["traffic"]))
    configs = []
    for c in bench["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            config = _shrink(json.load(f), hidden=8, head=8)
        file = f"graftbench/configs/tiny_{c['name']}.json"
        with open(os.path.join(dst, file), "w") as f:
            json.dump(config, f)
        configs.append(dict(c, name="tiny_" + c["name"], file=file))
    renamed = {w["name"]: t["name"] for w, t in zip(bench["workloads"], cells)}
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if "workloads" in m:
                m["workloads"] = [renamed[w] for w in m["workloads"]]
    bench["workloads"], bench["configs"] = cells, configs
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return dst


def cell(root: str, driver: str, chips: int = 1, model: str = "PNA") -> str:
    """Name of the copy's first cell with this driver, chip count and model
    family: the self-tests name no cell of the real benchmark."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for w in bench["workloads"]:
        with open(os.path.join(root, "graftbench", "traffic", w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        with open(os.path.join(root, files[w["config"]])) as f:
            kind = json.load(f)["NeuralNetwork"]["Architecture"]["model_type"]
        if (traffic["driver"], w["chips"], kind) == (driver, chips, model):
            return w["name"]
    raise LookupError((driver, chips, model))


def run_cell(root: str, workload: str, seconds: float = 1.0, trace: int = 0,
             seed: int = 0, devices: int = 1, timeout: float = 600.0,
             prelude: str = ""):
    """One run of ``workload`` from the copy at ``root``, in a process of its
    own on the CPU backend (``allow_cpu`` is an argument of ``main`` that no
    command line reaches). ``prelude`` is code run in that process first: a
    test breaks the timed path with it. Returns (exit code, parsed last line,
    stdout)."""
    code = prelude + (
        "\nimport sys; from graftbench.run import main; "
        f"sys.exit(main(['--workload', {workload!r}, '--seed', '{seed}', "
        f"'--seconds', '{seconds}', '--trace', '{trace}'], allow_cpu=True))"
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = root + os.pathsep + REPO
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + f" --xla_force_host_platform_device_count={devices}"
    )
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=root, env=env, text=True,
        capture_output=True, timeout=timeout,
    )
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    last = None
    if proc.returncode == 0 and lines:
        last = json.loads(lines[-1])
    return proc.returncode, last, proc.stdout + proc.stderr


if __name__ == "__main__":  # python3 graftbench/tests/tiny.py <dir> [cell...]
    target = make_copy(sys.argv[1])
    with open(os.path.join(target, "BENCHMARK.json")) as f:
        names = [w for w in json.load(f)["workloads"]]
    for w in names:
        if len(sys.argv) > 2 and w["name"] not in sys.argv[2:]:
            continue
        rc, last, text = run_cell(target, w["name"], devices=w["chips"])
        print(text[-3000:])
        print("EXIT", rc)
