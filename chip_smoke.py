#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls — train,
predict, serve — at the widest size the repo has run (PNA, one graph head +
three node heads, hidden 256 x 3 conv layers, batch 512, two bucket shapes),
on random-seeded synthetic data it generates itself, and checks every stage
by the repo's own means. The last line of standard output is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and the exit code is 0 only when every stage passed on a TPU. With no
accelerator, or away from the rest of the repo, it exits non-zero and prints
no result.

    python3 chip_smoke.py                    # one chip, every stage
    python3 chip_smoke.py --chips 4          # four-chip host: run_training on
                                             # a data mesh of 4, then on 2x2
    python3 chip_smoke.py --rehearse-on-cpu  # tiny sizes on the CPU; checks
                                             # the control flow, not the chip

This process never imports JAX: a process that has touched JAX holds the
chip, and each stage is a child that must hold it alone. Everything is
written under a fresh ``chip_smoke_out/`` (the children's working directory
and SERIALIZED_DATA_PATH); nothing is read from ``logs/``, ``dataset/`` or
``serialized_dataset/`` of the checkout.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import shutil
import signal
import subprocess
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
NEEDS = (
    "hydragnn_tpu/run_training.py",
    "tests/deterministic_graph_data.py",
    "tests/inputs/ci_multihead.json",
)

# The task is tests/inputs/ci_multihead.json; these are the sizes laid over
# it. "full" is bench.py's wide workload (hidden 256 x 3, batch 512) with the
# generator's lattice ranges raised so a graph has 8-36 atoms and a batch
# about 10k nodes. "tiny" exists for --rehearse-on-cpu only.
SIZES = {
    "full": dict(
        hidden_dim=256, num_conv_layers=3, head_dim=32, batch_size=512,
        num_epoch=3, num_buckets=2, learning_rate=1e-3,
        configurations=7680, cell_x=(2, 4), cell_y=(2, 4), cell_z=(1, 3),
        request_graphs=(1, 8, 32), certify=dict(e=131072, f=256, n=8192),
    ),
    "tiny": dict(
        hidden_dim=8, num_conv_layers=2, head_dim=8, batch_size=16,
        num_epoch=3, num_buckets=2, learning_rate=1e-2,
        configurations=120, cell_x=(1, 3), cell_y=(1, 3), cell_z=(1, 2),
        request_graphs=(1, 3, 8), certify=dict(e=1024, f=8, n=256),
    ),
}
# Served predictions against run_prediction's for the same graphs. The two
# pad the same graphs into different batch shapes, so the prefix sums of the
# sorted aggregation run at another length, and on the TPU an f32 matmul
# multiplies in bf16 (2^-8 relative an operand): measured 5.4e-3 at most on
# the chip (PR 21). A wrong checkpoint or a mis-wired head is off by O(1).
SERVE_ATOL = SERVE_RTOL = 2e-2
# run_prediction on one checkpoint, edge-sharded over the 2x2 mesh against one
# device: tests/test_largegraph.py's check (error and per-head RMSE of one
# forward pass), at the tolerance above for the same reasons — each edge
# shard runs its own prefix sums.
MESH_RTOL = 2e-2
# bf16 policy against f32 on the same batches: the repo's own gate
# (bench.py --precision, tests/test_mixed_precision.py) on the loss, relative
# to the f32 loss of the first epoch.
BF16_REL_GATE = 0.05
# The whole run, compilation included, has 1200 s; stages share what is left.
TOTAL_LIMIT_S = 1150.0
STAGE_LIMIT_S = dict(
    device=120, train=900, serve=420, kernels=600, warm=420, mesh=900
)

_children: list = []


def _sizes(args) -> dict:
    return SIZES["tiny" if args.rehearse_on_cpu else "full"]


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# --------------------------------------------------------------------- parent
def _run_child(stage: str, args, cwd: str, timeout: float, env: dict):
    """One stage = one child process holding the chip alone. The child
    inherits stdout; its verdict comes back in <out>/<stage>.json."""
    result_path = os.path.join(args.out, f"{stage}.json")
    cmd = [
        sys.executable, os.path.abspath(__file__), "--stage", stage,
        "--out", args.out, "--seed", str(args.seed),
        "--chips", str(args.chips),
    ] + (["--rehearse-on-cpu"] if args.rehearse_on_cpu else [])
    say(f"stage {stage}: starting (limit {timeout:.0f}s)")
    t0 = time.perf_counter()
    proc = subprocess.Popen(cmd, cwd=cwd, env=env, start_new_session=True)
    _children.append(proc)
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        _kill(proc)
        return None, f"hung past its {timeout:.0f}s limit"
    wall = time.perf_counter() - t0
    if rc != 0:
        return None, f"exit code {rc} after {wall:.1f}s"
    try:
        with open(result_path) as f:
            result = json.load(f)
    except (OSError, ValueError) as e:
        return None, f"no result file ({e})"
    result["stage_wall_s"] = round(wall, 1)
    return result, None


def _kill(proc) -> None:
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()


def _wanted_platform(args) -> str:
    """tpu — or cpu, for the explicit rehearsal and nothing else."""
    return "cpu" if args.rehearse_on_cpu else "tpu"


def _check_device(device: dict, args) -> str | None:
    want = _wanted_platform(args)
    if device.get("platform") != want:
        return (
            f"platform is {device.get('platform')!r}, not {want!r}"
            + ("" if args.rehearse_on_cpu else " — no accelerator, no result")
        )
    if device.get("count") != args.chips:
        return f"{device.get('count')} devices visible, --chips {args.chips}"
    return None


def _generate_data(args, size: dict) -> str:
    """The deterministic BCC-lattice generator of the test suite, writing
    LSMS-format text files, seeded from --seed."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "deterministic_graph_data",
        os.path.join(REPO, "tests", "deterministic_graph_data.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    raw = os.path.join(args.out, "dataset", "unit_test_multihead")
    os.makedirs(raw)
    t0 = time.perf_counter()
    mod.deterministic_graph_data(
        raw,
        number_configurations=size["configurations"],
        configuration_start=args.seed * size["configurations"],
        unit_cell_x_range=size["cell_x"],
        unit_cell_y_range=size["cell_y"],
        unit_cell_z_range=size["cell_z"],
    )
    say(
        f"data: {size['configurations']} graphs from seed {args.seed} in "
        f"{time.perf_counter() - t0:.1f}s -> {raw}"
    )
    return raw


def _write_config(args, size: dict, raw: str) -> dict:
    with open(os.path.join(REPO, "tests", "inputs", "ci_multihead.json")) as f:
        config = json.load(f)
    config["Verbosity"]["level"] = 0
    config["Dataset"]["path"] = {"total": raw}
    config["Dataset"]["num_buckets"] = size["num_buckets"]
    arch = config["NeuralNetwork"]["Architecture"]
    arch["hidden_dim"] = size["hidden_dim"]
    arch["num_conv_layers"] = size["num_conv_layers"]
    heads = arch["output_heads"]
    heads["graph"]["dim_sharedlayers"] = size["head_dim"]
    heads["graph"]["dim_headlayers"] = [size["head_dim"]] * 2
    heads["node"]["dim_headlayers"] = [size["head_dim"]] * 2
    training = config["NeuralNetwork"]["Training"]
    training["num_epoch"] = size["num_epoch"]
    training["learning_rate"] = size["learning_rate"]
    # batch_size is the GLOBAL batch of one process; on a data mesh every
    # device takes one loader batch per step, so divide to keep the step's
    # graph count the same on one chip and on four.
    training["batch_size"] = size["batch_size"] // args.chips
    # Plots cost a second evaluation pass and matplotlib; not the chip's work.
    config["Visualization"]["create_plots"] = 0
    with open(os.path.join(args.out, "config.json"), "w") as f:
        json.dump(config, f, indent=1)
    say(
        "sizes: PNA hidden_dim={hidden_dim} num_conv_layers={num_conv_layers} "
        "head_dim={head_dim} num_epoch={num_epoch} num_buckets={num_buckets} "
        "learning_rate={learning_rate} lattice x{cell_x} y{cell_y} z{cell_z} "
        "(2*x*y*z atoms a graph)".format(**size)
        + f" batch_size={training['batch_size']}/device x {args.chips} "
        f"perc_train={training['perc_train']}"
    )
    return config


def _http(url: str, doc=None, timeout: float = 120.0):
    data = None if doc is None else json.dumps(doc).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"}
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.read()


def _stage_serve(args, train: dict, env: dict, startup_timeout: float):
    """python -m hydragnn_tpu.serve on the checkpoint the train stage wrote;
    this process is the client (stdlib HTTP only)."""
    import numpy as np

    with open(os.path.join(args.out, "payloads.json")) as f:
        dump = json.load(f)
    cmd = [
        sys.executable, "-m", "hydragnn_tpu.serve",
        "--config", os.path.join("logs", train["log_name"], "config.json"),
        "--port", "0", "--bucket-ladder", dump["ladder"],
        "--max-batch-graphs", str(max(len(r["graphs"]) for r in dump["requests"])),
    ]
    say("stage serve: " + " ".join(cmd[1:]))
    t0 = time.perf_counter()
    log_path = os.path.join(args.out, "serve.log")
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            cmd, cwd=args.out, env=env, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
    _children.append(proc)
    try:
        base = None
        while base is None:
            if proc.poll() is not None:
                return None, f"server exited {proc.returncode} before listening"
            if time.perf_counter() - t0 > startup_timeout:
                return None, f"server not listening after {startup_timeout:.0f}s"
            with open(log_path) as f:
                for line in f:
                    if "listening on http://" in line:
                        base = line.split("listening on ")[1].split()[0]
            time.sleep(0.5)
        startup_s = time.perf_counter() - t0
        say(f"stage serve: {base} up after {startup_s:.1f}s")

        health = json.loads(_http(base + "/healthz"))
        device = health.get("device") or {}
        if device.get("platform") != _wanted_platform(args):
            return None, f"server weights live on {device!r}"
        if not health.get("ok") or health.get("degraded"):
            return None, f"/healthz not ok: {health}"

        worst = 0.0
        for req in dump["requests"]:
            doc = json.loads(_http(base + "/predict", {"graphs": req["graphs"]}))
            for got_graph, want_graph in zip(doc["predictions"], req["expected"]):
                for got, ref in zip(got_graph, want_graph):
                    got = np.asarray(got, np.float64)
                    ref = np.asarray(ref, np.float64).reshape(got.shape)
                    if not np.isfinite(got).all():
                        return None, "served prediction is not finite"
                    err = np.abs(got - ref)
                    worst = max(worst, float(err.max()))
                    if (err > SERVE_ATOL + SERVE_RTOL * np.abs(ref)).any():
                        return None, (
                            f"served != run_prediction: max |diff| "
                            f"{float(err.max()):.3e} beyond atol={SERVE_ATOL} "
                            f"rtol={SERVE_RTOL}"
                        )
        metrics = _http(base + "/metrics").decode()
        if "hydragnn_serve_ladder_fallback_total 0" not in metrics:
            return None, "ladder_fallback_total is not 0: a request missed the ladder"
        n_graphs = sum(len(r["graphs"]) for r in dump["requests"])
        if f"hydragnn_serve_requests_total {n_graphs}" not in metrics:
            return None, f"/metrics does not count {n_graphs} requests"

        proc.send_signal(signal.SIGINT)
        try:
            rc = proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            return None, "server did not stop within 60s of SIGINT"
        if rc != 0:
            return None, f"server exited {rc} after SIGINT"
        return {
            "device": device,
            "startup_s": round(startup_s, 1),
            "requests": [len(r["graphs"]) for r in dump["requests"]],
            "max_abs_diff_vs_run_prediction": worst,
            "tolerance": {"atol": SERVE_ATOL, "rtol": SERVE_RTOL},
            "compiled_buckets": health.get("compiled_buckets"),
            "ladder": dump["ladder"],
        }, None
    finally:
        _kill(proc)
        with open(log_path) as f:
            tail = f.read()[-3000:]
        print(tail, flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument(
        "--rehearse-on-cpu", action="store_true",
        help="tiny sizes on the CPU backend: checks the control flow only. "
        "Never a default and never entered because no chip was found.",
    )
    ap.add_argument("--out", default=os.path.join(REPO, "chip_smoke_out"))
    ap.add_argument("--stage", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    args.out = os.path.abspath(args.out)
    if args.stage:
        return _child_main(args)

    missing = [p for p in NEEDS if not os.path.exists(os.path.join(REPO, p))]
    if missing:
        print(
            f"chip_smoke.py needs the repo around it; missing: {missing}",
            file=sys.stderr,
        )
        return 2
    # A parent started in the background may have SIGINT ignored, and an
    # ignored signal stays ignored in children; a handler does not. The serve
    # stage stops its server with SIGINT.
    signal.signal(signal.SIGINT, signal.default_int_handler)
    t_start = time.perf_counter()
    size = _sizes(args)
    if os.path.isdir(args.out):
        shutil.rmtree(args.out)
    os.makedirs(args.out)

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["SERIALIZED_DATA_PATH"] = args.out
    for flag in ("HYDRAGNN_SEGMENT_SORTED", "HYDRAGNN_COMPILE_CACHE"):
        env.pop(flag, None)  # stages run the defaults
    if args.rehearse_on_cpu:
        say("REHEARSAL on the CPU at tiny sizes: nothing below is a chip result")
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}"
        )
        # Tiny programs compile in under JAX's one-second floor for
        # persisting an entry; lower it so the warm stage has entries to hit.
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        # XLA:CPU logs a page of machine features for every entry it loads.
        env["TF_CPP_MIN_LOG_LEVEL"] = "3"
    failures: list = []
    summary: dict = {"seed": args.seed, "chips": args.chips}

    def limit(stage) -> float:
        left = TOTAL_LIMIT_S - (time.perf_counter() - t_start)
        return max(min(STAGE_LIMIT_S[stage], left), 1.0)

    def record(stage, result, err):
        if err is not None:
            failures.append(f"{stage}: {err}")
            say(f"stage {stage}: FAILED — {err}")
            return None
        summary[stage] = result
        say(f"stage {stage}: ok {json.dumps(result)}")
        return result

    def run(stage, cwd=args.out):
        result, err = _run_child(stage, args, cwd, limit(stage), env)
        if err is None:
            err = _check_device(result.get("device", {}), args)
        return record(stage, result, err)

    try:
        probe = run("device")
        if probe is None:
            return _finish(failures, None, t_start)
        raw = _generate_data(args, size)
        _write_config(args, size, raw)
        if args.chips == 4:
            run("mesh")
        else:
            train = run("train")
            if train is not None:
                record("serve", *_stage_serve(args, train, env, limit("serve")))
            run("kernels")
            if train is not None:
                os.makedirs(os.path.join(args.out, "warm"))
                run("warm", cwd=os.path.join(args.out, "warm"))
        with open(os.path.join(args.out, "summary.json"), "w") as f:
            json.dump(summary, f, indent=1)
        return _finish(failures, probe["device"], t_start)
    finally:
        for proc in _children:
            _kill(proc)


def _finish(failures, device, t_start) -> int:
    say(f"total {time.perf_counter() - t_start:.1f}s")
    if failures or device is None:
        for line in failures:
            print(f"[chip_smoke] FAILED {line}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


# ------------------------------------------------------------------- children
def _device() -> dict:
    import jax

    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }


def _require_platform(args) -> dict:
    """First thing every child does: name the device and refuse the wrong
    platform — a CPU run is only ever the explicit rehearsal."""
    device = _device()
    say(
        f"platform={device['platform']} device_kind={device['kind']} "
        f"device_count={device['count']}"
    )
    want = _wanted_platform(args)
    if device["platform"] != want:
        raise SystemExit(
            f"[chip_smoke] platform is {device['platform']!r}, not {want!r}"
        )
    return device


class _CompileLog:
    """XLA compile requests of this process, from JAX's own monitoring
    events: when the last one ended, how many there were, how many seconds
    they took, and how many were served by the persistent cache."""

    def __init__(self):
        import jax

        self.t0 = time.perf_counter()
        self.last = self.t0
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs
            self.last = time.perf_counter()

    def _event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def report(self, cache_dir: str, entries_before: int) -> dict:
        return {
            "setup_s": round(self.last - self.t0, 1),
            "xla_compile_requests": self.count,
            "xla_compile_s": round(self.seconds, 1),
            "persistent_cache_hits": self.cache_hits,
            "persistent_cache_misses": self.cache_misses,
            "cache_dir": cache_dir,
            "cache_entries_before": entries_before,
            "cache_entries_after": _cache_entries(cache_dir),
        }


def _cache_entries(cache_dir: str) -> int:
    try:
        return sum(1 for n in os.listdir(cache_dir) if n.endswith("-cache"))
    except OSError:
        return 0


def _load_config(args) -> dict:
    with open(os.path.join(args.out, "config.json")) as f:
        return json.load(f)


def _check_history(history: dict, what: str) -> dict:
    import numpy as np

    losses = [float(v) for v in history["total_loss_train"]]
    if not np.isfinite(losses).all():
        raise SystemExit(f"[chip_smoke] {what}: non-finite loss {losses}")
    if not losses[-1] < losses[0]:
        raise SystemExit(f"[chip_smoke] {what}: loss did not fall {losses}")
    compiles = list(history["xla_compiles"])
    if any(compiles[1:]):
        raise SystemExit(
            f"[chip_smoke] {what}: XLA compiled after the first epoch "
            f"(per-epoch compile counts {compiles}, from "
            "analysis.sentinel.compile_count)"
        )
    return {"loss_per_epoch": losses, "xla_compiles_per_epoch": compiles}


def _peak_bytes() -> list:
    import jax

    return [
        (d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()
    ]


def _child_train(args) -> dict:
    import numpy as np

    device = _require_platform(args)
    from hydragnn_tpu import native, run_prediction, run_training
    from hydragnn_tpu.cache.jaxcache import place_jax_cache
    from hydragnn_tpu.preprocess.load_data import dataset_loading_and_splitting

    builder = "native C++ cell list, rebuilt from neighborlist.cc" if (
        native.rebuild()
    ) else "numpy/cKDTree (the native build failed)"
    say(f"neighbour builder: {builder}")
    cache_dir = place_jax_cache()
    entries_before = _cache_entries(cache_dir)
    compiles = _CompileLog()

    config = _load_config(args)
    t0 = time.perf_counter()
    history = run_training(copy.deepcopy(config))
    train_wall = time.perf_counter() - t0
    out = {"device": device, "neighbour_builder": builder}
    out.update(_check_history(history, "run_training"))
    out["compile"] = compiles.report(cache_dir, entries_before)
    out["run_training_wall_s"] = round(train_wall, 1)

    _err, rmse_task, _true, pred = run_prediction(copy.deepcopy(config))
    rmse_task = [float(r) for r in np.asarray(rmse_task).ravel()]
    if not np.isfinite(rmse_task).all():
        raise SystemExit(f"[chip_smoke] run_prediction: RMSE {rmse_task}")
    out["rmse_per_head"] = rmse_task

    # What the loader made of the data, and the served-vs-predicted dump.
    from hydragnn_tpu.graphs.collate import round_up_pow2
    from hydragnn_tpu.utils.config_utils import get_log_name_config

    out["log_name"] = get_log_name_config(config)
    with open(os.path.join("logs", out["log_name"], "config.json")) as f:
        completed = json.load(f)  # run_training's snapshot, as serve reads it
    train_loader, _val, test_loader, _ = dataset_loading_and_splitting(
        copy.deepcopy(config)
    )
    shapes: dict = {}
    nodes = []
    for b in train_loader:
        key = (int(b.node_features.shape[0]), int(b.senders.shape[0]))
        shapes[key] = shapes.get(key, 0) + 1
        nodes.append(int(np.asarray(b.node_mask).sum()))
    steps = sum(shapes.values())
    out["train_graphs"] = len(train_loader.dataset)
    out["steps_per_epoch"] = steps
    out["steps_total"] = steps * len(history["total_loss_train"])
    out["batch_shapes_nodes_x_edges"] = {
        f"{n}x{e}": c for (n, e), c in sorted(shapes.items())
    }
    out["real_nodes_per_batch_mean"] = round(float(np.mean(nodes)), 1)
    if not args.rehearse_on_cpu:
        if len(shapes) < 2:
            raise SystemExit(f"[chip_smoke] one batch shape only: {shapes}")
        if out["steps_total"] < 30:
            raise SystemExit(f"[chip_smoke] {out['steps_total']} steps < 30")

    # Consecutive slices of the test set, in run_prediction's row order; one
    # ladder rung for the smallest request and one that fits the others.
    samples = list(test_loader.dataset)
    node_start = np.concatenate([[0], np.cumsum([s.num_nodes for s in samples])])
    head_types = completed["NeuralNetwork"]["Architecture"]["output_type"]
    requests, rungs, lo = [], [], 0
    for count in _sizes(args)["request_graphs"]:
        graphs, expected = [], []
        for i in range(lo, lo + count):
            s = samples[i]
            doc = {
                "x": np.asarray(s.x).tolist(),
                "edge_index": np.asarray(s.edge_index).tolist(),
            }
            if s.edge_attr is not None:
                doc["edge_attr"] = np.asarray(s.edge_attr).tolist()
            graphs.append(doc)
            expected.append([
                np.asarray(
                    pred[h][i] if kind == "graph"
                    else pred[h][node_start[i]:node_start[i + 1]]
                ).tolist()
                for h, kind in enumerate(head_types)
            ])
        requests.append({"graphs": graphs, "expected": expected})
        rungs.append((
            round_up_pow2(node_start[lo + count] - node_start[lo] + 1),
            round_up_pow2(sum(s.num_edges for s in samples[lo:lo + count])),
        ))
        lo += count
    big = (max(n for n, _ in rungs[1:]), max(e for _, e in rungs[1:]))
    ladder = f"{rungs[0][0]}x{rungs[0][1]},{big[0]}x{big[1]}"
    with open(os.path.join(args.out, "payloads.json"), "w") as f:
        json.dump({"ladder": ladder, "requests": requests}, f)
    out["serve_ladder"] = ladder
    out["peak_bytes_in_use"] = _peak_bytes()
    say(f"peak_bytes_in_use per device: {out['peak_bytes_in_use']}")
    return out


def _child_warm(args) -> dict:
    """The train stage's set-up again in a new process: same programs, so
    JAX's persistent cache should serve them. One epoch is enough — every
    shape compiles in the first."""
    device = _require_platform(args)
    from hydragnn_tpu import run_training
    from hydragnn_tpu.cache.jaxcache import place_jax_cache

    cache_dir = place_jax_cache()
    entries_before = _cache_entries(cache_dir)
    compiles = _CompileLog()
    config = _load_config(args)
    config["NeuralNetwork"]["Training"]["num_epoch"] = 1
    run_training(config)
    warm = compiles.report(cache_dir, entries_before)
    with open(os.path.join(args.out, "train.json")) as f:
        cold = json.load(f)["compile"]
    say(
        f"set-up (run_training entry -> last XLA compile request): cold "
        f"{cold['setup_s']}s, warm {warm['setup_s']}s; compile seconds cold "
        f"{cold['xla_compile_s']}s, warm {warm['xla_compile_s']}s; cache "
        f"{cache_dir}: {cold['cache_entries_before']} entries before the cold "
        f"run, {warm['cache_entries_before']} before the warm run, "
        f"{warm['cache_entries_after']} after; warm hits "
        f"{warm['persistent_cache_hits']} misses {warm['persistent_cache_misses']}"
    )
    if warm["persistent_cache_hits"] < 1:
        raise SystemExit(
            "[chip_smoke] warm: no compile request was served by the "
            f"persistent cache at {cache_dir}"
        )
    return {"device": device, "cold": cold, "warm": warm}


def _child_kernels(args) -> dict:
    """Every aggregation arm the sorted route can take, on this platform (the
    extrema scan kernels are Mosaic's on the TPU, never the interpreter there),
    held to ops/certify.py's gates against an f64 ground truth, forward and
    gradient, next to ops/segment.py's own error on the same data; then the
    bf16 training policy against the f32 run."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    device = _require_platform(args)
    from hydragnn_tpu import run_training
    from hydragnn_tpu.cache.jaxcache import place_jax_cache
    from hydragnn_tpu.ops import aggregate as agg
    from hydragnn_tpu.ops import segment_sorted as srt
    from hydragnn_tpu.ops.certify import certify_aggregation

    place_jax_cache()
    on_tpu = device["platform"] == "tpu"
    if not on_tpu:
        # The rehearsal: the chip's arm under this CPU, by the one override.
        os.environ["HYDRAGNN_SEGMENT_SORTED"] = "1"
    if not srt.sorted_enabled():
        raise SystemExit("[chip_smoke] kernels: the sorted arm is not on")
    shape = _sizes(args)["certify"]
    out: dict = {"device": device, "shape": shape, "arms": {}}
    failed = []

    def mosaic_calls(fn) -> int:
        """tpu_custom_call sites in the lowering of ``fn(data, ids, row_ptr)``."""
        e, f, n = shape["e"], shape["f"], shape["n"]
        ids = jnp.sort(jax.random.randint(jax.random.PRNGKey(0), (e,), 0, n))
        row_ptr = jnp.searchsorted(ids, jnp.arange(n + 1)).astype(jnp.int32)
        text = jax.jit(lambda d: fn(d, ids, row_ptr)).lower(
            jnp.ones((e, f), jnp.float32)
        ).as_text()
        return text.count("tpu_custom_call")

    n = shape["n"]
    rep = certify_aggregation(**shape)
    out["tolerance"] = {"fwd": rep["tol"], "grad": rep["tol_grad"]}
    out["ops_segment"] = rep["xla"]
    arms = {
        # name: (selected by, the certifier's verdict, Mosaic kernels expected)
        "sorted": (
            "the sorted arm, no row_ptr, rows under WIDE_ROW columns",
            rep["arms"]["sorted"],
            mosaic_calls(lambda d, i, p: agg.fused_segment_stats(d, i, n)), 0,
        ),
        "csr": (
            "the sorted arm, the batch's row_ptr, rows under WIDE_ROW columns",
            rep["arms"]["csr"],
            mosaic_calls(
                lambda d, i, p: agg.fused_segment_stats(d, i, n, row_ptr=p)
            ), 0,
        ),
        # One XLA scatter-add told the ids are sorted, over ops/certify.py's
        # WIDE_CASES (an edge-sharded axis over this chip alone among them).
        "scatter_sorted": (
            "the sorted arm, rows of WIDE_ROW columns or more",
            {k: v for k, v in rep["arms"]["scatter_sorted"].items() if k != "cases"},
            mosaic_calls(
                lambda d, i, p: agg.fused_segment_stats(d, i, n, row_ptr=p)
            ), 0,
        ),
        # Value and gradient: the forward's scan and the backward's.
        "extrema_scan": (
            "the sorted arm, row_ptr, no edge-sharded axis",
            rep["extrema_scan"],
            mosaic_calls(jax.value_and_grad(
                lambda d, i, p: sum(
                    jnp.sum(o) for o in agg.segment_extrema(d, i, n, None, p)
                )
            )),
            2,
        ),
    }
    for name, (selected_by, verdict, mosaic, want_mosaic) in arms.items():
        arm = {"selected_by": selected_by, **verdict, "mosaic_custom_calls": mosaic}
        out["arms"][name] = arm
        say(f"arm {name}: {json.dumps(arm)}")
        if not verdict["ok"]:
            failed.append(f"{name} outside tolerance")
        if on_tpu and mosaic != want_mosaic:
            failed.append(
                f"{name} lowered with {mosaic} Mosaic kernels, not {want_mosaic}"
            )

    # Training.precision "bf16": the same data and seed through run_training,
    # one epoch, against the f32 run's first epoch.
    config = _load_config(args)
    config["NeuralNetwork"]["Training"]["precision"] = "bf16"
    config["NeuralNetwork"]["Training"]["num_epoch"] = 1
    os.makedirs(os.path.join(args.out, "bf16"), exist_ok=True)
    os.chdir(os.path.join(args.out, "bf16"))
    bf16_loss = float(run_training(config)["total_loss_train"][0])
    out["bf16"] = {"loss_epoch0": bf16_loss}
    try:
        with open(os.path.join(args.out, "train.json")) as f:
            f32_loss = json.load(f)["loss_per_epoch"][0]
    except OSError:
        failed.append("bf16: no f32 run to compare with (train stage failed)")
    else:
        rel = abs(bf16_loss - f32_loss) / abs(f32_loss)
        out["bf16"].update(
            f32_loss_epoch0=f32_loss, rel_diff=rel, gate=BF16_REL_GATE
        )
        if not (np.isfinite(bf16_loss) and rel < BF16_REL_GATE):
            failed.append(f"bf16 loss {bf16_loss} vs f32 {f32_loss}")
    say(f"bf16 policy: {json.dumps(out['bf16'])}")
    out["peak_bytes_in_use"] = _peak_bytes()
    if failed:
        with open(os.path.join(args.out, "kernels_failed.json"), "w") as f:
            json.dump(out, f, indent=1)
        raise SystemExit("[chip_smoke] kernels: " + "; ".join(failed))
    return out


class _DeviceWatch:
    """Samples, while a run is going, where its arrays live: for each count
    of devices an array is spread over, the most arrays and bytes seen at
    once; for each mesh an array is laid over, the most arrays at once and
    how many of them are split along 'graph'; each device's bytes_in_use."""

    def __init__(self):
        import threading

        self.arrays_by_device_count: dict = {}
        self.arrays_by_mesh: dict = {}
        self.max_bytes_in_use: list = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=10)

    def report(self) -> dict:
        return {
            "live_arrays_by_device_count": self.arrays_by_device_count,
            "live_arrays_by_mesh": self.arrays_by_mesh,
            "max_bytes_in_use": self.max_bytes_in_use,
        }

    def _run(self):
        import jax

        while not self._stop.wait(0.25):
            seen: dict = {}
            meshes: dict = {}
            for a in jax.live_arrays():
                k = len(a.sharding.device_set)
                n, b = seen.get(k, (0, 0))
                seen[k] = (n + 1, b + a.nbytes)
                if isinstance(a.sharding, jax.sharding.NamedSharding):
                    name = "x".join(
                        f"{ax}:{n}" for ax, n in a.sharding.mesh.shape.items()
                    )
                    n, g = meshes.get(name, (0, 0))
                    split = "graph" in str(a.sharding.spec)
                    meshes[name] = (n + 1, g + split)
            for into, now in (
                (self.arrays_by_device_count, seen), (self.arrays_by_mesh, meshes)
            ):
                for k, (x, y) in now.items():
                    old = into.get(k, [0, 0])
                    into[k] = [max(old[0], x), max(old[1], y)]
            now = [
                (d.memory_stats() or {}).get("bytes_in_use")
                for d in jax.devices()
            ]
            if not self.max_bytes_in_use:
                self.max_bytes_in_use = now
            else:
                self.max_bytes_in_use = [
                    m if v is None else max(m or 0, v)
                    for m, v in zip(self.max_bytes_in_use, now)
                ]


def _child_mesh(args) -> dict:
    """Four chips in one process, through run_training both times: the data
    mesh of 4 (mesh=make_mesh()), then the 2x2 ('data','graph') mesh the
    config's own Training.graph_axis asks for, at the one-chip smoke's batch
    a data shard — so the same padded shapes, each one's edges split over two
    chips — and run_prediction on that checkpoint, edge-sharded against one
    device."""
    import numpy as np

    device = _require_platform(args)
    from hydragnn_tpu import run_prediction, run_training
    from hydragnn_tpu.cache.jaxcache import place_jax_cache
    from hydragnn_tpu.parallel import make_mesh
    from hydragnn_tpu.preprocess.load_data import dataset_loading_and_splitting

    place_jax_cache()
    mesh = make_mesh()
    say(f"mesh {dict(mesh.shape)} over {[d.id for d in mesh.devices.flat]}")
    with _DeviceWatch() as watch:
        history = run_training(_load_config(args), mesh=mesh)
    # Did all four hold data? The arrays alive during training say which
    # devices they live on, and each device's allocator how much it held.
    out = {
        "device": device,
        "mesh": {k: int(v) for k, v in mesh.shape.items()},
        "peak_bytes_in_use": _peak_bytes(),
    }
    out.update(watch.report())
    out.update(_check_history(history, "run_training(mesh=make_mesh())"))
    say(
        "data mesh of 4 — arrays alive during training by how many devices "
        "hold them {devices: [arrays, bytes]}, bytes_in_use per device (max "
        f"seen) and peak_bytes_in_use per device: {json.dumps(out)}"
    )
    if 4 not in watch.arrays_by_device_count:
        raise SystemExit("[chip_smoke] mesh: no array lived on all 4 devices")
    # Device 0 also ran the model's init, so its peak may be the largest; a
    # device the run never used would have held next to nothing.
    peaks = out["peak_bytes_in_use"]
    if all(p is not None for p in peaks):
        if min(peaks) < 0.25 * max(peaks):
            raise SystemExit(
                f"[chip_smoke] mesh: devices did not share the data {peaks}"
            )
    elif not args.rehearse_on_cpu:
        raise SystemExit("[chip_smoke] mesh: backend reports no memory_stats")

    config = _load_config(args)
    training = config["NeuralNetwork"]["Training"]
    training["graph_axis"] = 2
    training["batch_size"] = _sizes(args)["batch_size"]
    training["num_epoch"] = 2
    loader = dataset_loading_and_splitting(copy.deepcopy(config))[0]
    shapes: dict = {}
    for b in loader:
        key = f"{b.node_features.shape[0]}x{b.senders.shape[0]}"
        shapes[key] = shapes.get(key, 0) + 1
    two = out["mesh_2x2"] = {
        "hidden_dim": config["NeuralNetwork"]["Architecture"]["hidden_dim"],
        "num_conv_layers": config["NeuralNetwork"]["Architecture"]["num_conv_layers"],
        "batch_size_per_data_shard": training["batch_size"],
        "batch_shapes_nodes_x_edges": shapes,
        "loader_batches_per_epoch": sum(shapes.values()),  # two a step
    }
    say(f"2x2 mesh — Training.graph_axis=2, sizes: {json.dumps(two)}")

    def split_along_graph(watch, what):
        on_2x2 = watch.arrays_by_mesh.get("data:2xgraph:2")
        if not on_2x2 or not on_2x2[1]:
            raise SystemExit(
                f"[chip_smoke] mesh: {what} split no array along 'graph' of "
                f"a 2x2 mesh {watch.arrays_by_mesh}"
            )

    def evaluate(cfg):
        error, rmse_task, _true, _pred = run_prediction(copy.deepcopy(cfg))
        return [float(error)] + [float(r) for r in np.asarray(rmse_task).ravel()]

    with _DeviceWatch() as watch:
        history = run_training(copy.deepcopy(config))
    two.update(watch.report())
    two.update(_check_history(history, "run_training(Training.graph_axis=2)"))
    split_along_graph(watch, "run_training")
    with _DeviceWatch() as watch:
        sharded = evaluate(config)
    split_along_graph(watch, "run_prediction")
    del training["graph_axis"]
    single = evaluate(config)
    two["error_and_rmse_per_head_2x2"] = sharded
    two["error_and_rmse_per_head_one_device"] = single
    two["rtol"] = MESH_RTOL
    say(f"2x2 mesh: {json.dumps(two)}")
    if not np.allclose(sharded, single, rtol=MESH_RTOL, atol=0.0):
        raise SystemExit(
            "[chip_smoke] mesh: run_prediction edge-sharded over 2x2 "
            f"{sharded} != one device {single} on the same checkpoint "
            f"(rtol {MESH_RTOL})"
        )
    return out


def _child_main(args) -> int:
    sys.path.insert(0, REPO)
    stage = {
        "device": lambda a: {"device": _require_platform(a)},
        "train": _child_train,
        "warm": _child_warm,
        "kernels": _child_kernels,
        "mesh": _child_mesh,
    }[args.stage]
    result = stage(args)
    with open(os.path.join(args.out, f"{args.stage}.json"), "w") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
