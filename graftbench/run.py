"""graftbench: one run of one cell.

    python3 -m graftbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process that loads, warms up every shape the cell's traffic uses,
measures for ``--seconds``, and prints ONE last line of standard output: a
JSON object with ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, traced ``breakdown`` and, last, where the driver compares numbers
against limits, ``compared``. Everything else worth reading goes on earlier
lines. A run that finds no TPU, or fewer chips than the cell asks
for, exits non-zero and prints no result; so does one started away from the
program it measures.

Nothing in this file names a cell, a configuration, a traffic mix or a
metric: the cell is an entry of ``BENCHMARK.json``, its configuration and
traffic are the data files that entry names, the driver and the graph
generator are modules found by the names in the traffic file, and each
per-layer metric is read by ``graftbench/layer_metrics/<metric>.py``.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # as near to process start as Python gets

import argparse
import copy
import importlib
import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# The traced window is short: traces are large and the tracer slows the host.
TRACE_SECONDS = 6.0


def say(msg: str) -> None:
    print(f"[graftbench] {msg}", flush=True)


class CompileLog:
    """XLA compile requests of this process from JAX's own monitoring events
    (the arithmetic of ``chip_smoke.py``'s ``_CompileLog``): how many, how
    many seconds, and how many the persistent cache served."""

    def __init__(self):
        import jax

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

    def _event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


class Cell:
    """What a driver is handed: the cell's data, the clocks, the window."""

    def __init__(self, entry, config, traffic, args, devices, compiles):
        self.name = entry["name"]
        self.chips = int(entry["chips"])
        self.config = config
        self.traffic = traffic
        self.seed = int(args.seed)
        self.trace = bool(args.trace)
        self.seconds = (
            min(float(args.seconds), TRACE_SECONDS) if self.trace
            else float(args.seconds)
        )
        self.devices = devices
        self.compiles = compiles
        self.cache_dir = os.path.join(HERE, ".cache")
        self.out_dir = os.path.join(HERE, "out", self.name)
        self.trace_dir = os.path.join(self.out_dir, "trace")
        self.marks = [("start", T_PROCESS)]
        self.window_s = None
        self.setup = None
        self._compiles_at_window = None

    def mark(self, phase: str) -> None:
        """End of a set-up phase (the split printed before the result)."""
        self.marks.append((phase, time.perf_counter()))

    def hydragnn_config(self, dataset_block: dict) -> dict:
        """The program's config: the configuration file's ``NeuralNetwork``
        block, the generator's ``Dataset`` block, the traffic's batch."""
        nn = copy.deepcopy(self.config["NeuralNetwork"])
        nn["Training"]["batch_size"] = int(self.traffic["batch_size"])
        nn["Training"].setdefault("num_epoch", 1)
        dataset = dict(dataset_block)
        if "num_buckets" in self.traffic:
            dataset["num_buckets"] = int(self.traffic["num_buckets"])
        return {
            "Verbosity": {"level": 0},
            "Dataset": dataset,
            "NeuralNetwork": nn,
            "Visualization": {"create_plots": 0},
        }

    def begin_window(self) -> float:
        """Set-up ends here. Returns the window's start on perf_counter."""
        import jax
        from hydragnn_tpu.analysis.sentinel import compile_count

        self.mark("warm-up")
        self._compiles_at_window = compile_count()
        c = self.compiles
        self.setup = {
            "setup_s": self.marks[-1][1] - T_PROCESS,
            "compile_s": c.seconds,
            "compile_requests": c.count,
            "cache_hits": c.cache_hits,
            "cache_misses": c.cache_misses,
        }
        if self.trace:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        from hydragnn_tpu import telemetry

        self._window_span = telemetry.span("graftbench.window")
        self._window_span.__enter__()
        return time.perf_counter()

    def end_window(self, t0: float) -> float:
        import jax
        from hydragnn_tpu.analysis.sentinel import compile_count

        self.window_s = time.perf_counter() - t0
        self._window_span.__exit__(None, None, None)
        self.compiles_in_window = compile_count() - self._compiles_at_window
        if self.trace:
            jax.profiler.stop_trace()
        return self.window_s


def _load_cell(workload: str):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(
            f"[graftbench] no workload {workload!r} in BENCHMARK.json; there "
            f"are {[w['name'] for w in bench['workloads']]}"
        )
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", entry["traffic"] + ".json")) as f:
        traffic = json.load(f)
    if int(traffic.get("chips", entry["chips"])) != int(entry["chips"]):
        raise SystemExit(
            f"[graftbench] {workload}: traffic file says chips="
            f"{traffic['chips']}, BENCHMARK.json says {entry['chips']}"
        )
    return bench, entry, config, traffic


def _metrics_of(bench: dict, kind: str, workload: str) -> list:
    """The entries of ``kind`` that ``workload`` reports."""
    return [
        m for m in bench[kind]
        if "workloads" not in m or workload in m["workloads"]
    ]


def main(argv=None, allow_cpu: bool = False) -> int:
    """``allow_cpu`` is for the self-tests' tiny rehearsals only: it is an
    argument of this function and of nothing a command line can reach."""
    ap = argparse.ArgumentParser(prog="python3 -m graftbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    try:
        import hydragnn_tpu  # noqa: F401  (the system under test)
    except ImportError as e:
        print(
            f"[graftbench] the program is not around the benchmark ({e}); "
            "no result", file=sys.stderr,
        )
        return 2
    bench, entry, config, traffic = _load_cell(args.workload)

    import jax

    devices = jax.devices()
    dev = devices[0]
    say(
        f"platform={dev.platform} device_kind={dev.device_kind} "
        f"device_count={len(devices)} cell={entry['name']} seed={args.seed}"
    )
    if dev.platform != "tpu" and not allow_cpu:
        print(
            f"[graftbench] platform is {dev.platform!r}, not 'tpu': no "
            "accelerator, no result", file=sys.stderr,
        )
        return 3
    if len(devices) < int(entry["chips"]):
        print(
            f"[graftbench] {len(devices)} devices visible, the cell needs "
            f"{entry['chips']}: no result", file=sys.stderr,
        )
        return 3
    devices = devices[: int(entry["chips"])]
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if dev.device_kind not in peaks and not allow_cpu:
        print(
            f"[graftbench] device_kind {dev.device_kind!r} is not in "
            "graftbench/peaks.json: add its peaks with their source",
            file=sys.stderr,
        )
        return 3

    from hydragnn_tpu.cache.jaxcache import place_jax_cache

    compiles = CompileLog()
    cell = Cell(entry, config, traffic, args, devices, compiles)
    say(f"jax compile cache: {place_jax_cache()}")
    cell.mark("import + device probe")
    os.makedirs(cell.out_dir, exist_ok=True)
    if cell.trace:
        import shutil

        shutil.rmtree(cell.trace_dir, ignore_errors=True)
    # The program writes logs/ and serialized_dataset/ under its working
    # directory; keep them with the benchmark's other outputs.
    os.chdir(cell.out_dir)
    os.environ["SERIALIZED_DATA_PATH"] = cell.out_dir

    from graftbench import memory

    driver = importlib.import_module(f"graftbench.drivers.{traffic['driver']}")
    result = driver.run(cell)

    marks = cell.marks
    split = ", ".join(
        f"{name} {t - marks[i][1]:.1f}s" for i, (name, t) in enumerate(marks[1:])
    )
    s = cell.setup
    say(
        f"set-up {s['setup_s']:.1f}s = {split}; of it XLA compile "
        f"{s['compile_s']:.1f}s in {s['compile_requests']} requests, "
        f"persistent cache hits {s['cache_hits']} misses {s['cache_misses']}"
    )
    why_not = list(result.get("why_not", ()))
    if cell.compiles_in_window:
        why_not.append(f"{cell.compiles_in_window} XLA compiles inside the window")
    # What the last line has no room for: the set-up split, the driver's
    # counts, each epoch's loss, the program's config as it was run.
    with open(os.path.join(cell.out_dir, "last_run.json"), "w") as f:
        json.dump(
            dict(
                cell=cell.name, seed=cell.seed, trace=cell.trace, setup=s,
                phases=[[n, t - T_PROCESS] for n, t in marks],
                why_not=why_not, end_to_end=result["end_to_end"],
                facts=result["facts"], extra=result.get("extra"),
            ),
            f, indent=1, default=str,
        )
    for line in why_not:
        say(f"NOT CORRECT: {line}")

    # Peak on the fullest chip (graftbench/memory.py), read by the driver
    # while its state was alive: the allocator's own peak, or what it held
    # after the window plus the temporaries the largest program reserves
    # while it runs, whichever is more. The two parts are given beside it;
    # the driver of the checks reads ``memory_peak_bytes`` alone.
    mem = result.get("memory") or memory.peak(devices, {})
    say(f"memory: {json.dumps(mem)}")
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
        "memory_peak_bytes": mem["peak_bytes"],
        "allocator_peak_bytes": mem["allocator_peak_bytes"],
        "program_temp_bytes": mem["program_temp_bytes"],
    }
    values = dict(result["end_to_end"], setup_s=s["setup_s"])
    breakdown = None
    if cell.trace:
        from graftbench import trace_reduce

        from hydragnn_tpu import telemetry

        spans = [r for r in telemetry.collected_records() if r["kind"] == "span"]
        reduced = trace_reduce.reduce_dir(
            cell.trace_dir, {r["name"] for r in spans}
        )
        say("trace: " + json.dumps(trace_reduce.summary(reduced)))
        run = types.SimpleNamespace(
            cell=cell, facts=result["facts"], setup=s, spans=spans,
            trace=reduced, peaks=peaks.get(dev.device_kind), device=device,
            memory=mem, end_to_end=values,
        )
        metrics = {}
        for m in _metrics_of(bench, "per_layer", cell.name):
            reader = importlib.import_module(
                f"graftbench.layer_metrics.{m['name']}"
            )
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        say(
            "end-to-end in this traced run (tracing overhead shows against "
            "a --trace 0 run): " + json.dumps(values)
        )
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = {
            "device_ops": reduced["top_ops"][:10],
            "idle_gaps": reduced["idle_gaps"][:10],
        }
    else:
        metrics = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in _metrics_of(bench, "end_to_end", cell.name)
        }
    line = {
        "correct": not why_not,
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    # Each number a driver compared beside its limit: the line's last key,
    # and the last lines of standard error.
    compared = result.get("compared")
    if compared:
        line["compared"] = compared
        for name, row in compared.items():
            print(f"[graftbench] compared {name}: {json.dumps(row)}", file=sys.stderr)
        sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
