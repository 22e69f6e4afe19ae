"""The serving cell: its entries, the driver's arithmetic on hand-made
cases, the engine's two flush regimes at a tiny size on the CPU, and whole
runs with the timed path broken underneath. Nothing here is a device number."""

import json
import os
import threading
import time
import types

import numpy as np
import pytest

import tiny

from graftbench.drivers import serve_closed as drv
from graftbench.layer_metrics import (
    serve_batch_occupancy,
    serve_collate_ms_per_flush,
    serve_device_ms_per_flush,
    serve_mfu,
    serve_padding_waste_nodes,
    serve_queue_wait_ms,
)

CELL = "pna_multihead_h256.serve_closed_lattice"
E2E = {"serve_graphs_per_s", "serve_p50_ms", "serve_p95_ms"}
LAYERS = {
    "serve_queue_wait_ms": "serve_p95_ms",
    "serve_batch_occupancy": "serve_graphs_per_s",
    "serve_collate_ms_per_flush": "serve_graphs_per_s",
    "serve_h2d_ms_per_flush": "serve_graphs_per_s",
    "serve_padding_waste_nodes": "serve_graphs_per_s",
    "serve_device_ms_per_flush": "serve_graphs_per_s",
    "serve_mfu": "serve_graphs_per_s",
    "serve_gather_roofline": "serve_graphs_per_s",
    "serve_agg_roofline": "serve_graphs_per_s",
    "serve_device_idle_share": "serve_graphs_per_s",
    "serve_peak_hbm_gb": "serve_graphs_per_s",
}


def _bench():
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _traffic():
    with open(os.path.join(tiny.BENCH_DIR, "traffic", "serve_closed_lattice.json")) as f:
        return json.load(f)


# ------------------------------------------------------------------ entries
def pytest_benchmark_json_holds_the_cell_and_its_metrics():
    bench = _bench()
    (entry,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "pna_multihead_h256", "serve_closed_lattice", 1
    )
    # C and the measured bytes are in the line that says why the cell exists.
    assert len(entry["why"]) <= 200 and "64 clients" in entry["why"]
    assert " GB" in entry["why"]
    for m in bench["end_to_end"]:
        if m["name"] in E2E:
            assert m["workloads"] == [CELL] and m["source"] == "host_clock"
            assert 0.01 <= m["bound"] <= 0.1
    assert E2E <= {m["name"] for m in bench["end_to_end"]}
    (setup,) = [m for m in bench["end_to_end"] if m["name"] == "setup_s"]
    assert "workloads" not in setup  # so it covers the cell
    found = {m["name"]: m for m in bench["per_layer"] if m["name"] in LAYERS}
    assert set(found) == set(LAYERS)
    for name, m in found.items():
        assert m["workloads"] == [CELL] and m["moves"] == LAYERS[name], name
        assert os.path.exists(
            os.path.join(tiny.BENCH_DIR, "layer_metrics", name + ".py")
        ), name
    assert found["serve_mfu"]["unit"] == "%"
    for name in ("serve_gather_roofline", "serve_agg_roofline"):
        assert found[name]["unit"] == "%" and found[name]["source"] == "device_trace"
    # No train metric gained the cell, and the cell reports no train metric.
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if CELL in m.get("workloads", ()):
                assert m["name"] in E2E | set(LAYERS), m["name"]


def pytest_traffic_is_one_cycle_by_construction():
    t = _traffic()
    engine = t["engine"]
    assert t["driver"] == "serve_closed" and t["chips"] == 1
    assert t["clients"] == engine["max_batch_graphs"] == 64
    assert engine["queue_limit"] >= 2 * t["clients"]
    # The deadline outlasts a forward (190 ms) and the clients' asking again:
    # two groups that a stall has split merge at the next flush.
    assert engine["max_delay_ms"] >= 400.0
    assert engine["precision"] == "f32" and engine["packing"] is False
    # Float32 as stated: XLA's own default is one bfloat16 pass on the TPU.
    assert t["matmul_precision"] == "highest"
    ladder = sorted(tuple(r) for r in t["bucket_ladder"])
    assert ladder == [tuple(r) for r in t["bucket_ladder"]]
    # The guard holds a flush of the largest graph: 2 x 8 x 8 x 6 atoms, and
    # under 47 directed edges an atom at radius 2.0.
    assert ladder[-1][0] > 64 * 768 and ladder[-1][1] >= 64 * 768 * 47 * 0.98
    g = t["graphs"]
    assert (g["cell_x"], g["cell_y"], g["cell_z"]) == ([6, 9], [6, 9], [4, 7])


# ----------------------------------------------------- the driver's own code
GRAPHS = {"generator": "bcc_lattice", "cell_x": [1, 3], "cell_y": [1, 2],
          "cell_z": [1, 3], "number_types": 2, "per_shape": 2}


def pytest_pool_and_client_streams_repeat_from_the_seed():
    a, _ = drv.make_pool(GRAPHS, 2.0, [0], seed=7)
    b, _ = drv.make_pool(GRAPHS, 2.0, [0], seed=7)
    c, _ = drv.make_pool(GRAPHS, 2.0, [0], seed=2**31 + 11)
    assert len(a) == 8  # four shapes, two of each
    for x, y in zip(a, b):
        assert np.array_equal(x.x, y.x) and np.array_equal(x.edge_index, y.edge_index)
    # Every seed holds the same sizes; the seed draws the atoms.
    assert [(s.num_nodes, s.num_edges) for s in a] == [(s.num_nodes, s.num_edges) for s in c]
    assert any(not np.array_equal(x.x, y.x) for x, y in zip(a, c))
    for s in a:  # both directions of every pair, no self loop
        send, recv = s.edge_index
        assert (send != recv).all()
        assert {(i, j) for i, j in zip(send, recv)} == {(j, i) for i, j in zip(send, recv)}
    one = drv.client_orders(8, 4, seed=7)
    two = drv.client_orders(8, 4, seed=7)
    assert all(np.array_equal(x, y) for x, y in zip(one, two))
    assert not np.array_equal(one[0], one[1])  # a client's stream is its own
    assert sorted(one[0][:8]) == list(range(8))  # whole permutations
    assert not np.array_equal(one[0], drv.client_orders(8, 4, seed=8)[0])


def pytest_percentiles_and_failures_on_a_hand_made_list():
    # Two clients; latencies 10, 20, ..., 100 ms, one request refused.
    t0 = 100.0
    first = [(0, t0 + i, t0 + i + 0.01 * (i + 1), ["reply"], None) for i in range(5)]
    second = [(1, t0 + i, t0 + i + 0.01 * (i + 6), ["reply"], None) for i in range(5)]
    second.append((1, t0 + 5, t0 + 5.001, None, "BackpressureError('full')"))
    out = drv.account([first, second], t0)
    assert (out["attempted"], out["failed"]) == (11, 1)
    assert out["errors"] == ["BackpressureError('full')"]
    assert out["serve_p50_ms"] == pytest.approx(55.0)
    assert out["serve_p95_ms"] == pytest.approx(95.5)
    assert out["beyond_p95"] == 1
    # Every reply over the time to the last one; the refusal adds neither.
    assert out["answered_s"] == pytest.approx(4.1)
    assert out["serve_graphs_per_s"] == pytest.approx(10 / 4.1)
    nothing = drv.account([[(0, t0, t0 + 1, None, "boom")]], t0)
    assert nothing["failed"] == 1 and "serve_p95_ms" not in nothing


def pytest_readers_on_hand_made_facts():
    facts = dict(
        flushes=10, graphs=38, max_batch_graphs=4, queue_wait_s=0.5, queue_wait_n=40,
        collate_s=0.2, collate_n=10, real_nodes=600, pad_nodes=1000,
        flush_ops=1.97e12, flush_bytes={"gather": 8.19e9, "agg": 0.0},
    )
    run = types.SimpleNamespace(
        facts=facts, trace={"programs": {"jit_f": {"runs": 10, "seconds": 0.5}}},
        peaks={"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9},
    )
    assert serve_batch_occupancy.read(run) == pytest.approx(0.95)
    assert serve_queue_wait_ms.read(run) == pytest.approx(12.5)
    assert serve_collate_ms_per_flush.read(run) == pytest.approx(20.0)
    assert serve_padding_waste_nodes.read(run) == pytest.approx(40.0)
    assert serve_device_ms_per_flush.read(run) == pytest.approx(50.0)
    assert serve_mfu.read(run) == pytest.approx(20.0)  # 1.97e12 / (0.05 s x 197e12)
    # Nothing to read gives nothing, never 0.
    empty = types.SimpleNamespace(facts={}, trace={"programs": {}}, peaks=run.peaks)
    for reader in (serve_batch_occupancy, serve_queue_wait_ms, serve_mfu,
                   serve_collate_ms_per_flush, serve_device_ms_per_flush,
                   serve_padding_waste_nodes):
        assert reader.read(empty) is None


# ------------------------------------------- the engine's two flush regimes
@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_copy(str(tmp_path_factory.mktemp("graftbench_serve")))


def _tiny_files(root):
    with open(os.path.join(root, "graftbench", "traffic", "tiny_serve_closed_lattice.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(root, "graftbench", "configs", "tiny_pna_multihead_h256.json")) as f:
        config = json.load(f)
    return traffic, config


@pytest.mark.parametrize("clients, full", [(4, True), (2, False)])
def pytest_full_flushes_read_occupancy_one_and_half_filled_ones_say_so(root, clients, full):
    """As many clients as a flush holds: every flush is full and fires on its
    size. Half as many: no flush can fill, each fires on the deadline, and the
    occupancy, the graphs short of full and the queue wait all say so."""
    traffic, config = _tiny_files(root)
    traffic["engine"]["max_delay_ms"] = 30.0
    nn = config["NeuralNetwork"]
    pool, dataset = drv.make_pool(
        traffic["graphs"], float(nn["Architecture"]["radius"]), [0], seed=5
    )
    arch = drv.completed_arch(config, dataset, pool)
    model, template, _ = drv.init_model(arch)
    engine = drv.start_engine(model, drv.seeded_weights(template, 5), traffic)
    try:
        before = drv.counters(engine)
        t0, rows = drv.closed_loop(
            engine, pool, drv.client_orders(len(pool), clients, 5), 0.5
        )
        used = drv.since(before, drv.counters(engine))
    finally:
        engine.close()
    stats = drv.account(rows, t0)
    assert stats["failed"] == 0 and stats["attempted"] == used["graphs"]
    run = types.SimpleNamespace(facts=dict(used, max_batch_graphs=4))
    occupancy = serve_batch_occupancy.read(run)
    short = used["flushes"] * 4 - used["graphs"]
    if full:
        # Only the window's last flush may be short: a client that saw the
        # end while the others had asked again.
        assert short <= 3 and occupancy >= 1.0 - 3 / (4 * used["flushes"])
        assert serve_queue_wait_ms.read(run) < 15.0
    else:
        assert occupancy == pytest.approx(0.5, abs=0.02)
        assert short == pytest.approx(2 * used["flushes"], abs=2)
        # The first of a flush's two requests waits the whole deadline out.
        assert serve_queue_wait_ms.read(run) > 10.0
        assert stats["serve_p50_ms"] > 30.0


class _StallsOnce:
    """An engine seen by ONE client, whose next request after ``after_s`` is
    ``stall_s`` late: a thread the host held back."""

    def __init__(self, engine, clock, after_s, stall_s):
        self.engine, self.clock = engine, clock
        self.after_s, self.stall_s, self.stalled = after_s, stall_s, False

    def submit(self, graph):
        if not self.stalled and time.perf_counter() > self.clock["t_end"] - 2.0 + self.after_s:
            self.stalled = True
            time.sleep(self.stall_s)
        return self.engine.submit(graph)


@pytest.mark.parametrize("delay_ms, heals", [(30.0, False), (400.0, True)])
def pytest_a_deadline_over_the_forward_merges_a_split_loop(root, delay_ms, heals):
    """Why the cell's deadline outlasts its forward. One client asks 150 ms
    late, once, so one flush goes without it. Under a deadline shorter than
    the forward (made 80 ms here) the two groups then flush in turn, each on
    the deadline, for the rest of the window: a second stable cycle. Under a
    longer one the short flush's successor waits for the other group's
    replies and every later flush is full."""
    traffic, config = _tiny_files(root)
    traffic["engine"]["max_delay_ms"] = delay_ms
    nn = config["NeuralNetwork"]
    pool, dataset = drv.make_pool(
        traffic["graphs"], float(nn["Architecture"]["radius"]), [0], seed=5
    )
    arch = drv.completed_arch(config, dataset, pool)
    model, template, _ = drv.init_model(arch)
    engine = drv.start_engine(model, drv.seeded_weights(template, 5), traffic)
    plain = engine._execute
    engine._execute = lambda batch: (time.sleep(0.08), plain(batch))[1]
    try:
        before = drv.counters(engine)
        gate, clock = threading.Event(), {}
        clients = [
            drv.Client(_StallsOnce(engine, clock, 0.3, 0.15) if c == 0 else engine,
                       pool, order, gate, clock)
            for c, order in enumerate(drv.client_orders(len(pool), 4, 5))
        ]
        for c in clients:
            c.start()
        clock["t_end"] = time.perf_counter() + 2.0
        gate.set()
        for c in clients:
            c.join()
        used = drv.since(before, drv.counters(engine))
    finally:
        engine.close()
    assert sum(r[4] is not None for c in clients for r in c.rows) == 0
    short = 4 * used["flushes"] - used["graphs"]  # graphs short of full
    if heals:
        # The flush the stall cut short (1), its partner (3), the last (<= 3).
        assert short <= 8, used
    else:
        assert short >= 0.3 * 4 * used["flushes"], used


# ------------------------------------- whole runs, the timed path broken
# Each prelude runs in the run's own process before ``main``. The first two
# are faults of the path: an answer altered where the engine produces it,
# and one head's weights perturbed in the engine alone (the reference keeps
# the benchmark's). The third is the control: the engine's own lower-precision
# arm in the program's place.
ALTERED = """
from hydragnn_tpu.serve import InferenceEngine
_plain = InferenceEngine._denormalize
def _altered(self, ihead, value):
    return _plain(self, ihead, value) + (0.05 if ihead == 2 else 0.0)
InferenceEngine._denormalize = _altered
"""
PERTURBED = """
import jax
from graftbench.drivers import serve_closed as drv
_start = drv.start_engine
def _spoiled(model, weights, traffic, **control):
    params = dict(weights["params"])
    params["head_1"] = jax.tree_util.tree_map(lambda a: a * 1.05, params["head_1"])
    return _start(model, dict(weights, params=params), traffic, **control)
drv.start_engine = _spoiled
"""
CONTROL = """
from graftbench.drivers import serve_closed as drv
_start = drv.start_engine
drv.start_engine = lambda model, weights, traffic: _start(
    model, weights, traffic, precision="bf16", tolerance=1e6)
"""


@pytest.fixture(scope="module")
def control_root(tmp_path_factory):
    """A copy at a size where the two readings stand apart on the CPU:
    16-24 atoms, hidden 32, three layers. The float32 engine reads under
    1e-6 there and its bf16 arm 4e-4 to 3e-3 (five seeds, PR 38), so the
    copy's limit is put between them, at 1e-5: at this size the cell's own
    5e-3 lies over both."""
    root = tiny.make_copy(str(tmp_path_factory.mktemp("graftbench_control")))
    path = os.path.join(root, "graftbench", "traffic", "tiny_serve_closed_lattice.json")
    with open(path) as f:
        traffic = json.load(f)
    traffic["graphs"].update(cell_x=[2, 4], cell_y=[2, 3], cell_z=[2, 3], number_types=3)
    traffic["bucket_ladder"] = [[128, 2048], [256, 4096]]
    traffic["limit"] = 1e-5
    with open(path, "w") as f:
        json.dump(traffic, f)
    path = os.path.join(root, "graftbench", "configs", "tiny_pna_multihead_h256.json")
    with open(path) as f:
        config = json.load(f)
    config["NeuralNetwork"]["Architecture"].update(hidden_dim=32, num_conv_layers=3)
    with open(path, "w") as f:
        json.dump(config, f)
    return root


def pytest_a_sound_run_is_correct_and_prints_what_it_compared(control_root):
    name = tiny.cell(control_root, "serve_closed")
    rc, line, text = tiny.run_cell(control_root, name, seconds=0.5, seed=2**31 + 5)
    assert rc == 0 and line["correct"] and line["failed"] == 0, text[-3000:]
    assert set(line["metrics"]) == E2E | {"setup_s"}
    assert list(line)[-1] == "compared"
    row = line["compared"]["reply_gap"]
    assert row["value"] <= row["limit"] == 1e-5 and row["replies"] == 8
    assert "[graftbench] compared reply_gap" in text.splitlines()[-1]
    assert line["attempted"] > 8


@pytest.mark.parametrize("prelude", [ALTERED, PERTURBED, CONTROL],
                         ids=["answer_altered", "head_weights_perturbed", "control_bf16"])
def pytest_a_broken_path_or_the_control_is_not_correct(control_root, prelude):
    name = tiny.cell(control_root, "serve_closed")
    rc, line, text = tiny.run_cell(control_root, name, seconds=0.5, seed=3, prelude=prelude)
    assert rc == 0 and line["correct"] is False, text[-3000:]
    row = line["compared"]["reply_gap"]
    assert row["value"] > row["limit"]
    assert "NOT CORRECT: reply against reference" in text


def pytest_a_traced_line_carries_the_serving_metrics(root):
    name = tiny.cell(root, "serve_closed")
    rc, line, text = tiny.run_cell(root, name, seconds=0.5, trace=1)
    assert rc == 0 and line["correct"], text[-3000:]
    # On a CPU there is no device plane: the trace's readers are left out.
    assert {"setup_compile_s", "setup_cache_hits", "setup_init_s",
            "serve_queue_wait_ms", "serve_batch_occupancy",
            "serve_collate_ms_per_flush", "serve_h2d_ms_per_flush",
            "serve_padding_waste_nodes"} <= set(line["metrics"])
    assert not {"serve_mfu", "serve_device_ms_per_flush", "serve_device_idle_share",
                "serve_gather_roofline", "serve_agg_roofline"} & set(line["metrics"])
    assert not E2E & set(line["metrics"])
    assert {"busy_s", "window_s", "memory_peak_bytes"} <= set(line["device"])
