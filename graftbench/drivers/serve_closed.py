"""Serve driver: a closed loop of clients through the program's own
``InferenceEngine``.

The engine is built as ``python -m hydragnn_tpu.serve`` builds it
(``InferenceEngine.from_config``'s steps, in its order): a COMPLETED
``Architecture`` block, ``create_model_config``, ``make_example_batch``, the
eager ``init_model_variables`` as the template of the weights, the weights
themselves put into that template (there a checkpoint, here the benchmark's
own from ``--seed``), ``InferenceEngine(model, variables, ...)`` with a fixed
``bucket_ladder``, ``warmup()``, ``start()``. Each client is a thread that
holds one graph, calls ``engine.submit(graph)`` and ``result()``, and draws
its next: no pre-staged batch, no call into the engine's stages from outside.
With as many clients as a flush holds and no think time every steady flush is
full and fires on its size, never on the deadline: one cycle.

The window: all clients start together, each stops asking once ``--seconds``
have passed, and the requests then in flight are waited for. The rate is every
reply over the time to the last one, the latencies are ``submit`` to
``result()`` of every request; a request that raises is a failure and misses
any latency.

``correct`` (``compare``): once the window has closed, the memory has been
read and the engine is gone, ``check_replies`` of the replies the CLIENTS
received, drawn from the seed with the largest graph among them, against the
plain float32 reference of that one graph under the same weights, the number
compared being the widest ``|reply - reference| / (1 + |reference|)`` over
the four heads; every reply finite and of its graph's shape; no request
failed. The reference takes the graphs, the weights (the benchmark's own) and
the configuration file's numbers, and nothing the program made: PNA's two
degree averages are worked out here from the file's histogram.

Traffic parameters read here: ``graphs`` (generator, cell ranges,
``per_shape``), ``clients``, ``engine`` (the engine's keyword arguments),
``matmul_precision``, ``bucket_ladder``, ``check_replies``, ``limit``.
"""

from __future__ import annotations

import copy
import gc
import importlib
import itertools
import math
import threading
import time
import types

import numpy as np

from graftbench import datasets, flops, memory, reference

STAGES = ("queue_wait", "collate", "h2d", "device")
# Requests a client's order holds before it repeats: more than a window asks.
ORDER_LENGTH = 4096
# A reply that has not come this long after its request is a failure.
REPLY_TIMEOUT_S = 120.0


def make_pool(graphs: dict, radius: float, columns, seed: int):
    """The graphs the clients hold: ``per_shape`` lattices of every cell
    shape of the ranges, so every seed has the same sizes; the seed draws the
    atom types. A request is what a caller sends: the selected node columns,
    min-max scaled over the pool as the training data is over its dataset,
    and the radius graph's edges both ways (the caller's own neighbour list:
    a cKDTree, distance <= radius)."""
    from scipy.spatial import cKDTree

    from hydragnn_tpu.graphs.sample import GraphSample

    gen = importlib.import_module(f"graftbench.datagen.{graphs['generator']}")
    shapes = itertools.product(
        *(range(*graphs[k]) for k in ("cell_x", "cell_y", "cell_z"))
    )
    raw = []
    for i, (ux, uy, uz) in enumerate(shapes):
        one = dict(
            graphs, graphs=int(graphs["per_shape"]), cell_x=[ux, ux + 1],
            cell_y=[uy, uy + 1], cell_z=[uz, uz + 1],
        )
        sub = np.random.SeedSequence([int(seed), i]).generate_state(1)[0]
        raw += gen.generate(one, int(sub))
    xs = np.concatenate([x for x, _, _ in raw])
    lo, hi = xs.min(0), xs.max(0)
    pool = []
    for x, pos, _ in raw:
        pairs = cKDTree(pos).query_pairs(radius, output_type="ndarray")
        both = np.concatenate([pairs, pairs[:, ::-1]]).T
        pool.append(GraphSample(
            x=np.ascontiguousarray(datasets._scale(x, lo, hi)[:, columns], np.float32),
            pos=pos, edge_index=np.ascontiguousarray(both, np.int32),
        ))
    return pool, gen.DATASET


def completed_arch(config: dict, dataset: dict, pool) -> dict:
    """The ``Architecture`` block as a served snapshot holds it: what config
    completion adds from the data (``utils/config_utils.py``), written from
    the files, since a server has no dataset to complete against."""
    nn = config["NeuralNetwork"]
    arch, voi = copy.deepcopy(nn["Architecture"]), nn["Variables_of_interest"]
    if arch.get("edge_features"):
        raise ValueError("serve_closed sends no edge features")
    dims = {
        "graph": dataset["graph_features"]["dim"],
        "node": dataset["node_features"]["dim"],
    }
    arch["input_dim"] = len(voi["input_node_features"])
    arch["output_type"] = list(voi["type"])
    arch["output_dim"] = [
        int(dims[kind][i]) for kind, i in zip(voi["type"], voi["output_index"])
    ]
    arch["edge_dim"] = None
    arch.setdefault("freeze_conv_layers", False)
    arch.setdefault("initial_bias", None)
    if arch["model_type"] == "PNA" and not arch.get("pna_deg"):
        # A configuration that pins no histogram (the self-tests' tiny ones)
        # takes the pool's in-degrees.
        degrees = np.concatenate(
            [np.bincount(s.edge_index[1], minlength=s.num_nodes) for s in pool]
        )
        arch["pna_deg"] = np.bincount(
            degrees, minlength=int(arch["max_neighbours"]) + 1
        ).tolist()
    return arch


def seeded_weights(template, seed: int):
    """Weights from ``--seed`` in the tree the program's initializer made
    (its shapes and names alone are read): matrices N(0, 1 / fan-in); vectors
    0, or 1 for a scale or a variance, plus N(0, 0.05) noise, so that biases
    and BatchNorm's scale, shift and running statistics all bear on the
    answer; a variance is kept over 0.5."""
    import jax

    leaves, tree = jax.tree_util.tree_flatten_with_path(dict(template))
    rng = np.random.default_rng([int(seed), 0x5EED])
    out = []
    for path, leaf in leaves:
        shape, name = np.shape(leaf), str(getattr(path[-1], "key", path[-1]))
        if len(shape) >= 2:
            fan_in = int(np.prod(shape[:-1]))
            value = rng.normal(0.0, 1.0 / math.sqrt(fan_in), shape)
        else:
            value = (1.0 if name in ("scale", "var") else 0.0) + rng.normal(0.0, 0.05, shape)
            if name == "var":
                value = np.abs(value) + 0.5
        out.append(value.astype(np.float32))
    return jax.tree_util.tree_unflatten(tree, out)


def reference_model(arch: dict):
    """What ``reference.forward`` reads of a model, from the configuration's
    numbers alone (PyG's PNA averages over the in-degree histogram)."""
    hist = np.asarray(arch.get("pna_deg") or [1.0], np.float64)
    degrees = np.arange(len(hist))
    return types.SimpleNamespace(
        conv_type=arch["model_type"], output_type=list(arch["output_type"]),
        use_edge_attr=False,
        pna_deg_avg_log=max(float((hist * np.log(degrees + 1)).sum() / hist.sum()), 1e-6),
        pna_deg_avg_lin=max(float((hist * degrees).sum() / hist.sum()), 1e-6),
    )


def state_precision(traffic: dict) -> None:
    """The matmul precision the traffic file states, process-wide, as a
    server started with ``JAX_DEFAULT_MATMUL_PRECISION`` has it. Left to
    itself XLA multiplies float32 operands on the TPU in ONE bfloat16 pass,
    and the float32 engine then answers as far from float32 arithmetic as its
    own bfloat16 arm does (PERF.md section 6, PR 38): ``"highest"`` is
    float32 as the configuration states it."""
    if traffic.get("matmul_precision"):
        import jax

        jax.config.update("jax_default_matmul_precision", traffic["matmul_precision"])


def init_model(arch: dict):
    """The model and the tree its weights go into, by ``from_config``'s
    steps: the program's initializer, eagerly, as the server calls it.
    Returns (model, template, seconds in the initializer)."""
    import jax

    from hydragnn_tpu.models.create import (
        create_model_config,
        init_model_variables,
        make_example_batch,
    )

    model = create_model_config(config=arch, verbosity=0)
    example = make_example_batch(
        arch["input_dim"], arch["output_dim"], arch["output_type"],
        edge_dim=arch.get("edge_dim"), num_nodes=4,
        with_positions=model.needs_positions,
    )
    t_init = time.perf_counter()
    template = jax.block_until_ready(init_model_variables(model, example))
    return model, template, time.perf_counter() - t_init


def start_engine(model, weights, traffic: dict, **control):
    """A warmed, started engine over the traffic's fixed ladder. ``control``
    overrides engine options: the lower-precision arm of the control
    (``graftbench/serve_readings.py``, the self-tests) and nothing else."""
    from hydragnn_tpu.serve import InferenceEngine

    engine = InferenceEngine(
        model, weights, warmup=False, autostart=False,
        bucket_ladder=[tuple(r) for r in traffic["bucket_ladder"]],
        **dict(traffic["engine"], **control),
    )
    engine.warmup()
    engine.start()
    return engine


def client_orders(pool_size: int, clients: int, seed: int):
    """Each client's own stream: whole permutations of the pool, one after
    another, from ``--seed`` and the client's index."""
    orders = []
    for c in range(clients):
        rng = np.random.default_rng([int(seed), 0xC11E, c])
        rounds = -(-ORDER_LENGTH // pool_size)
        orders.append(np.concatenate([rng.permutation(pool_size) for _ in range(rounds)]))
    return orders


def rung_flushes(pool, ladder, clients: int, seed: int):
    """For each rung a flush of ``clients`` graphs that lands in it, where
    the traffic can: seeded draws of a flush, kept by the rung they fit. The
    last rung is the ladder's guard (``clients`` of the largest graph)."""
    sizes = np.array([(s.num_nodes, s.num_edges) for s in pool])
    rng = np.random.default_rng([int(seed), 0x3A12])
    found = {}
    for _ in range(4096):
        if len(found) == len(ladder) - 1:
            break
        pick = rng.integers(0, len(pool), clients)
        n, e = sizes[pick].sum(0)
        rung = next((i for i, (rn, re) in enumerate(ladder) if rn > n and re >= e), None)
        if rung is not None:
            found.setdefault(rung, pick)
    found.setdefault(len(ladder) - 1, np.full(clients, int(np.argmax(sizes[:, 1]))))
    return [[pool[i] for i in found[r]] for r in sorted(found)]


class Client(threading.Thread):
    """One caller: ask, wait for the reply, ask again, until told the time."""

    def __init__(self, engine, pool, order, gate, clock):
        super().__init__(daemon=True)
        self.engine, self.pool, self.order = engine, pool, order
        self.gate, self.clock = gate, clock
        self.rows = []  # (pool index, asked, answered, reply or None, error)

    def run(self):
        self.gate.wait()
        t_end = self.clock["t_end"]
        for k in itertools.cycle(self.order):
            asked = time.perf_counter()
            if asked >= t_end:
                return
            try:
                reply = self.engine.submit(self.pool[k]).result(REPLY_TIMEOUT_S)
                self.rows.append((int(k), asked, time.perf_counter(), reply, None))
            except Exception as e:  # noqa: BLE001 -- a refusal is a failed request
                self.rows.append((int(k), asked, time.perf_counter(), None, repr(e)))
                time.sleep(min(getattr(e, "retry_after_s", 0.01), 0.1))


def closed_loop(engine, pool, orders, seconds: float, begin=time.perf_counter, end=None):
    """``len(orders)`` clients for ``seconds``. ``begin()`` returns the
    window's start and ``end(t0)`` closes it (the cell's clocks in a run).
    Returns every client's rows."""
    gate, clock = threading.Event(), {}
    clients = [Client(engine, pool, order, gate, clock) for order in orders]
    for c in clients:
        c.start()
    t0 = begin()
    clock["t_end"] = t0 + seconds
    gate.set()
    for c in clients:
        c.join()
    if end is not None:
        end(t0)
    return t0, [c.rows for c in clients]


def account(rows, t0: float) -> dict:
    """Rate, median and 95th percentile from the clients' rows: a failed
    request counts against those attempted and has no latency."""
    flat = [r for client in rows for r in client]
    done = [r for r in flat if r[4] is None]
    latency_ms = np.array([1e3 * (r[2] - r[1]) for r in done])
    last = max((r[2] for r in done), default=t0)
    out = dict(
        attempted=len(flat), failed=len(flat) - len(done),
        errors=sorted({r[4] for r in flat if r[4] is not None})[:3],
        answered_s=last - t0,
    )
    if len(done):
        p50, p95 = np.percentile(latency_ms, [50, 95])
        out.update(
            serve_graphs_per_s=len(done) / (last - t0), serve_p50_ms=float(p50),
            serve_p95_ms=float(p95), beyond_p95=int((latency_ms > p95).sum()),
        )
    return out


def counters(engine) -> dict:
    """The engine's own accounting as it stands (``serve/metrics.py``)."""
    m = engine.metrics
    snap = m.snapshot()
    return dict(
        flushes=snap["batches_total"], graphs=snap["graphs_total"],
        fallbacks=snap["bucket_cache"]["ladder_fallbacks"],
        rungs={k: v["batches"] for k, v in snap["per_bucket"].items()},
        **{s + "_s": m.latency[s].sum for s in STAGES},
        **{s + "_n": m.latency[s].count for s in STAGES},
    )


def since(before: dict, after: dict) -> dict:
    """How ``counters`` moved between two readings (a rung: its flushes)."""
    out = {}
    for key, value in after.items():
        if isinstance(value, dict):
            delta = {k: v - before[key].get(k, 0) for k, v in value.items()}
            out[key] = {k: v for k, v in delta.items() if v}
        else:
            out[key] = value - before[key]
    return out


def compare(rows, pool, model_view, weights, count: int, seed: int, limit: float):
    """The replies the clients got against the plain reference. Returns
    (the numbers compared, each beside its limit; the reasons why not; the
    same gap as a root mean square over a head, read beside it and not
    compared)."""
    flat = [
        (c, i, r) for c, client in enumerate(rows) for i, r in enumerate(client)
        if r[4] is None
    ]
    why_not, bad_shape = [], 0
    for _, _, (k, _, _, reply, _) in flat:
        for kind, out in zip(model_view.output_type, reply):
            rows_due = (pool[k].num_nodes,) if kind == "node" else ()
            if np.shape(out)[:-1] != rows_due or not np.isfinite(out).all():
                bad_shape += 1
    if bad_shape:
        why_not.append(f"{bad_shape} head outputs non-finite or not of their graph's shape")
    rng = np.random.default_rng([int(seed), 0xC4EC])
    chosen = []
    if flat:
        largest = max(range(len(flat)), key=lambda j: pool[flat[j][2][0]].num_edges)
        others = [j for j in rng.permutation(len(flat)) if j != largest]
        chosen = [largest] + others[: max(count - 1, 0)]
    gap, where, rms = 0.0, None, 0.0
    for j in chosen:
        c, i, (k, _, _, reply, _) = flat[j]
        want = reference.forward(model_view, weights, [pool[k]])[0]
        for h, (a, b) in enumerate(zip(reply, want)):
            a = np.asarray(a, np.float64).reshape(-1)
            b = np.asarray(b, np.float64).reshape(-1)
            if a.shape != b.shape:
                why_not.append(f"client {c} request {i} head {h}: shape {a.shape}, reference {b.shape}")
                continue
            d = np.abs(a - b)
            rms = max(rms, float(np.sqrt((d * d).mean()) / (1.0 + np.sqrt((b * b).mean()))))
            g = float((d / (1.0 + np.abs(b))).max())
            if not g <= gap:  # a NaN takes the place too
                gap, where = g, f"client {c} request {i} head {h} ({pool[k].num_nodes} atoms)"
    if not chosen:
        why_not.append("no reply to compare")
    elif not gap <= limit:
        why_not.append(f"reply against reference: widest gap {gap:.3e} over the limit {limit} at {where}")
    compared = {"reply_gap": {"value": gap, "limit": limit, "replies": len(chosen)}}
    return compared, why_not, rms


def run(cell) -> dict:
    from hydragnn_tpu import telemetry

    traffic = cell.traffic
    nn = cell.config["NeuralNetwork"]
    pool, dataset = make_pool(
        traffic["graphs"], float(nn["Architecture"]["radius"]),
        list(nn["Variables_of_interest"]["input_node_features"]), cell.seed,
    )
    arch = completed_arch(cell.config, dataset, pool)
    sizes = np.array([(s.num_nodes, s.num_edges) for s in pool])
    print(
        f"[graftbench] pool: {len(pool)} graphs of {sizes[:, 0].min()}-"
        f"{sizes[:, 0].max()} atoms, {sizes[:, 1].min()}-{sizes[:, 1].max()} "
        f"edges (mean {sizes[:, 0].mean():.0f} / {sizes[:, 1].mean():.0f})",
        flush=True,
    )
    cell.mark("data")

    clients = int(traffic["clients"])
    ladder = sorted(tuple(int(v) for v in r) for r in traffic["bucket_ladder"])
    state_precision(traffic)
    model, template, init_s = init_model(arch)
    weights = seeded_weights(template, cell.seed)
    telemetry.configure(collect=cell.trace, jax_annotations=cell.trace)
    telemetry.install_jax_hooks()
    engine = start_engine(model, weights, traffic)
    print(
        f"[graftbench] eager initializer (init_model_variables): {init_s:.1f}s "
        f"of the set-up; {engine.compiled_buckets} ladder rungs warmed",
        flush=True,
    )
    cell.mark("model + engine")
    # One full flush through every rung the traffic reaches, and the guard.
    for flush in rung_flushes(pool, ladder, clients, cell.seed):
        engine.predict(flush, timeout=REPLY_TIMEOUT_S)

    before = counters(engine)
    t0, rows = closed_loop(
        engine, pool, client_orders(len(pool), clients, cell.seed),
        cell.seconds, cell.begin_window, cell.end_window,
    )
    stats = account(rows, t0)
    used = since(before, counters(engine))

    # The compiler's temporaries of the largest rung the window ran, asked
    # of the engine's own jitted forward (graftbench/memory.py); read, with
    # the allocator's figures, while the engine still holds the chip.
    programs = memory.ProgramMemory(cell)
    if used["rungs"]:
        n_pad, e_pad = max(
            (tuple(int(v) for v in k.split("x")) for k in used["rungs"]),
            key=lambda r: r[1],
        )
        programs.note(
            "serve_forward", engine._jit, *engine._current_weights()[:2],
            engine._dummy_batch(n_pad, e_pad),
        )
    temps = programs.temp_bytes()
    mem = memory.peak(cell.devices, temps)
    engine.close()
    del engine, programs
    gc.collect()

    done = [r for client in rows for r in client if r[4] is None]
    real = sizes[[r[0] for r in done]].sum(0) if done else np.zeros(2, int)
    flushes = used["flushes"]
    pad_nodes = sum(n * int(k.split("x")[0]) for k, n in used["rungs"].items())
    pad_edges = sum(n * int(k.split("x")[1]) for k, n in used["rungs"].items())
    counted = flops.forward(arch, int(real[0]), int(real[1]), len(done))
    facts = dict(
        used, init_s=init_s, clients=clients,
        max_batch_graphs=int(traffic["engine"]["max_batch_graphs"]),
        # A flush under its size fired on the deadline (or was the last).
        graphs_short_of_full=flushes * int(traffic["engine"]["max_batch_graphs"]) - used["graphs"],
        answered_s=stats["answered_s"], window_s=cell.window_s,
        real_nodes=int(real[0]), real_edges=int(real[1]), real_graphs=len(done),
        pad_nodes=pad_nodes, pad_edges=pad_edges,
        steps=flushes, chips=len(cell.devices),
        flush_ops=counted["ops"] / max(flushes, 1),
        flush_bytes={k: v / max(flushes, 1) for k, v in counted["bytes"].items()},
        latency_samples=len(done), beyond_p95=stats.get("beyond_p95"),
        # One client's latencies in order are the cycles in order: a run that
        # reads far off shows here whether one flush stalled or all did.
        cycle_ms=[round(1e3 * (r[2] - r[1]), 1) for r in rows[0] if r[4] is None],
    )
    print(
        f"[graftbench] {stats['attempted']} requests, {stats['failed']} failed "
        f"{stats['errors']}; {flushes} flushes of {used['graphs']} graphs "
        f"({facts['graphs_short_of_full']} short of full) over rungs "
        f"{used['rungs']}, {used['fallbacks']} off the ladder; last reply "
        f"{stats['answered_s']:.3f}s after the start; latency samples "
        f"{len(done)}, {stats.get('beyond_p95')} beyond the 95th percentile; "
        f"client 0's first cycles (ms) {facts['cycle_ms'][:8]}, longest "
        f"{max(facts['cycle_ms'], default=None)}",
        flush=True,
    )
    why_not = []
    if stats["failed"]:
        why_not.append(f"{stats['failed']} of {stats['attempted']} requests failed: {stats['errors']}")
    if used["fallbacks"]:
        why_not.append(f"{used['fallbacks']} flushes missed the ladder")
    t_ref = time.perf_counter()
    compared, reasons, rms = compare(
        rows, pool, reference_model(arch), weights,
        int(traffic["check_replies"]), cell.seed, float(traffic["limit"]),
    )
    facts["reply_gap_rms"] = rms
    print(
        f"[graftbench] replies against the plain float32 reference: "
        f"{compared}; as a root mean square {rms:.3e} (not compared); "
        f"{time.perf_counter() - t_ref:.1f}s after the window", flush=True,
    )
    return dict(
        attempted=stats["attempted"], failed=stats["failed"],
        why_not=why_not + reasons, facts=facts, compared=compared,
        extra={"architecture": {k: v for k, v in arch.items() if k != "pna_deg"},
               "program_temp_bytes": temps},
        memory=mem,
        end_to_end={
            k: stats[k] for k in ("serve_graphs_per_s", "serve_p50_ms", "serve_p95_ms")
            if k in stats
        },
    )
