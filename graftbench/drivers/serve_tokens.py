"""Serve driver for a token family: a closed loop of clients that each hold
one DOCUMENT and ask the program's own ``InferenceEngine`` for the
log-probability of each of its tokens (what a perplexity filter over a crawl,
an evaluation harness's ``loglikelihood`` call and a reranker ask of a
language model).

What is ``drivers/serve_closed.py``'s, imported unchanged: the clients, their
seeded orders, the loop and its window (``client_orders``, ``closed_loop``),
the accounting of rate and latencies (``account``), the engine's counters as
they moved (``counters``, ``since``), ``start_engine`` (a warmed, started
engine over the traffic's fixed ladder), ``state_precision`` and the reply
timeout. The contract of the window, the rate and the latencies is that
file's.

What a token family needs and that driver could not carry:

* **A request is a document**: ``GraphSample(x=[T, 1] the min-max-scaled
  token column, pos=[T, 3] with the place in column 0, edge_index=None)``; no
  lattice, no neighbour list, no degree table. The pool holds the SAME
  document lengths every seed (``graphs.documents``: [tokens, how many]); the
  seed draws the tokens (``datagen/token_chain.py``) and each client's order.
* **The ladder is in tokens** (``bucket_ladder`` rungs ``[N_pad, 8]``; the
  engine gives every rung of a token family its 8 padding edges).
* **The reply is the class head's log-probabilities** ``[T, 1]`` and, beside
  it on the future, the experts each token chose (``routing`` [T, routed
  layers x K]). ``correct`` (``compare``): after the window, with the memory
  read and the engine closed, ``check_replies`` of the replies the CLIENTS
  received (the longest document among them and others drawn from the seed)
  against the family file's ``logprobs`` of that one document under the same
  weights, in float32 at ``highest`` on the device the engine has freed,
  routed by the reply's own ``routing``,
  which has to be a top-K of the reference's router logits within the
  family's ``ROUTE_EPS``; the numbers compared are the family's ``compare``'s
  (relative L2, under a limit that follows the document's length,
  ``rel_l2_limit``, and elementwise, on log-probabilities). Also every reply
  finite and ``[T, 1]``, no request failed, no flush off the ladder.
* **The weights** are the benchmark's own from ``--seed``, drawn ON THE
  DEVICE into the tree of the program's initializer (its shapes and names
  alone, by ``jax.eval_shape``: at 2.4 G parameters the eager initializer's
  9.7 GB would not fit beside the 9.7 GB that replace every leaf of it); the
  reference reads the same tree after the window.
* **The engine's routing counters** (``serve/metrics.py``:
  ``moe_rows_held_total``, ``moe_load_max_total``,
  ``moe_fallback_layers_total``) as they moved over the window, and the
  documents' real lengths, from which the per-layer readers count operations
  (``families/<model_type>.py``: ``counts``, ``head_counts``, ``attn_counts``,
  ``moe_counts``).

What a token family needs beside this driver: a family file with
``logprobs``, ``compare``, ``ROUTE_EPS`` and the counts; a configuration whose
one node head has ``Variables_of_interest.loss`` "cross_entropy". Traffic
parameters read here: ``graphs`` (generator, ``vocab``, ``successors``,
``documents``), ``clients``, ``engine``, ``matmul_precision`` (absent: the
stated precision rounds matmul operands to bf16), ``bucket_ladder``,
``check_replies``.
"""

from __future__ import annotations

import copy
import functools
import gc
import importlib
import time

import numpy as np

from graftbench import families, flops, memory
from graftbench.drivers.serve_closed import (
    REPLY_TIMEOUT_S, account, client_orders, closed_loop, counters, since,
    start_engine, state_precision,
)

ROUTED = ("moe_rows_held_total", "moe_load_max_total", "moe_fallback_layers_total")


def make_pool(graphs: dict, seed: int):
    """The documents the clients hold: ``documents`` = [[tokens, how many],
    ...], the same lengths every seed; the seed draws the tokens. A request is
    what a caller sends: the token column scaled by the slice's range, the
    places 0 .. T-1."""
    from hydragnn_tpu.graphs.sample import GraphSample

    gen = importlib.import_module(f"graftbench.datagen.{graphs['generator']}")
    vocab = int(graphs["vocab"])
    pool = []
    for i, (tokens, number) in enumerate(graphs["documents"]):
        one = dict(graphs, graphs=int(number), tokens=int(tokens))
        sub = np.random.SeedSequence([int(seed), i]).generate_state(1)[0]
        for x, pos, _ in gen.generate(one, int(sub)):
            pool.append(GraphSample(
                x=np.ascontiguousarray(x[:, :1] / (vocab - 1.0), np.float32), pos=pos,
            ))
    return pool


def completed_arch(config: dict) -> dict:
    """The ``Architecture`` block as a served snapshot holds it: what config
    completion adds from the data (``utils/config_utils.py``: the head as
    wide as its classes, the two tables of the token columns), written from
    the files, since a server has no dataset to complete against."""
    nn = config["NeuralNetwork"]
    arch, voi = copy.deepcopy(nn["Architecture"]), nn["Variables_of_interest"]
    vocab = int(arch["vocab_size"])
    if list(voi["type"]) != ["node"] or list(voi["loss"]) != ["cross_entropy"]:
        raise ValueError("serve_tokens scores documents with one cross_entropy node head")
    arch.update(
        input_dim=1, output_type=["node"], output_dim=[int(voi["num_classes"][0])],
        target_dim=[1], head_loss=["cross_entropy"], edge_dim=None,
        class_minmax=[[0.0, vocab - 1.0]], token_minmax=[0.0, vocab - 1.0],
    )
    arch.setdefault("freeze_conv_layers", False)
    arch.setdefault("initial_bias", None)
    return arch


def init_model(arch: dict):
    """The model and the TREE its weights go into: the program's initializer
    under ``jax.eval_shape`` (shapes and names; module docstring). Returns
    (model, template, seconds)."""
    import jax

    from hydragnn_tpu.models.create import (
        create_model_config,
        init_model_variables,
        make_example_batch,
    )

    model = create_model_config(config=arch, verbosity=0)
    example = make_example_batch(
        arch["input_dim"], arch["target_dim"], arch["output_type"],
        edge_dim=None, num_nodes=4, with_positions=model.needs_positions,
    )
    t_init = time.perf_counter()
    template = jax.eval_shape(lambda: init_model_variables(model, example))
    return model, template, time.perf_counter() - t_init


@functools.lru_cache(maxsize=None)
def _drawer(shape, mean: float, std: float):
    import jax

    return jax.jit(
        lambda key: mean + std * jax.random.normal(key, shape, jax.numpy.float32)
    )


def seeded_weights(template, seed: int):
    """Weights from ``--seed`` in the initializer's tree, drawn on the
    device: the embedding N(0, 1) (rows of rms 1, as a trained table's);
    matrices N(0, 1 / fan-in), the fan-in their contraction's (an expert's own
    rows, not all experts'), so that every normed row comes out a row of rms
    ~1 and the router's and the head's logits of rms ~1; norm weights
    1 + N(0, 0.05) and biases N(0, 0.05), so that each bears on the answer."""
    import jax

    leaves, tree = jax.tree_util.tree_flatten_with_path(dict(template))
    state = np.random.SeedSequence([int(seed), 0x5EED]).generate_state(2)
    keys = jax.random.split(jax.numpy.asarray(state, jax.numpy.uint32), len(leaves))
    out = []
    for key, (path, leaf) in zip(keys, leaves):
        shape, name = tuple(leaf.shape), str(getattr(path[-1], "key", path[-1]))
        if name == "embedding":
            mean, std = 0.0, 1.0
        elif len(shape) >= 2:
            mean, std = 0.0, float(shape[-2]) ** -0.5
        else:
            mean, std = (1.0 if name in ("weight", "scale") else 0.0), 0.05
        out.append(_drawer(shape, mean, std)(key))
    return jax.tree_util.tree_unflatten(tree, out)


class WithRouting:
    """The engine as ``serve_closed``'s clients call it, each reply a pair:
    (the per-head outputs, the request's ``routing`` rows)."""

    class _Reply:
        def __init__(self, future):
            self.future = future

        def result(self, timeout=None):
            return self.future.result(timeout), self.future.routing

    def __init__(self, engine):
        self.engine = engine

    def submit(self, sample):
        return self._Reply(self.engine.submit(sample))


def rung_flushes(pool, ladder, clients: int, seed: int):
    """For each rung a flush of ``clients`` documents that lands in it, where
    the traffic can: seeded draws, kept by the rung they fit. The last rung is
    the ladder's guard (``clients`` of the longest document)."""
    sizes = np.array([s.num_nodes for s in pool])
    rng = np.random.default_rng([int(seed), 0x3A12])
    found = {}
    for _ in range(4096):
        if len(found) == len(ladder) - 1:
            break
        pick = rng.integers(0, len(pool), clients)
        rung = next((i for i, (n, _) in enumerate(ladder) if n > sizes[pick].sum()), None)
        if rung is not None and rung < len(ladder) - 1:
            found.setdefault(rung, pick)
    found[len(ladder) - 1] = np.full(clients, int(np.argmax(sizes)))
    return [[pool[i] for i in found[r]] for r in sorted(found)]


def routed(engine) -> dict:
    snap = engine.metrics.snapshot()
    return {name: snap.get(name, 0) for name in ROUTED}


def reference_params(weights):
    """(the device the reference runs on, the weights there): where the
    weights already are. The engine is closed by then, and the family's
    reference multiplies in float32 at ``highest`` wherever it runs (on the
    host's 13 shared cores two documents took 190-220 s, on the freed chip
    under a minute: my chip runs, PR 39)."""
    import jax

    params = dict(weights["params"])
    return next(iter(jax.tree_util.tree_leaves(params)[0].devices())), params


def compare(rows, pool, model, weights, count: int, seed: int, family=None, plain=None):
    """The replies the clients got against the family's plain reference.
    Returns (the numbers compared, each beside its limit; the reasons why
    not; the readings not compared)."""
    import jax

    family = family or families.load(model.conv_type)
    flat = [
        (c, i, r) for c, client in enumerate(rows) for i, r in enumerate(client)
        if r[4] is None
    ]
    why_not, bad = [], 0
    for _, _, (k, _, _, (reply, routing), _) in flat:
        out = np.asarray(reply[0])
        if (
            len(reply) != 1 or out.shape != (pool[k].num_nodes, 1)
            or not np.isfinite(out).all()
            or (routing is not None and len(routing) != pool[k].num_nodes)
        ):
            bad += 1
    if bad:
        why_not.append(f"{bad} replies non-finite or not of their document's shape")
    rng = np.random.default_rng([int(seed), 0xC4EC])
    chosen = []
    if flat:
        longest = max(range(len(flat)), key=lambda j: pool[flat[j][2][0]].num_nodes)
        others = [j for j in rng.permutation(len(flat)) if j != longest]
        chosen = [longest] + others[: max(count - 1, 0)]
    read = dict(max_diff=0.0, rel_l2=0.0, rel_l2_limit=1.0, rel_l2_tokens=0, by_reply=[],
                route_margin=0.0, replies=len(chosen), tokens=0)
    if chosen:
        host, params = reference_params(weights)
    for j in chosen:
        c, i, (k, _, _, (reply, routing), _) = flat[j]
        kwargs = {} if plain is None else {"plain": plain}
        with jax.default_device(host):
            want, report = family.logprobs(
                model, params, {"x": pool[k].x, "pos": pool[k].pos}, routing, **kwargs
            )
        worst, rel, fail = family.compare(np.asarray(reply[0]), want)
        limit = family.rel_l2_limit(pool[k].num_nodes)
        read["max_diff"] = max(read["max_diff"], worst)
        read["by_reply"].append([pool[k].num_nodes, rel, limit])
        if rel / limit >= read["rel_l2"] / read["rel_l2_limit"]:  # the nearest to its limit
            read.update(rel_l2=rel, rel_l2_limit=limit, rel_l2_tokens=pool[k].num_nodes)
        read["route_margin"] = max(read["route_margin"], report["route_margin"])
        read["tokens"] += pool[k].num_nodes
        if report["route_margin"] > family.ROUTE_EPS:
            fail = fail or (
                f"route margin {report['route_margin']:.3e} beyond {family.ROUTE_EPS}: "
                "the engine chose experts that are no top-K of the reference's logits"
            )
        if fail:
            why_not.append(f"client {c} request {i} ({pool[k].num_nodes} tokens): {fail}")
    if not chosen:
        why_not.append("no reply to compare")
    compared = {
        # The reply nearest to its limit, which follows the document's length.
        "reply_rel_l2": {"value": read["rel_l2"], "limit": read["rel_l2_limit"],
                         "tokens": read["rel_l2_tokens"]},
        "reply_max_diff": {"value": read["max_diff"], "atol": family.ATOL, "rtol": family.RTOL},
        "route_margin": {"value": read["route_margin"], "limit": family.ROUTE_EPS},
    }
    return compared, why_not, read


def largest_rung_temp(engine, ladder) -> int:
    """The compiler's temporaries of the ladder's largest rung (the guard:
    set-up ran it), from the engine's own compiled executable."""
    n_pad, e_pad = ladder[-1]
    exe = engine._registry.get((int(n_pad), int(e_pad), engine._g_pad))
    try:
        return int(exe.memory_analysis().temp_size_in_bytes)
    except Exception:  # noqa: BLE001 -- an executable that cannot say: ask again
        lowered = engine._jit.lower(
            *engine._current_weights()[:2], engine._dummy_batch(int(n_pad), int(e_pad))
        )
        return int(lowered.compile().memory_analysis().temp_size_in_bytes)


def run(cell) -> dict:
    from hydragnn_tpu import telemetry

    traffic = cell.traffic
    pool = make_pool(traffic["graphs"], cell.seed)
    arch = completed_arch(cell.config)
    sizes = np.array([s.num_nodes for s in pool])
    print(
        f"[graftbench] pool: {len(pool)} documents of {sizes.min()}-{sizes.max()} "
        f"tokens (mean {sizes.mean():.0f})", flush=True,
    )
    cell.mark("data")

    clients = int(traffic["clients"])
    ladder = sorted(tuple(int(v) for v in r) for r in traffic["bucket_ladder"])
    state_precision(traffic)
    model, template, init_s = init_model(arch)
    family = families.load(model.conv_type)
    weights = seeded_weights(template, cell.seed)
    telemetry.configure(collect=cell.trace, jax_annotations=cell.trace)
    telemetry.install_jax_hooks()
    engine = start_engine(model, weights, traffic)
    ladder = sorted(engine._current_ladder())
    print(
        f"[graftbench] the initializer's tree (eval_shape): {init_s:.1f}s of the "
        f"set-up; {engine.compiled_buckets} ladder rungs warmed: {ladder}", flush=True,
    )
    cell.mark("model + engine")
    # One full flush through every rung the traffic reaches, and the guard.
    for flush in rung_flushes(pool, ladder, clients, cell.seed):
        engine.predict(flush, timeout=REPLY_TIMEOUT_S)

    before, routed_before = counters(engine), routed(engine)
    t0, rows = closed_loop(
        WithRouting(engine), pool, client_orders(len(pool), clients, cell.seed),
        cell.seconds, cell.begin_window, cell.end_window,
    )
    stats = account(rows, t0)
    used = since(before, counters(engine))
    moved = since(routed_before, routed(engine))

    temps = {"serve_forward": largest_rung_temp(engine, ladder)}
    mem = memory.peak(cell.devices, temps)
    engine.close()
    del engine
    gc.collect()

    done = [r for client in rows for r in client if r[4] is None]
    lengths = [int(sizes[r[0]]) for r in done]
    flushes = used["flushes"]
    pad_nodes = sum(n * int(k.split("x")[0]) for k, n in used["rungs"].items())
    rows_held = moved["moe_rows_held_total"]
    parts, _ = family.counts(arch, sum(lengths), 0, rows_held, lengths)
    counted = flops.total(
        list(parts) + family.head_counts(arch, sum(lengths), arch["output_dim"][0])
    )
    facts = dict(
        used, init_s=init_s, clients=clients,
        max_batch_graphs=int(traffic["engine"]["max_batch_graphs"]),
        # A flush under its size fired on the deadline (or was the last).
        graphs_short_of_full=flushes * int(traffic["engine"]["max_batch_graphs"]) - used["graphs"],
        answered_s=stats["answered_s"], window_s=cell.window_s,
        real_nodes=int(sum(lengths)), real_edges=0, real_graphs=len(done),
        pad_nodes=pad_nodes, pad_edges=0, doc_lengths=lengths,
        steps=flushes, chips=len(cell.devices),
        flush_ops=counted["ops"] / max(flushes, 1),
        moe_rows_held=rows_held, moe_load_max=moved["moe_load_max_total"],
        moe_fallback_layers=moved["moe_fallback_layers_total"],
        latency_samples=len(done), beyond_p95=stats.get("beyond_p95"),
        cycle_ms=[round(1e3 * (r[2] - r[1]), 1) for r in rows[0] if r[4] is None],
    )
    print(
        f"[graftbench] {stats['attempted']} requests, {stats['failed']} failed "
        f"{stats['errors']}; {flushes} flushes of {used['graphs']} documents "
        f"({facts['graphs_short_of_full']} short of full) over rungs "
        f"{used['rungs']}, {used['fallbacks']} off the ladder; rows to held "
        f"experts a flush {rows_held / max(flushes, 1):.0f}, layers past the "
        f"capacity {facts['moe_fallback_layers']}; last reply "
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
    compared, reasons, read = compare(
        rows, pool, model, weights, int(traffic["check_replies"]), cell.seed, family,
    )
    facts["reference"] = read
    print(
        f"[graftbench] replies against the plain float32 reference: {compared} "
        f"over {read['replies']} replies of {read['tokens']} tokens; "
        f"{time.perf_counter() - t_ref:.1f}s after the window (the reference on the "
        f"freed device)",
        flush=True,
    )
    return dict(
        attempted=stats["attempted"], failed=stats["failed"],
        why_not=why_not + reasons, facts=facts, compared=compared,
        extra={"architecture": arch, "program_temp_bytes": temps},
        memory=mem,
        end_to_end={
            k: stats[k] for k in ("serve_graphs_per_s", "serve_p50_ms", "serve_p95_ms")
            if k in stats
        },
    )
