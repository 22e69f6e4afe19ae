"""Graph packing + occupancy-aware bucket ladders (hydragnn_tpu/graphs/
packing.py) — tier-1, CPU, deterministic.

Covers the packing layer's contracts end to end:
  * first-fit-decreasing packer: joint (nodes, edges, graphs) capacity never
    violated, every item placed exactly once, determinism, oversize
    isolation;
  * ladder fitter: compile budget respected, rungs ascending with cummax'd
    edge pads, waste beaten vs the single worst-case rung, JSON/CLI round
    trip (the ``fit-ladder`` CLI + ``auto:`` spec forms);
  * training loader packing: bit-exact per-head targets/masks vs unpacked
    collation of the same membership, denser batches, capacity constraints,
    ``generation``-counter invalidation, quarantine/fault-drill interaction,
    and same-seed convergence parity (the loss-equivalence gate);
  * serving engine packing: per-request response demux identity and the
    zero-recompile-after-warmup steady state with packing enabled;
  * contract checker: the new ladder forms (literal, ``auto:`` histogram,
    ``auto:`` fitted ladder) and the ``Dataset.ladder_step``/``packing``
    knobs.
"""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as ge
from hydragnn_tpu.graphs.collate import GraphArena, collate_graphs, round_up_pow2
from hydragnn_tpu.graphs.packing import (
    PackCaps,
    SizeHistogram,
    first_fit_decreasing,
    fit_ladder,
    ladder_to_json,
    ladder_waste,
    resolve_ladder_spec,
    round_up_step,
)
from hydragnn_tpu.preprocess.dataloader import GraphDataLoader


# --------------------------------------------------------------------- packer
def pytest_ffd_respects_joint_capacity_and_places_every_item():
    rng = np.random.default_rng(11)
    for trial in range(5):
        count = int(rng.integers(20, 300))
        ns = rng.integers(1, 60, count)
        es = rng.integers(0, 200, count)
        caps = PackCaps(nodes=128, edges=512, graphs=12)
        bins = first_fit_decreasing(ns, es, caps)
        placed = sorted(i for b in bins for i in b)
        assert placed == list(range(count)), "every item exactly once"
        for b in bins:
            assert ns[b].sum() <= caps.nodes
            assert es[b].sum() <= caps.edges
            assert len(b) <= caps.graphs


def pytest_ffd_deterministic_and_order_tiebreak():
    ns = [10] * 8 + [30, 30]
    es = [10] * 10
    caps = PackCaps(nodes=64, edges=512, graphs=16)
    a = first_fit_decreasing(ns, es, caps)
    b = first_fit_decreasing(ns, es, caps)
    assert a == b, "same input -> same packing"
    # A different tie-break order permutes WHICH equal-size items share a
    # bin, not the bin count — the per-epoch shuffle seam.
    perm = list(reversed(range(10)))
    c = first_fit_decreasing(ns, es, caps, order=perm)
    assert len(c) == len(a)
    assert c != a


def pytest_ffd_oversize_item_is_isolated_not_dropped():
    caps = PackCaps(nodes=64, edges=64, graphs=8)
    bins = first_fit_decreasing([500, 10, 10], [10, 10, 10], caps)
    assert [0] in bins, "oversize graph gets its own (fallback) bin"
    assert sorted(i for b in bins for i in b) == [0, 1, 2]
    # The oversize bin is closed: nothing co-packs behind it.
    assert all(b == [0] or 0 not in b for b in bins)


def pytest_round_up_ladder_step_modes():
    assert round_up_step(520, mode="pow2") == 1024
    assert round_up_step(520, mode="mult64") == 576  # the pow2-waste fix
    assert round_up_step(100, mode="mult64") == 128  # small shapes stay pow2
    assert round_up_pow2(520) == 1024  # historical default untouched
    assert round_up_pow2(520, mode="mult64") == 576
    with pytest.raises(ValueError, match="ladder-step mode"):
        round_up_step(10, mode="mult3")


# -------------------------------------------------------------- ladder fitter
def _bimodal_hist():
    rng = np.random.default_rng(5)
    h = SizeHistogram()
    for _ in range(400):  # small 1-graph flushes
        n = int(rng.integers(8, 30))
        h.record_batch(n, n * 3, 1)
    for _ in range(100):  # full 16-graph batches
        n = int(rng.integers(220, 420))
        h.record_batch(n, n * 3, 16)
    return h


def pytest_fit_ladder_budget_shape_and_waste():
    h = _bimodal_hist()
    for budget in (1, 2, 4, 6):
        ladder = fit_ladder(h, max_rungs=budget)
        assert 1 <= len(ladder) <= budget, "compile budget respected"
        assert ladder == sorted(ladder), "rungs ascend"
        assert all(
            ladder[i][1] <= ladder[i + 1][1] for i in range(len(ladder) - 1)
        ), "edge pads cummax with node pads (top rung dominates)"
        worst_n = max(n for (n, e, g) in h.batches)
        assert ladder[-1][0] > worst_n, "top rung covers every observation"
    # The fitted ladder must beat the historical single worst-case pow2 rung
    # by the ROADMAP margin on this (SERVE_r06-shaped) bimodal load.
    fitted = fit_ladder(h, max_rungs=4)
    single = [
        (
            round_up_step(worst_n + 1, mode="pow2"),
            round_up_step(max(e for (n, e, g) in h.batches), mode="pow2"),
        )
    ]
    assert ladder_waste(fitted, h) < ladder_waste(single, h) / 2
    assert fit_ladder(h, max_rungs=4) == fitted, "deterministic"


def pytest_fit_ladder_rejects_empty_and_uses_graphs_fallback():
    with pytest.raises(ValueError, match="empty histogram"):
        fit_ladder(SizeHistogram())
    h = SizeHistogram()
    h.record_graph(20, 60)  # no batches recorded: single-request shape
    ladder = fit_ladder(h)
    assert ladder and ladder[0][0] > 20


def pytest_histogram_roundtrip_merge_and_cli(tmp_path):
    h = _bimodal_hist()
    hist_path = str(tmp_path / "hist.json")
    h.save(hist_path)
    loaded = SizeHistogram.load(hist_path)
    assert loaded.batches == h.batches and loaded.graphs == h.graphs
    other = SizeHistogram()
    other.record_batch(9, 27, 1)
    before = loaded.num_batches
    loaded.merge(other)
    assert loaded.num_batches == before + 1

    # fit-ladder CLI: histogram in -> fitted-ladder JSON out, consumable by
    # the auto: spec and byte-stable for identical inputs.
    from hydragnn_tpu.graphs.packing import main as packing_main

    ladder_path = str(tmp_path / "ladder.json")
    rc = packing_main(
        ["fit-ladder", "--hist", hist_path, "--out", ladder_path]
    )
    assert rc == 0
    with open(ladder_path) as f:
        doc = json.load(f)
    assert doc["schema"] == "hydragnn-bucket-ladder/v1"
    assert doc["ladder"] == [list(r) for r in fit_ladder(h, max_rungs=4)]
    assert doc["meta"]["observed_batches"] == h.num_batches

    # Every spec form resolves through one parser.
    assert resolve_ladder_spec("64x256, 512x2048") == [(64, 256), (512, 2048)]
    assert resolve_ladder_spec(f"auto:{ladder_path}") == fit_ladder(
        h, max_rungs=4
    )
    assert resolve_ladder_spec(f"auto:{hist_path}", max_rungs=4) == fit_ladder(
        h, max_rungs=4
    )
    with pytest.raises(ValueError, match="NxE"):
        resolve_ladder_spec("64x")
    with pytest.raises(ValueError, match="empty bucket ladder"):
        resolve_ladder_spec(" , ")
    with pytest.raises(FileNotFoundError):
        resolve_ladder_spec("auto:/nonexistent/hist.json")


# ---------------------------------------------------------- training collator
def _loader_pair(n_graphs=96, batch_size=8, **kw):
    rng = np.random.default_rng(0)
    graphs = ge._make_graphs(n_graphs, rng)
    base = dict(
        batch_size=batch_size,
        shuffle=True,
        seed=3,
        head_types=ge.TYPES,
        head_dims=ge.DIMS,
        edge_dim=1,
    )
    base.update(kw)
    plain = GraphDataLoader(list(graphs), **base)
    packed = GraphDataLoader(list(graphs), packing=True, **base)
    return graphs, plain, packed


def pytest_loader_packing_denser_capacity_respected_deterministic():
    graphs, plain, packed = _loader_pair()
    assert len(packed) < len(plain), "packing must shrink the batch count"
    caps = packed._pack_caps[0]
    plan = packed._batch_plan()
    seen = np.concatenate([idx for _, _, idx, _ in plan])
    assert sorted(seen.tolist()) == list(range(len(graphs)))
    ns = packed._ns
    es = packed._es
    for _, bi, idx, _ in plan:
        assert ns[idx].sum() <= caps.nodes
        assert es[idx].sum() <= caps.edges
        assert len(idx) <= caps.graphs
    # Same seed + epoch -> identical plan across loader instances; a new
    # epoch redraws batch order/ties.
    _, _, packed2 = _loader_pair()
    assert [i.tolist() for _, _, i, _ in packed2._batch_plan()] == [
        i.tolist() for _, _, i, _ in plan
    ]
    packed.set_epoch(1)
    assert [i.tolist() for _, _, i, _ in packed._batch_plan()] != [
        i.tolist() for _, _, i, _ in plan
    ]


def pytest_loader_packed_batches_bit_exact_vs_unpacked_collation():
    """A packed batch is the SAME collation as collate_graphs on its member
    list — packing changes membership, never per-head targets, masks, or
    edge wiring."""
    graphs, _, packed = _loader_pair(n_graphs=48)
    plan = packed._batch_plan()
    packed._arena = GraphArena(packed.dataset)
    for _, bi, idx, _ in plan[:4]:
        n_pad, e_pad, g_pad = packed._bucket_pads[bi]
        via_loader = packed._arena.collate(
            idx,
            head_types=ge.TYPES,
            head_dims=ge.DIMS,
            num_nodes_pad=n_pad,
            num_edges_pad=e_pad,
            num_graphs_pad=g_pad,
            edge_dim=1,
        )
        reference = collate_graphs(
            [packed.dataset[i] for i in idx],
            ge.TYPES,
            ge.DIMS,
            num_nodes_pad=n_pad,
            num_edges_pad=e_pad,
            num_graphs_pad=g_pad,
            edge_dim=1,
        )
        for field in (
            "node_features",
            "edge_features",
            "senders",
            "receivers",
            "node_graph",
            "node_mask",
            "edge_mask",
            "graph_mask",
        ):
            np.testing.assert_array_equal(
                getattr(via_loader, field), getattr(reference, field), field
            )
        for ih, (t_l, t_r) in enumerate(
            zip(via_loader.targets, reference.targets)
        ):
            np.testing.assert_array_equal(t_l, t_r, f"head {ih} targets")


def pytest_loader_padding_stats_and_histogram_record():
    graphs, plain, packed = _loader_pair()
    for loader in (plain, packed):
        for _ in loader:
            pass
    ps, pp = plain.padding_stats(), packed.padding_stats()
    assert pp["padding_waste_nodes"] < ps["padding_waste_nodes"]
    assert pp["batches"] == len(packed)
    assert packed.size_histogram.num_batches == len(packed)
    assert packed.size_histogram.num_graphs == len(graphs)
    packed.reset_padding_stats()
    assert packed.padding_stats()["batches"] == 0


def pytest_loader_set_packing_bumps_generation_and_rebuilds(tmp_path):
    graphs, plain, _ = _loader_pair()
    gen = plain.generation
    n_batches = len(plain)
    plain.set_packing(True)
    assert plain.generation == gen + 1, "external caches must invalidate"
    assert len(plain) < n_batches
    assert plain._pack_caps, "capacities rebuilt"
    plain.set_packing(False, ladder_step="mult64")
    assert plain.generation == gen + 2
    assert plain.ladder_step == "mult64"
    hist_path = str(tmp_path / "train_hist.json")
    plain.write_size_histogram(hist_path)
    assert SizeHistogram.load(hist_path).num_graphs == len(graphs)


def pytest_loader_packing_quarantine_and_fault_drill_interaction():
    """Packing composes with the PR-3 quarantine: seeded drill corruption is
    quarantined FIRST, then the packer plans only over survivors — every
    survivor packed exactly once, capacities still respected."""
    from hydragnn_tpu.faults.plan import FaultPlan

    rng = np.random.default_rng(2)
    graphs = ge._make_graphs(60, rng)
    loader = GraphDataLoader(
        [g.clone() for g in graphs],
        batch_size=8,
        shuffle=True,
        seed=1,
        head_types=ge.TYPES,
        head_dims=ge.DIMS,
        edge_dim=1,
        packing=True,
        skip_budget=4,
        fault_plan=FaultPlan("seed=3,corrupt_sample:count=3"),
    )
    assert len(loader.quarantined) == 3
    assert len(loader.dataset) == 57
    plan = loader._batch_plan()
    seen = np.concatenate([idx for _, _, idx, _ in plan])
    assert sorted(seen.tolist()) == list(range(57))
    caps = loader._pack_caps[0]
    for _, bi, idx, _ in plan:
        assert loader._ns[idx].sum() <= caps.nodes
    for batch in loader:  # collation runs clean over the packed survivors
        assert bool(np.isfinite(batch.node_features).all())


@pytest.mark.mpi_skip
def pytest_packed_training_convergence_parity_same_seed():
    """The loss-equivalence gate: packing changes batch membership (larger
    effective batches, fewer steps/epoch), not the objective — at MATCHED
    optimizer-step counts and the same init, packed vs unpacked training
    must land in the same loss basin, measured on one fixed (unshuffled,
    unpacked) eval loader: inside the band the unpacked arm's own shuffle
    seeds span. One model, one init, one jitted train/eval step pair shared
    by both arms, so only the loaders' batch plans differ."""
    import jax

    from hydragnn_tpu.models import init_model_variables
    from hydragnn_tpu.train.trainer import (
        create_train_state,
        make_eval_step,
        make_train_step,
    )
    from hydragnn_tpu.utils.optimizer import select_optimizer

    rng = np.random.default_rng(0)
    graphs = ge._make_graphs(48, rng)
    loader_kw = dict(
        batch_size=8,
        head_types=ge.TYPES,
        head_dims=ge.DIMS,
        edge_dim=1,
    )
    eval_loader = GraphDataLoader(
        [g.clone() for g in graphs], shuffle=False, **loader_kw
    )
    model = ge._build_model(hidden=8, layers=2)
    opt = select_optimizer("AdamW", 2e-2)
    train_step = make_train_step(model, opt, donate=False)
    eval_step = make_eval_step(model)
    key = jax.random.PRNGKey(0)

    def eval_loss(state):
        loss = count = 0.0
        for b in eval_loader:
            metrics, _ = eval_step(state, b)
            loss += float(metrics["loss"])
            count += float(metrics["count"])
        return loss / count

    variables = None
    initial = None

    def final_loss(packing, seed):
        nonlocal variables, initial
        loader = GraphDataLoader(
            [g.clone() for g in graphs],
            shuffle=True,
            seed=seed,
            packing=packing,
            **loader_kw,
        )
        if variables is None:
            variables = init_model_variables(model, next(iter(loader)))
        state = create_train_state(model, variables, opt)
        if initial is None:
            initial = eval_loss(state)
        steps = epoch = 0
        while steps < 42:  # packed epochs carry fewer, denser batches
            loader.set_epoch(epoch)
            for batch in loader:
                state, _ = train_step(state, batch, key)
                steps += 1
                if steps >= 42:
                    break
            epoch += 1
        return eval_loss(state)

    # The band the UNPACKED arm spans by its own shuffle seed, measured here
    # (42 steps at this learning rate leave 0.83-2.46 over seeds 1-8, PR 47:
    # one seed's loss says nothing to 15%, which this test asked for since
    # the seed and never met; the packed arm's seeds leave 0.64-1.03).
    band = [final_loss(False, seed) for seed in (5, 6, 7, 8)]
    uf, pf = band[0], final_loss(True, 5)
    results = {"unpacked": uf, "packed": pf, "unpacked by seed": band}
    assert uf < 0.9 * initial, f"unpacked run failed to converge: {results}"
    assert pf < 0.9 * initial, f"packed run failed to converge: {results}"
    # Same basin: no worse than the unpacked arm's worst shuffle, and not
    # another objective's minimum far below its best.
    assert 0.5 * min(band) <= pf <= max(band), (
        f"packed eval loss left the band of the unpacked arm's own seeds at "
        f"matched steps: {results}"
    )


# -------------------------------------------------------------------- serving
def _serve_engine(pool=16, **options):
    from hydragnn_tpu.graphs import collate_graphs as _collate
    from hydragnn_tpu.models import init_model_variables
    from hydragnn_tpu.serve import InferenceEngine

    rng = np.random.default_rng(0)
    graphs = ge._make_graphs(pool, rng)
    for g in graphs:
        g.y = g.y_loc = None
    model = ge._build_model(hidden=8, layers=2)
    batch = _collate(graphs[:2], (), (), edge_dim=1)
    variables = init_model_variables(model, batch)
    options.setdefault("max_batch_graphs", 16)
    options.setdefault("max_delay_ms", 20.0)
    return InferenceEngine(model, variables, **options), graphs


@pytest.mark.mpi_skip
def pytest_engine_packing_demux_identity():
    """Under packing, every future resolves to ITS OWN graph's prediction:
    node-head rows match the request's node count and values match the
    lone-request reference regardless of which bin the request landed in."""
    # The fitted ladder is derivable from the pool alone (deterministic
    # seed), so BOTH engines can share it: the reference engine serves every
    # single-graph request from the top rung (one compile) while the packed
    # engine exercises rung selection + bin splitting.
    pool = ge._make_graphs(10, np.random.default_rng(0))
    hist = SizeHistogram()
    for g in pool:
        hist.record_graph(g.num_nodes, g.num_edges)
        hist.record_batch(g.num_nodes, g.num_edges, 1)
    hist.record_batch(
        sum(g.num_nodes for g in pool),
        sum(g.num_edges for g in pool),
        len(pool),
    )
    ladder = fit_ladder(hist, max_rungs=2)

    ref_engine, graphs = _serve_engine(
        pool=10,
        max_batch_graphs=1,
        max_delay_ms=1.0,
        bucket_ladder=ladder[-1:],
    )
    try:
        reference = [ref_engine.predict([g])[0] for g in graphs]
    finally:
        ref_engine.close()

    engine, _ = _serve_engine(
        pool=10, bucket_ladder=ladder, warmup=True, packing=True
    )
    try:
        out = engine.predict(graphs, timeout=60.0)
        snap = engine.metrics.snapshot()
        assert snap["batches_total"] >= 1
        assert snap["bucket_cache"]["ladder_fallbacks"] == 0
        for g, o, r in zip(graphs, out, reference):
            for ihead, htype in enumerate(engine.model.output_type):
                if htype == "node":
                    assert o[ihead].shape[0] == g.num_nodes
                # Packed bins compile at DIFFERENT padded shapes than the
                # 1-graph reference — XLA:CPU tiling varies with N_pad, so
                # the contract here is numerical identity (demux), not
                # bit-exactness (which tests/test_serve_engine.py locks at
                # MATCHED shapes).
                np.testing.assert_allclose(
                    o[ihead], r[ihead], atol=5e-5, rtol=1e-5,
                    err_msg=f"head {ihead} demuxed wrong values",
                )
    finally:
        engine.close()


@pytest.mark.mpi_skip
def pytest_engine_packing_zero_recompile_after_warmup():
    """The steady-state contract survives packing: with a fitted ladder
    warmed, mixed traffic (singles, partial flushes, over-capacity flushes
    that split into bins) triggers ZERO XLA compiles — engine cache and
    sentinel agree."""
    hist = SizeHistogram()
    rng = np.random.default_rng(9)
    engine, graphs = _serve_engine()
    try:
        for g in graphs:
            hist.record_batch(g.num_nodes, g.num_edges, 1)
        for _ in range(20):
            take = rng.integers(2, len(graphs) + 1)
            sel = rng.permutation(len(graphs))[:take]
            hist.record_batch(
                sum(graphs[i].num_nodes for i in sel),
                sum(graphs[i].num_edges for i in sel),
                int(take),
            )
    finally:
        engine.close()
    ladder = fit_ladder(hist, max_rungs=4)
    engine, graphs = _serve_engine(
        bucket_ladder=ladder, warmup=True, packing=True, max_delay_ms=5.0
    )
    try:
        misses0 = engine.metrics.snapshot()["bucket_cache"]["misses"]
        assert misses0 == len(ladder)
        with engine.no_recompile(action="raise"):
            engine.predict(graphs[:1])
            engine.predict(graphs[:7])
            engine.predict(graphs)  # over-capacity flush -> packed bins
            engine.predict(graphs[3:5])
        snap = engine.metrics.snapshot()
        assert snap["bucket_cache"]["misses"] == misses0, snap["bucket_cache"]
        assert snap["bucket_cache"]["ladder_fallbacks"] == 0
        assert snap["per_bucket"], "per-bucket occupancy recorded"
        assert snap["graphs_total"] == len(graphs) + 10
    finally:
        engine.close()


# ----------------------------------------------------------- contract checker
def pytest_check_config_validates_ladder_forms(tmp_path):
    from hydragnn_tpu.analysis.contracts import check_config

    with open(
        os.path.join(os.path.dirname(__file__), "inputs", "ci.json")
    ) as f:
        config = json.load(f)

    def codes(**kw):
        rep = check_config(config, strict=False, deep=False, **kw)
        return [e["code"] for e in rep["errors"]], rep

    # Literal + auto: forms all validate through one resolver.
    h = _bimodal_hist()
    hist_path = str(tmp_path / "hist.json")
    h.save(hist_path)
    ladder_path = str(tmp_path / "ladder.json")
    with open(ladder_path, "w") as f:
        json.dump(ladder_to_json(fit_ladder(h)), f)
    for spec in (
        "512x4096,1024x8192",
        f"auto:{hist_path}",
        f"auto:{ladder_path}",
    ):
        errs, _ = codes(bucket_ladder=spec)
        assert errs == [], (spec, errs)
    for bad in ("1024", "auto:", "auto:/nonexistent.json", "0x12,axb"):
        errs, rep = codes(bucket_ladder=bad)
        assert "oob-bucket" in errs, (bad, rep["errors"])
    # Rung feasibility still applies to resolved auto: ladders.
    errs, _ = codes(bucket_ladder="1x0")
    assert "oob-bucket" in errs

    # Dataset knobs: ladder_step and packing.
    config["Dataset"]["ladder_step"] = "mult63"
    errs, _ = codes()
    assert "oob-bucket" in errs
    config["Dataset"]["ladder_step"] = "mult64"
    config["Dataset"]["packing"] = "yes"
    errs, _ = codes()
    assert "oob-bucket" in errs
    config["Dataset"]["packing"] = True
    errs, _ = codes()
    assert errs == []
