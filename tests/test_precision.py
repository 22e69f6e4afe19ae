"""graftprec — the end-to-end precision policy layer (docs/PRECISION.md).

Locks the tentpole contracts:
  * ``Training.precision="f32"`` compiles the byte-identical seed step
    (params bit-equal after training through the driver);
  * ``"bf16"`` keeps f32 master weights/optimizer state across steps while
    compute runs in bf16, and converges;
  * dynamic loss scaling: an injected NaN batch (the faults layer's
    ``nan_grad@K``) backs the scale off, skips the step, and recovers with
    NO rollback storm; telemetry carries the gauge + prec/* counters;
  * guard=True stays bit-inert under bf16 (the skip machinery is structural
    in the scaled step — the flag only adds the ``bad`` metric);
  * the serve quantized arm passes its tolerance gate and FAILS loudly on a
    deliberate violation;
  * precision is a CacheKey component: a bf16/int8 entry never hydrates an
    f32 lookup (and vice versa) in a shared graftcache store;
  * the certification tolerances are THE shared gate (precision/tolerance),
    consumed by ops/certify.py.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as ge
from hydragnn_tpu.graphs import GraphSample, collate_graphs
from hydragnn_tpu.models import create_model, init_model_variables
from hydragnn_tpu.precision import (
    KERNEL_CERT_GATE,
    LossScaleConfig,
    PrecisionPolicy,
    make_loss_scale_state,
    tolerance_report,
)
from hydragnn_tpu.serve import InferenceEngine, PrecisionToleranceError
from hydragnn_tpu.train.trainer import create_train_state, make_train_step
from hydragnn_tpu.utils.optimizer import select_optimizer

HEADS = {
    "graph": {
        "num_sharedlayers": 1,
        "dim_sharedlayers": 8,
        "num_headlayers": 2,
        "dim_headlayers": [8, 8],
    },
}


def _graphs(rng, count=24, lo=4, hi=10):
    out = []
    for _ in range(count):
        n = int(rng.integers(lo, hi))
        x = rng.normal(size=(n, 1)).astype(np.float32)
        ei = np.stack([np.arange(n), (np.arange(n) + 1) % n]).astype(np.int32)
        out.append(
            GraphSample(
                x=x,
                pos=np.zeros((n, 3), np.float32),
                y=np.array([x.sum()], np.float32),
                y_loc=np.array([[0, 1]], np.int64),
                edge_index=ei,
            )
        )
    return out


def _loader(graphs, **kw):
    from hydragnn_tpu.preprocess.dataloader import GraphDataLoader

    kw.setdefault("batch_size", 8)
    kw.setdefault("shuffle", False)
    loader = GraphDataLoader(graphs, **kw)
    loader.set_head_spec(("graph",), (1,))
    return loader


def _driver(loader, precision=None, loss_scale=None, fault_tolerance=None,
            fault_plan=None):
    from hydragnn_tpu.train.train_validate_test import TrainingDriver

    model = create_model("SAGE", 1, 8, (1,), ("graph",), HEADS, [1.0], 2)
    variables = init_model_variables(model, next(iter(loader)))
    opt = select_optimizer("AdamW", 5e-3)
    state = create_train_state(model, variables, opt)
    return TrainingDriver(
        model, opt, state,
        precision=precision, loss_scale=loss_scale,
        fault_tolerance=fault_tolerance, fault_plan=fault_plan,
    )


def _train(driver, loader, epochs=2):
    loss = None
    for epoch in range(epochs):
        loader.set_epoch(epoch)
        loss, _ = driver.train_epoch(loader)
    return loss


def _param_leaves(driver):
    import jax

    return jax.tree_util.tree_leaves(driver.state.params)


# ------------------------------------------------------------ f32 = the seed
@pytest.mark.mpi_skip
def pytest_f32_policy_byte_identical_to_seed():
    """precision='f32' resolves to NO policy object and trains bit-for-bit
    like a driver built without the precision arguments at all."""
    assert PrecisionPolicy.resolve(None) is None
    assert PrecisionPolicy.resolve("f32") is None
    graphs = _graphs(np.random.default_rng(0))
    da = _driver(lda := _loader(graphs))
    db = _driver(ldb := _loader(graphs), precision="f32")
    assert db.state.loss_scale is None
    seed_loss = _train(da, lda, epochs=1)
    f32_loss = _train(db, ldb, epochs=1)
    assert f32_loss == seed_loss
    for x, y in zip(_param_leaves(da), _param_leaves(db)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# --------------------------------------------------------- bf16 master weights
@pytest.mark.mpi_skip
def pytest_bf16_master_weights_stay_f32_across_steps():
    import jax
    import jax.numpy as jnp

    graphs = _graphs(np.random.default_rng(0))
    d = _driver(ld := _loader(graphs), precision="bf16")
    assert d.model.compute_dtype == "bfloat16"
    assert d.state.loss_scale is not None
    first = _train(d, ld, epochs=1)
    last = _train(d, ld, epochs=3)
    for leaf in _param_leaves(d):
        assert leaf.dtype == jnp.float32
    for leaf in jax.tree_util.tree_leaves(d.state.opt_state):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating):
            assert leaf.dtype == jnp.float32
    assert np.isfinite(last)
    assert last < first, (first, last)


# --------------------------------------------------- loss-scale backoff drill
@pytest.mark.mpi_skip
def pytest_loss_scale_backoff_drill_recovers_without_rollback_storm():
    """nan_grad@K under bf16: the poisoned batch overflows exactly once, the
    scale backs off in-jit, the guarded step skips it, and training continues
    — one bad step, ZERO rollbacks, counters + gauge + flight event on the
    telemetry surface (docs/PRECISION.md "Loss scaling")."""
    from hydragnn_tpu.faults import FaultCounters, FaultPlan
    from hydragnn_tpu.telemetry import graftel as telemetry

    FaultCounters.reset()
    telemetry.clear_counters("prec/")
    graphs = _graphs(np.random.default_rng(0), count=48)
    init_scale = 2.0**12
    d = _driver(
        ld := _loader(graphs),
        precision="bf16",
        loss_scale={"init": init_scale, "growth_interval": 1000},
        fault_tolerance={"enabled": 1, "max_bad_steps": 3},
        fault_plan=FaultPlan("nan_grad@2"),
    )
    loss = _train(d, ld, epochs=2)
    assert np.isfinite(loss)
    assert all(np.isfinite(np.asarray(p)).all() for p in _param_leaves(d))
    # Exactly the injected batch tripped; the streak never reached rollback.
    assert FaultCounters.get("injected_nan_batches") == 1
    assert FaultCounters.get("bad_steps") == 1
    assert d.guard.rollbacks == 0, "rollback storm"
    assert FaultCounters.get("loss_scale_backoff") == 1
    assert telemetry.counter_value("prec/overflow") == 1
    assert telemetry.counter_value("prec/backoff") == 1
    # The scale kept its backed-off value (growth_interval is out of reach).
    scale = float(d.state.loss_scale.scale)
    assert scale == init_scale * 0.5, scale
    assert telemetry.gauges_snapshot().get("train/loss_scale") == scale


@pytest.mark.mpi_skip
def pytest_guard_rollback_preserves_backed_off_scale():
    """A guard rollback restores params from the snapshot but must NOT
    restore the snapshot's (higher) loss scale — that would re-raise the
    scale that just overflowed and storm."""
    import jax

    graphs = _graphs(np.random.default_rng(0))
    d = _driver(
        ld := _loader(graphs),
        precision="bf16",
        loss_scale={"init": 2.0**12, "growth_interval": 1000},
        fault_tolerance={"enabled": 1, "max_bad_steps": 1},
    )
    # No training needed: the snapshot/rollback contract is host-side state
    # plumbing — exercising it on the initial state keeps tier-1 lean.
    d.guard.take_snapshot(d.state)
    backed_off = d.state.loss_scale.replace(
        scale=jax.numpy.asarray(4.0, jax.numpy.float32)
    )
    d.state = d.state.replace(loss_scale=backed_off)
    d.guard.rollback(d)
    assert float(d.state.loss_scale.scale) == 4.0
    assert d.guard.rollbacks == 1


@pytest.mark.mpi_skip
def pytest_bf16_rejects_contradictory_compute_dtype():
    """precision='bf16' with an explicit non-bf16 Architecture.compute_dtype
    must refuse to build — the driver would otherwise silently train at that
    dtype with pointless loss scaling armed."""
    from hydragnn_tpu.train.train_validate_test import TrainingDriver

    graphs = _graphs(np.random.default_rng(0), count=8)
    ld = _loader(graphs)
    model = create_model(
        "SAGE", 1, 8, (1,), ("graph",), HEADS, [1.0], 2,
        compute_dtype="float32",
    )
    variables = init_model_variables(model, next(iter(ld)))
    opt = select_optimizer("AdamW", 5e-3)
    state = create_train_state(model, variables, opt)
    with pytest.raises(ValueError, match="contradicts"):
        TrainingDriver(model, opt, state, precision="bf16")


# ----------------------------------------------------- guard bit-inertness
@pytest.mark.mpi_skip
def pytest_guard_flag_bit_inert_under_bf16():
    import jax

    rng = np.random.default_rng(0)
    batch = collate_graphs(_graphs(rng, count=8), ("graph",), (1,))
    model = create_model(
        "SAGE", 1, 8, (1,), ("graph",), HEADS, [1.0], 2,
        compute_dtype="bfloat16",
    )
    variables = init_model_variables(model, batch)
    opt = select_optimizer("AdamW", 5e-3)
    cfg = LossScaleConfig.from_config({"init": 2.0**10})
    key = jax.random.PRNGKey(0)
    ends = []
    for guard in (False, True):
        state = create_train_state(model, variables, opt).replace(
            loss_scale=make_loss_scale_state(cfg)
        )
        step = make_train_step(
            model, opt, donate=False, guard=guard, loss_scaling=cfg
        )
        for _ in range(4):
            state, m = step(state, batch, key)
        assert ("bad" in m) == guard
        ends.append(state)
    for x, y in zip(
        jax.tree_util.tree_leaves(ends[0].params),
        jax.tree_util.tree_leaves(ends[1].params),
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert float(ends[0].loss_scale.scale) == float(ends[1].loss_scale.scale)


# ------------------------------------------------------- serve tolerance gate
def _serve_fixture():
    rng = np.random.default_rng(0)
    graphs = ge._make_graphs(12, rng)
    model = ge._build_model(hidden=8, layers=2)
    batch = collate_graphs(graphs[:2], ge.TYPES, ge.DIMS, edge_dim=1)
    variables = init_model_variables(model, batch)
    return model, variables, graphs


@pytest.mark.mpi_skip
def pytest_quantized_serve_tolerance_gate_pass_and_fail():
    model, variables, graphs = _serve_fixture()
    # Pass: a generous bound on the int8 arm; the verdict lands in metrics.
    with InferenceEngine(
        model, variables, precision="int8", tolerance=0.5,
        max_batch_graphs=8, autostart=False,
    ) as eng:
        # An explicitly empty probe set is an upstream bug, never a silent
        # fall-back to synthetic calibration graphs.
        with pytest.raises(ValueError):
            eng.check_tolerance(samples=[])
        report = eng.check_tolerance()
        assert report["ok"] and report["arm"] == "int8"
        assert report["quantization"]["tensors_quantized"] > 0
        assert 0.0 < report["fwd_err"] < 0.5
        snap = eng.metrics.snapshot()["precision"]
        assert snap["arm"] == "int8"
        assert snap["gate_checks"] == 1 and snap["gate_failures"] == 0
        prom = eng.metrics.render_prometheus()
        assert 'hydragnn_serve_precision_info{arm="int8"} 1' in prom
        assert "hydragnn_serve_precision_tolerance_diff_bucket" in prom
        # Strict-parser validity: every bucket's le label must be distinct
        # (the tiny diff bounds must not collapse under decimal rounding).
        les = [
            line.split('le="')[1].split('"')[0]
            for line in prom.splitlines()
            if line.startswith("hydragnn_serve_precision_tolerance_diff_bucket")
        ]
        assert len(les) == len(set(les)), les
    # Deliberate violation: an impossible bound must FAIL the gate loudly.
    with InferenceEngine(
        model, variables, precision="int8", tolerance=1e-12,
        max_batch_graphs=8, autostart=False,
    ) as eng:
        with pytest.raises(PrecisionToleranceError) as exc:
            eng.check_tolerance()
        assert exc.value.report["fwd_err"] > 1e-12
        assert eng.metrics.snapshot()["precision"]["gate_failures"] == 1
        # The arm still SERVES after a failed gate check (the gate is a
        # startup decision, not an engine poison): start the pipeline and
        # resolve real traffic. (Arm-vs-f32 output tracking under live
        # predict traffic is measured by bench.py --precision.)
        eng.start()
        outs = eng.predict(graphs[:2])
        assert all(np.isfinite(v).all() for r in outs for v in r)


@pytest.mark.mpi_skip
def pytest_gate_reference_is_real_f32_for_bf16_pinned_checkpoints():
    """A checkpoint whose Architecture already pins compute_dtype='bfloat16'
    must NOT become its own tolerance reference (max_abs_diff identically 0
    would pass any bound without measuring anything): the gate clones the
    reference back to f32 compute."""
    rng = np.random.default_rng(0)
    model = ge._build_model(hidden=8, layers=2, compute_dtype="bfloat16")
    batch = collate_graphs(
        ge._make_graphs(4, rng)[:2], ge.TYPES, ge.DIMS, edge_dim=1
    )
    variables = init_model_variables(model, batch)
    with InferenceEngine(
        model, variables, precision="bf16", tolerance=0.5,
        max_batch_graphs=8, autostart=False,
    ) as eng:
        assert eng._ref_model.compute_dtype is None
        report = eng.check_tolerance()
        assert report["fwd_err"] > 0.0, "vacuous gate: reference == arm"


@pytest.mark.mpi_skip
def pytest_quantized_arm_requires_tolerance_and_f32_rejects_it():
    model, variables, _ = _serve_fixture()
    with pytest.raises(ValueError):
        InferenceEngine(model, variables, precision="int8", autostart=False)
    with pytest.raises(ValueError):
        InferenceEngine(
            model, variables, precision="bf16", tolerance=0.0, autostart=False
        )
    with pytest.raises(ValueError):
        InferenceEngine(
            model, variables, precision="f32", tolerance=0.1, autostart=False
        )
    with pytest.raises(ValueError):
        InferenceEngine(
            model, variables, precision="fp4", tolerance=0.1, autostart=False
        )
    # A typo'd loss-scale knob must never silently train with defaults.
    with pytest.raises(ValueError, match="unknown key"):
        LossScaleConfig.from_config({"growth_intervall": 2000})


# --------------------------------------------------- cache-key precision miss
@pytest.mark.mpi_skip
def pytest_cache_key_precision_component_blocks_cross_hits(tmp_path):
    """One shared graftcache store, four engines: the f32 warmup populates
    the store; a second f32 engine HYDRATES (the store works); bf16 and int8
    engines must compile fresh — zero cross-precision hydrations — and the
    bf16 entry must not serve the int8 arm either."""
    store = str(tmp_path / "exec_cache")
    ladder = [(32, 64)]
    model, variables, _ = _serve_fixture()

    def stats(precision=None, tolerance=None):
        eng = InferenceEngine(
            model, variables,
            max_batch_graphs=4, bucket_ladder=ladder, warmup=True,
            compile_cache=store, autostart=False,
            **(
                {"precision": precision, "tolerance": tolerance}
                if precision
                else {}
            ),
        )
        snap = eng.metrics.snapshot()["bucket_cache"]
        eng.close()
        return snap["misses"], snap["hydrated"]

    compiled, hydrated = stats()
    assert (compiled, hydrated) == (1, 0)
    # Control: same-precision second process hydrates from disk.
    compiled, hydrated = stats()
    assert (compiled, hydrated) == (0, 1)
    # bf16 must MISS the f32 entry.
    compiled, hydrated = stats("bf16", 0.5)
    assert (compiled, hydrated) == (1, 0), "bf16 hydrated a foreign entry"
    # int8 must miss BOTH the f32 and the bf16 entries (same module repr and
    # tree signature as bf16 — only the precision flag separates them).
    compiled, hydrated = stats("int8", 0.5)
    assert (compiled, hydrated) == (1, 0), "int8 hydrated a foreign entry"
    # And every arm hydrates its OWN entry on a rebuild.
    for arm in ("bf16", "int8"):
        compiled, hydrated = stats(arm, 0.5)
        assert (compiled, hydrated) == (0, 1), arm


# ------------------------------------------------------- shared tolerance gate
@pytest.mark.mpi_skip
def pytest_certify_aggregation_consumes_the_shared_gate(monkeypatch):
    """The aggregation's certification and quantized serving share ONE
    tolerance implementation: ops/certify.py's reported forward pin IS the
    gate constant, its gradient pin the incumbent's error and never under the
    forward gate, and its verdicts follow from the errors it reports."""
    from hydragnn_tpu.ops.certify import certify_aggregation

    assert KERNEL_CERT_GATE.fwd == 5e-4
    monkeypatch.delenv("HYDRAGNN_SEGMENT_SORTED", raising=False)
    with pytest.raises(RuntimeError, match="sorted arm"):
        certify_aggregation(e=64, f=4, n=8)  # a CPU's default is the XLA ops
    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    report = certify_aggregation(e=2048, f=24, n=256)
    assert report["tol"] == KERNEL_CERT_GATE.fwd
    assert report["tol_grad"] == max(
        KERNEL_CERT_GATE.fwd, report["xla"]["err_grad"]
    )
    assert set(report["arms"]) == {"sorted", "csr", "scatter_sorted"}
    # The prefix arms at the width asked for, the wide arm at four lane tiles.
    assert report["shape"]["f_narrow"] == 24 and report["shape"]["f_wide"] == 512
    for name in ("sorted", "csr"):
        arm = report["arms"][name]
        assert arm["ok"] == (
            arm["err_fwd"] < report["tol"] and arm["err_grad"] <= report["tol_grad"]
        )
    wide = report["arms"]["scatter_sorted"]
    assert wide["ok"] and wide["err_fwd"] < report["tol"]
    assert all(c["ok"] for c in wide["cases"].values())
    assert report["extrema_scan"]["bit_equal"]
    assert report["extrema_scan"]["grad_bit_equal"]
    assert report["ok"], report


@pytest.mark.mpi_skip
def pytest_tolerance_report_shapes_and_verdicts():
    a = [np.ones((4, 2), np.float32), np.zeros((3, 1), np.float32)]
    b = [np.ones((4, 2), np.float32) * 1.01, np.zeros((3, 1), np.float32)]
    rep = tolerance_report(a, b, 0.1, names=["g", "n"])
    assert rep["ok"] and len(rep["per_head"]) == 2
    assert rep["per_head"][0]["head"] == "g"
    assert not tolerance_report(a, b, 1e-6)["ok"]
    with pytest.raises(ValueError):
        tolerance_report(a, b[:1], 0.1)
    with pytest.raises(ValueError):
        tolerance_report([a[0]], [np.ones((5, 2), np.float32)], 0.1)
