"""Deterministic fault-drill matrix — ``python bench.py --faults``
(docs/FAULT_TOLERANCE.md "Drills").

Each drill injects exactly one fault from the taxonomy through the REAL
production path (GraphDataLoader → TrainingDriver scan/per-batch epochs, or
run_training under the supervisor) and checks that the designated mechanism —
guard skip, rollback, quarantine, transfer retry, supervised restart —
survived it: training completes, the final loss lands in the clean run's
ballpark, and the mechanism's counter incremented. Everything is seeded: the
same spec string produces the same drill, run to run.

Also measures what the guard COSTS: steady-epoch time with the guard enabled
(no faults) vs disabled on the same compiled-workload, plus a bit-inertness
check (guard-on clean params must equal guard-off params exactly).

Emits the ``FAULTS_rNN.json`` block consumed by bench.py's ``--faults`` mode.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Final-loss ballpark gate vs the clean run: a drill changes the trajectory
# (skipped steps, dropped samples, a rollback), not the problem — the loss
# must stay the same order of magnitude, not bit-match.
BALLPARK = (0.2, 5.0)

HEADS = {
    "graph": {
        "num_sharedlayers": 1,
        "dim_sharedlayers": 4,
        "num_headlayers": 1,
        "dim_headlayers": [8],
    },
}


def _dataset(seed=0, count=48, lo=4, hi=12):
    from hydragnn_tpu.graphs import GraphSample

    rng = np.random.default_rng(seed)
    graphs = []
    for _ in range(count):
        n = int(rng.integers(lo, hi))
        x = rng.normal(size=(n, 1)).astype(np.float32)
        ei = np.stack([np.arange(n), (np.arange(n) + 1) % n]).astype(np.int32)
        graphs.append(
            GraphSample(
                x=x,
                pos=np.zeros((n, 3), np.float32),
                y=np.array([x.sum()], np.float32),
                y_loc=np.array([[0, 1]], np.int64),
                edge_index=ei,
            )
        )
    return graphs


def _loader(graphs, **kw):
    from hydragnn_tpu.preprocess.dataloader import GraphDataLoader

    kw.setdefault("batch_size", 8)
    kw.setdefault("shuffle", False)
    loader = GraphDataLoader(graphs, **kw)
    loader.set_head_spec(("graph",), (1,))
    return loader


def _driver(loader, fault_tolerance=None, fault_plan=None, hidden=8, layers=2):
    from hydragnn_tpu.models import create_model, init_model_variables
    from hydragnn_tpu.train.train_validate_test import TrainingDriver
    from hydragnn_tpu.train.trainer import create_train_state
    from hydragnn_tpu.utils.optimizer import select_optimizer

    model = create_model("SAGE", 1, hidden, (1,), ("graph",), HEADS, [1.0], layers)
    variables = init_model_variables(model, next(iter(loader)))
    opt = select_optimizer("AdamW", 5e-3)
    state = create_train_state(model, variables, opt)
    return TrainingDriver(
        model, opt, state, fault_tolerance=fault_tolerance, fault_plan=fault_plan
    )


def _train(driver, loader, epochs=3):
    loss = None
    for epoch in range(epochs):
        loader.set_epoch(epoch)
        loss, _ = driver.train_epoch(loader)
    return loss


def _params_finite(driver):
    import jax

    return all(
        np.isfinite(np.asarray(leaf)).all()
        for leaf in jax.tree_util.tree_leaves(driver.state.params)
    )


def _params_equal(a, b):
    import jax

    return all(
        (np.asarray(x) == np.asarray(y)).all()
        for x, y in zip(
            jax.tree_util.tree_leaves(a.state.params),
            jax.tree_util.tree_leaves(b.state.params),
        )
    )


def _in_ballpark(loss, clean):
    return (
        np.isfinite(loss)
        and BALLPARK[0] * clean <= loss <= BALLPARK[1] * clean
    )


def _guard_overhead_pct(windows=6, batch=64, steps=8):
    """min-of steady scan-window time, guard on vs off, on the PR-2-baseline-
    shaped workload (flagship PNA, hidden 64, QM9-like graphs): the guard's
    in-jit cost is O(params) per step — isfinite over grads plus the
    state-sized keep-selects — so it must be measured against a step whose
    batch work dominates, like the production batch-256 workload, not the
    drill matrix's micro-epochs (where a fixed ~100 µs/step reads as double-
    digit percent). Windows are INTERLEAVED off/on and min-taken, the
    standard shared-host noise estimator (bench.py's WINDOWS rationale)."""
    import jax

    from __graft_entry__ import DIMS, TYPES, _build_model, _make_graphs
    from hydragnn_tpu.graphs import collate_graphs
    from hydragnn_tpu.models import init_model_variables
    from hydragnn_tpu.train.trainer import (
        create_train_state,
        make_train_epoch_scan,
        stack_batches,
    )
    from hydragnn_tpu.utils.optimizer import select_optimizer

    runs = {}
    count = np.asarray(steps, np.int32)  # every stacked batch is real
    for key, guard in (("off", False), ("on", True)):
        rng = np.random.default_rng(0)
        graphs = _make_graphs(batch, rng, n_lo=12, n_hi=26)
        b = collate_graphs(graphs, TYPES, DIMS, edge_dim=1)
        stacked = stack_batches([b] * steps, steps)
        model = _build_model(hidden=64, layers=3)
        variables = init_model_variables(model, b)
        opt = select_optimizer("AdamW", 1e-3)
        state = create_train_state(model, variables, opt)
        compiled = (
            make_train_epoch_scan(model, opt, guard=guard)
            .lower(state, stacked, count, jax.random.PRNGKey(0))
            .compile()
        )
        state, m = compiled(state, stacked, count, jax.random.PRNGKey(0))  # warmup
        jax.block_until_ready(m["loss"])
        runs[key] = (compiled, state, stacked)
    times = {"off": [], "on": []}
    for _ in range(windows):
        for key in ("off", "on"):
            compiled, state, stacked = runs[key]
            t0 = time.perf_counter()
            state, m = compiled(state, stacked, count, jax.random.PRNGKey(0))
            jax.block_until_ready(m["loss"])
            times[key].append(time.perf_counter() - t0)
            runs[key] = (compiled, state, stacked)
    best = {k: min(v) for k, v in times.items()}
    return round(100.0 * (best["on"] / best["off"] - 1.0), 2), best


def _ckpt_fallback_drill(kind: str) -> dict:
    """corrupt_ckpt / truncate_ckpt: train with keep_last_k retention, let the
    plan's post-save hook damage the LAST save (which also damages its
    hard-linked retained twin), then load through the verified chain — the
    newest intact retained entry must come back, with the fallback recorded
    in FaultCounters and the run's supervisor.json."""
    import tempfile

    from hydragnn_tpu.checkpoint import load_existing_model, save_model, set_post_save_hook
    from hydragnn_tpu.faults import FaultCounters, FaultPlan

    graphs = _dataset(seed=0)
    loader = _loader(list(graphs))
    d = _driver(loader)
    # Save indices 0..2; the drill hits the last one (epoch-3 state).
    plan = FaultPlan(f"seed=5,{kind}@2")
    before = FaultCounters.get("ckpt_fallback_loads")
    with tempfile.TemporaryDirectory() as tmp:
        path = tmp + "/"
        set_post_save_hook(plan.on_checkpoint_saved)
        try:
            for epoch in (1, 2, 3):
                loader.set_epoch(epoch)
                d.train_epoch(loader)
                save_model(
                    {"params": d.state.params, "batch_stats": d.state.batch_stats},
                    d.state.opt_state,
                    "drill",
                    path=path,
                    meta={"epoch": epoch},
                    keep_last_k=3,
                )
        finally:
            set_post_save_hook(None)
        variables = {"params": d.state.params, "batch_stats": d.state.batch_stats}
        _, _, meta = load_existing_model(variables, "drill", path=path, return_meta=True)
        with open(os.path.join(tmp, "drill", "supervisor.json")) as f:
            recorded = json.load(f).get("checkpoint_fallbacks", [])
    return {
        "survived": meta.get("epoch") == 2
        and FaultCounters.get("ckpt_fallback_loads") == before + 1
        and bool(recorded),
        "mechanism": "ckpt_fallback_chain",
        "recovered_epoch": meta.get("epoch"),
        "fallback_recorded": bool(recorded),
    }


def _ckpt_kill_save_drill(num_epoch: int = 3) -> dict:
    """corrupt_ckpt + kill@save under run_training(supervise=True), end to
    end: incarnation 0 saves epoch 1 cleanly, then its epoch-2 save is
    bit-flipped and the process SIGKILLed right after. The restart's resume
    hits the corrupt latest, falls back to the epoch-1 retained entry, and
    completes — restart metadata AND the fallback record land in the same
    supervisor.json."""
    import subprocess
    import sys as _sys
    import tempfile

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with tempfile.TemporaryDirectory() as tmp:
        script = f"""
import json, os, sys
os.chdir({tmp!r})
os.environ["SERIALIZED_DATA_PATH"] = {tmp!r}
os.environ["HYDRAGNN_FAULTS"] = "seed=5,corrupt_ckpt@1,kill@save1"
sys.path.insert(0, {repo!r})
sys.path.insert(0, os.path.join({repo!r}, "tests"))
import jax
jax.config.update("jax_platforms", "cpu")
from deterministic_graph_data import deterministic_graph_data
import hydragnn_tpu
from hydragnn_tpu.utils.config_utils import get_log_name_config
from hydragnn_tpu.utils.model import load_checkpoint_meta
with open(os.path.join({repo!r}, "tests/inputs/ci.json")) as f:
    config = json.load(f)
config["Visualization"] = {{"create_plots": False}}
tr = config["NeuralNetwork"]["Training"]
tr["num_epoch"] = {num_epoch}
tr["periodic_checkpoint_every"] = 1
tr["checkpoint_keep_last_k"] = 3
for split, cnt in {{"train": 24, "test": 8, "validate": 8}}.items():
    p = f"dataset/unit_test_singlehead_{{split}}"
    os.makedirs(p, exist_ok=True)
    deterministic_graph_data(p, number_configurations=cnt)
    config["Dataset"]["path"][split] = p
meta = hydragnn_tpu.run_training(config, supervise=True, max_restarts=2)
log_name = get_log_name_config(config)
meta["final_epoch"] = load_checkpoint_meta(log_name).get("epoch")
print("SUPERVISOR_META " + json.dumps(meta))
"""
        proc = subprocess.run(
            [_sys.executable, "-c", script],
            capture_output=True,
            text=True,
            timeout=900,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        line = next(
            (
                l
                for l in proc.stdout.splitlines()
                if l.startswith("SUPERVISOR_META ")
            ),
            None,
        )
        if line is None:
            return {
                "survived": False,
                "mechanism": "supervised_restart+ckpt_fallback",
                "error": (proc.stderr or proc.stdout)[-400:],
            }
        meta = json.loads(line[len("SUPERVISOR_META ") :])
        fallbacks = meta.get("checkpoint_fallbacks", [])
        return {
            "survived": bool(meta.get("completed"))
            and meta.get("restarts", 0) >= 1
            and bool(fallbacks)
            and meta.get("final_epoch") == num_epoch,
            "mechanism": "supervised_restart+ckpt_fallback",
            "restarts": meta.get("restarts"),
            "fallback_recorded": bool(fallbacks),
            "final_epoch": meta.get("final_epoch"),
        }


def _ckpt_save_stall(reps: int = 5) -> dict:
    """Train-thread stall per checkpoint, sync vs async, min-of-reps (the
    shared-host noise estimator): a sync save holds the thread through
    serialize+fsync+rename; the async path only through the device->host
    snapshot + enqueue. ``ckpt_save_stall_ms`` in FAULTS_rNN.json."""
    import tempfile

    from hydragnn_tpu.checkpoint import AsyncCheckpointer, save_model

    graphs = _dataset(seed=0)
    loader = _loader(graphs)
    d = _driver(loader, hidden=128, layers=3)  # big enough to serialize measurably
    variables = {"params": d.state.params, "batch_stats": d.state.batch_stats}
    sync_s, async_s = [], []
    with tempfile.TemporaryDirectory() as tmp:
        path = tmp + "/"
        for i in range(reps):
            t0 = time.perf_counter()
            save_model(variables, d.state.opt_state, "sync", path=path,
                       meta={"epoch": i})
            sync_s.append(time.perf_counter() - t0)
        ac = AsyncCheckpointer()
        for i in range(reps):
            ac.wait()  # measure the save() stall alone, not the prior write
            async_s.append(
                ac.save(variables, d.state.opt_state, "async", path=path,
                        meta={"epoch": i})
            )
        ac.close()
        identical = (
            open(os.path.join(tmp, "sync", "sync.pk"), "rb").read()
            == open(os.path.join(tmp, "async", "async.pk"), "rb").read()
        )
    return {
        "sync_ms": round(min(sync_s) * 1e3, 3),
        "async_ms": round(min(async_s) * 1e3, 3),
        "payload_bit_identical": identical,
    }


def _supervisor_drill(kill_step: int = 2, num_epoch: int = 4) -> dict:
    """kill@K under run_training(supervise=True): the child dies by SIGKILL
    mid-run, the supervisor restarts it, Training.resume picks up the last
    periodic checkpoint, and the run completes with restart metadata. The
    drill config feeds ONE train batch per epoch (24 samples, batch 32), so
    kill@2 fires in epoch 2 — after the epoch-1 and epoch-2 checkpoints."""
    import subprocess
    import sys as _sys
    import tempfile

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with tempfile.TemporaryDirectory() as tmp:
        # Subprocess so the drill controls cwd/env without mutating ours.
        script = f"""
import json, os, sys
os.chdir({tmp!r})
os.environ["SERIALIZED_DATA_PATH"] = {tmp!r}
os.environ["HYDRAGNN_FAULTS"] = "kill@{kill_step}"
sys.path.insert(0, {repo!r})
sys.path.insert(0, os.path.join({repo!r}, "tests"))
import jax
jax.config.update("jax_platforms", "cpu")
from deterministic_graph_data import deterministic_graph_data
import hydragnn_tpu
with open(os.path.join({repo!r}, "tests/inputs/ci.json")) as f:
    config = json.load(f)
config["Visualization"] = {{"create_plots": False}}
tr = config["NeuralNetwork"]["Training"]
tr["num_epoch"] = {num_epoch}
tr["periodic_checkpoint_every"] = 1
for split, cnt in {{"train": 24, "test": 8, "validate": 8}}.items():
    p = f"dataset/unit_test_singlehead_{{split}}"
    os.makedirs(p, exist_ok=True)
    deterministic_graph_data(p, number_configurations=cnt)
    config["Dataset"]["path"][split] = p
meta = hydragnn_tpu.run_training(config, supervise=True, max_restarts=2)
print("SUPERVISOR_META " + json.dumps(meta))
"""
        proc = subprocess.run(
            [_sys.executable, "-c", script],
            capture_output=True,
            text=True,
            timeout=900,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        line = next(
            (
                l
                for l in proc.stdout.splitlines()
                if l.startswith("SUPERVISOR_META ")
            ),
            None,
        )
        if line is None:
            return {
                "survived": False,
                "mechanism": "supervised_restart",
                "error": (proc.stderr or proc.stdout)[-400:],
            }
        meta = json.loads(line[len("SUPERVISOR_META ") :])
        return {
            "survived": bool(meta.get("completed"))
            and meta.get("restarts", 0) >= 1,
            "mechanism": "supervised_restart",
            "restarts": meta.get("restarts"),
            "attempts": len(meta.get("attempts", [])),
        }


def _flywheel_promote_rollback_drill() -> dict:
    """Fast promote-and-rollback smoke for the continuous-learning flywheel
    (CI ``--flywheel`` subset; the full gauntlet lives in
    benchmarks/flywheel_soak.py). One replica, one genuine candidate
    auto-promoted through the shadow gate, one wrecked candidate refused
    and quarantined, then an operator ``rollback()`` restoring the
    pre-flywheel live — all against the real registry/router/engine
    stack, no subprocesses."""
    import glob
    import tempfile

    from benchmarks.serve_load import (
        _host_variables,
        _perturb,
        _swap_fixture,
        build_serving_engine,
    )
    from hydragnn_tpu.checkpoint.io import save_model
    from hydragnn_tpu.flywheel import Flywheel, FlywheelConfig
    from hydragnn_tpu.lifecycle import LifecycleManager
    from hydragnn_tpu.route import InProcessReplica, Router

    with tempfile.TemporaryDirectory() as tmp:
        registry, engines, graphs, run_dir, vars0 = _swap_fixture(
            tmp, n_replicas=1
        )
        engine = engines[0]
        shadow, _ = build_serving_engine(model_version="shadow")
        router = Router(
            [InProcessReplica("fw-smoke", engine)],
            health_interval_s=0.1,
            jitter_seed=0,
        )
        fly = None
        try:
            initial = registry.live.short
            manager = LifecycleManager(registry, [engine], router=router)
            fly = Flywheel(
                registry,
                manager,
                router,
                shadow,
                [(g.num_nodes, g.num_edges, 1) for g in graphs],
                config=FlywheelConfig(
                    shadow_fraction=1.0,
                    shadow_tolerance=0.5,
                    shadow_min_samples=2,
                    gate_window_s=0.0,
                    gate_patience_s=60.0,
                    refit_interval_s=3600.0,
                ),
                run_dir=run_dir,
            )
            fly.attach()

            def drive(want_state):
                state = None
                for i in range(128):
                    router.predict(
                        [graphs[i % len(graphs)]], request_id=f"fw-{i}"
                    )
                    state = fly.tick()["weights"].get("state")
                    if state == want_state:
                        return True
                return state == want_state

            # Genuine candidate (diff ~1e-2, an order under the 0.5 bound):
            # the gate must go green and auto-promote.
            save_model(
                _perturb(vars0, 1e-3, seed=21), None, registry.name,
                path=tmp, meta={"epoch": 1}, keep_last_k=3,
            )
            promoted = drive("promoted")
            live_after_promote = registry.live.short
            # Wrecked candidate (diff orders above the bound): refused and
            # quarantined, live untouched.
            save_model(
                _perturb(vars0, 5.0, seed=22), None, registry.name,
                path=tmp, meta={"epoch": 2}, keep_last_k=3,
            )
            rejected = drive("rejected")
            live_after_reject = registry.live.short
            dumps = glob.glob(
                os.path.join(run_dir, "flightrec_*_flywheel_reject.json")
            )
            quarantined = glob.glob(os.path.join(run_dir, "quarantine", "*"))
            # Operator rollback: previous (= the pre-flywheel live) returns.
            manager.rollback()
            counters = fly.report()["counters"]
            survived = (
                promoted
                and rejected
                and live_after_promote != initial
                and live_after_reject == live_after_promote
                and registry.live.short == initial
                and counters["promotions"] == 1
                and counters["rejections"] == 1
                and len(dumps) >= 1
                and len(quarantined) >= 1
            )
            return {
                "survived": bool(survived),
                "mechanism": "shadow_gate",
                "initial": initial,
                "promoted_to": live_after_promote,
                "live_after_reject": live_after_reject,
                "live_after_rollback": registry.live.short,
                "reject_flight_dumps": len(dumps),
                "quarantined": len(quarantined),
                "counters": counters,
            }
        finally:
            if fly is not None:
                fly.stop()
            router.close()
            engine.close()
            shadow.close()


def run_fault_drills(include_supervisor: bool = True, only: "str | None" = None) -> dict:
    from hydragnn_tpu.faults import FaultCounters, FaultPlan

    FaultCounters.reset()
    if only == "flywheel":
        # The CI smoke (static-analysis workflow --flywheel): one in-process
        # promote-and-rollback pass through the real shadow gate — no soak,
        # no subprocess kills (benchmarks/flywheel_soak.py owns those).
        drills = {
            "flywheel_promote_rollback": _flywheel_promote_rollback_drill(),
        }
        passed = sum(1 for v in drills.values() if v["survived"])
        return {
            "metric": "fault_drills",
            "value": round(passed / len(drills), 4),
            "unit": "drills_passed_frac",
            "subset": "flywheel",
            "drills_passed": passed,
            "drills_total": len(drills),
            "drills": drills,
            "counters": FaultCounters.snapshot(),
        }
    if only == "checkpoint":
        # The CI subset (static-analysis workflow): the two local checkpoint
        # drills plus the stall/byte-identity split — no subprocess
        # supervisor runs, no guard-overhead windows. Byte identity GATES
        # the subset: an async/sync payload divergence must fail CI here,
        # not only in tier-1.
        stall = _ckpt_save_stall()
        drills = {
            "corrupt_ckpt_fallback": _ckpt_fallback_drill("corrupt_ckpt"),
            "truncate_ckpt_fallback": _ckpt_fallback_drill("truncate_ckpt"),
            "async_sync_byte_identity": {
                "survived": bool(stall["payload_bit_identical"]),
                "mechanism": "single_serializer",
                **stall,
            },
        }
        passed = sum(1 for v in drills.values() if v["survived"])
        return {
            "metric": "fault_drills",
            "value": round(passed / len(drills), 4),
            "unit": "drills_passed_frac",
            "subset": "checkpoint",
            "drills_passed": passed,
            "drills_total": len(drills),
            "drills": drills,
            "ckpt_save_stall_ms": stall,
            "counters": FaultCounters.snapshot(),
        }
    graphs = _dataset(seed=0)
    drills = {}

    # ---- clean reference (guard off) -------------------------------------
    clean_loader = _loader(list(graphs))
    clean = _driver(clean_loader)
    clean_loss = _train(clean, clean_loader)

    # ---- guard on, no faults: bit-inert ----------------------------------
    inert_loader = _loader(list(graphs))
    inert = _driver(inert_loader, fault_tolerance={"enabled": True})
    inert_loss = _train(inert, inert_loader)
    guard_bit_inert = (inert_loss == clean_loss) and _params_equal(clean, inert)

    # ---- nan_grad: guard skips the poisoned step -------------------------
    loader = _loader(list(graphs))
    d = _driver(
        loader,
        fault_tolerance={"enabled": True, "max_bad_steps": 8},
        fault_plan=FaultPlan("nan_grad@3"),
    )
    loss = _train(d, loader)
    drills["nan_grad_skip"] = {
        "survived": _in_ballpark(loss, clean_loss)
        and _params_finite(d)
        and d.guard.bad_steps == 1,
        "mechanism": "guard_skip",
        "bad_steps": d.guard.bad_steps,
        "final_loss": round(float(loss), 6),
    }

    # ---- nan_grad burst: rollback to last-good + LR backoff --------------
    loader = _loader(list(graphs))
    d = _driver(
        loader,
        fault_tolerance={"enabled": True, "max_bad_steps": 2, "lr_backoff": 0.5},
        fault_plan=FaultPlan("nan_grad@6-11"),
    )
    loss = _train(d, loader)
    drills["nan_grad_rollback"] = {
        "survived": _in_ballpark(loss, clean_loss)
        and _params_finite(d)
        and d.guard.rollbacks >= 1,
        "mechanism": "rollback",
        "rollbacks": d.guard.rollbacks,
        "final_loss": round(float(loss), 6),
    }

    # ---- corrupt samples: quarantined at loader construction -------------
    loader = _loader(
        list(graphs),
        skip_budget=4,
        fault_plan=FaultPlan("seed=3,corrupt_sample:count=2"),
    )
    d = _driver(loader)
    loss = _train(d, loader)
    drills["corrupt_sample_quarantine"] = {
        "survived": _in_ballpark(loss, clean_loss)
        and len(loader.quarantined) == 2,
        "mechanism": "quarantine",
        "quarantined": len(loader.quarantined),
        "final_loss": round(float(loss), 6),
    }

    # ---- slow host collate: pipeline absorbs the stall -------------------
    loader = _loader(list(graphs))
    d = _driver(loader, fault_plan=FaultPlan("slow_collate@2:ms=30"))
    loss = _train(d, loader)
    drills["slow_collate"] = {
        "survived": loss == clean_loss,  # a stall must not change results
        "mechanism": "async_pipeline",
        "final_loss": round(float(loss), 6),
    }

    # ---- transient transfer crash: retried with backoff ------------------
    loader = _loader(list(graphs))
    d = _driver(loader, fault_plan=FaultPlan("transfer_crash@0"))
    loss = _train(d, loader)
    drills["transfer_crash_retry"] = {
        "survived": loss == clean_loss
        and FaultCounters.get("transfer_retries") >= 1,
        "mechanism": "transfer_retry",
        "retries": FaultCounters.get("transfer_retries"),
        "final_loss": round(float(loss), 6),
    }

    # ---- checkpoint corruption: verified-load fallback chain -------------
    drills["corrupt_ckpt_fallback"] = _ckpt_fallback_drill("corrupt_ckpt")
    drills["truncate_ckpt_fallback"] = _ckpt_fallback_drill("truncate_ckpt")

    # ---- process kill: supervised restart + crash resume -----------------
    if include_supervisor:
        drills["kill_supervised_restart"] = _supervisor_drill()
        # kill@save + corrupt_ckpt end to end: restart resumes THROUGH the
        # fallback chain (docs/CHECKPOINTING.md "Fallback semantics").
        drills["kill_at_save_ckpt_fallback"] = _ckpt_kill_save_drill()

    # Async/sync payload byte identity gates the matrix like any drill.
    stall = _ckpt_save_stall()
    drills["async_sync_byte_identity"] = {
        "survived": bool(stall["payload_bit_identical"]),
        "mechanism": "single_serializer",
        **stall,
    }

    overhead_pct, times = _guard_overhead_pct()
    passed = sum(1 for v in drills.values() if v["survived"])
    return {
        "metric": "fault_drills",
        "value": round(passed / len(drills), 4),
        "unit": "drills_passed_frac",
        "drills_passed": passed,
        "drills_total": len(drills),
        "drills": drills,
        "guard_bit_inert": guard_bit_inert,
        "guard_overhead_pct": overhead_pct,
        "guard_epoch_s": {k: round(v, 5) for k, v in times.items()},
        "ckpt_save_stall_ms": stall,
        "clean_final_loss": round(float(clean_loss), 6),
        "counters": FaultCounters.snapshot(),
    }


if __name__ == "__main__":
    only = (
        "checkpoint"
        if "--checkpoint" in sys.argv
        else "flywheel" if "--flywheel" in sys.argv else None
    )
    result = run_fault_drills(
        include_supervisor="--no-supervisor" not in sys.argv, only=only
    )
    print(json.dumps(result))
    sys.exit(0 if result["value"] == 1.0 else 1)
