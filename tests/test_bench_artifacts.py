"""bench.py behaviour that must hold WITHOUT a device: the default invocation
refuses anything but a TPU and fails on any failing phase; the side rigs'
stale-artifact readers."""

import json
import os
import time


def pytest_bench_unknown_device_kind_is_an_error(monkeypatch):
    """A chip that is not in the peaks table is an error, not a null MFU."""
    import jax
    import pytest

    import bench

    class _Dev:
        device_kind = "TPU v99 imaginary"

    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    with pytest.raises(RuntimeError, match="v99 imaginary.*_PEAK_BF16"):
        bench._chip_peak_flops()
    _Dev.device_kind = "TPU v5 lite"
    assert bench._chip_peak_flops() == 197e12


def pytest_bench_default_invocation_refuses_a_cpu(capsys):
    """No chip: non-zero exit, the device named, and no figure of any kind —
    no CPU number, no stale block, no fallback child."""
    import pytest

    import bench

    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code == 1
    doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert doc["platform"] == "cpu" and doc["device_count"] >= 1
    assert "measures the TPU" in doc["error"]
    assert not {
        "value", "vs_baseline", "bucketed_throughput", "mfu",
        "last_known_hardware", "cpu_fallback", "retries",
    } & set(doc)


def pytest_bench_phase_failure_fails_the_run(monkeypatch, capsys):
    """The cached-epoch and wide-model phases used to be
    caught as non-fatal; any of them failing now fails the run."""
    import pytest

    import bench

    tpu = {
        "platform": "tpu", "backend": "tpu",
        "device_kind": "TPU v5 lite", "device_count": 1,
    }
    monkeypatch.setattr(bench, "_device_block", lambda: dict(tpu))
    monkeypatch.setattr(bench, "_chip_peak_flops", lambda: 197e12)
    monkeypatch.setattr(bench, "_peak_workload", lambda: {"value": 1.0})
    monkeypatch.setattr(bench, "_production_workload", lambda: {})

    def boom():
        raise ValueError("cached epoch broke")

    monkeypatch.setattr(bench, "_cached_epoch_workload", boom)
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code == 1
    doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "cached epoch broke" in doc["error"]
    assert "bucketed_cached_error" not in doc


def pytest_last_known_serving_picks_latest_real_measurement(tmp_path):
    from bench import _last_known_serving

    real = {
        "saturation_graphs_per_sec": 1200.0,
        "closed_loop": {"p95_ms": 9.5},
        "recompiles_after_warmup": 0,
        "platform": "cpu",
    }
    (tmp_path / "SERVE_r06.json").write_text(json.dumps(real))
    # A failed --serve round writes no saturation number — never "last known".
    (tmp_path / "SERVE_r07.json").write_text(
        json.dumps({"error": "TimeoutError", "saturation_graphs_per_sec": 0.0})
    )
    now = time.time()
    os.utime(tmp_path / "SERVE_r06.json", (now - 50, now - 50))
    os.utime(tmp_path / "SERVE_r07.json", (now - 10, now - 10))

    blk = _last_known_serving(str(tmp_path))
    assert blk is not None
    assert blk["saturation_graphs_per_sec"] == 1200.0
    assert blk["closed_loop_p95_ms"] == 9.5
    assert blk["provenance"] == "stale"
    assert blk["source_artifact"] == "SERVE_r06.json"


def pytest_last_known_serving_none_when_no_measurements(tmp_path):
    from bench import _last_known_serving

    (tmp_path / "SERVE_bad.json").write_text("{not json")
    assert _last_known_serving(str(tmp_path)) is None


def pytest_last_known_router_picks_latest_real_measurement(tmp_path):
    from bench import _last_known_router

    real = {
        "replicas": 2,
        "open_loop": [
            {"fleet_p99_ms": 12.0, "offered_graphs_per_sec": 25.0},
            {"fleet_p99_ms": 40.1, "offered_graphs_per_sec": 300.0},
        ],
        "kill_replica_drill": {"zero_lost": True},
        "scaleup_drill": {"warm_spinup": {"warmup_xla_compiles": 0}},
        "platform": "cpu",
        "device_kind": "cpu",
    }
    (tmp_path / "ROUTER_r12.json").write_text(json.dumps(real))
    # A failed --router round carries no open-loop sweep — never "last known".
    (tmp_path / "ROUTER_r13.json").write_text(
        json.dumps({"error": "TimeoutError"})
    )
    now = time.time()
    os.utime(tmp_path / "ROUTER_r12.json", (now - 50, now - 50))
    os.utime(tmp_path / "ROUTER_r13.json", (now - 10, now - 10))

    blk = _last_known_router(str(tmp_path))
    assert blk is not None
    assert blk["fleet_p99_ms_at_top_load"] == 40.1
    assert blk["offered_graphs_per_sec_top"] == 300.0
    assert blk["kill_drill_zero_lost"] is True
    assert blk["scaleup_warmup_xla_compiles"] == 0
    assert blk["provenance"] == "stale"
    assert blk["source_artifact"] == "ROUTER_r12.json"


def pytest_last_known_router_none_when_no_measurements(tmp_path):
    from bench import _last_known_router

    (tmp_path / "ROUTER_bad.json").write_text("{not json")
    (tmp_path / "ROUTER_r09.json").write_text(json.dumps({"error": "boom"}))
    assert _last_known_router(str(tmp_path)) is None


def pytest_last_known_swap_picks_latest_real_measurement(tmp_path):
    from bench import _last_known_swap

    real = {
        "drills_total": 4,
        "drills_passed": 4,
        "swap_under_load": {
            "p99_swap_over_steady": 1.32,
            "recompiles_after_swap": 0,
            "zero_version_torn": True,
            "swap_wall_s": 0.008,
        },
        "platform": "cpu",
        "device_kind": "cpu",
    }
    (tmp_path / "SWAP_r13.json").write_text(json.dumps(real))
    # A failed --swap round carries no drill block — never "last known".
    (tmp_path / "SWAP_r14.json").write_text(
        json.dumps({"error": "TimeoutError"})
    )
    now = time.time()
    os.utime(tmp_path / "SWAP_r13.json", (now - 50, now - 50))
    os.utime(tmp_path / "SWAP_r14.json", (now - 10, now - 10))

    blk = _last_known_swap(str(tmp_path))
    assert blk is not None
    assert blk["p99_swap_over_steady"] == 1.32
    assert blk["recompiles_after_swap"] == 0
    assert blk["zero_version_torn"] is True
    assert blk["drills_passed"] == 4
    assert blk["provenance"] == "stale"
    assert blk["source_artifact"] == "SWAP_r13.json"


def pytest_last_known_swap_none_when_no_measurements(tmp_path):
    from bench import _last_known_swap

    (tmp_path / "SWAP_bad.json").write_text("{not json")
    (tmp_path / "SWAP_r09.json").write_text(json.dumps({"error": "boom"}))
    assert _last_known_swap(str(tmp_path)) is None


def pytest_committed_swap_artifact_readable():
    """The committed SWAP_r* round is a valid last-known block with the
    acceptance gates green (zero recompiles, zero torn responses)."""
    from bench import _last_known_swap

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    blk = _last_known_swap(repo)
    assert blk is not None
    assert blk["drills_passed"] == blk["drills_total"]
    assert blk["recompiles_after_swap"] == 0
    assert blk["zero_version_torn"] is True


def pytest_last_known_compile_cache_picks_latest_real_round(tmp_path):
    from bench import _last_known_compile_cache

    real = {
        "metric": "compile_cache_warm_speedup",
        "value": 26.7,
        "unit": "x_cold_vs_warm_warmup_wall",
        "recompiles_after_warmup": 0,
        "bit_exact_warm_vs_cold": True,
        "corrupt_fallback_ok": True,
        "backend": "cpu",
    }
    (tmp_path / "COMPILECACHE_r10.json").write_text(json.dumps(real))
    # A failed --compile-cache round carries value 0.0 — never "last known".
    (tmp_path / "COMPILECACHE_r11.json").write_text(
        json.dumps({"metric": "compile_cache_warm_speedup", "value": 0.0,
                    "error": "TimeoutError"})
    )
    now = time.time()
    os.utime(tmp_path / "COMPILECACHE_r10.json", (now - 50, now - 50))
    os.utime(tmp_path / "COMPILECACHE_r11.json", (now - 10, now - 10))

    blk = _last_known_compile_cache(str(tmp_path))
    assert blk is not None
    assert blk["value"] == 26.7
    assert blk["recompiles_after_warmup"] == 0
    assert blk["provenance"] == "stale"
    assert blk["source_artifact"] == "COMPILECACHE_r10.json"


def pytest_committed_compile_cache_artifact_readable():
    """The committed COMPILECACHE_r* round is a valid last-known block (the
    stale-fallback convention every bench arm follows)."""
    from bench import _last_known_compile_cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    blk = _last_known_compile_cache(repo)
    assert blk is not None
    assert blk["value"] >= 5.0 and blk["bit_exact_warm_vs_cold"] is True


def pytest_last_known_precision_picks_latest_real_round(tmp_path):
    from bench import _last_known_precision

    real = {
        "metric": "precision_ab",
        "value": 1.42,
        "unit": "f32_over_bf16_policy_steady_window_time",
        "timings_meaningful": True,
        "convergence": {"ok": True},
        "serve": {"bf16": {"gate_ok": True}, "int8": {"gate_ok": True}},
        "backend": "tpu",
    }
    (tmp_path / "PRECISION_r11.json").write_text(json.dumps(real))
    # A failed --precision round carries value 0.0 — never "last known".
    (tmp_path / "PRECISION_r12.json").write_text(
        json.dumps({"metric": "precision_ab", "value": 0.0,
                    "error": "TimeoutError"})
    )
    now = time.time()
    os.utime(tmp_path / "PRECISION_r11.json", (now - 50, now - 50))
    os.utime(tmp_path / "PRECISION_r12.json", (now - 10, now - 10))

    blk = _last_known_precision(str(tmp_path))
    assert blk is not None
    assert blk["value"] == 1.42
    assert blk["convergence_ok"] is True
    assert blk["serve_arms_ok"] is True
    assert blk["provenance"] == "stale"
    assert blk["source_artifact"] == "PRECISION_r11.json"


def pytest_committed_precision_artifact_readable():
    """The committed PRECISION_r* round is a valid last-known block with the
    acceptance gates green (step-matched convergence, quantized serve)."""
    from bench import _last_known_precision

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    blk = _last_known_precision(repo)
    assert blk is not None
    assert blk["convergence_ok"] is True
    assert blk["serve_arms_ok"] is True


def pytest_last_known_multichip_picks_latest_real_measurement(tmp_path):
    from bench import _last_known_multichip

    real = {
        "metric": "multichip_overlap_ab",
        "value": 1.08,
        "unit": "x_single_psum_vs_bucketed_step",
        "devices": 8,
        "overlap_fraction": {"bucketed": 0.41, "ring": 0.33},
        "grads_allclose_ok": True,
        "timings_meaningful": False,
        "backend": "cpu",
    }
    (tmp_path / "MULTICHIP_r14.json").write_text(json.dumps(real))
    # Pre-graftmesh dry-run smokes have no metric field — never "last known".
    (tmp_path / "MULTICHIP_r05.json").write_text(
        json.dumps({"n_devices": 8, "rc": 0, "ok": True})
    )
    # A failed round carries value 0.0 — also never "last known".
    (tmp_path / "MULTICHIP_r15.json").write_text(
        json.dumps({"metric": "multichip_overlap_ab", "value": 0.0})
    )
    now = time.time()
    os.utime(tmp_path / "MULTICHIP_r14.json", (now - 50, now - 50))
    os.utime(tmp_path / "MULTICHIP_r05.json", (now - 10, now - 10))
    os.utime(tmp_path / "MULTICHIP_r15.json", (now - 5, now - 5))

    blk = _last_known_multichip(str(tmp_path))
    assert blk is not None
    assert blk["value"] == 1.08
    assert blk["overlap_fraction"]["bucketed"] == 0.41
    assert blk["grads_allclose_ok"] is True
    assert blk["provenance"] == "stale"
    assert blk["source_artifact"] == "MULTICHIP_r14.json"


def pytest_last_known_multichip_none_when_no_measurements(tmp_path):
    from bench import _last_known_multichip

    (tmp_path / "MULTICHIP_bad.json").write_text("{not json")
    (tmp_path / "MULTICHIP_r05.json").write_text(
        json.dumps({"n_devices": 8, "ok": True})
    )
    assert _last_known_multichip(str(tmp_path)) is None


def pytest_committed_multichip_artifact_readable():
    """The committed MULTICHIP_r* round is a valid last-known block with the
    acceptance gates green (cross-arm grads allclose, overlap fraction
    measured, CPU rounds labeled non-meaningful)."""
    from bench import _last_known_multichip

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    blk = _last_known_multichip(repo)
    assert blk is not None
    assert blk["grads_allclose_ok"] is True
    assert blk["overlap_fraction"]["bucketed"] is not None
    if blk["backend"] == "cpu":
        assert blk["timings_meaningful"] is False


def pytest_last_known_elastic_picks_latest_real_measurement(tmp_path):
    from bench import _last_known_elastic

    real = {
        "metric": "elastic_drills",
        "value": 4.0,
        "unit": "drills_passed",
        "drills_passed": 4,
        "drills_total": 4,
        "convergence_parity": {"ok": True},
        "warm_restart": {"ok": True},
        "backend": "cpu",
    }
    (tmp_path / "ELASTIC_r15.json").write_text(json.dumps(real))
    # A failed round carries drills_passed 0 — never "last known".
    (tmp_path / "ELASTIC_r16.json").write_text(
        json.dumps({"metric": "elastic_drills", "value": 0.0, "drills_passed": 0})
    )
    now = time.time()
    os.utime(tmp_path / "ELASTIC_r15.json", (now - 50, now - 50))
    os.utime(tmp_path / "ELASTIC_r16.json", (now - 5, now - 5))

    blk = _last_known_elastic(str(tmp_path))
    assert blk is not None
    assert blk["drills_passed"] == 4
    assert blk["convergence_parity_ok"] is True
    assert blk["warm_restart_ok"] is True
    assert blk["provenance"] == "stale"
    assert blk["source_artifact"] == "ELASTIC_r15.json"


def pytest_last_known_elastic_none_when_no_measurements(tmp_path):
    from bench import _last_known_elastic

    (tmp_path / "ELASTIC_bad.json").write_text("{not json")
    (tmp_path / "ELASTIC_r09.json").write_text(
        json.dumps({"ok": True, "value": 1.0})  # no metric field
    )
    assert _last_known_elastic(str(tmp_path)) is None


def pytest_committed_elastic_artifact_readable():
    """The committed ELASTIC_r* round is a valid last-known block with all
    four drills green plus the parity and warm-restart gates."""
    from bench import _last_known_elastic

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    blk = _last_known_elastic(repo)
    assert blk is not None
    assert blk["drills_passed"] == blk["drills_total"] == 4
    assert blk["convergence_parity_ok"] is True
    assert blk["warm_restart_ok"] is True


def pytest_last_known_stream_picks_latest_real_measurement(tmp_path):
    from bench import _last_known_stream

    real = {
        "metric": "stream_ab",
        "value": 2776.1,
        "unit": "batch_infer_graphs_per_sec",
        "ok": True,
        "train_ab": {
            "params_bit_exact": True,
            "streamed_over_inmemory_wall": 1.02,
        },
        "drills_passed": 2,
        "drills_total": 2,
        "backend": "cpu",
    }
    (tmp_path / "STREAM_r06.json").write_text(json.dumps(real))
    # A failed round (ok false) is never "last known".
    (tmp_path / "STREAM_r07.json").write_text(
        json.dumps({"metric": "stream_ab", "value": 0.0, "ok": False})
    )
    now = time.time()
    os.utime(tmp_path / "STREAM_r06.json", (now - 50, now - 50))
    os.utime(tmp_path / "STREAM_r07.json", (now - 5, now - 5))

    blk = _last_known_stream(str(tmp_path))
    assert blk is not None
    assert blk["value"] == 2776.1
    assert blk["params_bit_exact"] is True
    assert blk["streamed_over_inmemory_wall"] == 1.02
    assert blk["drills_passed"] == 2
    assert blk["provenance"] == "stale"
    assert blk["source_artifact"] == "STREAM_r06.json"


def pytest_last_known_stream_none_when_no_measurements(tmp_path):
    from bench import _last_known_stream

    (tmp_path / "STREAM_bad.json").write_text("{not json")
    (tmp_path / "STREAM_r05.json").write_text(
        json.dumps({"ok": True, "value": 1.0})  # no metric field
    )
    assert _last_known_stream(str(tmp_path)) is None


def pytest_committed_stream_artifact_readable():
    """The committed STREAM_r* round is a valid last-known block: bit-exact
    A/B, wall ratio recorded, both drills green."""
    from bench import _last_known_stream

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    blk = _last_known_stream(repo)
    assert blk is not None
    assert blk["params_bit_exact"] is True
    assert blk["streamed_over_inmemory_wall"] is not None
    assert blk["drills_passed"] == blk["drills_total"] == 2


def pytest_last_known_flywheel_picks_latest_real_measurement(tmp_path):
    from bench import _last_known_flywheel

    real = {
        "drills_total": 2,
        "drills_passed": 2,
        "soak": {
            "counters": {"promotions": 2, "rejections": 1},
            "poisoned_never_served": True,
            "recompiles_after_warmup": 0,
            "lost_total": 0,
            "zero_version_torn": True,
        },
        "platform": "cpu",
        "device_kind": "cpu",
    }
    (tmp_path / "FLYWHEEL_r17.json").write_text(json.dumps(real))
    # A failed --flywheel round carries no soak block — never "last known".
    (tmp_path / "FLYWHEEL_r18.json").write_text(
        json.dumps({"error": "TimeoutError"})
    )
    now = time.time()
    os.utime(tmp_path / "FLYWHEEL_r17.json", (now - 50, now - 50))
    os.utime(tmp_path / "FLYWHEEL_r18.json", (now - 10, now - 10))

    blk = _last_known_flywheel(str(tmp_path))
    assert blk is not None
    assert blk["promotions"] == 2
    assert blk["rejections"] == 1
    assert blk["poisoned_never_served"] is True
    assert blk["recompiles_after_warmup"] == 0
    assert blk["lost_total"] == 0
    assert blk["provenance"] == "stale"
    assert blk["source_artifact"] == "FLYWHEEL_r17.json"


def pytest_last_known_flywheel_none_when_no_measurements(tmp_path):
    from bench import _last_known_flywheel

    (tmp_path / "FLYWHEEL_bad.json").write_text("{not json")
    (tmp_path / "FLYWHEEL_r09.json").write_text(json.dumps({"error": "boom"}))
    assert _last_known_flywheel(str(tmp_path)) is None


def pytest_committed_flywheel_artifact_readable():
    """The committed FLYWHEEL_r* round is a valid last-known block with the
    acceptance gates green: >=2 auto-promotions, the poisoned candidate
    refused without serving, zero lost accepted requests, zero torn
    versions, zero recompiles after warm-up."""
    from bench import _last_known_flywheel

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    blk = _last_known_flywheel(repo)
    assert blk is not None
    assert blk["drills_passed"] == blk["drills_total"]
    assert blk["promotions"] >= 2
    assert blk["rejections"] == 1
    assert blk["poisoned_never_served"] is True
    assert blk["recompiles_after_warmup"] == 0
    assert blk["lost_total"] == 0
    assert blk["zero_version_torn"] is True
