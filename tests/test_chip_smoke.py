"""chip_smoke.py (repo root) — what the CPU can check of it: it refuses
anything but a TPU, it refuses to run away from the repo (both tier-1), and
its explicit CPU rehearsal drives every stage end to end at a tiny size
(`slow`: five JAX processes, 130 s here; `pytest tests/test_chip_smoke.py`
runs it). What it proves about the chip, only a chip run shows."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd, env=None, timeout=900):
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=env or dict(os.environ),
        capture_output=True, text=True, timeout=timeout,
    )


def _result_lines(stdout):
    return [ln for ln in stdout.splitlines() if ln.startswith('{"ok"')]


@pytest.mark.mpi_skip
def pytest_chip_smoke_refuses_a_cpu_without_the_rehearsal_argument(tmp_path):
    """JAX finds no accelerator here (the session pins the CPU): non-zero
    exit, no result line, and nothing generated — the probe comes first."""
    out = _run([SMOKE, "--out", str(tmp_path / "out")], cwd=REPO, timeout=300)
    assert out.returncode != 0, out.stdout[-2000:]
    assert not _result_lines(out.stdout)
    assert "platform is 'cpu', not 'tpu'" in out.stdout + out.stderr
    assert not os.path.exists(tmp_path / "out" / "dataset")


def pytest_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    out = _run(["chip_smoke.py", "--rehearse-on-cpu"], cwd=tmp_path, timeout=60)
    assert out.returncode != 0
    assert not _result_lines(out.stdout)
    assert "needs the repo around it" in out.stderr


@pytest.mark.mpi_skip
@pytest.mark.slow
def pytest_chip_smoke_rehearsal_passes_end_to_end(tmp_path):
    """Every stage — device probe, train + predict, serve over HTTP, every
    aggregation arm + bf16, warm start — at the tiny size, on the CPU, with
    JAX's cache where JAX_COMPILATION_CACHE_DIR says (so the program sets
    none) and switched back on for the children (conftest turns it off)."""
    env = dict(os.environ)
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    out = _run(
        [SMOKE, "--rehearse-on-cpu", "--out", str(tmp_path / "out")],
        cwd=REPO, env=env,
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert "REHEARSAL on the CPU" in out.stdout
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}
    }
    with open(tmp_path / "out" / "summary.json") as f:
        summary = json.load(f)
    assert {"device", "train", "serve", "kernels", "warm"} <= set(summary)
    assert summary["train"]["xla_compiles_per_epoch"][1:] == [0, 0]
    assert set(summary["kernels"]["arms"]) == {
        "sorted", "csr", "scatter_sorted", "extrema_scan",
    }
    assert summary["kernels"]["arms"]["scatter_sorted"]["ok"]
    scan = summary["kernels"]["arms"]["extrema_scan"]
    assert scan["bit_equal"] and scan["grad_bit_equal"] and scan["ok"]
    assert summary["warm"]["warm"]["persistent_cache_hits"] > 0
    assert summary["warm"]["warm"]["cache_dir"] == str(tmp_path / "jax_cache")
