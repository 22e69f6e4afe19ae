"""The SPAWN arm of the 2-worker data-parallel tests (tests/test_multiprocess.py
has both arms' story and what they share): two OS processes rendezvous
through jax.distributed and train over the global mesh. On a backend without
cross-process collectives the arm keeps its PRECISE skip."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tests.test_multiprocess import (  # noqa: E402
    REPO, _launch_two_process, _make_split_datasets,
)


@pytest.mark.mpi_skip
def pytest_two_process_rendezvous_arm(tmp_path):
    """The genuinely-multiprocess arm: two OS processes rendezvous through
    jax.distributed and train over the global mesh. Keeps its PRECISE skip
    on backends without cross-process collectives (the loopback tests above
    carry the distributed coverage there); on capable backends the old
    assertions apply unchanged."""
    with open(os.path.join(REPO, "tests/inputs/ci.json")) as f:
        config = json.load(f)
    config["NeuralNetwork"]["Training"]["num_epoch"] = 3
    config["Visualization"] = {"create_plots": False}
    _make_split_datasets(
        config, tmp_path, {"train": 48, "test": 16, "validate": 16}
    )

    outs = _launch_two_process(config, tmp_path)

    losses = []
    for out in outs:
        lines = [l for l in out.splitlines() if l.startswith("FINAL_LOSS")]
        assert lines, out[-2000:]
        losses.append(float(lines[-1].split()[1]))
    # Metrics are globally psum-reduced: every process must report the SAME loss.
    assert losses[0] == pytest.approx(losses[1], rel=1e-6), losses

    # rank-0-only checkpoint exists
    logdirs = os.listdir(tmp_path / "logs")
    assert any(
        os.path.exists(tmp_path / "logs" / d / (d + ".pk")) for d in logdirs
    )


@pytest.mark.mpi_skip
@pytest.mark.slow
@pytest.mark.time_limit(960)
def pytest_two_process_pna_convergence(tmp_path):
    """Full PNA ci.json convergence under 2 rendezvousing processes with the
    UNCHANGED single-process accuracy thresholds (reference CI runs its whole
    suite via mpirun -n 2, /root/reference/.github/workflows/CI.yml:47-52) —
    thresholds from tests/test_graphs.py THRESHOLDS['PNA']. Spawn arm:
    precise-skips where the backend lacks multiprocess collectives."""
    with open(os.path.join(REPO, "tests/inputs/ci.json")) as f:
        config = json.load(f)
    config["Visualization"] = {"create_plots": False}
    perc_train = config["NeuralNetwork"]["Training"]["perc_train"]
    num_samples_tot = 500
    _make_split_datasets(
        config, tmp_path, {
            "train": int(num_samples_tot * perc_train),
            "test": int(num_samples_tot * (1 - perc_train) * 0.5),
            "validate": int(num_samples_tot * (1 - perc_train) * 0.5),
        },
    )

    outs = _launch_two_process(
        config,
        tmp_path,
        extra_env={"HYDRAGNN_MP_THRESHOLDS": "0.20 0.20 0.75"},
        timeout=900,
    )
    for out in outs:
        assert any(
            l.startswith("CONVERGENCE_OK") for l in out.splitlines()
        ), out[-2000:]
