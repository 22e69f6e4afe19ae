"""2-worker data-parallel integration tests — the TPU-native analog of the
reference CI's ``mpirun -n 2`` distributed pass (/root/reference/.github/
workflows/CI.yml:47-52), in TWO arms since graftmesh (docs/DISTRIBUTED.md):

* LOOPBACK (REAL, tier-1): two logical workers on the in-process harness
  (hydragnn_tpu/parallel/loopback.py) — per-rank loader shards, host
  rendezvous, ONE shard_map DP step over a real 2-device virtual mesh, psum
  gradient all-reduce — and every worker must report the same
  globally-reduced loss. This arm runs on every backend; it replaced the
  precise skip the 2-process path carried since PR 10.
* SPAWN (the genuinely-multiprocess rendezvous arm): two OS processes
  rendezvous through jax.distributed and train over the global mesh. On
  backends without cross-process collectives (XLA:CPU raises "Multiprocess
  computations aren't implemented") this arm keeps its PRECISE skip — the
  capability is the backend's, not ours; the loopback arm carries the
  distributed coverage there. Its tests are tests/test_multiprocess_spawn.py
  (a file of their own: ``--dist loadfile`` gives a file to ONE worker, and
  the arm's three epochs in two fresh processes are the longest test here);
  what both arms share stays in this module."""

import json
import os
import socket
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tests.deterministic_graph_data import deterministic_graph_data

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The backend's own "this platform has no multiprocess collectives" error
# (XLA:CPU raises it at the first cross-process psum). When a worker dies
# with exactly this, the 2-process test is environmentally impossible — a
# PRECISE skip, not a failure: nothing in the repo is broken, the backend
# lacks the capability (ROADMAP item 5 is the portable-collectives fix).
_NO_MULTIPROCESS_MARKER = "Multiprocess computations aren't implemented"


def _skip_if_backend_lacks_multiprocess(outs):
    for out in outs:
        if _NO_MULTIPROCESS_MARKER in out:
            import jax

            pytest.skip(
                "2-process rendezvous is environmentally dead: the "
                f"{jax.default_backend()} backend reports "
                f"{_NO_MULTIPROCESS_MARKER!r} — multi-process DP needs a "
                "backend with cross-process collectives (ROADMAP item 5)"
            )


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _make_split_datasets(config, tmp_path, counts):
    """Point each config split at a freshly generated dataset under tmp_path."""
    for split in list(config["Dataset"]["path"]):
        p = str(tmp_path / f"dataset/unit_test_singlehead_{split}")
        config["Dataset"]["path"][split] = p
        os.makedirs(p, exist_ok=True)
        deterministic_graph_data(p, number_configurations=counts[split])


def _launch_two_process(config, tmp_path, extra_env=None, timeout=270):
    """Write config, spawn 2 rendezvousing workers, return their outputs.
    ``timeout`` stays under the test's own limit (tests/conftest.py), so
    that the workers are killed and the test fails with a reason of its own."""
    config_path = str(tmp_path / "config.json")
    with open(config_path, "w") as f:
        json.dump(config, f)

    port = _free_port()
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.update(
            OMPI_COMM_WORLD_SIZE="2",
            OMPI_COMM_WORLD_RANK=str(rank),
            MASTER_ADDR="127.0.0.1",
            MASTER_PORT=str(port),
            HYDRAGNN_REPO=REPO,
            HYDRAGNN_WORLD_SIZE="1",  # workers run scripts, not pytest
            SERIALIZED_DATA_PATH=str(tmp_path),
        )
        env.update(extra_env or {})
        procs.append(
            subprocess.Popen(
                [sys.executable, os.path.join(REPO, "tests/mp_train_worker.py"),
                 config_path],
                env=env, cwd=str(tmp_path),
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            )
        )

    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("2-process training timed out")
        outs.append(out)
    if any(p.returncode != 0 for p in procs):
        _skip_if_backend_lacks_multiprocess(outs)
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out[-3000:]}"
    return outs


@pytest.mark.mpi_skip
def pytest_two_worker_loopback_dp_training(tmp_path, monkeypatch):
    """REAL 2-worker DP e2e on the loopback harness (no skip): per-rank
    loader shards, host rendezvous, shard_map step over a 2-device virtual
    mesh — the assertions the env-dead spawn test carried: every worker
    reports the SAME psum-reduced loss, and training makes progress."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 (virtual) devices")
    from hydragnn_tpu.parallel import loopback_train

    with open(os.path.join(REPO, "tests/inputs/ci.json")) as f:
        config = json.load(f)
    config["NeuralNetwork"]["Training"]["num_epoch"] = 2
    config["Visualization"] = {"create_plots": False}
    _make_split_datasets(
        config, tmp_path, {"train": 32, "test": 8, "validate": 8}
    )
    monkeypatch.setenv("SERIALIZED_DATA_PATH", str(tmp_path))
    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        results = loopback_train(config, world_size=2)
    finally:
        os.chdir(cwd)
    assert [r["rank"] for r in results] == [0, 1]
    # Metrics are globally psum-reduced: every worker reports the SAME loss.
    assert results[0]["final_loss"] == results[1]["final_loss"], results
    for r in results:
        hist = r["history"]["total_loss_train"]
        assert all(float(x) == float(x) for x in hist)  # finite
        assert hist[-1] < hist[0], hist
        assert r["mesh"] == "data:2xgraph:1"


@pytest.mark.mpi_skip
def pytest_two_worker_loopback_overlap_arm_agrees(tmp_path, monkeypatch):
    """The bucketed overlapped all-reduce rides the SAME loopback e2e and
    lands within fp32 trajectory noise of the single-psum arm — the
    end-to-end twin of test_graftmesh's step-level allclose gate."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 (virtual) devices")
    from hydragnn_tpu.parallel import loopback_train

    with open(os.path.join(REPO, "tests/inputs/ci.json")) as f:
        config = json.load(f)
    config["NeuralNetwork"]["Training"]["num_epoch"] = 1
    config["Visualization"] = {"create_plots": False}
    _make_split_datasets(
        config, tmp_path, {"train": 24, "test": 8, "validate": 8}
    )
    monkeypatch.setenv("SERIALIZED_DATA_PATH", str(tmp_path))
    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        single = loopback_train(config, world_size=2, grad_sync="single")
        bucketed = loopback_train(config, world_size=2, grad_sync="bucketed")
    finally:
        os.chdir(cwd)
    assert bucketed[0]["final_loss"] == bucketed[1]["final_loss"]
    assert single[0]["final_loss"] == pytest.approx(
        bucketed[0]["final_loss"], rel=1e-4
    )
