"""End-to-end convergence tests — the backbone of the suite (reference
/root/reference/tests/test_graphs.py:21-196): train each conv family on the
synthetic deterministic dataset through the full high-level API
(run_training → run_prediction), then assert the SAME accuracy thresholds the
reference CI enforces (BASELINE.md).

This module holds what the matrix shares (the thresholds, the training cell,
the dataset generator) and no test of its own: the 15 cases live in one file
a conv family (``tests/test_graphs_<family>.py``; PNA's four in two), because
``--dist loadfile`` gives a file to ONE worker and starts the files with the
fewest tests last. Cases that share a log name (a family's ``ci.json`` with
and without edge lengths) stay in one file: two workers must never train one
``./logs/<name>`` at once."""

import fcntl
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import hydragnn_tpu
from tests.deterministic_graph_data import deterministic_graph_data

# [head/total RMSE, sample MAE, sample max-abs-error] — reference
# test_graphs.py:124-136.
THRESHOLDS = {
    "SAGE": [0.20, 0.20, 0.75],
    "PNA": [0.20, 0.20, 0.75],
    "MFC": [0.20, 0.20, 1.5],
    "GIN": [0.25, 0.20, 0.75],
    "GAT": [0.60, 0.70, 0.99],
    "CGCNN": [0.50, 0.40, 0.95],
}
THRESHOLDS_LENGTHS = {"CGCNN": [0.15, 0.15, 0.40], "PNA": [0.10, 0.10, 0.40]}
THRESHOLDS_VECTOR = {"PNA": [0.2, 0.15, 0.85]}


def unittest_train_model(model_type, ci_input, use_lengths, overwrite_data=False):
    os.environ["SERIALIZED_DATA_PATH"] = os.getcwd()

    config_file = os.path.join(os.getcwd(), "tests/inputs", ci_input)
    config = load_ci_config(ci_input, model_type)

    # MFC favors graph-level over node-level heads; bump the graph weight down
    # (reference test_graphs.py:63-66).
    if model_type == "MFC" and ci_input == "ci_multihead.json":
        config["NeuralNetwork"]["Architecture"]["task_weights"][0] = 2

    if use_lengths:
        config["NeuralNetwork"]["Architecture"]["edge_features"] = ["lengths"]

    ensure_raw_datasets(config)

    # PNA without lengths exercises the config-file overload of run_training
    # (reference test_graphs.py:109-114).
    if model_type == "PNA" and not use_lengths:
        hydragnn_tpu.run_training(config_file)
    else:
        hydragnn_tpu.run_training(config)

    error, error_rmse_task, true_values, predicted_values = (
        hydragnn_tpu.run_prediction(config)
    )

    thresholds = dict(THRESHOLDS)
    if use_lengths and "vector" not in ci_input:
        thresholds.update(THRESHOLDS_LENGTHS)
    if use_lengths and "vector" in ci_input:
        thresholds.update(THRESHOLDS_VECTOR)

    for ihead in range(len(true_values)):
        error_head_rmse = error_rmse_task[ihead]
        assert (
            error_head_rmse < thresholds[model_type][0]
        ), f"Head RMSE checking failed for {ihead}: {error_head_rmse}"

        head_true = np.asarray(true_values[ihead])
        head_pred = np.asarray(predicted_values[ihead])
        sample_mean_abs_error = np.abs(head_true - head_pred).mean()
        sample_max_abs_error = np.abs(head_true - head_pred).max()
        assert (
            sample_mean_abs_error < thresholds[model_type][1]
        ), f"MAE sample checking failed: {sample_mean_abs_error}"
        assert (
            sample_max_abs_error < thresholds[model_type][2]
        ), f"Max. sample checking failed: {sample_max_abs_error}"

    assert error < thresholds[model_type][0], (
        "Total RMSE checking failed!" + str(error)
    )


def load_ci_config(ci_input, model_type=None):
    """Load a tests/inputs config, set the model family, and substitute the
    serialized pkl fixtures when present (reference test_graphs.py:43-61).
    ONE copy of the '/serialized_dataset/<name><suffix>.pkl' rewrite rule,
    shared by every suite that reuses the CI fixtures."""
    with open(os.path.join(os.getcwd(), "tests/inputs", ci_input)) as f:
        config = json.load(f)
    if model_type is not None:
        config["NeuralNetwork"]["Architecture"]["model_type"] = model_type
    root = os.environ.get("SERIALIZED_DATA_PATH", os.getcwd())
    for dataset_name in list(config["Dataset"]["path"].keys()):
        suffix = "" if dataset_name == "total" else "_" + dataset_name
        pkl_file = (
            root
            + "/serialized_dataset/"
            + config["Dataset"]["name"]
            + suffix
            + ".pkl"
        )
        if os.path.exists(pkl_file):
            config["Dataset"]["path"][dataset_name] = pkl_file
    return config


def ensure_raw_datasets(config, num_samples_tot=500):
    """Generate the deterministic raw text datasets a config points at, if
    missing. Rank 0 generates; other ranks of a multi-process run (the
    mpirun -n 2 CI analog) wait on a sibling sentinel so shared fixture files
    are never written concurrently. World-safe tests outside this file
    (e.g. test_resume_2proc.py) share this helper."""
    pkl_input = list(config["Dataset"]["path"].values())[0].endswith(".pkl")
    if not pkl_input:
        import time as _time

        from hydragnn_tpu.parallel.distributed import init_comm_size_and_rank

        _, world_rank = init_comm_size_and_rank()
        perc_train = config["NeuralNetwork"]["Training"]["perc_train"]
        # Per-launch nonce (MASTER_PORT is shared by all ranks of one launch,
        # unique per launch) so a stale sentinel from an earlier run can't
        # release waiting ranks early.
        run_id = os.environ.get("MASTER_PORT", "serial")
        def _dir_state(path):
            """Fingerprint of the generated dataset: sorted (name, size)
            pairs. A partially written file has a different size, so a match
            means the directory is byte-complete."""
            try:
                entries = sorted(
                    (n, os.path.getsize(os.path.join(path, n)))
                    for n in os.listdir(path)
                )
            except OSError:
                return None
            return repr(entries) if entries else None

        for dataset_name, data_path in config["Dataset"]["path"].items():
            # Sentinels live in the system temp dir, NOT next to the dataset:
            # per-port names accumulated in the tree across 2-proc runs
            # (r03/r04 advisor note). All ranks of one launch share the host,
            # so tempdir + a digest of the dataset path rendezvous the same.
            import hashlib
            import tempfile

            digest = hashlib.md5(
                os.path.abspath(data_path).encode()
            ).hexdigest()[:12]
            sentinel_base = os.path.join(
                tempfile.gettempdir(), f"hydragnn_dataset_{digest}.done"
            )
            sentinel = f"{sentinel_base}.{run_id}"
            if world_rank == 0:
                # Purge this launch's own sentinel plus STALE ones from prior
                # launches (>1h old — a live concurrent launch's sentinel must
                # survive, or its waiting ranks would hang to their timeout).
                # Waiting ranks additionally validate the sentinel CONTENT
                # against the live directory state below, so even a stale
                # sentinel read before this removal cannot release them
                # against an incomplete dataset.
                import glob as _glob

                now = _time.time()
                for old in _glob.glob(f"{sentinel_base}.*"):
                    try:
                        if old == sentinel or now - os.path.getmtime(old) > 3600:
                            os.remove(old)
                    except OSError:
                        pass
                num_samples = {
                    "total": num_samples_tot,
                    "train": int(num_samples_tot * perc_train),
                    "test": int(num_samples_tot * (1 - perc_train) * 0.5),
                    "validate": int(num_samples_tot * (1 - perc_train) * 0.5),
                }[dataset_name]
                os.makedirs(data_path, exist_ok=True)
                # Rank 0 of EVERY xdist worker comes through here, and in a
                # fresh checkout several find the directory empty at once:
                # the count and the generation are one worker's at a time,
                # and the one that waited uses what the first made.
                with open(f"{sentinel_base}.lock", "w") as lock:
                    fcntl.flock(lock, fcntl.LOCK_EX)
                    # One file per configuration: any other count means a
                    # crashed earlier generation left a partial directory —
                    # regenerate rather than fingerprinting incomplete data
                    # as "done".
                    existing = os.listdir(data_path)
                    if len(existing) != num_samples:
                        for name in existing:
                            os.remove(os.path.join(data_path, name))
                        deterministic_graph_data(
                            data_path, number_configurations=num_samples
                        )
                with open(sentinel, "w") as f:
                    f.write(_dir_state(data_path) or "")
            else:
                deadline = _time.time() + 300
                while True:
                    # Release only when the recorded fingerprint matches the
                    # directory RIGHT NOW — a stale sentinel (same port, dir
                    # since cleared/regenerating) cannot match mid-generation.
                    try:
                        with open(sentinel) as f:
                            recorded = f.read()
                    except OSError:
                        recorded = None
                    if recorded and recorded == _dir_state(data_path):
                        break
                    if _time.time() > deadline:
                        raise TimeoutError(f"rank 0 never finished {data_path}")
                    _time.sleep(0.1)
