"""North-star artifact: QM9 free-energy regression with PNA
(BASELINE.json: "node-MAE (QM9 PNA multi-task)"; reference example
/root/reference/examples/qm9/qm9.py:15-44 trains on the PyG QM9 download).

This image has zero network egress, so the published GDB-9 archive cannot be
fetched — section ``download_probe`` records the dated attempt. What CAN be
proven offline is recorded in two runs through the real production pipeline
(load → pre_transform → radius graph → split → loaders → config completion →
PNA → train → evaluate):

- ``real_gdb9_fit``: the genuine dsgdb9nsd_00000{1..5}.xyz records committed
  under tests/fixtures/qm9_raw (published bytes, incl. ``*^`` exponents) —
  proves the real-format path end-to-end: parse, graph-build, train to
  near-zero fit error on real molecules. protocol=fit_only (train==test).
- ``real_gdb9_loo``: leave-one-out over those 5 records — the only honest
  held-out protocol a 5-record corpus admits. protocol=held_out.
- ``synthetic_1000``: the deterministic offline stand-in at example scale —
  held-out example split; the HEADLINE number until egress exists.

Every block carries a ``protocol`` field ("held_out" | "fit_only"); fit-only
blocks emit ``fit_*`` keys, never ``test_*``.

Usage: python benchmarks/qm9_northstar.py [--out QM9_r05.json] [--epochs N]
Runs on the platform --platform names (cpu by default) and records it in the
artifact.
"""

import argparse
import json
import os
import shutil
import sys
import time

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)


def _probe_download(timeout_s: float = 8.0) -> dict:
    """Dated record of whether the published QM9 archive is reachable."""
    import urllib.request

    url = "https://data.pyg.org/datasets/qm9_v3.zip"  # what PyG's QM9 fetches
    t0 = time.time()
    try:
        req = urllib.request.Request(url, method="HEAD")
        with urllib.request.urlopen(req, timeout=timeout_s) as r:
            return {"url": url, "reachable": True, "status": r.status}
    except Exception as e:
        return {
            "url": url,
            "reachable": False,
            "error": f"{type(e).__name__}: {e}"[:200],
            "elapsed_s": round(time.time() - t0, 2),
            "ts_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        }


def _pna_config() -> dict:
    """examples/qm9/qm9.json retargeted to the north-star model family (PNA)."""
    with open(os.path.join(REPO, "examples", "qm9", "qm9.json")) as f:
        config = json.load(f)
    arch = config["NeuralNetwork"]["Architecture"]
    arch["model_type"] = "PNA"
    arch["hidden_dim"] = 16
    arch["num_conv_layers"] = 3
    return config


def _run_pipeline(
    config: dict,
    dataset_root: str,
    num_samples,
    epochs: int,
    lr: float = None,
    full_batch: bool = False,
    loo_index: int = None,
) -> dict:
    import numpy as np

    import hydragnn_tpu as hydragnn
    from hydragnn_tpu.datasets.qm9 import PROPERTY_INDEX

    var_config = config["NeuralNetwork"]["Variables_of_interest"]
    compute_edges = hydragnn.preprocess.get_radius_graph_config(
        config["NeuralNetwork"]["Architecture"]
    )

    def pre_transform(sample):
        sample.y = np.array(
            [sample.y[PROPERTY_INDEX["G"]] / sample.num_nodes], dtype=np.float32
        )
        hydragnn.preprocess.update_predicted_values(
            var_config["type"], var_config["output_index"], [1], [1], sample
        )
        compute_edges(sample)
        return sample

    dataset = hydragnn.datasets.load_qm9(
        root=dataset_root, num_samples=num_samples, pre_transform=pre_transform
    )
    n_real_files = (
        len(os.listdir(os.path.join(dataset_root, "raw")))
        if os.path.isdir(os.path.join(dataset_root, "raw"))
        else 0
    )
    # Split protocol — every result block is labeled with it so a fit-only
    # number can never be mistaken for generalization:
    #   held_out  — test graphs disjoint from train (the example's split, or
    #               leave-one-out via ``loo_index``)
    #   fit_only  — train==test (tiny-corpus fit demonstration); MAE keys are
    #               renamed ``fit_*`` and no ``test_*`` key is emitted.
    if loo_index is not None:
        all_graphs = list(dataset)
        test = [all_graphs[loo_index]]
        train = val = [g for i, g in enumerate(all_graphs) if i != loo_index]
        protocol = "held_out"
    elif len(dataset) >= 30:
        train, val, test = hydragnn.preprocess.split_dataset(
            dataset, config["NeuralNetwork"]["Training"]["perc_train"], False
        )
        protocol = "held_out"
    else:
        train = val = test = list(dataset)
        protocol = "fit_only"
    # Enforce the label: a held_out block must have zero train/test overlap.
    if protocol == "held_out":
        assert not (set(map(id, train)) & set(map(id, test))), (
            "held_out protocol violated: test graphs appear in train"
        )
    # A corpus smaller than the batch trains as ONE full batch: with tiny
    # ragged batches the BatchNorm running statistics never match any batch's
    # own statistics and eval error decouples from train error.
    batch_size = (
        len(train)
        if full_batch
        else min(config["NeuralNetwork"]["Training"]["batch_size"], len(train))
    )
    train_loader, val_loader, test_loader, _ = hydragnn.preprocess.create_dataloaders(
        train, val, test, batch_size
    )
    config = hydragnn.utils.update_config(config, train_loader, val_loader, test_loader)

    model = hydragnn.models.create_model_config(
        config=config["NeuralNetwork"]["Architecture"]
    )
    variables = hydragnn.models.init_model_variables(model, next(iter(train_loader)))
    optimizer = hydragnn.utils.select_optimizer(
        "AdamW", lr or config["NeuralNetwork"]["Training"]["learning_rate"]
    )
    state = hydragnn.train.create_train_state(model, variables, optimizer)
    driver = hydragnn.train.TrainingDriver(model, optimizer, state, verbosity=0)

    t_epochs = []
    for _ in range(epochs):
        t0 = time.time()
        driver.train_epoch(train_loader)
        t_epochs.append(time.time() - t0)
    # Steady state excludes the compile epoch; a 1-epoch run has no steady
    # sample, so fall back to the compile epoch rather than reporting 0.
    steady_avg = (
        round(sum(t_epochs[1:]) / (len(t_epochs) - 1), 4)
        if len(t_epochs) > 1
        else round(t_epochs[0], 4) if t_epochs else 0.0
    )
    t_epochs = t_epochs[:1] + [steady_avg]
    loss, rmses, tv, pv = driver.evaluate(test_loader, return_values=True)
    mae = float(np.mean(np.abs(np.asarray(tv[0]) - np.asarray(pv[0]))))
    # Steady-state throughput: exclude the first (compile) epoch when possible.
    steady = t_epochs[-1]
    # ``test_*`` keys exist ONLY under the held_out protocol; a fit-only run
    # reports ``fit_*`` so the number cannot be read as generalization.
    tag = "test" if protocol == "held_out" else "fit"
    return {
        "protocol": protocol,
        "num_samples": len(dataset),
        "real_gdb9_files": n_real_files,
        "num_train_graphs": len(train),
        "num_test_graphs": len(test),
        "epochs": epochs,
        f"{tag}_loss": round(float(loss), 6),
        f"{tag}_rmse": [round(float(r), 6) for r in np.atleast_1d(rmses)],
        f"{tag}_mae_eV_per_atom": round(mae * 27.2114, 6),  # target is Ha/atom
        f"{tag}_mae_Ha_per_atom": round(mae, 6),
        "graphs_per_sec": round(len(train) / max(steady, 1e-9), 2),
        "compile_epoch_s": round(t_epochs[0], 2),
        "steady_epoch_s": steady,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO, "QM9_r05.json"))
    ap.add_argument("--epochs", type=int, default=600)
    ap.add_argument("--synthetic-epochs", type=int, default=40)
    ap.add_argument("--workdir", default=None)
    ap.add_argument(
        "--platform",
        choices=("cpu", "tpu"),
        default="cpu",
    )
    args = ap.parse_args()

    import jax

    # JAX starts on the named platform or fails: no silent other backend.
    jax.config.update("jax_platforms", args.platform)

    result = {
        "ts_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "model": "PNA hidden=16 x3 conv (examples/qm9/qm9.json retargeted)",
        "target": "Gibbs free energy G per atom (Ha)",
        "download_probe": _probe_download(),
    }

    work = args.workdir or os.path.join(REPO, "logs", "qm9_northstar_work")
    os.makedirs(work, exist_ok=True)
    cwd = os.getcwd()
    os.chdir(work)
    os.environ.setdefault("SERIALIZED_DATA_PATH", work)
    try:
        # Real GDB-9 bytes through the full pipeline.
        real_root = os.path.join(work, "qm9_real")
        if os.path.isdir(real_root):
            shutil.rmtree(real_root)
        shutil.copytree(
            os.path.join(REPO, "tests", "fixtures", "qm9_raw"),
            os.path.join(real_root, "raw"),
        )
        # 5 molecules fit with a hot LR in one full batch (Adam's per-step
        # travel at lr=1e-3 cannot cross the ~-9 Ha/atom offset in any
        # reasonable epoch count). protocol=fit_only: train==test.
        result["real_gdb9_fit"] = _run_pipeline(
            _pna_config(), real_root, None, args.epochs, lr=0.02, full_batch=True
        )
        # Honest held-out on the real bytes: leave-one-out over the 5
        # committed molecules (train 4 / test 1 per fold). Tiny, but every
        # tested molecule is unseen — the only held-out protocol a 5-record
        # corpus admits. Corpus growth is egress-blocked (download_probe).
        folds = []
        for i in range(5):
            folds.append(
                _run_pipeline(
                    _pna_config(), real_root, None, args.epochs,
                    lr=0.02, full_batch=True, loo_index=i,
                )
            )
        result["real_gdb9_loo"] = {
            "protocol": "held_out",
            "method": "leave-one-out over 5 committed GDB-9 records",
            "test_mae_Ha_per_atom_per_fold": [
                f["test_mae_Ha_per_atom"] for f in folds
            ],
            "test_mae_Ha_per_atom_mean": round(
                sum(f["test_mae_Ha_per_atom"] for f in folds) / len(folds), 6
            ),
            "epochs_per_fold": args.epochs,
        }
        # Synthetic stand-in at example scale — held-out example split; the
        # HEADLINE number until egress exists.
        result["synthetic_1000"] = _run_pipeline(
            _pna_config(), os.path.join(work, "qm9_synth"), 1000,
            args.synthetic_epochs,
        )
        result["headline"] = {
            "metric": "synthetic_1000 held-out test MAE (Ha/atom)",
            "value": result["synthetic_1000"]["test_mae_Ha_per_atom"],
            "protocol": result["synthetic_1000"]["protocol"],
            "note": "real-QM9 generalization unmeasurable offline; "
            "real_gdb9_loo is the held-out protocol on real bytes",
        }
    finally:
        os.chdir(cwd)

    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
