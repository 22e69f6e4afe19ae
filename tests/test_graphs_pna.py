"""The convergence matrix's PNA single-head cases (tests/test_graphs.py has the
training cell, the thresholds and the why of one file a conv family)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tests.test_graphs import unittest_train_model  # noqa: E402


@pytest.mark.parametrize("model_type", ["PNA"])
@pytest.mark.parametrize("ci_input", ["ci.json"])
def pytest_train_model(model_type, ci_input, overwrite_data=False):
    unittest_train_model(model_type, ci_input, False, overwrite_data)


@pytest.mark.parametrize("model_type", ["PNA"])
def pytest_train_model_lengths(model_type, overwrite_data=False):
    unittest_train_model(model_type, "ci.json", True, overwrite_data)


def pytest_fitted_and_worst_case_pads_train_alike():
    """The same seed through a loader's fitted shapes and through the
    worst-case shapes it had before (``keep_worst_case_pads``): same batches
    in the same order, fewer padding rows, and padding rows are masked out of
    every sum, statistic and batch norm, so each epoch's training loss and the
    evaluation after it agree to float32 round-off. Toy width; two buckets of
    a lattice-like mix, so both train shapes and the evaluation shape differ."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hydragnn_tpu.graphs import GraphSample
    from hydragnn_tpu.models import create_model, init_model_variables
    from hydragnn_tpu.preprocess.dataloader import GraphDataLoader, keep_worst_case_pads
    from hydragnn_tpu.train.train_validate_test import TrainingDriver
    from hydragnn_tpu.train.trainer import create_train_state
    from hydragnn_tpu.utils.model import calculate_PNA_degree
    from hydragnn_tpu.utils.optimizer import select_optimizer

    rng = np.random.default_rng(7)
    graphs = []
    for n in rng.choice((8, 12, 12, 16, 18, 24, 24, 36), size=896):
        n = int(n)
        x = rng.normal(size=(n, 1)).astype(np.float32)
        graphs.append(GraphSample(
            x=x, pos=np.zeros((n, 3), np.float32), y=np.array([x.sum()], np.float32),
            y_loc=np.array([[0, 1]], np.int64),
            edge_index=rng.integers(0, n, size=(2, 4 * n)).astype(np.int32),
        ))
    heads = {"graph": {"num_sharedlayers": 1, "dim_sharedlayers": 4,
                       "num_headlayers": 1, "dim_headlayers": [4]}}
    model = create_model(
        "PNA", 1, 8, (1,), ("graph",), heads, [1.0], 2,
        pna_deg=calculate_PNA_degree(graphs, 12),
    )
    spec = dict(  # multiples of 64 rows: at this size the tile rounds to powers of two
        batch_size=128, head_types=("graph",), head_dims=(1,), with_positions=False,
        ladder_step="mult64",
    )

    def run(worst_case):
        train = GraphDataLoader(graphs[:640], shuffle=True, num_buckets=2, seed=3, **spec)
        held = GraphDataLoader(graphs[640:], shuffle=False, **spec)
        if worst_case:
            keep_worst_case_pads(train)
            keep_worst_case_pads(held)
        variables = init_model_variables(model, next(iter(train)))
        # Plain SGD: a bias in front of a batch norm has a gradient of rounding
        # alone, and Adam would step by its sign, a different one a shape.
        opt = select_optimizer("SGD", 2e-2)
        driver = TrainingDriver(
            model, opt, create_train_state(model, jax.tree_util.tree_map(jnp.array, variables), opt)
        )
        losses = []
        for epoch in range(3):
            train.set_epoch(epoch)
            losses.append((driver.train_epoch(train)[0], driver.evaluate(held)[0]))
        assert train.padding_stats()["fallback_batches"] == 0
        return np.asarray(losses), train._bucket_pads + held._bucket_pads

    fitted, fitted_pads = run(False)
    worst, worst_pads = run(True)
    assert all(f[0] < w[0] and f[1] < w[1] for f, w in zip(fitted_pads, worst_pads)), (
        fitted_pads, worst_pads,
    )
    assert fitted[-1, 0] < fitted[0, 0]  # it trains
    np.testing.assert_allclose(fitted, worst, rtol=2e-5)
