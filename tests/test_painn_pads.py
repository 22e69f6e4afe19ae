"""A loader's two pads (the kernels' tile, the next power of two) train alike,
for PNA, GAT and PaiNN (tests/test_painn.py has the small models and the why
of a file of its own)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from graftbench.drivers.train_epochs import shaken  # noqa: E402
from hydragnn_tpu.models import init_model_variables  # noqa: E402
from tests.test_painn import (  # noqa: E402
    DIMS, TYPES, _assert_trees_close, _graphs, _model, _program_loss,
)


@pytest.mark.parametrize("kind", ["PNA", "GAT", "PAINN"])
def pytest_a_loaders_tile_pad_and_power_of_two_pad_train_alike(kind, monkeypatch):
    """One dataset behind two loaders, ``ladder_step`` absent (the pad rounds
    up to the kernels' tile) and ``"pow2"`` named: same membership, same
    order, fewer padding rows. On the chip's arm (sorted / CSR sums) a
    batch's loss and every gradient agree within PaiNN's limit (1e-4; a
    gradient to 1e-4 of its leaf's largest entry, as the padding test above
    holds them), and so do the training loss of an epoch of the scan path's
    stacked chunk and the evaluation after it. Rows that only the round-up
    made carry nothing."""
    from hydragnn_tpu.graphs.collate import loader_pad_tile
    from hydragnn_tpu.preprocess.dataloader import GraphDataLoader
    from hydragnn_tpu.train.train_validate_test import TrainingDriver
    from hydragnn_tpu.train.trainer import create_train_state
    from hydragnn_tpu.utils.optimizer import select_optimizer

    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    graphs = _graphs(seed=5, sizes=tuple(range(16, 28)) * 16, box=6.0)
    # GAT's attention dropout draws over [N_pad + E_pad, heads]: another pad is
    # another draw, equal in law and not in value, so it is off here.
    model = _model(kind).clone(dropout=0.0)
    loaders = {
        step: GraphDataLoader(
            graphs, batch_size=96, shuffle=False, head_types=TYPES, head_dims=DIMS,
            ladder_step=step, with_positions=model.needs_positions,
        )
        for step in (None, "pow2")
    }
    tile = loader_pad_tile()
    (n_tile, e_tile, _), (n_pow2, e_pow2, _) = (loaders[k].pad_sizes for k in (None, "pow2"))
    assert n_tile % tile == 0 and e_tile % tile == 0
    # (An unshuffled loader is sized to its own two batches: the edge rows'
    # rung of the tile-rounded worst case can be the power of two itself.)
    assert n_tile < n_pow2 and e_tile <= e_pow2 and n_pow2 & (n_pow2 - 1) == 0
    batches = {step: next(iter(loader)) for step, loader in loaders.items()}
    assert batches[None].senders.shape[0] == e_tile
    assert int(batches[None].edge_mask.sum()) == int(batches["pow2"].edge_mask.sum())
    assert batches[None].row_ptr is not None  # the CSR arm's contract
    variables = shaken(init_model_variables(model, batches[None]), 3)

    stats = {k: v for k, v in variables.items() if k != "params"}  # PNA's, GAT's norms
    loss_and_grads = jax.jit(
        jax.value_and_grad(lambda p, batch: _program_loss(model, p, batch, **stats))
    )
    results = {
        step: loss_and_grads(variables["params"], batch) for step, batch in batches.items()
    }
    np.testing.assert_allclose(results[None][0], results["pow2"][0], rtol=1e-4)
    _assert_trees_close(results[None][1], results["pow2"][1], rtol=1e-4, atol_of_scale=1e-4)

    # An epoch of the scan path: one stacked chunk of the loader's two batches.
    # The second step's loss and the evaluation after it see the first update.
    # (The parameters themselves are not compared: a bias in front of a batch
    # norm has a gradient of rounding alone, and Adam steps by its sign.)
    after = {}
    for step, loader in loaders.items():
        opt = select_optimizer("AdamW", 1e-3)
        fresh = jax.tree_util.tree_map(jnp.array, variables)  # the step donates its state
        driver = TrainingDriver(model, opt, create_train_state(model, fresh, opt))
        driver.scan_chunk = 2
        before = driver.evaluate(loader)[0]
        loader.reset_padding_stats()
        train_loss, _ = driver.train_epoch(loader)
        assert loader.padding_stats()["batches"] == 2
        after[step] = (train_loss, driver.evaluate(loader)[0])
        assert after[step][1] != pytest.approx(before, rel=1e-3)  # it moved
    np.testing.assert_allclose(after[None], after["pow2"], rtol=1e-4)
