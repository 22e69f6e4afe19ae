"""Laguna's routed layers with compact row arrays INSIDE the model, under
``nn.remat`` and inside a scanned epoch (tests/test_laguna.py has the small
model and the why of a file of its own)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from graftbench.drivers.train_epochs import shaken  # noqa: E402
from hydragnn_tpu.models import init_model_variables, token_routed  # noqa: E402
from tests.test_laguna import ARCH, ROUTED, _model  # noqa: E402
from tests.test_lfm2 import _collate, _sequences, assert_bit_equal  # noqa: E402


@pytest.mark.parametrize("where", ["rematerialized_blocks", "scanned_epoch"])
def pytest_compact_row_arrays_inside_the_model(where, monkeypatch):
    """At 170 tokens the four routed layers size their row arrays at 256 of
    352 rows and one pass takes every live row: under ``nn.remat`` (the
    cell's blocks) loss and every gradient, and inside a scanned epoch of two
    steps the parameters AdamW leaves, are bit-equal at another capacity
    (320), and the loss equals to rounding what one pass over all ``K N`` rows
    gives (a capacity out of reach: no loop is compiled); the counter reads
    one a routed layer and step, and none there."""
    from hydragnn_tpu.train.trainer import (
        _loss_and_metrics, create_train_state, make_train_epoch_scan,
    )
    from hydragnn_tpu.utils.optimizer import select_optimizer

    model = _model(remat=True)
    batch = _collate(_sequences((60, 70, 40)))
    rows = batch.node_features.shape[0] * ARCH["num_experts_per_tok"]
    assert token_routed._capacity(rows, 4, 16) == 256 < 320 < rows
    variables = shaken(init_model_variables(model, batch), 35)
    opt = select_optimizer("AdamW", 1e-3)

    def run():
        if where == "rematerialized_blocks":
            (loss, aux), grads = jax.jit(jax.value_and_grad(
                lambda p: _loss_and_metrics(
                    model, p, {}, batch, jax.random.PRNGKey(0), counters=True
                ), has_aux=True,
            ))(variables["params"])
            return (loss, grads), aux[2], 1
        state = create_train_state(model, variables, opt)
        stacked = jax.tree_util.tree_map(lambda a: jnp.stack([a, a]), batch)
        state, metrics = make_train_epoch_scan(model, opt, donate=False)(
            state, stacked, np.asarray(2, np.int32), jax.random.PRNGKey(0)
        )
        return (metrics["loss"], state.params), metrics, 2

    got, counted, steps = run()
    monkeypatch.setattr(token_routed, "_capacity", lambda *_: 320)
    wider, counted_wider, _ = run()
    monkeypatch.setattr(token_routed, "_capacity", lambda *_: rows)
    every_row, counted_every_row, _ = run()
    passes = len(ROUTED) * steps
    assert float(counted["moe_layers_compact"]) == passes
    assert float(counted_wider["moe_layers_compact"]) == passes
    assert float(counted_every_row["moe_layers_compact"]) == 0
    assert float(counted["moe_rows_held"]) == float(counted_every_row["moe_rows_held"]) > 0
    assert_bit_equal(got, wider)
    assert abs(float(got[0]) - float(every_row[0])) <= 1e-6 * abs(float(every_row[0]))
