"""Multi-task losses over padded batches (reference Base.loss_rmse /
loss_hpweighted, /root/reference/hydragnn/models/Base.py:271-315).

Total loss = Σ_i w_i · RMSE_i with the weights pre-normalized to Σ|w| = 1
(Base.py:74-75). RMSEs are computed over real rows only via the batch masks.

A head may instead be a classifier (``head_loss`` "cross_entropy", not a
reference feature): its width is its number of classes, its target ONE
min-max-scaled column that holds the class id, and its term of the total is
the mean cross-entropy over real rows."""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from ..graphs.batch import GraphBatch
from .layers import scaled_ids


def normalize_task_weights(weights: Sequence[float]) -> Tuple[float, ...]:
    total = sum(abs(w) for w in weights)
    return tuple(w / total for w in weights)


def head_mse(
    pred: jnp.ndarray, target: jnp.ndarray, mask: jnp.ndarray
) -> jnp.ndarray:
    """Masked mean squared error over rows where mask is True (all columns)."""
    sq = jnp.square(pred - target) * mask[:, None]
    count = jnp.maximum(jnp.sum(mask), 1.0) * pred.shape[1]
    return jnp.sum(sq) / count


def class_ids(target: jnp.ndarray, minmax, num_classes: int) -> jnp.ndarray:
    """Class ids from a min-max-scaled target column [rows, 1], exactly."""
    return scaled_ids(target[:, 0], minmax, num_classes)


def head_cross_entropy(
    logits: jnp.ndarray, labels: jnp.ndarray, mask: jnp.ndarray
) -> jnp.ndarray:
    """Masked mean of ``logsumexp(logits) - logits[label]`` in float32. The
    label's logit is picked by a compare against an iota, which fuses into
    the pass over the logits (a gather would not)."""
    logits = logits.astype(jnp.float32)
    classes = jnp.arange(logits.shape[1], dtype=jnp.int32)
    picked = jnp.sum(
        jnp.where(classes[None, :] == labels[:, None], logits, 0.0), axis=-1
    )
    nll = (jax.nn.logsumexp(logits, axis=-1) - picked) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)


def multihead_rmse_loss(
    outputs: Sequence[jnp.ndarray],
    batch: GraphBatch,
    output_type: Sequence[str],
    task_weights: Sequence[float],
    ilossweights_nll: int = 0,
    head_loss: Sequence[str] = (),
    class_minmax: Sequence = (),
):
    """Returns (total_weighted_loss, per-head loss array: an RMSE, or the
    mean cross-entropy of a ``head_loss`` "cross_entropy" head).

    ``ilossweights_nll=1`` (uncertainty-weighted NLL) is unfinished in the
    reference too — it raises there (Base.py:277-281); we keep the config knob
    and the same explicit error rather than silently mis-shaping the loss."""
    if ilossweights_nll == 1:
        raise ValueError("loss_nll() not ready yet")
    rmses = []
    total = 0.0
    for ihead, (pred, target, htype, w) in enumerate(zip(
        outputs, batch.targets, output_type, task_weights
    )):
        mask = batch.graph_mask if htype == "graph" else batch.node_mask
        if head_loss and head_loss[ihead] == "cross_entropy":
            labels = class_ids(target, class_minmax[ihead], pred.shape[1])
            xent = head_cross_entropy(pred, labels, mask)
            rmses.append(xent)
            total = total + w * xent
            continue
        # max() floor keeps the sqrt VJP finite when a head's masked MSE is
        # exactly 0 (all-masked padding batches from stack_batches would
        # otherwise inject NaN grads that pmean spreads to every replica).
        rmse = jnp.sqrt(jnp.maximum(head_mse(pred, target, mask), 1e-16))
        rmses.append(rmse)
        total = total + w * rmse
    return total, jnp.stack(rmses)
