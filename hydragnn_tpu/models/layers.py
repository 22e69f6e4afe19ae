"""Shared neural layers: padding-aware BatchNorm and plain MLP stacks.

The reference applies torch_geometric.nn.BatchNorm over the ragged node dimension
(/root/reference/hydragnn/models/Base.py:236-243). Under static padding the batch
statistics MUST exclude padding rows or they are biased toward zero — this masked
variant computes mean/var over real rows only and keeps torch-style running
averages (momentum 0.1, i.e. decay 0.9) for eval mode.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax.numpy as jnp
import flax.linen as nn

from ..ops.segment import masked_mean


def scaled_ids(column: jnp.ndarray, minmax, count: int) -> jnp.ndarray:
    """Integer ids from a min-max-scaled column, exactly. Node columns are
    min-max-scaled floats by this system's data contract
    (preprocess/raw_loader.py), so an id is un-scaled with the dataset's own
    (min, max) and rounded: float32 holds ``id / (hi - lo)`` to 2^-24
    relative, which un-scales to within ``id * 2^-23`` of the id -- under
    0.004 at 16384 ids, far from the 0.5 where rounding would pick a
    neighbour (tests/test_lfm2.py walks a whole vocabulary slice)."""
    lo, hi = minmax
    ids = jnp.round(column.astype(jnp.float32) * (hi - lo) + lo).astype(jnp.int32)
    return jnp.clip(ids, 0, count - 1)


class MaskedBatchNorm(nn.Module):
    features: int
    momentum: float = 0.9  # running = momentum * running + (1-momentum) * batch
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x: jnp.ndarray, mask: jnp.ndarray, train: bool) -> jnp.ndarray:
        ra_mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros((self.features,), jnp.float32)
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda: jnp.ones((self.features,), jnp.float32)
        )
        scale = self.param("scale", nn.initializers.ones, (self.features,))
        bias = self.param("bias", nn.initializers.zeros, (self.features,))

        in_dtype = x.dtype
        # Statistics always in float32 — bf16 mixed-precision compute must not
        # degrade the running mean/var (sums over many rows lose bits in bf16).
        x = x.astype(jnp.float32)
        if train:
            mean = masked_mean(x, mask, axis=0)
            mean_sq = masked_mean(jnp.square(x), mask, axis=0)
            var = jnp.maximum(mean_sq - jnp.square(mean), 0.0)
            if not self.is_initializing():
                ra_mean.value = self.momentum * ra_mean.value + (1 - self.momentum) * mean
                ra_var.value = self.momentum * ra_var.value + (1 - self.momentum) * var
        else:
            mean, var = ra_mean.value, ra_var.value

        y = (x - mean) * jnp.reciprocal(jnp.sqrt(var + self.eps)) * scale + bias
        # Keep padding rows at zero so downstream masked statistics stay exact.
        return jnp.where(mask[:, None], y, 0.0).astype(in_dtype)


class MLP(nn.Module):
    """Dense stack: Linear(dims[0]) → ReLU → ... → Linear(dims[-1]), optionally with
    a trailing activation and a custom final-bias constant (UQ initial_bias,
    reference Base._set_bias, Base.py:113-118).

    ``inner_activation=False`` drops the ReLUs BETWEEN Linears (the trailing
    ``activate_final`` ReLU is unaffected) — the reference's shared-MLP
    Sequential grammar (Base.py:155-162 builds [ReLU, Linear, Linear, ...,
    ReLU]: activation only before the first Linear — a no-op on the
    non-negative pooled encoder output — and after the last). The
    checkpoint importer needs this layout to reproduce reference forwards
    exactly for ``num_sharedlayers > 1`` (utils/torch_import.py)."""

    dims: Sequence[int]
    activate_final: bool = False
    final_bias_value: float | None = None
    inner_activation: bool = True
    # Matmul precision of every layer (None: the backend's default, one bf16
    # pass on the TPU). PaiNN's heads run at HIGHEST (models/painn.py).
    precision: Any = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        for i, d in enumerate(self.dims):
            last = i == len(self.dims) - 1
            if last and self.final_bias_value is not None:
                x = nn.Dense(
                    d,
                    bias_init=nn.initializers.constant(self.final_bias_value),
                    precision=self.precision,
                    name=f"dense_{i}",
                )(x)
            else:
                x = nn.Dense(d, precision=self.precision, name=f"dense_{i}")(x)
            if (last and self.activate_final) or (
                not last and self.inner_activation
            ):
                x = nn.relu(x)
        return x
