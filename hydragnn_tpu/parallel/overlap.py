"""graftmesh gradient-sync arms — bucketed all-reduce overlapped with
backward compute, and a ppermute-ring alternative (docs/DISTRIBUTED.md).

The single-psum DP step (train/trainer.make_train_step_dp, the DDP-allreduce
analog) reduces the WHOLE gradient tree after the full backward: XLA sees one
psum that depends on every cotangent, so no collective can start until the
last backward op retires. This module restructures the dataflow so each
gradient BUCKET's all-reduce depends only on that bucket's cotangents:

* ``plan_buckets`` partitions the param leaves into size-targeted buckets in
  REVERSE flatten order — parameters consumed late in the forward (output
  heads) produce their cotangents FIRST in the backward, so the first bucket's
  reduce can dispatch while the conv stack's backward is still running.
* ``attach_grad_sync`` threads the params through per-bucket ``custom_vjp``
  identities whose backward performs the reduce. The forward is untouched
  (identity); in the backward graph each bucket's collective is a separate op
  whose operands are exactly that bucket's cotangents — XLA's latency-hiding
  scheduler is then FREE to overlap it with the remaining backward compute
  (async collectives on TPU; on CPU the ops serialize, which is why
  MULTICHIP artifacts label CPU overlap fractions non-meaningful).
* ``ring_psum`` is the ppermute-ring arm: the same bucket hook, but the
  reduce is an explicit (axis_size - 1)-step rotate-and-accumulate ring —
  the hand-scheduled alternative A/B'd against the compiler-scheduled psum
  (bench.py --multichip).

Weighting contract: the callers multiply each shard's LOCAL loss by
``count / max(psum(count), 1)`` before differentiation, so the plain SUM the
bucket reduce computes equals the single-psum arm's graph-count-weighted
mean gradient exactly (the weight is constant w.r.t. params) — the arms are
allclose by construction, locked by tests/test_graftmesh.py.

Everything here is traced inside the shard_map step: no host state, no wall
clock, no global RNG.
"""

from __future__ import annotations

from typing import Any, Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..telemetry import scopes

GRAD_SYNC_MODES = ("single", "bucketed", "ring")
DEFAULT_BUCKET_MB = 4.0


def resolve_grad_sync(value) -> str:
    """Validate a ``Training.grad_sync`` knob (None → the single-psum arm).
    The runtime twin of the contract checker's ``bad-mesh`` finding."""
    if value in (None, ""):
        return "single"
    if value not in GRAD_SYNC_MODES:
        raise ValueError(
            f"grad_sync {value!r} is not one of {GRAD_SYNC_MODES}"
        )
    return str(value)


def plan_buckets(params: Any, bucket_bytes: float) -> List[Tuple[int, ...]]:
    """Partition the param tree's flat leaves into size-targeted buckets.

    Leaves are walked in REVERSE flatten order (flax flatten order follows
    module definition order, which follows forward execution order — its
    reverse approximates backward cotangent availability). Greedy fill: a
    bucket closes when adding the next leaf would exceed ``bucket_bytes``;
    single leaves larger than the target get their own bucket. Derived from
    static shapes/dtypes only, so the plan is a trace-time constant."""
    leaves = jax.tree_util.tree_leaves(params)
    bucket_bytes = max(float(bucket_bytes), 1.0)
    buckets: List[Tuple[int, ...]] = []
    cur: List[int] = []
    cur_bytes = 0.0
    for i in reversed(range(len(leaves))):
        leaf = leaves[i]
        nbytes = float(leaf.size) * jnp.dtype(leaf.dtype).itemsize
        if cur and cur_bytes + nbytes > bucket_bytes:
            buckets.append(tuple(cur))
            cur, cur_bytes = [], 0.0
        cur.append(i)
        cur_bytes += nbytes
    if cur:
        buckets.append(tuple(cur))
    return buckets


def ring_psum(tree: Any, axis_name: str, axis_size: int) -> Any:
    """Explicit ring all-reduce: ``axis_size - 1`` rotate-and-accumulate
    ppermute steps. Same value as ``lax.psum`` up to f32 summation order
    (each shard accumulates the ring in ITS OWN rotation order), which is why
    the equivalence gate is allclose, not bitwise. ``axis_size`` must be the
    static mesh axis size (ppermute permutations are trace-time constants)."""
    if axis_size <= 1:
        return tree
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
    acc, cur = tree, tree
    for _ in range(axis_size - 1):
        cur = jax.tree_util.tree_map(
            lambda x: jax.lax.ppermute(x, axis_name, perm), cur
        )
        acc = jax.tree_util.tree_map(jnp.add, acc, cur)
    return acc


def make_reduce(
    grad_sync: str, grad_axes: Sequence[str], data_axis_size: int
) -> Callable[[Any], Any]:
    """The per-bucket reduce for :func:`attach_grad_sync`: psum (or ring
    all-reduce) over 'data', then pmean over 'graph' when the mesh has a
    nontrivial graph axis (edge-shard contributions are means over the
    replicated node params — the same composition the single-psum arm
    applies after the full backward)."""
    graph = "graph" in grad_axes

    def reduce_fn(cots: Any) -> Any:
        if grad_sync == "ring":
            out = ring_psum(cots, "data", data_axis_size)
        else:
            # One psum bind over the bucket's tuple → one variadic
            # all-reduce op whose operands are exactly this bucket.
            out = jax.lax.psum(cots, "data")
        if graph:
            out = jax.lax.pmean(out, "graph")
        return out

    return reduce_fn


def _make_bucket_sync(reduce_fn: Callable[[Any], Any]):
    """Identity-forward / reduce-backward hook for ONE bucket. The primal is
    the tuple of the bucket's param leaves; the backward reduces the tuple of
    cotangents in one collective."""

    @jax.custom_vjp
    def sync(leaves):
        return leaves

    def fwd(leaves):
        return leaves, None

    def bwd(_, cots):
        return (reduce_fn(cots),)

    sync.defvjp(fwd, bwd)
    return sync


def attach_grad_sync(
    params: Any,
    plan: Sequence[Tuple[int, ...]],
    reduce_fn: Callable[[Any], Any],
) -> Any:
    """Thread ``params`` through the per-bucket sync hooks. Forward math is
    untouched; gradients come back ALREADY reduced, bucket by bucket, at the
    point in the backward graph where each bucket's cotangents finalize."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    out = list(leaves)
    for bucket in plan:
        sync = _make_bucket_sync(reduce_fn)
        # The hook's backward (the collective) takes this call's name stack.
        with jax.named_scope(scopes.GRAD_SYNC):
            synced = sync(tuple(out[i] for i in bucket))
        for j, i in enumerate(bucket):
            out[i] = synced[j]
    return jax.tree_util.tree_unflatten(treedef, out)


def overlap_fraction(
    t_single: float, t_overlapped: float, t_nosync: float
) -> "float | None":
    """Fraction of the gradient all-reduce wall hidden behind backward
    compute, from three steady step times: the single-psum arm, the
    overlapped arm, and a no-sync lower bound (local step, no collectives).
    ``(t_single - t_overlapped) / (t_single - t_nosync)``, clamped to [0, 1];
    None when the collective share is too small to measure (denominator
    within noise of zero)."""
    denom = t_single - t_nosync
    if denom <= 1e-9 or not all(
        x > 0 for x in (t_single, t_overlapped, t_nosync)
    ):
        return None
    return max(0.0, min(1.0, (t_single - t_overlapped) / denom))
