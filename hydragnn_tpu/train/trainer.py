"""Compiled train/eval steps. THE distribution contract (SURVEY.md §7 pillar 2):
there is no DDP wrapper object — data parallelism is a psum inside the
shard_map-compiled step over the 'data' mesh axis, replacing the reference's
DistributedDataParallel + NCCL allreduce (/root/reference/hydragnn/utils/
distributed.py:216-226, gradient sync at train_validate_test.py:231).

Two step flavors:
  * make_train_step(model, opt)            — single-device jit.
  * make_train_step_dp(model, opt, mesh)   — batch stacked [D, ...] over the
    'data' axis; grads/metrics psum'd over ICI. Eval metrics are also reduced
    (fixing the reference's per-rank-only eval metrics, SURVEY.md §3.4).

Metrics are returned as (weighted sum, count) pairs so the host can form
graph-count-weighted epoch averages exactly like the reference's
loss.item()*num_graphs accumulation (train_validate_test.py:234-237).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct
from jax.extend.core import jaxpr_as_fun
from jax.sharding import PartitionSpec as P

from ..graphs.batch import GraphBatch
from ..models.base import HydraGNN
from ..models.loss import multihead_rmse_loss
from ..models.token_routed import INTERMEDIATES, split_intermediates
from ..ops.segment import platform_override
from ..telemetry import graftel as telemetry
from ..telemetry import scopes


def _mesh_platform(mesh) -> str:
    """Platform of the devices a mesh's step will execute on — what the
    aggregation's arm must key off (jax.default_backend() lies when a
    TPU-attached host traces a step for a CPU-device mesh)."""
    return next(iter(mesh.devices.flat)).platform


@struct.dataclass
class TrainState:
    params: Any
    batch_stats: Any
    opt_state: Any
    step: jnp.ndarray
    # Dynamic loss-scale state (precision/policy.LossScaleState) — present
    # only under Training.precision="bf16"; None is an empty pytree subtree,
    # so the f32 state (and every compiled f32 program) is unchanged.
    loss_scale: Any = None


@telemetry.setup_phase("create_state", until_ready=True)
def create_train_state(model, variables, optimizer) -> TrainState:
    # init() on a COPY of params: optimizers that store the params pytree in
    # their state (optax.lbfgs memory) would otherwise alias params buffers,
    # and the donating train steps may not donate the same buffer twice.
    params_copy = jax.tree_util.tree_map(jnp.array, variables["params"])
    return TrainState(
        params=variables["params"],
        batch_stats=variables.get("batch_stats", {}),
        opt_state=optimizer.init(params_copy),
        step=jnp.zeros((), jnp.int32),
    )


def _cast_floats(tree, dtype):
    """Cast floating leaves to dtype (ints/masks untouched)."""
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype)
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
        else x,
        tree,
    )


def _apply_model(model: HydraGNN, params, batch_stats, batch, **kwargs):
    """model.apply with the model's mixed-precision policy: bf16 compute
    (params + input features cast inside the differentiated function, so
    gradients accumulate in the float32 master params), float32 outputs."""
    cd = model.compute_dtype
    if cd:
        params = _cast_floats(params, jnp.dtype(cd))
        batch = batch.replace(
            node_features=batch.node_features.astype(jnp.dtype(cd)),
            edge_features=None
            if batch.edge_features is None
            else batch.edge_features.astype(jnp.dtype(cd)),
        )
    out = model.apply({"params": params, "batch_stats": batch_stats}, batch, **kwargs)
    if cd:
        if isinstance(out, tuple):  # (outputs, mutated)
            return [o.astype(jnp.float32) for o in out[0]], *out[1:]
        return [o.astype(jnp.float32) for o in out]
    return out


def _model_loss(model: HydraGNN, outputs, batch):
    with jax.named_scope(scopes.LOSS):
        return multihead_rmse_loss(
            outputs, batch, model.output_type, model.task_weights,
            head_loss=model.head_loss, class_minmax=model.class_minmax,
        )


def _loss_and_metrics(
    model: HydraGNN, params, batch_stats, batch, dropout_key, counters=False
):
    """``counters``: also ask the model for what its routed layers count a
    step (``HydraGNN.counts_routing``); the aux then has a third entry, the
    dict of them (models/token_routed.py ``COUNTERS``)."""
    outputs, mut = _apply_model(
        model,
        params,
        batch_stats,
        batch,
        train=True,
        mutable=["batch_stats"] + ([INTERMEDIATES] if counters else []),
        rngs={"dropout": dropout_key},
    )
    loss, rmses = _model_loss(model, outputs, batch)
    # A family without batch norm (PaiNN) has no such collection.
    aux = (mut.get("batch_stats", batch_stats), rmses)
    if counters:
        aux += (split_intermediates(mut.get(INTERMEDIATES))[1],)
    return loss, aux


def state_donation_safe(state: TrainState) -> bool:
    """Donation requires every buffer in the state to appear exactly once;
    optimizers that store the params pytree inside their own state (optax
    lbfgs memory) repeat buffers and must run without donation."""
    seen = set()
    for leaf in jax.tree_util.tree_leaves(state):
        if isinstance(leaf, jax.Array):
            if id(leaf) in seen:
                return False
            seen.add(id(leaf))
    return True


def _all_finite(loss, grads):
    """ONE fused reduction: loss and every gradient leaf are finite. The
    compiled step's non-finite guard flag (docs/FAULT_TOLERANCE.md)."""
    ok = jnp.isfinite(loss)
    for g in jax.tree_util.tree_leaves(grads):
        ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(g)))
    return ok


def _keep_if(ok, new_tree, old_tree):
    """Elementwise select: the new pytree on a finite step, the old one on a
    bad step. Deliberately ``where`` and NOT ``lax.cond``: a conditional
    region changes XLA's fusion boundaries and the clean path would no longer
    be bit-identical to the unguarded build (measured on CPU), while
    ``jnp.where(True, n, o)`` selects ``n`` exactly. The select pass costs a
    state-sized read per step — noise next to fwd+bwd at production batch
    sizes (guard_overhead_pct in FAULTS_rNN.json tracks it)."""
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(ok, n, o), new_tree, old_tree
    )


def _step_body(
    model: HydraGNN, optimizer, guard: bool = False, loss_scaling=None
):
    """The single-device gradient step shared by make_train_step and the
    scanned epoch (one definition — the two compiled paths must never drift).

    With ``guard=True`` the step additionally computes an all-finite flag over
    loss + grads and SKIPS the update on a non-finite step: params, opt_state,
    and batch_stats keep their previous values, the step's metrics carry zero
    weight, and ``metrics["bad"]`` reports the skip (summed per chunk on the
    scan path) for the host-side StepGuard policy. guard=False emits exactly
    the historical computation — the flag costs nothing when disabled.

    ``loss_scaling`` (a precision.LossScaleConfig, docs/PRECISION.md) selects
    the mixed-precision step: the loss is multiplied by the running scale in
    ``state.loss_scale`` before value_and_grad (bf16's exponent range would
    otherwise flush small gradients to zero), gradients are unscaled in f32
    before the optimizer, and the guard's skip machinery is ALWAYS on — an
    overflowed step must not apply inf/NaN updates — with the scale backing
    off on overflow and growing after a clean streak, all inside the jit so
    the policy rides ``lax.scan`` epochs per step. ``None`` emits the
    historical body byte-for-byte."""
    from ..utils.optimizer import ValueFnTransformation

    needs_value_fn = isinstance(optimizer, ValueFnTransformation)
    if loss_scaling is not None:
        if needs_value_fn:
            raise NotImplementedError(
                "loss scaling + LBFGS is unsupported: the zoom linesearch "
                "re-evaluates the SCALED loss along the search direction and "
                "its Wolfe conditions are not scale-invariant under dynamic "
                "rescaling; use a first-order optimizer with precision='bf16'"
            )
        return _scaled_step_body(model, optimizer, guard, loss_scaling)

    def body(state: TrainState, batch: GraphBatch, rng):
        dropout_key = jax.random.fold_in(rng, state.step)
        grad_fn = jax.value_and_grad(
            lambda p: _loss_and_metrics(
                model, p, state.batch_stats, batch, dropout_key,
                counters=model.counts_routing,
            ),
            has_aux=True,
        )
        # ``counted``: () or (the routed layers' counters of this step,).
        (loss, (new_bstats, rmses, *counted)), grads = grad_fn(state.params)
        with jax.named_scope(scopes.OPTIMIZER):
            if needs_value_fn:
                # LBFGS zoom linesearch: update() re-evaluates the loss along the
                # search direction via value_fn (deterministic eval — same batch,
                # same dropout key).
                def value_fn(p):
                    return _loss_and_metrics(
                        model, p, state.batch_stats, batch, dropout_key
                    )[0]

                updates, new_opt = optimizer.update(
                    grads,
                    state.opt_state,
                    state.params,
                    value=loss,
                    grad=grads,
                    value_fn=value_fn,
                )
            else:
                updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
            new_params = jax.tree_util.tree_map(
                lambda p, u: p + u, state.params, updates
            )
            count = batch.count_real_graphs().astype(jnp.float32)
            if guard:
                ok = _all_finite(loss, grads)
                new_params = _keep_if(ok, new_params, state.params)
                new_opt = _keep_if(ok, new_opt, state.opt_state)
                new_bstats = _keep_if(ok, new_bstats, state.batch_stats)
                okf = ok.astype(jnp.float32)
                count = count * okf
                # Zero the VALUES before weighting: NaN * 0 is NaN, so a bad
                # step's loss must be selected away, not merely zero-weighted.
                metrics = {
                    "loss": jnp.where(ok, loss, 0.0) * count,
                    "rmses": jnp.where(ok, rmses, jnp.zeros_like(rmses)) * count,
                    "count": count,
                    "bad": 1.0 - okf,
                }
            else:
                metrics = {"loss": loss * count, "rmses": rmses * count, "count": count}
            for extra in counted:  # summed over a scanned epoch like the rest
                metrics.update(extra)
        new_state = TrainState(
            params=new_params,
            batch_stats=new_bstats,
            opt_state=new_opt,
            step=state.step + 1,
            loss_scale=state.loss_scale,
        )
        return new_state, metrics

    return body


def _scaled_step_body(
    model: HydraGNN, optimizer, guard: bool, loss_scaling
):
    """The mixed-precision step (docs/PRECISION.md): scaled loss → f32
    unscaled grads → guarded (always) update → in-jit dynamic-scale update.
    Metric semantics mirror the guarded body — an overflowed step carries
    zero weight, its values are selected away before weighting — plus the
    precision pair ``overflow`` / ``scale_growths`` (summed per chunk on the
    scan path) consumed by the host LossScaleMonitor. ``guard`` only adds
    the ``bad`` metric for StepGuard's streak accounting: the computation is
    bit-inert to the flag (the skip machinery is structural here)."""
    from ..precision.policy import loss_scale_update

    def body(state: TrainState, batch: GraphBatch, rng):
        dropout_key = jax.random.fold_in(rng, state.step)
        ls = state.loss_scale

        def scaled_loss(p):
            loss, aux = _loss_and_metrics(
                model, p, state.batch_stats, batch, dropout_key
            )
            # The ONE extra multiply of the policy: everything downstream of
            # value_and_grad sees gradients of scale*loss; the aux carries
            # the unscaled loss for metrics.
            return loss * ls.scale, (loss, aux)

        (_, (loss, (new_bstats, rmses))), sgrads = jax.value_and_grad(
            scaled_loss, has_aux=True
        )(state.params)
        with jax.named_scope(scopes.OPTIMIZER):
            inv = 1.0 / ls.scale
            # Unscale in the grads' own (f32 master) dtype: inf/NaN from an
            # overflowed backward survive the divide, so the finite check below
            # sees them; finite grads come out exactly scale-free.
            grads = jax.tree_util.tree_map(lambda g: g * inv, sgrads)
            updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
            new_params = jax.tree_util.tree_map(
                lambda p, u: p + u, state.params, updates
            )
            ok = _all_finite(loss, grads)
            new_params = _keep_if(ok, new_params, state.params)
            new_opt = _keep_if(ok, new_opt, state.opt_state)
            new_bstats = _keep_if(ok, new_bstats, state.batch_stats)
            new_ls, grew = loss_scale_update(ls, ok, loss_scaling)
            okf = ok.astype(jnp.float32)
            count = batch.count_real_graphs().astype(jnp.float32) * okf
            metrics = {
                "loss": jnp.where(ok, loss, 0.0) * count,
                "rmses": jnp.where(ok, rmses, jnp.zeros_like(rmses)) * count,
                "count": count,
                "overflow": 1.0 - okf,
                "scale_growths": grew.astype(jnp.float32),
            }
            if guard:
                metrics["bad"] = 1.0 - okf
        new_state = TrainState(
            params=new_params,
            batch_stats=new_bstats,
            opt_state=new_opt,
            step=state.step + 1,
            loss_scale=new_ls,
        )
        return new_state, metrics

    return body


def make_train_step(
    model: HydraGNN,
    optimizer,
    donate: bool = True,
    guard: bool = False,
    loss_scaling=None,
) -> Callable:
    body = _step_body(model, optimizer, guard, loss_scaling)

    # donate_argnums: params/opt_state buffers are reused in place, halving
    # HBM traffic for the state update (callers must drop the old state).
    def step(state: TrainState, batch: GraphBatch, rng):
        # The compiled-step half of the graftel trace bridge
        # (docs/OBSERVABILITY.md): a named scope is pure op metadata — the
        # emitted computation is numerically identical — and XLA writes it
        # into every operation's ``op_name``. In a captured trace that is the
        # ``tf_op`` stat of the operation's EVENT METADATA on the device
        # plane's ``XLA Ops`` line (``jax.profiler.ProfileData`` shows an
        # event's own stats only, so it hides it;
        # ``graftbench/xplane_scopes.py`` reads it). telemetry/scopes.py has
        # the vocabulary.
        with jax.named_scope(scopes.TRAIN_STEP):
            return body(state, batch, rng)

    return jax.jit(step, donate_argnums=(0,) if donate else ())


def make_eval_step(model: HydraGNN) -> Callable:
    @jax.jit
    def step(state: TrainState, batch: GraphBatch):
        with jax.named_scope(scopes.EVAL_STEP):
            outputs = _apply_model(
                model, state.params, state.batch_stats, batch, train=False
            )
            loss, rmses = _model_loss(model, outputs, batch)
            count = batch.count_real_graphs().astype(jnp.float32)
        return (
            {"loss": loss * count, "rmses": rmses * count, "count": count},
            outputs,
        )

    return step


def make_train_epoch_scan(
    model: HydraGNN,
    optimizer,
    donate: bool = True,
    guard: bool = False,
    loss_scaling=None,
) -> Callable:
    """The scan path's program: ``epoch(state, batches[L, ...], count, rng)``
    runs the train step over the first ``count <= L`` of a stack of ``L``
    batches in ONE dispatch. The trip count is an ARGUMENT (an int32 array,
    never a Python int or a static argument), so a batch shape has one
    compiled program whatever an epoch's length: a full chunk is ``count =
    L``, a shape's tail of ``r`` batches is the same stack with ``count = r``,
    and the slots past ``count`` are never read. The loop itself is never
    differentiated (each step differentiates inside itself); the metrics are
    summed in the carry, matching EpochMetrics' weighted accumulation. With
    ``guard``, the per-step skip rides INSIDE the loop (a NaN step never
    poisons later steps of the same chunk) and the summed ``bad`` metric
    reports how many steps were skipped. With ``loss_scaling`` the
    dynamic-scale state rides the carry (TrainState.loss_scale), so
    backoff/growth stay exact per step inside a dispatch of several."""

    body = _step_body(model, optimizer, guard, loss_scaling)

    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def epoch(state: TrainState, batches: GraphBatch, count, rng):
        # The step is traced ONCE, to a jaxpr that the loop replays: the
        # carry needs the metrics' shapes before the loop is built, and a
        # second Python trace of the model for them costs a second or more
        # of set-up a batch shape.
        one = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), batches
        )
        traced, shapes = jax.make_jaxpr(body, return_shape=True)(state, one, rng)
        replay = jaxpr_as_fun(traced)
        out_tree = jax.tree_util.tree_structure(shapes)

        def step(i, carry):
            state, summed = carry
            batch = jax.tree_util.tree_map(
                lambda x: jax.lax.dynamic_index_in_dim(x, i, keepdims=False),
                batches,
            )
            state, metrics = jax.tree_util.tree_unflatten(
                out_tree, replay(*jax.tree_util.tree_leaves((state, batch, rng)))
            )
            return state, jax.tree_util.tree_map(jnp.add, summed, metrics)

        # Trace-annotation bridge: same metadata-only scope as
        # make_train_step, so scanned epochs attribute identically.
        with jax.named_scope(scopes.TRAIN_EPOCH_SCAN):
            zeros = jax.tree_util.tree_map(
                lambda m: jnp.zeros(m.shape, m.dtype), shapes[1]
            )
            return jax.lax.fori_loop(0, count, step, (state, zeros))

    return epoch


# ------------------------------------------------------------- DP × graph-par
def _batch_pspec(batch: GraphBatch, graph_sharded: bool) -> GraphBatch:
    """PartitionSpec tree. Every array is sharded on its leading (device) axis
    over 'data'. With graph_sharded, edge arrays are ALSO sharded over 'graph'
    (edge-partitioned message passing — nodes replicated, one collective per
    aggregation inside the convs)."""
    edge_spec = P("data", "graph") if graph_sharded else P("data")
    return GraphBatch(
        node_features=P("data"),
        edge_features=None if batch.edge_features is None else edge_spec,
        senders=edge_spec,
        receivers=edge_spec,
        node_graph=P("data"),
        node_mask=P("data"),
        edge_mask=edge_spec,
        graph_mask=P("data"),
        targets=tuple(P("data") for _ in batch.targets),
        # CSR boundaries are node-/graph-indexed (never edge-sharded;
        # replicated across 'graph', where the ops layer LOCALIZES them per
        # edge shard — ops/aggregate.py localize_row_ptr, the graftmesh
        # halo/edge-cut contract — so graph-partitioned steps stay
        # zero-searchsorted).
        row_ptr=None if batch.row_ptr is None else P("data"),
        graph_ptr=None if batch.graph_ptr is None else P("data"),
        # Node-indexed, replicated across 'graph' like the features.
        positions=None if batch.positions is None else P("data"),
        num_graphs_pad=batch.num_graphs_pad,
    )


def _dp_local_graftmesh(
    model: HydraGNN,
    optimizer,
    guard: bool,
    loss_scaling,
    grad_sync: str,
    grad_bucket_mb: float,
    grad_axes,
    data_axis_size: int,
):
    """The generalized per-shard DP body (graftmesh, docs/DISTRIBUTED.md):
    selected whenever the step needs dynamic loss scaling and/or an
    overlapped gradient-sync arm. The default single-psum unscaled path keeps
    its historical body in ``make_train_step_dp`` byte-for-byte.

    Overlapped arms (``grad_sync`` = "bucketed" | "ring") multiply the LOCAL
    loss by ``count / max(psum(count), 1)`` before differentiation and let
    the per-bucket backward hooks SUM cotangents across shards — identical
    math to the single arm's weighted psum (the weight is constant w.r.t.
    params), but each bucket's collective depends only on its own backward
    segment, so it can overlap remaining backward compute.

    With ``loss_scaling`` the scale state machine updates in LOCKSTEP after
    the reduction: the all-finite flag is computed from the REDUCED loss and
    gradients, so every shard sees the same overflow verdict and the
    backoff/growth update applies identically everywhere (the property
    tests/test_graftmesh.py pins: a NaN on one shard backs off all)."""
    from ..parallel import overlap

    scaled = loss_scaling is not None
    if scaled:
        from ..precision.policy import loss_scale_update
    graph = "graph" in grad_axes

    def body(state: TrainState, batch: GraphBatch, rng):
        batch = jax.tree_util.tree_map(lambda x: x[0], batch)
        dropout_key = jax.random.fold_in(
            rng, state.step * 1000 + jax.lax.axis_index("data")
        )
        ls = state.loss_scale
        count = batch.count_real_graphs().astype(jnp.float32)
        with jax.named_scope(scopes.GRAD_SYNC):
            count_total = jax.lax.psum(count, "data")
        denom = jnp.maximum(count_total, 1.0)
        scale = ls.scale if scaled else jnp.float32(1.0)

        if grad_sync == "single":
            def fn(p):
                loss, (bstats, rmses) = _loss_and_metrics(
                    model, p, state.batch_stats, batch, dropout_key
                )
                return loss * scale, (loss, bstats, rmses)

            (_, (loss, new_bstats, rmses)), sgrads = jax.value_and_grad(
                fn, has_aux=True
            )(state.params)
            with jax.named_scope(scopes.GRAD_SYNC):
                grads = jax.tree_util.tree_map(
                    lambda g: jax.lax.psum(g * count, "data") / denom, sgrads
                )
                if graph:
                    grads = jax.lax.pmean(grads, "graph")
        else:
            w = count / denom
            plan = overlap.plan_buckets(
                state.params, grad_bucket_mb * (1 << 20)
            )
            reduce_fn = overlap.make_reduce(
                grad_sync, grad_axes, data_axis_size
            )

            def fn(p):
                ps = overlap.attach_grad_sync(p, plan, reduce_fn)
                loss, (bstats, rmses) = _loss_and_metrics(
                    model, ps, state.batch_stats, batch, dropout_key
                )
                return loss * scale * w, (loss, bstats, rmses)

            # The bucket hooks already reduced these across shards.
            (_, (loss, new_bstats, rmses)), grads = jax.value_and_grad(
                fn, has_aux=True
            )(state.params)
        if scaled:
            # Unscale AFTER the reduction in the grads' f32 master dtype —
            # inf/NaN from an overflowed shard survives the psum and the
            # divide, so the lockstep finite check below sees it everywhere.
            with jax.named_scope(scopes.OPTIMIZER):
                inv = 1.0 / ls.scale
                grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
        with jax.named_scope(scopes.GRAD_SYNC):
            new_bstats = jax.tree_util.tree_map(
                lambda s: jax.lax.psum(s * count, "data") / denom, new_bstats
            )
            if graph:
                new_bstats = jax.lax.pmean(new_bstats, "graph")
            loss_sum = jax.lax.psum(loss * count, "data")
            rmses_sum = jax.lax.psum(rmses * count, "data")
        with jax.named_scope(scopes.OPTIMIZER):
            updates, new_opt = optimizer.update(
                grads, state.opt_state, state.params
            )
            new_params = jax.tree_util.tree_map(
                lambda p, u: p + u, state.params, updates
            )
        metrics = {"loss": loss_sum, "rmses": rmses_sum, "count": count_total}
        new_ls = ls
        if scaled or guard:
            with jax.named_scope(scopes.OPTIMIZER):
                # Post-reduction flag: every shard computes the SAME verdict from
                # the reduced values, so skip/keep (and the scale update) apply
                # in lockstep — no shard can diverge.
                ok = _all_finite(loss_sum, grads)
                new_params = _keep_if(ok, new_params, state.params)
                new_opt = _keep_if(ok, new_opt, state.opt_state)
                new_bstats = _keep_if(ok, new_bstats, state.batch_stats)
                okf = ok.astype(jnp.float32)
                metrics = {
                    "loss": jnp.where(ok, loss_sum, 0.0),
                    "rmses": jnp.where(ok, rmses_sum, jnp.zeros_like(rmses_sum)),
                    "count": count_total * okf,
                }
                if scaled:
                    new_ls, grew = loss_scale_update(ls, ok, loss_scaling)
                    metrics["overflow"] = 1.0 - okf
                    metrics["scale_growths"] = grew.astype(jnp.float32)
                if guard:
                    metrics["bad"] = 1.0 - okf
        new_state = TrainState(
            params=new_params,
            batch_stats=new_bstats,
            opt_state=new_opt,
            step=state.step + 1,
            loss_scale=new_ls,
        )
        return new_state, metrics

    return body


def make_train_step_dp(
    model: HydraGNN,
    optimizer,
    mesh,
    donate: bool = True,
    guard: bool = False,
    loss_scaling=None,
    grad_sync: str = "single",
    grad_bucket_mb: float = 4.0,
) -> Callable:
    """SPMD step over a ('data', 'graph') mesh. ``batch`` arrays carry a leading
    device axis [D, ...] dealt over 'data'; when the model was built with
    graph_axis='graph' and the mesh has a nontrivial 'graph' axis, edges are
    additionally sharded over 'graph'. Grads are pmean'd over BOTH axes — with
    JAX's psum-transposes-to-psum rule this recovers the exact full gradient
    (replicated node contributions stay unscaled, edge-shard contributions sum).

    ``grad_sync`` selects the gradient-reduction arm (graftmesh,
    docs/DISTRIBUTED.md): "single" (default) reduces the whole tree in one
    psum after the full backward — the historical step, byte-identical;
    "bucketed" / "ring" dispatch per-bucket collectives as each backward
    segment completes (``grad_bucket_mb`` sizes the buckets), overlapping
    all-reduce with backward compute. ``loss_scaling`` arms the bf16 dynamic
    loss-scale state machine with the backoff update in lockstep post-psum."""
    from ..parallel.overlap import resolve_grad_sync
    from ..utils.optimizer import ValueFnTransformation

    if isinstance(optimizer, ValueFnTransformation):
        raise NotImplementedError(
            "LBFGS is not supported in the distributed (mesh) train step: the "
            "zoom linesearch would evaluate per-shard losses and diverge "
            "across devices. Use a first-order optimizer (AdamW) for "
            "distributed runs, or LBFGS on a single device."
        )
    grad_sync = resolve_grad_sync(grad_sync)
    graph_sharded = model.graph_axis is not None and mesh.shape.get("graph", 1) > 1
    grad_axes = ("data", "graph") if graph_sharded else ("data",)
    if loss_scaling is not None or grad_sync != "single":
        _local = _dp_local_graftmesh(
            model, optimizer, guard, loss_scaling, grad_sync,
            float(grad_bucket_mb), grad_axes, int(mesh.shape["data"]),
        )
        return _wrap_dp_step(_local, mesh, graph_sharded, donate)

    def _local(state, batch, rng):
        # Inside shard_map the leading device axis is size 1: drop it.
        batch = jax.tree_util.tree_map(lambda x: x[0], batch)
        dropout_key = jax.random.fold_in(
            rng, state.step * 1000 + jax.lax.axis_index("data")
        )
        grad_fn = jax.value_and_grad(
            lambda p: _loss_and_metrics(model, p, state.batch_stats, batch, dropout_key),
            has_aux=True,
        )
        (loss, (new_bstats, rmses)), grads = grad_fn(state.params)
        count = batch.count_real_graphs().astype(jnp.float32)
        # Gradient allreduce (the DDP-allreduce analog, over ICI), weighted by
        # real-graph count so all-masked tail-padding batches contribute zero
        # weight instead of diluting the step (count=0 ⇒ zero numerator term).
        with jax.named_scope(scopes.GRAD_SYNC):
            count_total = jax.lax.psum(count, "data")
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.psum(g * count, "data")
                / jnp.maximum(count_total, 1.0),
                grads,
            )
            new_bstats = jax.tree_util.tree_map(
                lambda s: jax.lax.psum(s * count, "data")
                / jnp.maximum(count_total, 1.0),
                new_bstats,
            )
            if "graph" in grad_axes:
                # Edge-shard contributions sum under pmean (psum-transpose
                # rule).
                grads = jax.lax.pmean(grads, "graph")
                new_bstats = jax.lax.pmean(new_bstats, "graph")
            loss_sum = jax.lax.psum(loss * count, "data")
            rmses_sum = jax.lax.psum(rmses * count, "data")
            count_sum = jax.lax.psum(count, "data")
        with jax.named_scope(scopes.OPTIMIZER):
            updates, new_opt = optimizer.update(
                grads, state.opt_state, state.params
            )
            new_params = jax.tree_util.tree_map(
                lambda p, u: p + u, state.params, updates
            )
            metrics = {"loss": loss_sum, "rmses": rmses_sum, "count": count_sum}
            if guard:
                # Checked AFTER the psum: a NaN on any shard propagates into
                # the reduced grads/metrics, so every device computes the
                # SAME flag and skips (or keeps) the replicated state update
                # in lockstep.
                ok = _all_finite(loss_sum, grads)
                new_params = _keep_if(ok, new_params, state.params)
                new_opt = _keep_if(ok, new_opt, state.opt_state)
                new_bstats = _keep_if(ok, new_bstats, state.batch_stats)
                okf = ok.astype(jnp.float32)
                metrics = {
                    "loss": jnp.where(ok, loss_sum, 0.0),
                    "rmses": jnp.where(ok, rmses_sum, jnp.zeros_like(rmses_sum)),
                    "count": count_sum * okf,
                    "bad": 1.0 - okf,
                }
        new_state = TrainState(
            params=new_params,
            batch_stats=new_bstats,
            opt_state=new_opt,
            step=state.step + 1,
            loss_scale=state.loss_scale,
        )
        return new_state, metrics

    return _wrap_dp_step(_local, mesh, graph_sharded, donate)


def _wrap_dp_step(local, mesh, graph_sharded: bool, donate: bool):
    """shard_map + jit wrapper shared by every DP train-step arm (one
    definition so the graftmesh arms and the historical body can never
    diverge in specs/donation/platform pinning)."""
    platform = _mesh_platform(mesh)

    def step(state, batch, rng):
        # Tracing happens inside this call: pin the aggregation's platform to
        # the mesh's for the duration. The root scope is the
        # one-device step's (telemetry/scopes.py): one name a program.
        with platform_override(platform), jax.named_scope(scopes.TRAIN_STEP):
            sharded = jax.shard_map(
                local,
                mesh=mesh,
                in_specs=(P(), _batch_pspec(batch, graph_sharded), P()),
                out_specs=(P(), P()),
                check_vma=False,
            )
            return sharded(state, batch, rng)

    return jax.jit(step, donate_argnums=(0,) if donate else ())


def make_eval_step_dp(model: HydraGNN, mesh) -> Callable:
    graph_sharded = model.graph_axis is not None and mesh.shape.get("graph", 1) > 1

    def _local(state, batch):
        batch = jax.tree_util.tree_map(lambda x: x[0], batch)
        outputs = _apply_model(
            model, state.params, state.batch_stats, batch, train=False
        )
        loss, rmses = _model_loss(model, outputs, batch)
        count = batch.count_real_graphs().astype(jnp.float32)
        metrics = {
            "loss": jax.lax.psum(loss * count, "data"),
            "rmses": jax.lax.psum(rmses * count, "data"),
            "count": jax.lax.psum(count, "data"),
        }
        outputs = [o[None] for o in outputs]  # restore device axis for gather
        return metrics, outputs

    platform = _mesh_platform(mesh)

    def step(state, batch):
        with platform_override(platform), jax.named_scope(scopes.EVAL_STEP):
            sharded = jax.shard_map(
                _local,
                mesh=mesh,
                in_specs=(P(), _batch_pspec(batch, graph_sharded)),
                out_specs=(P(), [P("data") for _ in model.output_dim]),
                check_vma=False,
            )
            return sharded(state, batch)

    return jax.jit(step)


def stack_batches(batches: Sequence[GraphBatch], n_devices: int) -> GraphBatch:
    """Stack per-device GraphBatches along a new leading axis, padding the tail
    with empty (all-masked) batches so every device has work every step."""
    batches = list(batches)
    template = batches[0]
    while len(batches) < n_devices:
        empty = jax.tree_util.tree_map(lambda x: np.zeros_like(x), template)
        empty = empty.replace(
            senders=np.full_like(template.senders, template.num_nodes_pad - 1),
            receivers=np.full_like(template.receivers, template.num_nodes_pad - 1),
            node_graph=np.full_like(template.node_graph, template.num_graphs_pad - 1),
        )
        batches.append(empty)
    return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *batches)
