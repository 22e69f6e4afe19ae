"""Epoch loop + train/validate/test
(reference /root/reference/hydragnn/train/train_validate_test.py:32-304).

Per epoch: loader.set_epoch (DP reshuffle) → train over all batches → validate →
test → plateau-scheduler step on validation RMSE → TensorBoard scalars + history.
Deviations from the reference, on purpose: eval metrics are reduced across all
devices/processes (the reference reports per-rank-local averages, SURVEY.md §3.4),
and the TensorBoard writer actually works (model.py:50-54 quirk)."""

from __future__ import annotations

import contextlib
import logging
import os
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.sentinel import compile_count
from ..graphs.batch import GraphBatch
from ..models.base import HydraGNN
from ..models.token_routed import COUNTERS
from ..utils.optimizer import ReduceLROnPlateau, get_learning_rate, set_learning_rate
from ..telemetry import graftel as telemetry
from ..telemetry.stall import RUN_DELAY, StallAccount
from ..utils.print_utils import iterate_tqdm, print_distributed
from ..utils.profile import Profiler
from ..utils.time_utils import Timer
from .pipeline import (  # noqa: F401  (_Prefetcher re-exported for compat)
    DeviceFeed,
    FeedStats,
    _Prefetcher,
    traced_batches,
)
from .trainer import (
    TrainState,
    _batch_pspec,
    make_eval_step,
    make_eval_step_dp,
    make_train_epoch_scan,
    make_train_step,
    make_train_step_dp,
    stack_batches,
    state_donation_safe,
)


# One pump per process: started lazily by the first supervised epoch loop.
_heartbeat_pump_started = False


def _start_supervisor_heartbeat_pump() -> None:
    """graftelastic child-side liveness (docs/DISTRIBUTED.md "Elastic
    runbook"): under an elastic supervisor (``HYDRAGNN_ELASTIC_COORD``), a
    daemon timer thread beats every ``heartbeat_s / 4`` for the PROCESS
    lifetime — liveness must not depend on epoch cadence, or a
    compile-inflated first epoch (XLA compiles dwarf the steady wall) and
    the beat-less post-loop finalization would read as hangs. The per-epoch
    beat below still runs for epoch attribution in the coordinator log."""
    global _heartbeat_pump_started
    if _heartbeat_pump_started or not os.environ.get("HYDRAGNN_ELASTIC_COORD"):
        return
    _heartbeat_pump_started = True
    import threading

    try:
        hb = float(os.environ.get("HYDRAGNN_ELASTIC_HEARTBEAT_S") or 5.0)
    except ValueError:
        hb = 5.0
    interval = max(0.2, hb / 4.0)

    def pump() -> None:
        while True:
            _post_supervisor_heartbeat(None)
            time.sleep(interval)

    threading.Thread(
        target=pump, name="elastic-heartbeat-pump", daemon=True
    ).start()


def _post_supervisor_heartbeat(epoch: Optional[int] = None) -> None:
    """One best-effort beat into the elastic supervisor's coordinator
    mailbox (no-op without ``HYDRAGNN_ELASTIC_COORD``). Best-effort by
    design — a beat that cannot land is exactly the signal the supervisor's
    heartbeat deadline exists to catch, and a failed post must never take
    down the training it reports on."""
    addr = os.environ.get("HYDRAGNN_ELASTIC_COORD")
    if not addr:
        return
    from ..parallel.loopback import LoopbackError, ProxyRendezvous

    rank = jax.process_index()
    try:
        ProxyRendezvous.post(
            addr,
            "heartbeat",
            rank=rank,
            payload={"wid": f"proc{rank}", "epoch": epoch, "pid": os.getpid()},
            timeout_s=5.0,
            connect_retries=1,
        )
    except (LoopbackError, OSError):
        pass  # missed beat == the supervisor's deadline does its job


# The leaf phases that partition an ``epoch`` span on the dispatching thread;
# what they leave of its wall lay under no leaf. ``train_epoch`` and
# ``evaluate`` contain them.
EPOCH_LEAVES = (
    "epoch_head", "feed_wait", "device_step", "feed_drain", "eval_step",
    "epoch_tail",
)
_EPOCH_CONTAINERS = ("epoch", "train_epoch", "evaluate")
NO_LEAF = "(no leaf)"
# What a step's two clock readings book beside the span names: the seconds
# from a ``device_step`` / ``eval_step`` span's opening to ``_dispatch``'s
# return, and those of the blocking readback after it.
DISPATCH_S, WAIT_S = "dispatch_s", "wait_s"
_FEED_END = object()  # what a pull returns from an exhausted feed

# Batches a dispatch on the scan path: ONE constant for every model (no
# config key, no environment variable). A chunk is handed to the chip once
# this many batches of its shape are collated, so an epoch's first wait is
# SCAN_CHUNK collations and one chunk's transfer (at the 64 it was, with
# 12-45 batches an epoch, it was the whole epoch's collation with the chip
# idle), and each dispatch pays one dispatch-and-readback gap: 1.5-3 ms in
# the graph cells, 6-10 where the state is gigabytes (the token cells).
# 4 is where the sum over the benchmark's graph cells is largest with the
# token cells inside their spread (PERF.md section 6, PR 36:
# benchmarks/scan_chunk_lengths.py over 1, 2, 4, 8 and 64 on the chip).
SCAN_CHUNK = 4

_log = logging.getLogger(__name__)


def _pad_fallbacks(loader) -> int:
    """``fallback_batches`` of a loader's ``padding_stats()``; 0 for a batch
    source that keeps no such books."""
    stats = getattr(loader, "padding_stats", None)
    return stats()["fallback_batches"] if stats is not None else 0


class EpochAccount(StallAccount):
    """The last epochs' ``epoch`` walls, and each span name's seconds in
    them: the difference of graftel's running totals across the epoch, all
    threads, beside the dispatching thread's own three (every step's dispatch
    and readback wait, and how long the thread was runnable and not run).
    Kept by the driver, so it lives across ``train_validate_test`` calls (a
    caller may run one epoch a call). An epoch far over the median of those
    before it emits ``train/epoch_stall`` with where its seconds went, dumps
    the flight recorder and logs one warning line (the rule:
    ``telemetry/stall.py``)."""

    def __init__(self):
        super().__init__(
            "epoch", "train/epoch_stall", "train/epoch_stalls", "epoch_stall",
            _log, dispatch=(DISPATCH_S,), wait=(WAIT_S,),
            containers=_EPOCH_CONTAINERS,
        )
        self.open()

    def open(self) -> None:
        """An epoch begins: the totals and the thread's turn as they stand."""
        self._before = telemetry.span_totals()
        self._sched0 = telemetry.thread_sched()
        self._turn = {"run_delay_s": None, "nivcsw": None}
        self._steps = {DISPATCH_S: 0.0, WAIT_S: 0.0}
        self._longest = None

    def note_step(self, kind, index, dispatch_s, wait_s, run_delay_s, nivcsw):
        """One ``device_step`` / ``eval_step`` of the open epoch; the longest
        is kept whole (a stalled epoch names it)."""
        self._steps[DISPATCH_S] += dispatch_s
        self._steps[WAIT_S] += wait_s
        if self._longest is None or dispatch_s + wait_s > self._longest[0]:
            self._longest = (
                dispatch_s + wait_s,
                dict(
                    span=kind, index=index, dispatch_s=round(dispatch_s, 4),
                    wait_s=round(wait_s, 4), nivcsw=nivcsw,
                    run_delay_s=None if run_delay_s is None else round(run_delay_s, 4),
                ),
            )

    def thread_turn(self) -> dict:
        """``run_delay_s`` / ``nivcsw`` of the calling thread since ``open``:
        the ``epoch`` span's attributes, read as it closes (None for what the
        platform does not count)."""
        delay, switches = telemetry.sched_since(self._sched0)
        self._turn = {
            "run_delay_s": None if delay is None else round(delay, 6),
            "nivcsw": switches,
        }
        return self._turn

    def close(self, epoch: int, wall_s: float) -> None:
        """Book the epoch opened by ``open``, and report it if it stalled."""
        before = self._before
        seconds = {
            name: total - before.get(name, 0.0)
            for name, total in telemetry.span_totals().items()
            if total > before.get(name, 0.0)
        }
        seconds[NO_LEAF] = max(
            wall_s - sum(seconds.get(name, 0.0) for name in EPOCH_LEAVES), 0.0
        )
        seconds.update(self._steps)
        if self._turn["run_delay_s"] is not None:
            seconds[RUN_DELAY] = self._turn["run_delay_s"]
        self.book(
            epoch, wall_s, seconds, epoch=epoch, nivcsw=self._turn["nivcsw"],
            dispatch_s=round(self._steps[DISPATCH_S], 4),
            wait_s=round(self._steps[WAIT_S], 4),
            longest_step=self._longest[1] if self._longest else None,
        )

    def _extra(self, seconds, usual):
        # The leaves partition the dispatching thread's wall: the one that
        # grew most is where that thread was; the other names say why.
        phase = max(
            EPOCH_LEAVES + (NO_LEAF,),
            key=lambda name: seconds.get(name, 0.0) - usual.get(name, 0.0),
        )
        return (
            dict(phase=phase, no_leaf_s=round(seconds[NO_LEAF], 4)),
            f"; on the dispatching thread the excess is in {phase} "
            f"({seconds[NO_LEAF]:.3f} s under no leaf phase)",
        )


class EpochMetrics:
    """Graph-count-weighted averages accumulated over an epoch. The guarded
    step's extra ``bad`` metric is consumed by StepGuard (per step/chunk) and
    aggregated process-wide in FaultCounters, not here — bad steps carry zero
    ``count`` weight so the averages are already skip-correct."""

    def __init__(self):
        self.loss = 0.0
        self.rmses = None
        self.count = 0.0
        # What the routed layers of a step counted (models/token_routed.py
        # ``COUNTERS``), summed over the epoch; empty for every other model.
        self.counters = {}

    def update(self, metrics):
        self.loss += float(metrics["loss"])
        r = np.asarray(metrics["rmses"])
        self.rmses = r if self.rmses is None else self.rmses + r
        self.count += float(metrics["count"])
        for name in COUNTERS:
            if name in metrics:
                self.counters[name] = self.counters.get(name, 0.0) + float(
                    metrics[name]
                )

    def averages(self):
        c = max(self.count, 1.0)
        return self.loss / c, (
            (self.rmses / c).tolist() if self.rmses is not None else []
        )


class TrainingDriver:
    """Owns the compiled steps + scheduler/profiler state for one model run."""

    @telemetry.setup_phase("driver")
    def __init__(
        self,
        model: HydraGNN,
        optimizer,
        state: TrainState,
        mesh=None,
        verbosity: int = 0,
        fault_tolerance: Optional[dict] = None,
        fault_plan=None,
        compile_cache: Optional[str] = None,
        compile_cache_fingerprint: str = "",
        precision: Optional[str] = None,
        loss_scale: Optional[dict] = None,
        grad_sync: Optional[str] = None,
        grad_bucket_mb: Optional[float] = None,
    ):
        from ..faults import FaultPlan, StepGuard

        self.model = model
        self.optimizer = optimizer
        self.state = state
        self.mesh = mesh
        self.verbosity = verbosity
        self.n_devices = 1
        self.multihost = jax.process_count() > 1
        # Non-finite step guard (Training.fault_tolerance): None = disabled =
        # the compiled steps are built WITHOUT the flag — bit-identical to
        # the historical build. Fault injection (drills) is env/config-driven
        # and independent of the guard.
        self.guard = StepGuard.from_config(fault_tolerance, verbosity)
        self.fault_plan = (
            fault_plan if fault_plan is not None else FaultPlan.from_env()
        )
        # Checkpoint drills (corrupt_ckpt/truncate_ckpt/kill@save) ride the
        # checkpoint subsystem's post-save hook. Registered (or CLEARED — a
        # stale hook from a previous driver must never corrupt this run's
        # saves) for every driver construction.
        from ..checkpoint import set_post_save_hook

        set_post_save_hook(
            self.fault_plan.on_checkpoint_saved
            if self.fault_plan is not None and self.fault_plan.active
            else None
        )
        # Precision policy (graftprec, docs/PRECISION.md): Training.precision
        # = "bf16" clones the model onto its own compute_dtype mechanism (bf16
        # compute, f32 master weights — trainer._apply_model) and arms dynamic
        # loss scaling; "f32"/None resolves to no policy object at all, so the
        # compiled steps below are byte-identical to the seed build.
        from ..precision import (
            LossScaleMonitor,
            PrecisionPolicy,
            make_loss_scale_state,
        )

        self.precision = PrecisionPolicy.resolve(precision, loss_scale)
        self.precision_monitor = None
        loss_scaling = None
        if self.precision is not None:
            if model.compute_dtype is None:
                model = model.clone(
                    compute_dtype=self.precision.compute_dtype
                )
                self.model = model
            elif model.compute_dtype != self.precision.compute_dtype:
                # The runtime mirror of the check-config contradiction gate:
                # an explicit non-bf16 compute_dtype under precision='bf16'
                # would silently train at that dtype with pointless loss
                # scaling armed — never proceed on a lie.
                raise ValueError(
                    f"Training.precision='{self.precision.mode}' contradicts "
                    f"Architecture.compute_dtype={model.compute_dtype!r} — "
                    "unset compute_dtype (the policy sets it) or pin it to "
                    f"{self.precision.compute_dtype!r}"
                )
            state = state.replace(
                loss_scale=make_loss_scale_state(self.precision.loss_scale)
            )
            self.state = state
            loss_scaling = self.precision.loss_scale
            self.precision_monitor = LossScaleMonitor(verbosity)
        guard = self.guard is not None
        # graftmesh gradient-sync arm (Training.grad_sync, docs/
        # DISTRIBUTED.md): "single" (default) is the historical one-psum
        # step; "bucketed"/"ring" overlap per-bucket all-reduce with the
        # backward. Resolved here so a bad knob fails at driver build, not
        # mid-epoch inside a trace.
        from ..parallel.overlap import DEFAULT_BUCKET_MB, resolve_grad_sync

        self.grad_sync = resolve_grad_sync(grad_sync)
        self.grad_bucket_mb = float(
            grad_bucket_mb if grad_bucket_mb is not None else DEFAULT_BUCKET_MB
        )
        if self.grad_sync != "single" and mesh is None:
            # The knob selects the MESH step's reduction arm; on a
            # single-device driver it would be silently ignored — say so
            # loudly (and below, keep it OUT of the cache flags so the
            # compiled single-device program keeps its warm store entries).
            import warnings

            warnings.warn(
                f"Training.grad_sync={self.grad_sync!r} has no effect "
                "without a device mesh (single-device run) — the knob "
                "selects the distributed step's gradient-reduction arm",
                RuntimeWarning,
                stacklevel=2,
            )
        if mesh is not None:
            # Each process stacks only its LOCAL slice of the data axis; the
            # stacked host-local array is lifted to a global jax.Array below —
            # otherwise every host would feed its own copy and devices would
            # silently take non-matching slices.
            self.n_devices = (
                mesh.local_mesh.shape["data"] if self.multihost
                else mesh.shape["data"]
            )
            donate = state_donation_safe(state)
            self.train_step = make_train_step_dp(
                model, optimizer, mesh, donate, guard=guard,
                loss_scaling=loss_scaling,
                grad_sync=self.grad_sync,
                grad_bucket_mb=self.grad_bucket_mb,
            )
            self.eval_step = make_eval_step_dp(model, mesh)
        else:
            donate = state_donation_safe(state)
            self.train_step = make_train_step(
                model, optimizer, donate, guard=guard,
                loss_scaling=loss_scaling,
            )
            self.eval_step = make_eval_step(model)
            self.epoch_scan = make_train_epoch_scan(
                model, optimizer, donate, guard=guard,
                loss_scaling=loss_scaling,
            )
        # The scan path steps ``scan_chunk`` batches a dispatch (SCAN_CHUNK,
        # above, has the reason for its value). Tests set the attribute.
        self.scan_chunk = SCAN_CHUNK
        self.rng = jax.random.PRNGKey(0)
        # Device-resident batch caches (reshuffle="batch" train loaders and
        # static eval loaders): id(loader) -> {"loader": strong ref (keeps
        # the id stable), "chunks"/"batches": device pytrees} or None once a
        # loader is known to exceed the byte budget. Batches are never
        # donated by the compiled steps, so reuse is safe.
        self._scan_cache: dict = {}
        self._eval_cache: dict = {}
        # Permuted replay of a cached chunk, compiled: the within-chunk order
        # shuffle rides INSIDE the jit (one dispatch, fused gather) instead
        # of eager per-leaf gathers. State is donated like epoch_scan; the
        # cached payload must NOT be (it is reused every epoch).
        self._perm_scan = None
        if mesh is None:
            self._perm_scan = jax.jit(
                lambda s, p, perm, count, rng: self.epoch_scan(
                    s, jax.tree_util.tree_map(lambda x: x[perm], p), count, rng
                ),
                donate_argnums=(0,),
            )
        # Persistent compiled-executable store (graftcache, docs/
        # COMPILE_CACHE.md): ALL compiled steps — the single-device train_step
        # / epoch_scan / perm_scan / eval_step AND the shard_map mesh steps
        # (graftmesh) — dispatch through the shared ExecutableRegistry — the
        # same locked lookup→compile-outside-lock→store path the serve engine
        # uses — so a crash-resumed or restarted run hydrates its train
        # compile from disk in well under a second. Mesh programs carry the
        # mesh axis layout as a CacheKey component (a 4-device step must
        # never hydrate a 2-device executable; the environment topology
        # string already pins the device count). Opt-in
        # (Training.compile_cache / HYDRAGNN_COMPILE_CACHE); disabled = the
        # dispatch helper is a pass-through to the jit wrappers,
        # byte-identical to the historical path.
        cache_dir = (
            compile_cache
            if compile_cache is not None
            else os.environ.get("HYDRAGNN_COMPILE_CACHE", "")
        )
        self._exec_registry = None
        self._cache_fingerprint = ""
        self._cache_flags: tuple = ()
        self._cache_mesh = ""
        self._cache_devices = None  # the default device
        if mesh is not None:
            from ..parallel.distributed import mesh_descriptor

            self._cache_mesh = mesh_descriptor(mesh)
            self._cache_devices = tuple(d.id for d in mesh.devices.flat)
        if cache_dir:
            import hashlib

            from ..cache import ExecutableRegistry, ExecutableStore
            from ..checkpoint.format import param_fingerprint

            self._exec_registry = ExecutableRegistry(
                ExecutableStore(cache_dir), name="train"
            )
            # Program identity: the caller's config digest (run_training
            # hashes the Architecture + optimizer blocks) on top of the
            # checkpoint layer's param/opt-state tree fingerprints and the
            # module field repr — any model/optimizer change is a miss.
            self._cache_fingerprint = hashlib.sha256(
                (
                    compile_cache_fingerprint
                    + param_fingerprint(state.params)
                    + param_fingerprint(
                        {"opt": state.opt_state, "bstats": state.batch_stats}
                    )
                    + repr(model)
                ).encode()
            ).hexdigest()
            self._cache_flags = (
                (("donate",) if donate else ())
                + (("guard",) if guard else ())
                # Precision is a program-mode key component: a bf16 step and
                # the f32 seed step must NEVER hydrate each other's entries
                # (docs/PRECISION.md "Cache-key interaction").
                + (
                    (f"precision={self.precision.mode}",)
                    if self.precision is not None
                    else ()
                )
                # The gradient-sync arm AND its bucket size change the
                # compiled MESH program (plan_buckets groups leaves into
                # different per-bucket collectives) without changing any tree
                # shape; on a single-device driver the knob is inert and must
                # not cool a warm store (byte-identical program, same key).
                + (
                    (
                        f"grad_sync={self.grad_sync}"
                        f":bucket_mb={self.grad_bucket_mb}",
                    )
                    if self.grad_sync != "single" and mesh is not None
                    else ()
                )
            )
        # Whether the 'graph' mesh axis is active (edge arrays then need the
        # P('data','graph') placement the sharded step expects).
        self._graph_sharded = (
            mesh is not None
            and model.graph_axis is not None
            and mesh.shape.get("graph", 1) > 1
        )
        # Per-epoch transfer-vs-compute split of the LAST epoch-level call
        # (train_epoch / evaluate): filled by the device-feed pipeline,
        # credited into the Timer registry, read by bench.py.
        self.feed_stats = FeedStats()
        # The last epochs' walls and where their seconds went: says when an
        # epoch stalled and in what (``train_validate_test`` books each one).
        self.epoch_account = EpochAccount()
        # Batch structure -> NamedSharding tree. Written from the
        # transfer thread AND the main-thread eval path; safe without a
        # lock because it is an idempotent memo (the value for a key is
        # deterministic, dict get/set are single-bytecode atomic under
        # the GIL, and a racing duplicate store just re-memoizes).
        self._sharding_trees: dict = {}  # guarded-by: none(idempotent memo; deterministic value per key; GIL-atomic dict ops; duplicate store is a benign re-memoization)

    # -------------------------------------------------- per-update host hooks
    def _after_update(self, metrics) -> None:
        """The host half of the step policies, once per step (streamed path)
        or per scan chunk: the precision monitor folds the summed overflow/
        growth metrics into telemetry (train/loss_scale gauge, prec/*
        counters, backoff flight event), then StepGuard runs its skip/rollback
        streak accounting — in that order, so a rollback's flight dump
        already carries the scale movement that preceded it."""
        if self.precision_monitor is not None:
            self.precision_monitor.after_update(self, metrics)
        if self.guard is not None:
            self.guard.after_update(self, metrics)

    # ------------------------------------------------- compiled-step dispatch
    def _dispatch(self, program: str, fn, shape_key, *args):
        """Route one compiled-step call through the shared
        :class:`~hydragnn_tpu.cache.ExecutableRegistry` when the persistent
        compile cache is enabled; otherwise call the jit wrapper directly
        (byte-identical to the historical path — the registry is the ONLY
        behavioral delta, and a cache-hit executable is bit-exact against a
        fresh compile, tests/test_compile_cache.py).

        ``shape_key`` is the caller's CHEAP signature of the varying
        arguments (the payload batch's padded shapes — state/rng structure
        is constant per driver, and the registry is per-driver): steady-state
        memory hits pay one tuple build, never fingerprint arithmetic. The
        full args-tree digest and environment key are computed lazily inside
        the miss closure only."""
        reg = self._exec_registry
        if reg is None:
            return fn(*args)
        from ..cache import CacheKey, tree_signature

        exe, _outcome, _seconds = reg.lookup_or_compile(
            (program, shape_key),
            lambda: CacheKey.for_environment(
                program=program,
                config_fingerprint=self._cache_fingerprint,
                flags=self._cache_flags,
                args_digest=tree_signature(args),
                mesh=self._cache_mesh,
                devices=self._cache_devices,
            ),
            lambda: fn.lower(*args),
        )
        return exe(*args)

    @staticmethod
    def _dispatch_shape_key(batch: GraphBatch):
        """Cheap per-batch signature for _dispatch's in-memory key: padded
        array shapes plus the head-spec layout (targets change with
        set_head_spec without moving node shapes — they must miss)."""
        return (
            batch.node_features.shape,
            batch.senders.shape,
            batch.num_graphs_pad,
            batch.edge_features is None,
            tuple(t.shape for t in batch.targets),
        )

    def _run_step(self, step, metrics, reply: int, program, fn, shape_key, *args):
        """One compiled program under the OPEN span ``step`` (a
        ``device_step`` or an ``eval_step``), its dispatch told from its
        wait: the clock is read once between ``_dispatch``'s return and the
        blocking readback (``metrics.update`` of ``out[reply]``), and the
        thread's turn (``telemetry.thread_sched``) at the two ends. The
        record carries ``dispatch_s`` (span open -> ``_dispatch`` returned:
        cache lookup, argument handling, launch), ``wait_s`` (the readback;
        the two add to ``dur_s`` to the clock's grain), ``run_delay_s`` and
        ``nivcsw`` (None for what the platform does not count); no span opens
        in here (a child on the dispatching thread would be the leaf the
        device's programs are booked to). The caller books the closed span
        (``_book_step``)."""
        sched0 = telemetry.thread_sched()
        out = self._dispatch(program, fn, shape_key, *args)
        dispatch_s = step.elapsed_s()
        metrics.update(out[reply])
        run_delay_s, nivcsw = telemetry.sched_since(sched0)
        step.attrs.update(
            dispatch_s=dispatch_s, wait_s=step.elapsed_s() - dispatch_s,
            run_delay_s=run_delay_s, nivcsw=nivcsw,
        )
        return out

    def _book_step(self, step) -> None:
        """A closed ``device_step`` / ``eval_step`` in the running accounts:
        its seconds to ``FeedStats.step_s`` (the span's one clock pair), its
        split to the counters ``train/dispatch_s``, ``train/readback_wait_s``
        and, where the platform counts it, ``host/run_delay_s`` (live with
        collection off, in Prometheus)
        and to the open epoch's account. After the span, so that nothing but
        the program lies between the readback's end and the span's."""
        self.feed_stats.credit("step_s", step.dur_s)
        a = step.attrs
        telemetry.counter("train/dispatch_s", a["dispatch_s"])
        telemetry.counter("train/readback_wait_s", a["wait_s"])
        if a["run_delay_s"] is not None:
            telemetry.counter("host/run_delay_s", a["run_delay_s"])
        self.epoch_account.note_step(
            step.name, a.get("index"), a["dispatch_s"], a["wait_s"],
            a["run_delay_s"], a["nivcsw"],
        )

    # ----------------------------------------------------------- device feed
    def _sharding_tree(self, batch):
        """NamedSharding tree matching the placement the sharded step expects
        (the same _batch_pspec its shard_map uses), so the pipeline's
        device_put commits arrays exactly where the step reads them.
        Shardings are shape-agnostic, so the tree is memoized per batch
        STRUCTURE (edge presence, head count, static pad) — the transfer
        thread must not rebuild ~10 NamedShardings per batch."""
        from jax.sharding import NamedSharding, PartitionSpec

        key = (
            batch.edge_features is None,
            len(batch.targets),
            batch.num_graphs_pad,
        )
        cached = self._sharding_trees.get(key)
        if cached is None:
            spec = _batch_pspec(batch, self._graph_sharded)
            cached = jax.tree_util.tree_map(
                lambda s: NamedSharding(self.mesh, s),
                spec,
                is_leaf=lambda x: isinstance(x, PartitionSpec),
            )
            self._sharding_trees[key] = cached
        return cached

    def _wrap_faults(self, iterable):
        """Route a host batch source through the fault plan's injection hooks
        (NaN batches, collation stalls, process kill) — identity when no plan
        is active. Sits on the pipeline's host thread, BEFORE chunk stacking
        and transfer, on every train path."""
        if self.fault_plan is None or not self.fault_plan.active:
            return iterable
        return self.fault_plan.wrap_batches(iterable)

    def _put_timed(self, payload):
        """The transfer stage: ONE blocking device_put per payload, on the
        pipeline's transfer thread. Batch k+1 commits (DMA) while step k
        computes; blocking here records true wire seconds, not dispatch.
        Transient failures (including the fault plan's injected transfer
        crashes, consulted here) are retried by the DeviceFeed's backoff
        wrapper around this function. The wire time is the graftel ``h2d``
        span, parented to the epoch context the DeviceFeed attached to this
        thread (a host event of the trace when one is open), and the same
        seconds are what ``FeedStats`` adds up."""
        if self.fault_plan is not None:
            self.fault_plan.on_transfer()
        nbytes = self._tree_nbytes(payload)
        with telemetry.span("h2d", bytes=int(nbytes)) as span:
            t0 = time.perf_counter()
            if self.multihost:
                dev = self._lift(payload)
            elif self.mesh is not None:
                dev = jax.device_put(payload, self._sharding_tree(payload))
            else:
                dev = jax.device_put(payload)
            jax.block_until_ready(dev)
            span.attrs["index"] = self.feed_stats.record_h2d(
                nbytes, time.perf_counter() - t0
            )
        return dev

    def _put_chunk(self, item):
        """One transfer a chunk: the stack and its count of real batches go
        together, so the dispatch finds both on the device."""
        steps, stacked = item
        return steps, self._put_timed((stacked, np.asarray(steps, np.int32)))

    def _drain_feed(self, feed, label: str):
        """End-of-epoch teardown: cancel the pipeline and give its threads a
        bounded window to exit BEFORE the stats are credited/reset — an
        in-flight transfer completing later must not record H2D into the
        next epoch's split (the join is bounded so a transfer wedged on a
        dead device link cannot hang the caller)."""
        with telemetry.span("feed_drain"):
            feed.close()
            feed.join(2.0)
        self._credit_timers(label)

    def _pulls(self, feed):
        """Iterate a device feed with every blocking pull a ``feed_wait``
        span, credited to ``FeedStats.feed_wait_s``: batch ACQUISITION (the
        device-queue wait, where an input-bound pipeline actually stalls;
        collation, the multi-host lift and the H2D transfer already happened
        on the pipeline threads). The last one finds the feed exhausted."""
        it = iter(feed)
        while True:
            with telemetry.span("feed_wait") as wait:
                item = next(it, _FEED_END)
            self.feed_stats.credit("feed_wait_s", wait.dur_s)
            if item is _FEED_END:
                return
            yield item

    def _credit_timers(self, label: str):
        """Fold the epoch's split into the Timer registry (print_timers)."""
        s = self.feed_stats
        if s.h2d_transfers:
            Timer.credit(f"{label}_h2d_transfer", s.h2d_s)
        if s.step_s:
            Timer.credit(f"{label}_device_step", s.step_s)
        if s.feed_wait_s:
            Timer.credit(f"{label}_feed_wait", s.feed_wait_s)

    @staticmethod
    def _cache_budget_bytes() -> int:
        import os

        return int(os.environ.get("HYDRAGNN_DEVICE_CACHE_MB", "512")) * (1 << 20)

    @staticmethod
    def _tree_nbytes(tree) -> int:
        return sum(
            getattr(leaf, "nbytes", 0) for leaf in jax.tree_util.tree_leaves(tree)
        )

    # ------------------------------------------------------------------ train
    @staticmethod
    def _shape_key(batch: GraphBatch):
        return (
            batch.node_features.shape,
            batch.senders.shape,
            batch.num_graphs_pad,
        )

    def _device_groups(self, loader):
        """Lazily yield per-device batch groups stacked for shard_map. Used for
        ANY mesh run (even data_axis=1 — the sharded step always expects the
        leading device axis). Bucketed loaders emit several static shapes;
        groups are formed per shape (tail groups are padded with empty
        batches by stack_batches)."""
        groups: dict = {}
        for b in loader:
            key = self._shape_key(b)
            group = groups.setdefault(key, [])
            group.append(b)
            if len(group) == self.n_devices:
                # Host-side numpy only — the TRANSFER stage lifts to device
                # arrays one group at a time (bounded device queue), so the
                # host prefetch queue never pins HBM.
                yield stack_batches(group, self.n_devices)
                groups[key] = []
        for group in groups.values():
            if group:
                yield stack_batches(group, self.n_devices)

    def _lift(self, stacked):
        """Host-local stacked batch → global jax.Array across processes."""
        if not self.multihost:
            return stacked
        from jax.experimental import multihost_utils
        from jax.sharding import PartitionSpec as P

        return multihost_utils.host_local_array_to_global_array(
            stacked, self.mesh, P("data")
        )

    def train_epoch(self, loader, profiler: Optional[Profiler] = None):
        fallbacks = _pad_fallbacks(loader)
        averages = self._train_epoch(loader, profiler)
        # Batches of this epoch that did not fit their bucket's shape and took
        # the worst-case one: each new such shape is one compiled program.
        telemetry.gauge(
            "train/pad_fallback_batches_per_epoch",
            _pad_fallbacks(loader) - fallbacks,
        )
        return averages

    def _train_epoch(self, loader, profiler):
        self.feed_stats.reset()
        if self.guard is not None:
            # Epoch-start last-good snapshot: the rollback target (taken
            # before the donating step can consume these buffers).
            self.guard.begin_epoch(self)
        # The epoch-level telemetry span: its context is handed to the
        # DeviceFeed threads so collate/h2d spans parent here (the
        # flight-recorder timeline a guard-trip dump carries).
        with telemetry.span(
            "train_epoch", epoch=getattr(loader, "epoch", None)
        ) as ep:
            # Scan path only when nothing needs per-step host hooks.
            if self.mesh is None and not (profiler and profiler.active):
                return self._train_epoch_scan(loader, ep.ctx)
            metrics = EpochMetrics()
            # Two-stage device feed: collation thread -> transfer thread
            # (device_put with the step's placement) -> this consumer. Batch
            # k+1 is committed device memory while step k executes.
            batches = DeviceFeed(
                self._device_groups(
                    traced_batches(self._wrap_faults(loader))
                )
                if self.mesh is not None
                else traced_batches(self._wrap_faults(iter(loader))),
                transfer=self._put_timed,
                ctx=ep.ctx,
            )
            try:
                for bi, batch in enumerate(
                    self._pulls(iterate_tqdm(batches, self.verbosity))
                ):
                    with telemetry.span("device_step", index=bi) as step:
                        self.state, m = self._run_step(
                            step, metrics, 1, "train_step", self.train_step,
                            self._dispatch_shape_key(batch),
                            self.state, batch, self.rng,
                        )
                    self._book_step(step)
                    self._after_update(m)
                    if profiler:
                        profiler.step()
            finally:
                self._drain_feed(batches, "train")
            return self._train_epoch_done(metrics)

    def _train_epoch_done(self, metrics: "EpochMetrics", scan_chunks: int = 0):
        """The epoch's averages; its counters are published as gauges,
        ``train/<counter>_per_epoch`` (none where the model counts none),
        and the scan path's dispatches as ``train/scan_chunks_per_epoch`` (0
        on the per-batch paths)."""
        telemetry.gauge("train/scan_chunks_per_epoch", scan_chunks)
        for name, value in metrics.counters.items():
            telemetry.gauge(f"train/{name}_per_epoch", value)
        return metrics.averages()

    def _train_epoch_scan(self, loader, ctx=None):
        """The epoch as dispatches of ``scan_chunk`` steps each, buffered per
        batch shape (bucketed loaders emit a handful of static shapes). A
        chunk goes to the chip as soon as ``scan_chunk`` batches of its shape
        are collated; a shape's tail of fewer is the same stack with a smaller
        ``count`` (``make_train_epoch_scan``), so a batch shape has exactly
        ONE step program whatever the epoch's length, and a loader whose
        per-shape count moves between epochs compiles nothing. Several shapes
        interleave a chunk at a time, in the loader's own order. The tqdm bar
        (verbosity 2/4) ticks per batch as batches are consumed into chunks.

        reshuffle="batch" loaders (frozen membership) additionally get their
        stacked chunks cached ON DEVICE after the first epoch: steady-state
        epochs then do zero host collation and zero host->device transfer.
        Batch visit order still reshuffles per epoch (chunk dispatch order on
        host, plus a device-side permutation of each chunk's real slots).
        Capped by
        HYDRAGNN_DEVICE_CACHE_MB (default 512). Cache entries carry the
        loader's head-spec generation; a set_head_spec after the build makes
        the entry a miss (the device batches baked the old targets)."""
        gen = getattr(loader, "generation", None)
        cached = self._scan_cache.get(id(loader))
        if cached is not None and cached.get("generation") != gen:
            del self._scan_cache[id(loader)]
            cached = None
        if cached is not None and cached.get("chunks") is not None:
            metrics = EpochMetrics()
            rng = np.random.default_rng(
                getattr(loader, "seed", 0) + getattr(loader, "epoch", 0)
            )
            # Recompile sentinel over steady replay epochs: the FIRST replay
            # epoch legitimately compiles the permuted-replay dispatch
            # (_perm_scan); from the second on, every executable exists and a
            # compile means a static-shape contract broke. Warn (never die)
            # in production; HYDRAGNN_NO_RECOMPILE=raise hardens it for
            # benchmarks/tests, =off silences it.
            from ..analysis import no_recompile

            sentinel_action = os.environ.get("HYDRAGNN_NO_RECOMPILE", "warn")
            if sentinel_action not in ("raise", "warn", "count", "off"):
                # An observability knob must never kill a training run: a
                # typo'd value degrades to the default, not a ValueError.
                sentinel_action = "warn"
            sentinel = (
                no_recompile(action=sentinel_action, label="cached replay epoch")
                if cached.get("warm") and sentinel_action != "off"
                else contextlib.nullcontext()
            )
            with sentinel:
                for ci in rng.permutation(len(cached["chunks"])):
                    steps, (stacked, count) = cached["chunks"][ci]
                    with telemetry.span(
                        "device_step", index=int(ci), cached=True, steps=steps
                    ) as step:
                        # Batch-level order reshuffle WITHIN the chunk too —
                        # compiled into the scan dispatch (see _perm_scan), so
                        # the mode's "order reshuffles per epoch" promise holds
                        # even when the whole epoch fits one chunk. Membership
                        # and batch->chunk assignment stay frozen (the cache).
                        # Only the real slots move: a padded tail keeps its
                        # real batches in the first ``count`` slots.
                        slots = jax.tree_util.tree_leaves(stacked)[0].shape[0]
                        perm = jnp.asarray(np.concatenate(
                            [rng.permutation(steps), np.arange(steps, slots)]
                        ))
                        self.state, m = self._run_step(
                            step, metrics, 1, "perm_scan", self._perm_scan,
                            self._dispatch_shape_key(stacked),
                            self.state, stacked, perm, count, self.rng,
                        )
                    self._book_step(step)
                    self._after_update(m)
            cached["warm"] = True
            self._credit_timers("train")
            return self._train_epoch_done(metrics, len(cached["chunks"]))

        cacheable = (
            getattr(loader, "reshuffle", None) == "batch"
            # A fixed-order loader (shuffle=False) must never be replayed
            # with per-epoch permutations: the cache's replay contract IS
            # the "membership frozen, order reshuffles" mode.
            and getattr(loader, "shuffle", False)
            and self.mesh is None
            and id(loader) not in self._scan_cache  # not marked over-budget
        )
        sink: Optional[dict] = {"items": [], "bytes": 0} if cacheable else None
        metrics = EpochMetrics()
        # Two-stage device feed over stacked chunks: collation + stacking on
        # the host thread, device_put on the transfer thread, so chunk k+1
        # is committed while chunk k's scan executes. device_depth=1 (not
        # the per-batch default): payloads here are scan chunks, and one
        # queued + one transferring + one computing already bounds the
        # transient HBM at ~3 chunks while keeping the overlap.
        feed = DeviceFeed(
            self._host_chunks(loader),
            transfer=self._put_chunk,
            device_depth=1,
            ctx=ctx,
        )
        chunks = 0
        try:
            # The pull waits for a CHUNK: ``scan_chunk`` collations of one
            # shape, or the loader's end where a shape has fewer left.
            for ci, (steps, payload) in enumerate(self._pulls(feed)):
                sink = self._run_scan_chunk(
                    steps, payload, metrics, sink, index=ci
                )
                chunks += 1
        finally:
            self._drain_feed(feed, "train")
        if cacheable:
            # A None sink means the budget was blown mid-epoch. The loader
            # ref is kept EITHER WAY: the verdict is keyed by id(loader),
            # and without a strong ref a garbage-collected loader could hand
            # its id to a new loader that would silently inherit it.
            self._scan_cache[id(loader)] = {
                "loader": loader,
                "generation": gen,
                "chunks": sink["items"] if sink is not None else None,
            }
        return self._train_epoch_done(metrics, chunks)

    def _host_chunks(self, loader):
        """Stage-1 producer for the scan path: collate (loader.__iter__) and
        group batches by shape into stacks of ``scan_chunk``, yielding
        ``(real batches, host stack)`` as soon as a shape has a full chunk and
        each shape's tail when the loader ends. Runs on the pipeline's host
        thread, so numpy stacking also overlaps device compute."""
        bufs: dict = {}
        for b in traced_batches(
            self._wrap_faults(iterate_tqdm(loader, self.verbosity))
        ):
            buf = bufs.setdefault(self._shape_key(b), [])
            buf.append(b)
            if len(buf) == self.scan_chunk:
                yield self._stack_chunk(buf)
                buf.clear()
        for buf in bufs.values():
            if buf:
                yield self._stack_chunk(buf)

    def _stack_chunk(self, batches):
        """``(len(batches), a stack of scan_chunk)``: a tail's padding slots
        repeat its last batch. The counted program never reads them."""
        slots = batches + batches[-1:] * (self.scan_chunk - len(batches))
        return len(batches), stack_batches(slots, len(slots))

    def _run_scan_chunk(
        self, steps, payload, metrics, sink: Optional[dict], index: int = 0
    ):
        """Dispatch one device-resident chunk (``payload``: the stack and its
        count of real batches, ``steps`` of them); when ``sink`` is given,
        retain THE SAME device copy for the reshuffle="batch" cache — the
        pipeline already transferred it, so the cache-building epoch performs
        exactly one host->device transfer per chunk. Returns None instead once
        the byte budget is exceeded; ``sink`` carries a running byte total so
        the first (timed) epoch's bookkeeping stays O(1) per chunk."""
        stacked, count = payload
        with telemetry.span("device_step", index=index, steps=steps) as step:
            self.state, m = self._run_step(
                step, metrics, 1, "epoch_scan", self.epoch_scan,
                self._dispatch_shape_key(stacked),
                self.state, stacked, count, self.rng,
            )
        self._book_step(step)
        self._after_update(m)
        if sink is not None:
            nbytes = self._tree_nbytes(payload)
            if sink["bytes"] + nbytes <= self._cache_budget_bytes():
                sink["items"].append((steps, payload))
                sink["bytes"] += nbytes
            else:
                sink = None
        return sink

    # ------------------------------------------------------------------- eval
    def evaluate(
        self, loader, return_values: bool = False, split: Optional[str] = None
    ):
        """validate()/test() analog. With return_values, also gathers per-head
        (true, predicted) arrays over real rows (test(), reference
        train_validate_test.py:267-304). ``split`` ("val", "test") only names
        the pass on its ``evaluate`` span."""
        with telemetry.span("evaluate", split=split) as ep:
            return self._evaluate(loader, return_values, ep.ctx)

    def _evaluate(self, loader, return_values, ctx=None):
        self.feed_stats.reset()
        metrics = EpochMetrics()
        num_heads = len(self.model.output_dim)
        true_values: List[List[np.ndarray]] = [[] for _ in range(num_heads)]
        pred_values: List[List[np.ndarray]] = [[] for _ in range(num_heads)]

        def to_host(arr):
            """Local rows of a possibly multi-host global array (per-process
            values, like the reference's per-rank test() lists)."""
            if self.multihost and hasattr(arr, "addressable_shards"):
                return np.concatenate(
                    [np.asarray(s.data) for s in arr.addressable_shards]
                )
            return np.asarray(arr)

        def consume(batch_host: GraphBatch, outputs):
            for ih, (htype, out) in enumerate(
                zip(self.model.output_type, outputs)
            ):
                out = to_host(out)
                if out.ndim == 3:  # DP: [D, rows, dim] → per-device slices
                    out = out.reshape(-1, out.shape[-1])
                mask = to_host(
                    batch_host.graph_mask if htype == "graph" else batch_host.node_mask
                ).reshape(-1)
                tgt = to_host(batch_host.targets[ih]).reshape(-1, out.shape[-1])
                pred_values[ih].append(out[mask])
                true_values[ih].append(tgt[mask])

        # Static eval loaders (shuffle=False: membership AND order are fixed,
        # so caching changes nothing semantically) keep their batches device-
        # resident after the first evaluate() — the per-epoch validation pass
        # then skips collation and host->device transfer entirely. Host
        # copies ride along for consume()'s masks/targets. With or without a
        # mesh: what is held on one is the feed's own pair, the host-local
        # stacked group of _device_groups and its sharded global array.
        gen = getattr(loader, "generation", None)
        cached = self._eval_cache.get(id(loader))
        if cached is not None and cached.get("generation") != gen:
            # set_head_spec bumped the loader's generation after this cache
            # was built: the device batches baked the old head spec/targets.
            del self._eval_cache[id(loader)]
            cached = None
        if cached is not None and cached.get("batches") is not None:
            for ei, (host_b, dev_b) in enumerate(cached["batches"]):
                with telemetry.span(
                    "eval_step", index=ei, cached=True
                ) as step:
                    m, outputs = self._run_step(
                        step, metrics, 0, "eval_step", self.eval_step,
                        self._dispatch_shape_key(dev_b),
                        self.state, dev_b,
                    )
                self._book_step(step)
                if return_values:
                    consume(host_b, outputs)
            self._credit_timers("eval")
            steps = cached_steps = len(cached["batches"])
        else:
            cacheable = (
                getattr(loader, "shuffle", True) is False
                and id(loader) not in self._eval_cache
            )
            sink: Optional[dict] = {"items": [], "bytes": 0} if cacheable else None
            # Two-stage device feed, pairing each host batch (consume()'s
            # masks/targets are host-side, like the reference's per-rank
            # test() lists) with its device copy — which on a mesh is the
            # same GLOBAL [D_global, ...] lift train_epoch performs. The
            # cache sink reuses that same device copy: one transfer per
            # batch (per group on a mesh), cache build included. The budget
            # counts the host copy's bytes: this process's local rows.
            batches = DeviceFeed(
                self._device_groups(loader) if self.mesh is not None else iter(loader),
                transfer=lambda b: (b, self._put_timed(b)),
                ctx=ctx,
            )
            steps = cached_steps = 0
            try:
                for ei, (batch, dev_b) in enumerate(self._pulls(batches)):
                    steps += 1
                    with telemetry.span("eval_step", index=ei) as step:
                        m, outputs = self._run_step(
                            step, metrics, 0, "eval_step", self.eval_step,
                            self._dispatch_shape_key(dev_b),
                            self.state, dev_b,
                        )
                    self._book_step(step)
                    if return_values:
                        consume(batch, outputs)
                    if sink is not None:
                        nbytes = self._tree_nbytes(batch)
                        if sink["bytes"] + nbytes <= self._cache_budget_bytes():
                            sink["items"].append((batch, dev_b))
                            sink["bytes"] += nbytes
                        else:
                            sink = None
            finally:
                self._drain_feed(batches, "eval")
            if cacheable:
                # Keep the loader ref even on an over-budget verdict so a
                # recycled id() cannot inherit it (see _scan_cache).
                self._eval_cache[id(loader)] = {
                    "loader": loader,
                    "generation": gen,
                    "batches": sink["items"] if sink is not None else None,
                    "bytes": sink["bytes"] if sink is not None else 0,
                }
        # The pass's steps, those of them served from the cache (all or
        # none), and what every evaluation loader's cache holds by now.
        telemetry.gauge("eval/steps_per_pass", steps)
        telemetry.gauge("eval/cached_steps_per_pass", cached_steps)
        held = sum(c["bytes"] for c in self._eval_cache.values())
        telemetry.gauge("eval/cache_mb", round(held / (1 << 20), 4))

        loss, rmses = metrics.averages()
        if return_values:
            tv = [np.concatenate(v) if v else np.zeros((0, 1)) for v in true_values]
            pv = [np.concatenate(v) if v else np.zeros((0, 1)) for v in pred_values]
            return loss, rmses, tv, pv
        return loss, rmses


def train_validate_test(
    driver: TrainingDriver,
    train_loader,
    val_loader,
    test_loader,
    num_epoch: int,
    writer=None,
    scheduler: Optional[ReduceLROnPlateau] = None,
    profiler: Optional[Profiler] = None,
    verbosity: int = 0,
    visualizer=None,
    output_names: Optional[List[str]] = None,
    plot_init_solution: bool = True,
    plot_hist_solution: bool = False,
    checkpoint_name: Optional[str] = None,
    checkpoint_every: int = 0,
    checkpoint_keep_last_k: int = 0,
    checkpoint_async: bool = True,
    start_epoch: int = 0,
    history: Optional[dict] = None,
):
    """The epoch loop (train_validate_test.py:94-137). Returns the loss history
    dict consumed by the Visualizer. With a visualizer attached, mirrors the
    reference's plot hooks: graph-size histogram + initial-solution scatter
    before training (train_validate_test.py:68-85), optional per-epoch scatter
    (plot_hist_solution, :131-137)."""
    if visualizer is not None:
        visualizer.num_nodes_plot()
        if plot_init_solution:
            _, _, tv, pv = driver.evaluate(
                test_loader, return_values=True, split="test"
            )
            visualizer.create_scatter_plots(
                tv, pv, output_names=output_names, iepoch=-1
            )
    history = history or {
        "total_loss_train": [],
        "total_loss_val": [],
        "total_loss_test": [],
        "task_loss_train": [],
        "task_loss_val": [],
        "task_loss_test": [],
    }
    timer = Timer("train_validate_test")
    timer.start()
    # Cross-layer telemetry (docs/OBSERVABILITY.md): XLA compiles fold into
    # the graftel registry (jax/compiles, jax/compile_s), and each epoch
    # publishes its step/h2d/feed-wait/compile split as hydragnn_train_*
    # Prometheus gauges — the training analog of the serve /metrics surface.
    telemetry.install_jax_hooks()
    telemetry.install_gc_hook()
    # Async checkpointing (docs/CHECKPOINTING.md): periodic saves snapshot
    # device→host on this thread and hand serialize/fsync/rename to a single
    # background writer — the epoch loop stalls for the snapshot only. The
    # per-save stall (async) or full save wall (sync) is credited to the
    # ``ckpt_save_stall`` timer so print_timers/bench expose what
    # checkpointing costs the training thread.
    checkpointer = None
    if checkpoint_name and checkpoint_every > 0 and checkpoint_async:
        from ..checkpoint import AsyncCheckpointer

        checkpointer = AsyncCheckpointer()
    try:
        for epoch in range(start_epoch, num_epoch):
            driver.epoch_account.open()
            # The dispatching thread's epoch, partitioned into leaf phases
            # (EPOCH_LEAVES; docs/OBSERVABILITY.md "The host's timeline").
            # The span carries JAX's cumulative trace / lower / compile /
            # cache-load seconds as they stood when it opened and, set as it
            # closes, how long this thread was runnable and not run over its
            # whole wall (``run_delay_s``, ``nivcsw``).
            with telemetry.span(
                "epoch", epoch=epoch, **telemetry.jax_seconds()
            ) as epoch_span:
                with telemetry.span("epoch_head"):
                    _start_supervisor_heartbeat_pump()
                    _post_supervisor_heartbeat(epoch)
                    for loader in (train_loader, val_loader, test_loader):
                        if hasattr(loader, "set_epoch"):
                            loader.set_epoch(epoch)
                    if profiler:
                        profiler.set_current_epoch(epoch)
                    compile_s0 = telemetry.counter_value("jax/compile_s")
                    compiles0 = compile_count()

                t_epoch0 = time.perf_counter()
                train_loss, train_rmses = driver.train_epoch(
                    train_loader, profiler
                )
                train_wall_s = time.perf_counter() - t_epoch0
                train_split = driver.feed_stats.as_dict()
                val_loss, val_rmses = driver.evaluate(val_loader, split="val")
                test_loss, test_rmses = driver.evaluate(
                    test_loader, split="test"
                )
                if visualizer is not None and plot_hist_solution:
                    _, _, tv, pv = driver.evaluate(
                        test_loader, return_values=True, split="test"
                    )
                    visualizer.create_scatter_plots(
                        tv, pv, output_names=output_names, iepoch=epoch
                    )

                with telemetry.span("epoch_tail"):
                    # Per-epoch training gauges (rendered by telemetry.
                    # render_prometheus; served by /metrics in a co-resident
                    # serve process, dumped to logs/<name>/train_metrics.prom
                    # at run end).
                    telemetry.gauge("train/epoch", epoch)
                    telemetry.gauge(
                        "train/epoch_wall_s", round(train_wall_s, 4)
                    )
                    telemetry.gauge(
                        "train/step_s_per_epoch", train_split["step_s"]
                    )
                    telemetry.gauge(
                        "train/h2d_s_per_epoch", train_split["h2d_s"]
                    )
                    telemetry.gauge(
                        "train/h2d_mb_per_epoch",
                        round(train_split["h2d_bytes"] / (1 << 20), 4),
                    )
                    telemetry.gauge(
                        "train/feed_wait_s_per_epoch", train_split["feed_wait_s"]
                    )
                    telemetry.gauge(
                        "train/compile_s_epoch",
                        round(
                            telemetry.counter_value("jax/compile_s")
                            - compile_s0,
                            4,
                        ),
                    )

                    if scheduler is not None:
                        current_lr = get_learning_rate(driver.state.opt_state)
                        # None = no injected LR knob (LBFGS: linesearch owns
                        # the step size) — the plateau scheduler has nothing
                        # to act on.
                        new_lr = (
                            scheduler.step(val_loss, current_lr)
                            if current_lr is not None
                            else None
                        )
                        if new_lr is not None and new_lr != current_lr:
                            driver.state = driver.state.replace(
                                opt_state=set_learning_rate(
                                    driver.state.opt_state, new_lr
                                )
                            )
                            print_distributed(
                                verbosity,
                                f"Epoch {epoch}: learning rate reduced to {new_lr}",
                            )

                    if writer is not None:
                        writer.add_scalar("train error", train_loss, epoch)
                        writer.add_scalar("validate error", val_loss, epoch)
                        writer.add_scalar("test error", test_loss, epoch)
                        for ivar, rmse in enumerate(train_rmses):
                            writer.add_scalar(
                                f"train error of task {ivar}", rmse, epoch
                            )

                    print_distributed(
                        verbosity,
                        f"Epoch: {epoch:4d}  Train: {train_loss:.8f}  "
                        f"Val: {val_loss:.8f}  Test: {test_loss:.8f}",
                    )
                    history["total_loss_train"].append(train_loss)
                    history["total_loss_val"].append(val_loss)
                    history["total_loss_test"].append(test_loss)
                    history["task_loss_train"].append(train_rmses)
                    history["task_loss_val"].append(val_rmses)
                    history["task_loss_test"].append(test_rmses)
                    # XLA compiles this epoch (train + both evaluations),
                    # from the recompile sentinel: after the first epoch a run
                    # on static bucket shapes should record zeros.
                    history.setdefault("xla_compiles", []).append(
                        compile_count() - compiles0
                    )

                    # Mid-training periodic checkpoint — an improvement over
                    # the reference, which saves only once at the very end
                    # (SURVEY.md §5.4); a preempted multi-hour run warm-starts
                    # from the last save. Non-blocking by default
                    # (checkpoint_async).
                    if (
                        checkpoint_name
                        and checkpoint_every > 0
                        and (epoch + 1) % checkpoint_every == 0
                    ):
                        ckpt_vars = {
                            "params": driver.state.params,
                            "batch_stats": driver.state.batch_stats,
                        }
                        ckpt_meta = {
                            "epoch": epoch + 1,
                            "scheduler": (
                                scheduler.state_dict() if scheduler else None
                            ),
                            "history": history,
                        }
                        if checkpointer is not None:
                            stall = checkpointer.save(
                                ckpt_vars,
                                driver.state.opt_state,
                                checkpoint_name,
                                meta=ckpt_meta,
                                keep_last_k=checkpoint_keep_last_k,
                            )
                        else:
                            from ..utils.model import save_model

                            t0 = time.perf_counter()
                            save_model(
                                ckpt_vars,
                                driver.state.opt_state,
                                checkpoint_name,
                                meta=ckpt_meta,
                                keep_last_k=checkpoint_keep_last_k,
                            )
                            stall = time.perf_counter() - t0
                        Timer.credit("ckpt_save_stall", stall)
                        telemetry.event(
                            "train/checkpoint_saved",
                            epoch=epoch + 1,
                            stall_s=round(stall, 4),
                        )
                epoch_span.attrs.update(driver.epoch_account.thread_turn())
            driver.epoch_account.close(epoch, epoch_span.dur_s)
    finally:
        if checkpointer is not None:
            # Run-exit wait barrier: every queued write lands before the run
            # returns (resume/predict reads the file next). On the clean path
            # a writer failure re-raises here; on an exception path it must
            # not mask the original error.
            import sys as _sys

            checkpointer.close(raise_errors=_sys.exc_info()[0] is None)
    if profiler:
        profiler.stop()
    timer.stop()
    return history
