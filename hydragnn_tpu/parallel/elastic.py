"""graftelastic — elastic data-parallel training over the graftmesh harness
(docs/DISTRIBUTED.md "Elastic runbook").

PR 14 left ``Training.elastic`` as validated metadata: the supervisor
persisted the launch topology and nothing acted on membership. This module is
the acting half — a membership/heartbeat layer over the PR-14 rendezvous and
a world-transition protocol, built so tier-1 can actually run it (worker
threads over the loopback harness; the spawn path rides the same
``ProxyRendezvous`` mailbox):

* :class:`MembershipTracker` — heartbeat/membership state. Workers beat
  through the rendezvous one-way mailbox (``LoopbackRendezvous.post`` /
  ``ProxyRendezvous.post``); the coordinator drains the mailbox and declares
  a worker dead when its last beat ages past ``Training.elastic.heartbeat_s``
  (or immediately, on a rendezvous abort naming the corpse). Joins and clean
  leaves are posted the same way.
* :func:`shard_schedule` — the deterministic re-shard: one GLOBAL per-epoch
  batch plan (the unsharded loader's own shuffled plan) consumed
  window-by-window, ``world`` batches per lockstep step. Every batch is
  consumed exactly once per epoch NO MATTER how many transitions happen
  mid-epoch, per-rank views are disjoint by construction, and the tail
  window pads with empty (all-masked) batches instead of wrapping — the
  documented wrap-pad divergence from ``GraphDataLoader``'s round-robin
  dealing (an elastic epoch must conserve the sample multiset exactly; a
  wrap would double-count tail samples every transition). The same dealing
  contract holds for an out-of-core GSHD corpus: ``StreamingGraphLoader``
  (datasets/stream.py, docs/DATA_PLANE.md) exposes identical
  ``num_shards``/``shard_rank`` views and a live ``reshard()`` for world
  transitions — elastic training never requires the corpus in host RAM.
* :class:`ElasticTrainer` — the world-transition protocol. On a membership
  change within ``[min_workers, max_workers]``: quiesce at the next step
  boundary, checkpoint through the existing v2 layer (atomic, digest
  verified), rebuild the mesh + compiled step for the NEW world size,
  restore through the fallback chain (``checkpoint.io.load_verified_chain``
  + :func:`~hydragnn_tpu.checkpoint.io.verify_elastic_handoff`), and resume
  from the persisted cursor. A DIRTY death (rendezvous abort) degrades
  gracefully: shrink below the corpse and resume from the last periodic
  checkpoint instead of dying; a join grows back up to ``max_workers``,
  with graftcache hydrating previously-seen-topology executables (the
  ``mesh`` CacheKey component already distinguishes them). A kill DURING a
  transition is survivable by the incarnation contract: the handoff save is
  atomic, so the next incarnation restores either the pre- or post-handoff
  state — never a torn one.

Drills: ``benchmarks/elastic_drills.py`` (kill / join-under-load / churn /
kill-during-transition) -> ``bench.py --elastic`` -> ``ELASTIC_rNN.json``.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..analysis import tsan
from ..telemetry import graftel as telemetry
from .loopback import (
    LoopbackError,
    LoopbackRendezvous,
    LoopbackWorker,
    run_workers,
)

HEARTBEAT_TAG = "heartbeat"


class ElasticError(RuntimeError):
    """An elastic world failed: below min_workers, torn handoff, or a
    transition that cannot complete."""


class WorkerKilled(ElasticError):
    """A drill-injected dirty worker death (the SIGKILL analog for the
    in-process harness)."""

    def __init__(self, worker_id: str):
        super().__init__(f"worker {worker_id} killed")
        self.worker_id = worker_id


class TransitionKilled(ElasticError):
    """A drill-injected death INSIDE a world transition — after the handoff
    checkpoint landed, before the new world resumed (the incarnation-contract
    drill)."""


# --------------------------------------------------------------------- config
@dataclass(frozen=True)
class ElasticConfig:
    """The ``Training.elastic`` knobs (validated by the bad-mesh contract,
    analysis/contracts.py)."""

    min_workers: int = 1
    max_workers: int = 8
    heartbeat_s: float = 5.0

    def __post_init__(self):
        if not (1 <= self.min_workers <= self.max_workers):
            raise ValueError(
                f"elastic range [{self.min_workers}, {self.max_workers}] is "
                "unsatisfiable — need 1 <= min_workers <= max_workers"
            )
        if self.heartbeat_s <= 0:
            raise ValueError(
                f"heartbeat_s must be positive, got {self.heartbeat_s}"
            )

    @classmethod
    def from_training(cls, training_cfg: Optional[dict]) -> Optional["ElasticConfig"]:
        """The config's ``Training.elastic`` block as an :class:`ElasticConfig`
        (None when elasticity is not configured). Malformed blocks raise an
        ACTIONABLE ValueError — direct supervisor-CLI launches reach this
        before any config gate runs, and a raw AttributeError on
        ``"elastic": "yes"`` would bury the bad-mesh diagnosis."""
        block = (training_cfg or {}).get("elastic")
        if not block:
            return None
        if not isinstance(block, dict):
            raise ValueError(
                "Training.elastic must be a dict of worker-range knobs "
                "(min_workers/max_workers/heartbeat_s), got "
                f"{type(block).__name__} — see the bad-mesh contract "
                "(docs/DISTRIBUTED.md)"
            )
        try:
            return cls(
                min_workers=int(block.get("min_workers", 1)),
                max_workers=int(block.get("max_workers", 8)),
                heartbeat_s=float(block.get("heartbeat_s", 5.0)),
            )
        except (TypeError, ValueError) as e:
            raise ValueError(
                f"Training.elastic is malformed ({e}) — min_workers/"
                "max_workers must be ints >= 1 with min <= max, heartbeat_s "
                "a positive number (docs/DISTRIBUTED.md)"
            ) from e

    def admits(self, world: int) -> bool:
        return self.min_workers <= world <= self.max_workers


# ----------------------------------------------------------------- membership
@dataclass(frozen=True)
class MembershipChange:
    """One detected membership delta (the quiesce trigger)."""

    dead: Tuple[str, ...] = ()
    left: Tuple[str, ...] = ()
    joined: Tuple[str, ...] = ()

    def __bool__(self) -> bool:
        return bool(self.dead or self.left or self.joined)


class MembershipTracker:
    """Heartbeat/membership state shared between worker heartbeat pumps and
    the coordinator's poll loop.

    ``heartbeat``/``join``/``request_leave`` are called from worker (and
    pump) threads; ``poll``/``alive`` from the coordinator — every field is
    under one lock, registered with the tsan drill
    (benchmarks/tsan_drill.py ``_elastic_drill``)."""

    def __init__(
        self,
        heartbeat_s: float,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.heartbeat_s = float(heartbeat_s)
        self._clock = clock
        self._lock = tsan.instrument_lock(
            threading.Lock(), "MembershipTracker._lock"
        )
        self._beats: Dict[str, float] = {}  # guarded-by: self._lock
        self._dead: set = set()  # guarded-by: self._lock
        self._leaves: set = set()  # guarded-by: self._lock
        self._joins: List[str] = []  # guarded-by: self._lock
        self._log: List[dict] = []  # guarded-by: self._lock

    # ------------------------------------------------------------- worker side
    def join(self, worker_id: str) -> None:
        """Announce a (new or returning) worker; its first beat is implicit."""
        now = self._clock()
        with self._lock:
            fresh = worker_id not in self._beats
            self._beats[worker_id] = now
            self._dead.discard(worker_id)
            if fresh:
                self._joins.append(worker_id)
                self._log.append({"event": "join", "worker": worker_id, "t": now})

    def heartbeat(self, worker_id: str) -> None:
        tsan.yield_point("elastic.membership.heartbeat")
        with self._lock:
            self._beats[worker_id] = self._clock()

    def request_leave(self, worker_id: str) -> None:
        """A clean, announced leave — quiesce at the next step boundary
        instead of waiting for the heartbeat deadline."""
        with self._lock:
            self._leaves.add(worker_id)
            self._log.append(
                {"event": "leave_requested", "worker": worker_id, "t": self._clock()}
            )

    def forget(self, worker_id: str) -> None:
        """Remove every trace of a worker (refused join, permanent removal):
        it neither ages into a death nor resurfaces as an arrival."""
        with self._lock:
            self._beats.pop(worker_id, None)
            self._dead.discard(worker_id)
            self._leaves.discard(worker_id)
            self._joins = [w for w in self._joins if w != worker_id]

    def mark_dead(self, worker_id: str) -> None:
        """Out-of-band death report (a rendezvous abort names the corpse
        faster than the heartbeat deadline can)."""
        with self._lock:
            self._dead.add(worker_id)
            self._log.append(
                {"event": "marked_dead", "worker": worker_id, "t": self._clock()}
            )

    def drain(self, posts: Sequence[Tuple[int, Any]]) -> int:
        """Fold rendezvous-mailbox heartbeat posts (``(rank, payload)`` with
        ``payload["wid"]``) into the beat table; returns how many landed."""
        n = 0
        for _rank, payload in posts:
            wid = (payload or {}).get("wid") if isinstance(payload, dict) else None
            if wid:
                self.heartbeat(str(wid))
                n += 1
        return n

    # -------------------------------------------------------- coordinator side
    def alive(self, now: Optional[float] = None) -> set:
        """Workers whose last beat is within the heartbeat deadline and that
        were not explicitly marked dead."""
        now = self._clock() if now is None else now
        with self._lock:
            return {
                wid
                for wid, t in self._beats.items()
                if wid not in self._dead and now - t <= self.heartbeat_s
            }

    def last_beat(self, worker_id: str) -> Optional[float]:
        with self._lock:
            return self._beats.get(worker_id)

    def poll(self, expected: Sequence[str]) -> MembershipChange:
        """One coordinator poll: which of ``expected`` died (missed deadline
        or marked dead), which asked to leave, and which new workers joined.
        Consumed deltas are cleared — a change is reported exactly once."""
        now = self._clock()
        with self._lock:
            dead = tuple(
                wid
                for wid in expected
                if wid in self._dead
                or (
                    wid in self._beats
                    and now - self._beats[wid] > self.heartbeat_s
                )
            )
            left = tuple(w for w in self._leaves if w in expected and w not in dead)
            joined = tuple(w for w in self._joins if w not in expected)
            self._leaves -= set(left)
            # Every announcement is consumed by the poll that saw it: a
            # member's own (stale) join must not resurface as an arrival
            # after it later leaves the roster.
            self._joins = []
            for wid in dead:
                self._dead.add(wid)
                self._beats.pop(wid, None)
            for wid in left:
                self._beats.pop(wid, None)
                self._log.append({"event": "left", "worker": wid, "t": now})
            if dead:
                self._log.append(
                    {"event": "declared_dead", "workers": list(dead), "t": now}
                )
        return MembershipChange(dead=dead, left=left, joined=joined)

    def log(self) -> List[dict]:
        with self._lock:
            return list(self._log)


class HeartbeatPump:
    """One worker's heartbeat thread: posts ``{"wid": ...}`` into the
    rendezvous mailbox (the coordinator drains it into the tracker) every
    ``interval_s`` until stopped. The pump dying WITH its worker is the
    point — a dirty death stops the beats and the deadline fires."""

    def __init__(
        self,
        rdv: LoopbackRendezvous,
        rank: int,
        worker_id: str,
        interval_s: float,
    ):
        self._rdv = rdv
        self._rank = rank
        self.worker_id = worker_id
        self.interval_s = float(interval_s)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run,
            name=f"elastic-heartbeat-{worker_id}",
            daemon=True,
        )

    def _run(self) -> None:
        while not self._stop.is_set():
            self._rdv.post(
                self._rank, {"wid": self.worker_id}, tag=HEARTBEAT_TAG
            )
            self._stop.wait(self.interval_s)

    def start(self) -> "HeartbeatPump":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(5.0)


# ----------------------------------------------------------- deterministic re-shard
def shard_window(
    num_batches: int, cursor: int, world: int
) -> List[Optional[int]]:
    """ONE lockstep step's per-rank window: rank ``r`` takes global batch
    ``cursor + r`` (``None`` = an empty padding batch past the tail). THE
    dealing rule — the segment loop (`ElasticTrainer._run_segment`) and the
    whole-epoch :func:`shard_schedule` both consume it, so the tested
    exactly-once/disjoint properties and the production dealing can never
    diverge."""
    if world < 1:
        raise ValueError(f"world must be >= 1, got {world}")
    return [
        cursor + r if cursor + r < num_batches else None for r in range(world)
    ]


def shard_schedule(
    num_batches: int, cursor: int, world: int
) -> List[List[Optional[int]]]:
    """The deterministic elastic re-shard over one epoch's GLOBAL batch plan:
    :func:`shard_window` repeated from ``cursor`` to the tail. Pure function
    of ``(num_batches, cursor, world)`` — a world transition at any cursor
    resumes with the remaining window untouched, so per-epoch batch
    consumption is exactly once regardless of transitions and per-rank views
    are disjoint by construction (tests/test_elastic.py pins both)."""
    steps: List[List[Optional[int]]] = []
    c = max(0, int(cursor))
    while c < num_batches:
        steps.append(shard_window(num_batches, c, world))
        c += world
    return steps


# ---------------------------------------------------------------- drill schedule
@dataclass
class ElasticEvent:
    """One scheduled drill event, keyed on the global step counter:

    * ``kill``  — worker ``worker`` dies DIRTY at this step (no quiesce);
    * ``leave`` — worker ``worker`` announces a clean leave;
    * ``join``  — a new worker named ``worker`` asks to join;
    * ``kill_transition`` — the NEXT transition at/after this step dies
      after its handoff checkpoint (the incarnation-contract drill).
    """

    step: int
    kind: str
    worker: Optional[str] = None


class ElasticSchedule:
    """Thread-safe drill schedule: workers consult ``kill_due`` per step,
    the leader consults ``control_events`` / ``transition_kill_due`` —
    each event fires exactly once."""

    KINDS = ("kill", "leave", "join", "kill_transition")

    def __init__(self, events: Optional[Sequence[ElasticEvent]] = None):
        for e in events or ():
            if e.kind not in self.KINDS:
                raise ValueError(f"unknown elastic event kind {e.kind!r}")
        self._lock = tsan.instrument_lock(
            threading.Lock(), "ElasticSchedule._lock"
        )
        self._pending: List[ElasticEvent] = sorted(
            events or (), key=lambda e: e.step
        )  # guarded-by: self._lock

    def kill_due(self, worker_id: str, step: int) -> bool:
        with self._lock:
            for e in self._pending:
                if e.kind == "kill" and e.worker == worker_id and step >= e.step:
                    self._pending.remove(e)
                    return True
        return False

    def control_events(self, step: int) -> List[ElasticEvent]:
        """Leader-side: due leave/join events (consumed)."""
        with self._lock:
            due = [
                e
                for e in self._pending
                if e.kind in ("leave", "join") and step >= e.step
            ]
            for e in due:
                self._pending.remove(e)
        return due

    def transition_kill_due(self, step: int) -> bool:
        with self._lock:
            for e in self._pending:
                if e.kind == "kill_transition" and step >= e.step:
                    self._pending.remove(e)
                    return True
        return False


# --------------------------------------------------------------- the trainer
class ElasticTrainer:
    """Supervisor-driven elastic DP training over the loopback harness.

    One instance owns the model/optimizer/loader and drives segments: a
    segment is a lockstep run at a fixed world size; between segments the
    world transitions (quiesce → v2 handoff checkpoint → rebuild mesh +
    re-shard → verified restore → resume). The loader must be UNSHARDED
    (``num_shards=1``) and single-bucket — the global plan IS the shard
    authority; :func:`shard_schedule` deals it.
    """

    def __init__(
        self,
        model,
        optimizer,
        loader,
        elastic: ElasticConfig,
        run_path: str,
        name: str = "elastic",
        compile_cache: Optional[str] = None,
        checkpoint_every_steps: int = 4,
        keep_last_k: int = 3,
        grad_sync: str = "single",
        seed: int = 0,
    ):
        import jax

        from ..models.create import init_model_variables
        from ..train.trainer import create_train_state

        if getattr(loader, "num_shards", 1) != 1:
            raise ElasticError(
                "ElasticTrainer needs the UNSHARDED loader (num_shards=1): "
                "the global batch plan is the shard authority and "
                "shard_schedule deals it per world size"
            )
        if getattr(loader, "num_buckets", 1) != 1:
            raise ElasticError(
                "ElasticTrainer requires a single-bucket loader (one static "
                "pad shape) — multi-bucket elastic stacking is future work"
            )
        if hasattr(loader, "_size_buckets"):
            # The lockstep step stacks the plan's batches by position: one
            # shape for EVERY batch, whatever a shuffle draws.
            from ..preprocess.dataloader import keep_worst_case_pads

            keep_worst_case_pads(loader)
        self.model = model
        self.optimizer = optimizer
        self.loader = loader
        self.elastic = elastic
        self.run_path = run_path
        self.name = name
        self.compile_cache = compile_cache
        self.checkpoint_every_steps = int(checkpoint_every_steps)
        self.keep_last_k = int(keep_last_k)
        self.grad_sync = grad_sync
        self.rng = jax.random.PRNGKey(seed)
        if len(jax.devices()) < elastic.max_workers:
            raise ElasticError(
                f"elastic max_workers={elastic.max_workers} needs that many "
                f"devices; {len(jax.devices())} visible — pin XLA_FLAGS="
                "--xla_force_host_platform_device_count"
            )
        variables = init_model_variables(model, next(iter(loader)))
        self.state = create_train_state(model, variables, optimizer)
        self._steps: Dict[int, Any] = {}  # world -> compiled DP step
        self._epoch_cache: Dict[int, list] = {}  # epoch -> collated batches
        self.tracker = MembershipTracker(elastic.heartbeat_s)
        # Leader-only writes ordered by the rendezvous lockstep contract;
        # the coordinator reads them strictly after run_workers' join.
        self.global_step = 0  # guarded-by: external(leader-thread-only by the rendezvous lockstep contract; coordinator reads after join)
        self.incarnation = 0
        self.transitions: List[dict] = []
        self.loss_trace: List[dict] = []  # guarded-by: external(leader-thread-only by the rendezvous lockstep contract; coordinator reads after join)
        self.checkpoints_written = 0  # guarded-by: external(leader-thread-only by the rendezvous lockstep contract; coordinator reads after join)
        # Drill observability: every checkpointed (epoch, cursor) position —
        # "zero lost progress beyond the last checkpoint" asserts the resumed
        # position is a member — and the per-epoch batch-consumption ledger
        # backing the exactly-once conservation gate (reset to the restored
        # cursor on rollback, so the ledger tracks the SURVIVING trajectory).
        self.save_log: List[dict] = []  # guarded-by: external(leader-thread-only by the rendezvous lockstep contract; coordinator reads after join)
        self.consumed: Dict[int, set] = {}  # guarded-by: external(leader-thread-only by the rendezvous lockstep contract; coordinator reads after join)
        self.epoch_sizes: Dict[int, int] = {}
        self.segment_log: List[dict] = []
        self._joined_serial = 0
        self._exec_registry = None
        self._cache_fingerprint = ""
        if compile_cache:
            import hashlib

            from ..cache import ExecutableRegistry, ExecutableStore
            from ..checkpoint.format import param_fingerprint

            self._exec_registry = ExecutableRegistry(
                ExecutableStore(compile_cache), name="elastic"
            )
            # Program identity follows the TrainingDriver convention: the
            # param/opt tree fingerprints + module repr — NEVER the run name,
            # so a restarted incarnation (or a second trainer over the same
            # store) hydrates the same entries.
            self._cache_fingerprint = hashlib.sha256(
                (
                    param_fingerprint(self.state.params)
                    + param_fingerprint(
                        {"opt": self.state.opt_state, "bstats": self.state.batch_stats}
                    )
                    + repr(model)
                ).encode()
            ).hexdigest()

    # ------------------------------------------------------------- checkpoints
    @property
    def run_dir(self) -> str:
        import os

        return os.path.join(self.run_path, self.name)

    def _save(
        self, state, epoch: int, cursor: int, world: int, num_batches: int
    ) -> None:
        """The handoff/periodic checkpoint: the existing v2 save path plus
        the elastic meta block :func:`verify_elastic_handoff` consumes.
        ``state`` is passed explicitly — mid-segment saves run on the leader
        worker thread against the segment's live state cell."""
        from ..checkpoint.io import elastic_handoff_meta, save_model

        meta = {
            "epoch": epoch,
            "elastic": elastic_handoff_meta(
                world_size=world,
                epoch=epoch,
                cursor=cursor,
                incarnation=self.incarnation,
                global_step=self.global_step,
                num_batches=num_batches,
            ),
        }
        save_model(
            {"params": state.params, "batch_stats": state.batch_stats},
            state.opt_state,
            self.name,
            path=self.run_path,
            meta=meta,
            keep_last_k=self.keep_last_k,
        )
        self.checkpoints_written += 1
        self.save_log.append(
            {"epoch": int(epoch), "cursor": int(cursor), "world": int(world)}
        )

    def _restore(self, new_world: int) -> Tuple[int, int]:
        """Verified restore through the fallback chain; returns the resume
        ``(epoch, cursor)`` after the world-size-independent handoff
        assertions (checkpoint/io.py)."""
        import jax
        import numpy as np

        from ..checkpoint.io import load_verified_chain, verify_elastic_handoff

        template = {
            "params": self.state.params,
            "batch_stats": self.state.batch_stats,
        }
        new_vars, opt_state, meta, _report = load_verified_chain(
            template, self.run_dir, self.name, self.state.opt_state
        )
        handoff = verify_elastic_handoff(
            meta,
            new_world,
            min_workers=self.elastic.min_workers,
            max_workers=self.elastic.max_workers,
        )
        state = self.state.replace(
            params=new_vars["params"],
            batch_stats=new_vars["batch_stats"],
            opt_state=opt_state,
        )
        # Normalize EVERY leaf to host memory: arrays still committed to the
        # OLD world's mesh devices (state.step survives the replace above)
        # would poison the NEW world's dispatch — the world-size-independent
        # handoff means the new mesh re-places everything itself.
        self.state = jax.tree_util.tree_map(
            lambda x: np.asarray(x) if isinstance(x, jax.Array) else x, state
        )
        if handoff.get("global_step") is not None:  # 0 is a real position
            self.global_step = int(handoff["global_step"])
        epoch, cursor = int(handoff["epoch"]), int(handoff["cursor"])
        # Rewind the consumption ledger to the restored trajectory: batches
        # past the checkpointed cursor (and any later epoch) replay.
        self.consumed[epoch] = set(range(cursor))
        for later in [e for e in self.consumed if e > epoch]:
            del self.consumed[later]
        return epoch, cursor

    # ------------------------------------------------------------ compiled step
    def _step_for(self, world: int):
        """The compiled shard_map DP step for a ``world``-device data mesh,
        dispatched through the shared graftcache registry when configured —
        the ``mesh`` CacheKey component keeps each topology's executable
        distinct, so returning to a previously-seen world size hydrates
        instead of recompiling (the join-under-load drill's
        ``warmup_xla_compiles=0`` gate)."""
        import jax

        from ..train.trainer import make_train_step_dp
        from .distributed import make_mesh, mesh_descriptor

        cached = self._steps.get(world)
        if cached is not None:
            return cached
        mesh = make_mesh(data_axis=world, devices=jax.devices()[:world])
        step = make_train_step_dp(
            self.model,
            self.optimizer,
            mesh,
            donate=False,
            grad_sync=self.grad_sync,
        )
        reg = self._exec_registry
        if reg is None:
            dispatch = step
        else:
            from ..cache import CacheKey, tree_signature

            descriptor = mesh_descriptor(mesh)
            device_ids = tuple(d.id for d in mesh.devices.flat)

            def dispatch(state, batch, rng, _step=step, _md=descriptor):
                exe, _outcome, _s = reg.lookup_or_compile(
                    ("elastic_step", world),
                    lambda: CacheKey.for_environment(
                        program="elastic_step",
                        config_fingerprint=self._cache_fingerprint,
                        flags=(f"grad_sync={self.grad_sync}",),
                        args_digest=tree_signature((state, batch, rng)),
                        mesh=_md,
                        devices=device_ids,
                    ),
                    lambda: _step.lower(state, batch, rng),
                )
                return exe(state, batch, rng)

        self._steps[world] = dispatch
        return dispatch

    def _epoch_batches(self, epoch: int) -> list:
        """The epoch's GLOBAL batch plan, collated once (the unsharded
        loader's own per-epoch shuffle is the plan authority)."""
        cached = self._epoch_cache.get(epoch)
        if cached is None:
            self.loader.set_epoch(epoch)
            cached = list(self.loader)
            self._epoch_cache = {epoch: cached}  # one epoch resident at a time
            self.epoch_sizes[epoch] = len(cached)
        return cached

    # ---------------------------------------------------------------- segments
    def _run_segment(
        self,
        epoch: int,
        cursor: int,
        roster: List[str],
        schedule: ElasticSchedule,
    ) -> dict:
        """One lockstep segment at the fixed world ``len(roster)``: workers
        exchange their global batch indices per step, the leader dispatches
        the stacked shard_map step and broadcasts metrics + the control
        decision (continue / quiesce / epoch_done). Returns the leader's
        outcome dict. A dirty worker death aborts the rendezvous and raises
        ``LoopbackError`` (handled by :meth:`run`)."""
        import jax

        from ..train.trainer import stack_batches

        world = len(roster)
        batches = self._epoch_batches(epoch)
        dispatch = self._step_for(world)
        rdv = LoopbackRendezvous(world)
        tracker = self.tracker
        # Leader-owned mutable cells; ordered by the rendezvous lockstep
        # contract exactly as in loopback_train.
        cell = {"state": self.state, "outcome": None}  # guarded-by: external(leader-thread-only by the rendezvous lockstep contract)
        since_ckpt = {"steps": 0}  # guarded-by: external(leader-thread-only by the rendezvous lockstep contract)

        def leader_decision(worker_cursor: int) -> dict:
            """Post-step control: drain heartbeats, apply due drill events,
            poll membership, checkpoint on cadence. Leader-only."""
            tracker.drain(rdv.posts(HEARTBEAT_TAG))
            for ev in schedule.control_events(self.global_step):
                if ev.kind == "leave" and ev.worker in roster:
                    tracker.request_leave(ev.worker)
                elif ev.kind == "join":
                    # Admission happens in run() against the POST-leave
                    # roster (a leave + a join in the same quiesce is a
                    # net-zero resize, not a refusal); over-capacity joins
                    # are refused there, with telemetry.
                    tracker.join(ev.worker or self._next_worker_id())
            change = tracker.poll(roster)
            done = worker_cursor >= len(batches)
            if change:
                return {
                    "decision": "quiesce",
                    "cursor": worker_cursor,
                    "change": {
                        "dead": list(change.dead),
                        "left": list(change.left),
                        "joined": list(change.joined),
                    },
                }
            if done:
                return {"decision": "epoch_done", "cursor": worker_cursor}
            if (
                self.checkpoint_every_steps > 0
                and since_ckpt["steps"] >= self.checkpoint_every_steps
            ):
                self._save(
                    cell["state"], epoch, worker_cursor, world, len(batches)
                )
                since_ckpt["steps"] = 0
            return {"decision": "continue", "cursor": worker_cursor}

        def worker_fn(worker: LoopbackWorker) -> dict:
            wid = roster[worker.rank]
            tracker.join(wid)
            pump = HeartbeatPump(
                rdv, worker.rank, wid,
                interval_s=self.elastic.heartbeat_s / 4.0,
            ).start()
            local_cursor = cursor
            try:
                while True:
                    if schedule.kill_due(wid, self.global_step):
                        raise WorkerKilled(wid)
                    mine = shard_window(len(batches), local_cursor, world)[
                        worker.rank
                    ]
                    group = worker.exchange(mine, tag="elastic_step")
                    live_idx = [i for i in group if i is not None]
                    m = None
                    if worker.is_leader and live_idx:
                        stacked = stack_batches(
                            [batches[i] for i in live_idx], world
                        )
                        cell["state"], m = dispatch(
                            cell["state"], stacked, self.rng
                        )
                        self.global_step += 1
                        since_ckpt["steps"] += 1
                        self.consumed.setdefault(epoch, set()).update(live_idx)
                        self.loss_trace.append(
                            {
                                "step": self.global_step,
                                "epoch": epoch,
                                "world": world,
                                "loss": float(m["loss"])
                                / max(float(m["count"]), 1.0),
                            }
                        )
                    local_cursor += len(live_idx)
                    control = worker.broadcast(
                        leader_decision(local_cursor)
                        if worker.is_leader
                        else None,
                        src=0,
                        tag="elastic_control",
                    )
                    local_cursor = control["cursor"]
                    if control["decision"] != "continue":
                        worker.barrier("elastic_quiesce")
                        if worker.is_leader:
                            cell["outcome"] = control
                        return control
            finally:
                pump.stop()

        try:
            run_workers(world, worker_fn, rdv=rdv)
        finally:
            self.state = cell["state"]
        outcome = cell["outcome"]
        if outcome is None:  # pragma: no cover - run_workers raised first
            raise ElasticError("segment ended without a leader outcome")
        return outcome

    def _next_worker_id(self) -> str:
        self._joined_serial += 1
        return f"j{self._joined_serial}"

    # -------------------------------------------------------------- transitions
    def _transition(
        self,
        kind: str,
        reason: str,
        epoch: int,
        cursor: int,
        old_roster: List[str],
        new_roster: List[str],
        schedule: ElasticSchedule,
        save_first: bool,
    ) -> Tuple[int, int]:
        """The world-transition protocol: (handoff save when the old state is
        clean) → rebuild for the new world → verified restore → resume. The
        drill's ``kill_transition`` fires between the save and the restore —
        the atomic v2 install guarantees the next incarnation sees either the
        pre- or post-handoff checkpoint, never a torn one. Returns the
        resumed ``(epoch, cursor)``."""
        old_world, new_world = len(old_roster), len(new_roster)
        if new_world < self.elastic.min_workers:
            raise ElasticError(
                f"world shrank to {new_world} < min_workers="
                f"{self.elastic.min_workers} ({reason}) — an elastic run "
                "cannot degrade below its configured floor"
            )
        t0 = time.perf_counter()
        with telemetry.span(
            "elastic_transition", kind=kind, reason=reason,
            from_world=old_world, to_world=new_world,
        ):
            if save_first:
                # Collate only on the save path: a dirty-death transition
                # must not re-materialize a possibly-evicted epoch just to
                # measure a length it never uses.
                batches = self._epoch_batches(epoch)
                self._save(self.state, epoch, cursor, old_world, len(batches))
            if schedule.transition_kill_due(self.global_step):
                # The incarnation-contract drill: die AFTER the handoff
                # landed, BEFORE the new world resumed.
                raise TransitionKilled(
                    f"transition {old_world}->{new_world} killed post-handoff "
                    f"(incarnation {self.incarnation})"
                )
            resume_epoch, resume_cursor = self._restore(new_world)
            self._step_for(new_world)  # rebuild (or rehydrate) the mesh step
        wall = time.perf_counter() - t0
        entry = {
            "kind": kind,
            "reason": reason,
            "from_world": old_world,
            "to_world": new_world,
            "epoch": resume_epoch,
            "cursor": resume_cursor,
            "global_step": self.global_step,
            "incarnation": self.incarnation,
            "wall_s": round(wall, 4),
        }
        self.transitions.append(entry)
        telemetry.counter("elastic/transitions")
        # Counter family matches the entry's kind exactly (a net-zero-size
        # replacement — one leave + one join in the same quiesce — is a
        # "resize", never misfiled as a grow or shrink).
        telemetry.counter(f"elastic/{kind}s")
        telemetry.event("elastic/transition", **entry)
        if kind == "shrink" and reason == "worker_death":
            # Flight-dump trigger (docs/OBSERVABILITY.md): the timeline that
            # led into a dirty shrink, next to the checkpoint it resumed from.
            telemetry.flight_dump(
                "elastic_transition", run_dir=self.run_dir, extra=entry
            )
        return resume_epoch, resume_cursor

    # --------------------------------------------------------------------- run
    def run(
        self,
        num_epochs: int,
        start_world: int,
        schedule: Optional[ElasticSchedule] = None,
    ) -> dict:
        """Train ``num_epochs`` epochs starting at ``start_world`` workers,
        transitioning on every membership change the schedule (or a real
        tracker feed) produces. Returns the run report consumed by the drill
        matrix."""
        if not self.elastic.admits(start_world):
            raise ElasticError(
                f"start_world={start_world} outside the elastic range "
                f"[{self.elastic.min_workers}, {self.elastic.max_workers}]"
            )
        schedule = schedule or ElasticSchedule()
        roster = [f"w{i}" for i in range(start_world)]
        for wid in roster:
            self.tracker.join(wid)
        self.tracker.poll(roster)  # consume the initial joins
        epoch, cursor = 0, 0
        self._save(
            self.state, epoch, cursor, len(roster),
            len(self._epoch_batches(0)),
        )
        from ..analysis.sentinel import compile_count

        while epoch < num_epochs:
            c0 = compile_count()
            try:
                outcome = self._run_segment(epoch, cursor, roster, schedule)
            except LoopbackError as e:
                self._log_segment(epoch, len(roster), compile_count() - c0)
                # Only MEMBERSHIP failures degrade: an injected/real worker
                # death (WorkerKilled) or a rendezvous-level abort/broken
                # barrier (bare LoopbackError). A programming error in the
                # step (TypeError from dispatch, a shape bug) must surface —
                # shrinking and retrying the same broken step would bury the
                # root cause under bogus worker_death telemetry until the
                # min_workers floor kills the run anyway.
                cause = e.__cause__
                if cause is not None and not isinstance(
                    cause, (WorkerKilled, LoopbackError)
                ):
                    raise
                # Dirty death: graceful degradation — name the corpse, mark
                # it dead, shrink below it, resume from the last checkpoint.
                corpse = self._corpse_of(e, roster)
                self.tracker.mark_dead(corpse)
                self.tracker.poll(roster)
                telemetry.counter("elastic/worker_deaths")
                new_roster = [w for w in roster if w != corpse]
                epoch, cursor = self._retryable_transition(
                    "shrink", "worker_death", epoch, cursor,
                    roster, new_roster, schedule, save_first=False,
                )
                roster = new_roster
                continue
            self._log_segment(epoch, len(roster), compile_count() - c0)
            if outcome["decision"] == "epoch_done":
                epoch += 1
                cursor = 0
                if epoch < num_epochs:
                    self._epoch_batches(epoch)
                continue
            # Clean quiesce: apply the membership change, then transition.
            change = outcome["change"]
            cursor = outcome["cursor"]
            new_roster = [
                w
                for w in roster
                if w not in change["dead"] and w not in change["left"]
            ]
            room = self.elastic.max_workers - len(new_roster)
            admitted = list(change["joined"])[: max(0, room)]
            for refused in list(change["joined"])[max(0, room):]:
                # Over-capacity arrival: refuse LOUDLY and forget its beats —
                # a refused joiner must neither linger in the tracker nor
                # resurface as a ghost arrival later.
                telemetry.event(
                    "elastic/join_refused",
                    worker=refused,
                    world=len(new_roster),
                    max_workers=self.elastic.max_workers,
                )
                self.tracker.forget(refused)
            new_roster.extend(admitted)
            if new_roster == roster:
                # The quiesce's only content was refused arrivals: nothing
                # changed — resume the same world, no phantom transition.
                continue
            if len(new_roster) > len(roster):
                kind = "grow"
            elif len(new_roster) < len(roster):
                kind = "shrink"
            else:
                kind = "resize"  # same-size replacement (leave + join)
            if change["dead"]:
                reason = "worker_death"
            elif admitted and change["left"]:
                reason = "worker_replacement"
            elif admitted:
                reason = "worker_join"
            else:
                reason = "worker_leave"
            epoch, cursor = self._retryable_transition(
                kind, reason, epoch, cursor, roster, new_roster, schedule,
                save_first=True,
            )
            roster = new_roster
        final_loss = self._final_eval_loss()
        conservation = {
            e: self.consumed.get(e, set()) == set(range(size))
            for e, size in self.epoch_sizes.items()
        }
        return {
            "completed": True,
            "epochs": int(num_epochs),
            "final_world": len(roster),
            "roster": list(roster),
            "global_steps": self.global_step,
            "incarnations": self.incarnation,
            "checkpoints_written": self.checkpoints_written,
            "transitions": list(self.transitions),
            "loss_trace": list(self.loss_trace),
            "final_eval_loss": final_loss,
            "membership_log": self.tracker.log(),
            "save_log": list(self.save_log),
            "segment_log": list(self.segment_log),
            "epoch_conservation": conservation,
            "epoch_conservation_ok": all(conservation.values()),
        }

    def _log_segment(self, epoch: int, world: int, compiles: int) -> None:
        self.segment_log.append(
            {"epoch": int(epoch), "world": int(world), "compiles": int(compiles)}
        )

    def _retryable_transition(self, *args, **kwargs) -> Tuple[int, int]:
        """A transition killed mid-flight (the drill) is retried by the next
        incarnation: the handoff save already landed atomically, so the
        retry restores the exact saved state — the 'state never torn'
        contract the kill-during-transition drill asserts."""
        try:
            return self._transition(*args, **kwargs)
        except TransitionKilled as e:
            self.incarnation += 1
            telemetry.event(
                "elastic/transition_killed",
                incarnation=self.incarnation,
                error=str(e),
            )
            # The retry must not re-save: the interrupted incarnation's
            # handoff is the authoritative state.
            kwargs["save_first"] = False
            return self._transition(*args, **kwargs)

    @staticmethod
    def _corpse_of(err: LoopbackError, roster: List[str]) -> str:
        cause = err.__cause__
        if isinstance(cause, WorkerKilled):
            return cause.worker_id
        # An unattributed abort: blame the highest rank (deterministic) —
        # real deployments resolve this via the heartbeat deadline instead.
        return roster[-1]

    def _final_eval_loss(self) -> float:
        """World-independent convergence probe: the single-device eval step
        over epoch 0's fixed plan — comparable across elastic and
        fixed-world runs of the same seed (the parity gate's measurement)."""
        from ..train.trainer import make_eval_step

        eval_step = make_eval_step(self.model)
        total, count = 0.0, 0.0
        for batch in self._epoch_batches(0):
            m, _outputs = eval_step(self.state, batch)
            total += float(m["loss"])
            count += float(m["count"])
        return total / max(count, 1.0)


# ------------------------------------------------------- restart topology check
def check_restart_topology(
    mesh_meta: dict,
    world_size: int,
    graph_axis: int,
    elastic: Optional[ElasticConfig],
) -> Optional[dict]:
    """Consume the supervisor.json ``mesh`` block on restart: an incarnation
    resuming under a topology that CONTRADICTS the persisted world/axis
    metadata must fail loudly with both topologies named — unless
    ``Training.elastic`` admits the new world size, in which case the
    transition descriptor is returned for the caller to log (None = same
    topology). ``graph_axis`` changes are never elastic: the edge-sharding
    layout bakes into every compiled step and checkpointed batch-stat
    reduction."""
    if not mesh_meta:
        return None
    saved_world = mesh_meta.get("world_size")
    saved_axis = int(mesh_meta.get("graph_axis") or 1)
    if saved_axis != int(graph_axis or 1):
        raise RuntimeError(
            "restart topology contradiction: supervisor.json persisted "
            f"graph_axis={saved_axis} but this incarnation is launching with "
            f"graph_axis={graph_axis} — edge sharding is not elastic; "
            "restore the original axis or start a fresh run"
        )
    if saved_world is None or int(saved_world) == int(world_size):
        return None
    if elastic is None or not elastic.admits(int(world_size)):
        bounds = (
            f"[{elastic.min_workers}, {elastic.max_workers}]"
            if elastic is not None
            else "not configured"
        )
        raise RuntimeError(
            "restart topology contradiction: supervisor.json persisted "
            f"world_size={saved_world} but this incarnation sees "
            f"world_size={world_size}, and Training.elastic admits "
            f"{bounds} — a non-elastic run must restart at its launch "
            "topology (or configure Training.elastic to permit the change)"
        )
    return {
        "kind": "grow" if int(world_size) > int(saved_world) else "shrink",
        "from_world": int(saved_world),
        "to_world": int(world_size),
    }
