"""graftmesh loopback harness — the backend-portable distributed layer
tier-1 can actually run (docs/DISTRIBUTED.md).

The genuinely-multiprocess path (``jax.distributed`` rendezvous, one process
per host) is environmentally dead on the CPU backend: XLA:CPU raises
"Multiprocess computations aren't implemented" at the first cross-process
psum, so since PR 10 the 2-process suite was a precise skip and every
distributed claim rested on single-caller virtual-mesh unit tests. This
module restores REAL multi-worker coverage without cross-process XLA
collectives:

* ``LoopbackRendezvous`` — an in-process rendezvous for N logical workers
  (threads): named barriers with lockstep-divergence detection, allgather/
  exchange, broadcast. The host-coordination analog of
  ``jax.distributed``'s barrier/bootstrap, over ``threading`` primitives.
* ``run_workers`` — spawn N worker threads over one rendezvous; a worker
  death aborts the barriers so the rest fail loudly instead of hanging.
* ``loopback_train`` — the 2-process DP e2e, in process: each worker owns a
  rank-sharded loader view (the same ``num_shards``/``shard_rank`` dealing a
  real multi-process launch uses) and collates its OWN batches on its OWN
  thread; per step the workers exchange host batches through the rendezvous,
  the leader stacks ``[D, ...]`` and dispatches the shard_map DP step over a
  REAL >1-size device mesh (pinned fake topology —
  ``XLA_FLAGS=--xla_force_host_platform_device_count``), and every worker
  independently accumulates the psum-reduced metrics. Gradient all-reduce is
  the step's own psum over 'data' — actual XLA collectives over the virtual
  mesh, not a host emulation.
* ``ProxyRendezvous`` — the spawn-path twin: the same barrier/allgather
  protocol over a localhost TCP socket, for workers that really are separate
  OS processes (elastic supervisor coordination, spawn-mode drills). It
  coordinates HOSTS only; cross-process device collectives still need a
  backend with multiprocess support, which is why the spawned
  ``jax.distributed`` arm keeps its precise skip on CPU.

CLI (used by tests/run_suite_2proc.py as the loopback fallback)::

    python -m hydragnn_tpu.parallel.loopback <config.json> \
        [--workers 2] [--epochs N] [--thresholds "rmse mae maxae"]
"""

from __future__ import annotations

import json
import threading
from dataclasses import dataclass
from typing import Any, Callable, List, Optional, Sequence

from ..analysis import tsan

_BARRIER_TIMEOUT_S = 300.0
# One-way mailbox post (heartbeats, membership announcements) read/write
# deadline. Named so the static config gate (contracts.bad-elastic-timing)
# can check Training.elastic.heartbeat_s against the SAME number the wire
# path actually uses.
_POST_TIMEOUT_S = 10.0


class LoopbackError(RuntimeError):
    """A loopback world failed: worker exception, lockstep divergence, or a
    broken/abandoned barrier."""


class LoopbackRendezvous:
    """In-process rendezvous for ``world_size`` worker threads.

    Collective calls must be made by ALL workers in the same order (the
    lockstep contract every distributed rendezvous imposes); named barriers
    verify the order and fail loudly on divergence instead of deadlocking."""

    def __init__(self, world_size: int, timeout_s: float = _BARRIER_TIMEOUT_S):
        if world_size < 1:
            raise ValueError(f"world_size must be >= 1, got {world_size}")
        self.world_size = int(world_size)
        self._lock = tsan.instrument_lock(
            threading.Lock(), "LoopbackRendezvous._lock"
        )
        # Exchange slots + per-round tag, written by every worker thread.
        self._slots: List[Any] = [None] * world_size  # guarded-by: self._lock
        self._tags: List[Any] = [None] * world_size  # guarded-by: self._lock
        self._aborted = False  # guarded-by: self._lock, dirty-reads(monotonic bool; a stale False only delays the LoopbackError by one barrier)
        # One-way mailbox (graftelastic, docs/DISTRIBUTED.md "Elastic
        # runbook"): non-collective posts — heartbeats, join/leave
        # announcements — that must NOT block on a barrier (a dead worker
        # would wedge them forever). tag -> [(rank, payload), ...].
        self._mailbox: dict = {}  # guarded-by: self._lock
        # Barrier is self-synchronizing; two phases per collective (publish /
        # consume) so a fast worker cannot overwrite a slot before every
        # peer has read the previous round.
        self._publish = threading.Barrier(world_size, timeout=timeout_s)
        self._consume = threading.Barrier(world_size, timeout=timeout_s)

    # ------------------------------------------------------------- lifecycle
    def abort(self) -> None:
        """Break every waiting/future barrier — called when a worker dies so
        the surviving workers raise instead of hanging to the timeout."""
        with self._lock:
            self._aborted = True
        self._publish.abort()
        self._consume.abort()

    def _wait(self, barrier: threading.Barrier, what: str) -> None:
        if self._aborted:
            raise LoopbackError(f"loopback world aborted before {what}")
        try:
            barrier.wait()
        except threading.BrokenBarrierError:
            raise LoopbackError(
                f"loopback barrier broken at {what} — a peer worker died or "
                "timed out (see the first worker error)"
            ) from None

    # ------------------------------------------------------------ collectives
    def exchange(self, rank: int, obj: Any, tag: str = "exchange") -> List[Any]:
        """Allgather: every worker contributes ``obj``; all receive the
        rank-ordered list. ``tag`` is the lockstep check — divergent call
        sites across workers are an immediate LoopbackError."""
        with self._lock:
            self._slots[rank] = obj
            self._tags[rank] = tag
        self._wait(self._publish, f"exchange({tag}) publish")
        with self._lock:
            out = list(self._slots)
            tags = list(self._tags)
        if any(t != tag for t in tags):
            self.abort()
            raise LoopbackError(
                f"lockstep divergence: worker {rank} at {tag!r}, peers at "
                f"{sorted(set(map(repr, tags)))}"
            )
        self._wait(self._consume, f"exchange({tag}) consume")
        return out

    def barrier(self, rank: int, name: str = "barrier") -> None:
        self.exchange(rank, None, tag=f"barrier:{name}")

    def broadcast(self, rank: int, obj: Any, src: int = 0, tag: str = "bcast") -> Any:
        return self.exchange(rank, obj if rank == src else None, tag=tag)[src]

    # --------------------------------------------------------------- mailbox
    def post(self, rank: int, payload: Any, tag: str = "post") -> None:
        """Non-collective one-way message (heartbeats, membership
        announcements): never blocks on a barrier, so a dying peer cannot
        wedge the sender."""
        with self._lock:
            self._mailbox.setdefault(tag, []).append((rank, payload))

    def posts(self, tag: str = "post") -> List[tuple]:
        """Drain (and clear) the mailbox for ``tag`` — the coordinator-side
        read feeding :class:`~hydragnn_tpu.parallel.elastic.MembershipTracker`."""
        with self._lock:
            return self._mailbox.pop(tag, [])


@dataclass
class LoopbackWorker:
    """One logical worker's handle: rank + world + the shared rendezvous."""

    rank: int
    world_size: int
    rdv: LoopbackRendezvous

    def exchange(self, obj: Any, tag: str = "exchange") -> List[Any]:
        return self.rdv.exchange(self.rank, obj, tag=tag)

    def barrier(self, name: str = "barrier") -> None:
        self.rdv.barrier(self.rank, name)

    def broadcast(self, obj: Any = None, src: int = 0, tag: str = "bcast") -> Any:
        return self.rdv.broadcast(self.rank, obj, src=src, tag=tag)

    @property
    def is_leader(self) -> bool:
        return self.rank == 0


def run_workers(
    world_size: int,
    fn: Callable[[LoopbackWorker], Any],
    rdv: Optional[LoopbackRendezvous] = None,
) -> List[Any]:
    """Run ``fn(worker)`` on ``world_size`` threads over one rendezvous.
    Returns rank-ordered results; the FIRST worker exception re-raises (the
    rendezvous is aborted first so no peer hangs)."""
    rdv = rdv if rdv is not None else LoopbackRendezvous(world_size)
    results: List[Any] = [None] * world_size
    # Append-only error log; list.append is GIL-atomic and each worker
    # appends at most once, so the join below observes a complete log.
    errors: List[tuple] = []  # guarded-by: none(append-only under the GIL; read only after join)

    def runner(rank: int) -> None:
        try:
            results[rank] = fn(LoopbackWorker(rank, world_size, rdv))
        except BaseException as e:  # noqa: BLE001 — re-raised on the caller
            errors.append((rank, e))
            rdv.abort()

    threads = [
        threading.Thread(
            target=runner, args=(r,), name=f"mesh-worker-{r}", daemon=True
        )
        for r in range(world_size)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        errors.sort(key=lambda it: it[0])
        rank, err = errors[0]
        if isinstance(err, LoopbackError) and len(errors) > 1:
            # Barrier-broken errors are the SYMPTOM; surface a root cause.
            for r, e in errors:
                if not isinstance(e, LoopbackError):
                    rank, err = r, e
                    break
        raise LoopbackError(f"loopback worker {rank} failed: {err}") from err
    return results


# --------------------------------------------------------------- loopback e2e
def _shard_loader_view(loader, world_size: int, rank: int):
    """Rank ``rank``'s view of a loader: same dataset/head-spec/seed, dealt
    ``num_shards=world_size`` — the identical wrap-pad round-robin a real
    multi-process launch gets from create_dataloaders, so every worker yields
    the same number of identically-shaped batches per epoch."""
    from ..preprocess.dataloader import GraphDataLoader

    shard_batch = max(1, -(-loader.batch_size // world_size))
    return GraphDataLoader(
        loader.dataset,
        batch_size=shard_batch,
        shuffle=loader.shuffle,
        seed=loader.seed,
        num_shards=world_size,
        shard_rank=rank,
        head_types=loader.head_types,
        head_dims=loader.head_dims,
        edge_dim=loader.edge_dim,
        num_buckets=getattr(loader, "_num_buckets_requested", 1),
        reshuffle=loader.reshuffle,
        packing=loader.packing,
        ladder_step=loader.ladder_step,
        with_positions=loader.with_positions,
    )


def loopback_train(
    config: dict,
    world_size: int = 2,
    num_epochs: Optional[int] = None,
    grad_sync: Optional[str] = None,
) -> List[dict]:
    """The 2-process DP e2e on the loopback harness: ``world_size`` worker
    threads, each with its own rank-sharded loader, lockstep-stepping ONE
    shard_map DP train step over a ``world_size``-device mesh; eval reduced
    the same way. Returns the rank-ordered per-worker result dicts — every
    worker's metrics are the globally psum-reduced values, so the workers
    must agree exactly (the property the old 2-process test asserted).

    The leader thread owns the TrainState and the compiled step; batches are
    exchanged host-side (numpy pytrees), the gradient all-reduce is the
    step's own psum over the 'data' mesh axis. Dispatch stays on the leader
    because a JAX runtime is process-global — exactly why the loopback world
    is threads, not processes, on backends without multiprocess collectives."""
    import jax
    import numpy as np

    from ..analysis.contracts import gate_config
    from ..models.create import create_model_config, init_model_variables
    from ..preprocess.load_data import dataset_loading_and_splitting
    from ..train.train_validate_test import EpochMetrics
    from ..train.trainer import (
        create_train_state,
        make_eval_step_dp,
        make_train_step_dp,
        stack_batches,
    )
    from ..utils.config_utils import update_config
    from ..utils.optimizer import select_optimizer
    from .distributed import make_mesh, mesh_descriptor

    if len(jax.devices()) < world_size:
        raise LoopbackError(
            f"loopback world of {world_size} needs {world_size} devices; "
            f"{len(jax.devices())} visible — pin XLA_FLAGS="
            "--xla_force_host_platform_device_count"
        )
    # Same env default as run_training: the raw→serialized dataset convert
    # lands next to the caller unless pointed elsewhere.
    import os

    os.environ.setdefault("SERIALIZED_DATA_PATH", os.getcwd())
    gate_config(config, mode="training")
    train_loader, val_loader, test_loader, _ = dataset_loading_and_splitting(
        config=config
    )
    config = update_config(config, train_loader, val_loader, test_loader)
    training_cfg = config["NeuralNetwork"]["Training"]
    epochs = int(num_epochs or training_cfg["num_epoch"])
    model = create_model_config(
        config=config["NeuralNetwork"]["Architecture"], verbosity=0
    )
    example = next(iter(train_loader))
    variables = init_model_variables(model, example)
    optimizer = select_optimizer(
        training_cfg["optimizer"], training_cfg["learning_rate"]
    )
    mesh = make_mesh(
        data_axis=world_size, devices=jax.devices()[:world_size]
    )
    step = make_train_step_dp(
        model, optimizer, mesh,
        grad_sync=grad_sync or training_cfg.get("grad_sync") or "single",
        grad_bucket_mb=float(training_cfg.get("grad_bucket_mb") or 4.0),
    )
    eval_step = make_eval_step_dp(model, mesh)
    # Leader-owned mutable cell: ONLY the rank-0 thread reads/writes it, and
    # every access is ordered by the exchange barriers around the step.
    cell = {"state": create_train_state(model, variables, optimizer)}  # guarded-by: external(leader-thread-only by the rendezvous lockstep contract)
    rng = jax.random.PRNGKey(0)

    def _reduce_epoch(worker, loader_view, dispatch):
        """One lockstep pass over a rank-sharded loader: exchange host
        batches, leader dispatches, every worker accumulates the reduced
        metrics independently."""
        metrics = EpochMetrics()
        it = iter(loader_view)
        while True:
            batch = next(it, None)
            group = worker.exchange(batch, tag="step_batches")
            if all(b is None for b in group):
                break
            live = [b for b in group if b is not None]
            m = None
            if worker.is_leader:
                stacked = stack_batches(live, world_size)
                m = dispatch(stacked)
            m = worker.broadcast(m, src=0, tag="step_metrics")
            metrics.update(m)
        return metrics.averages()

    def worker_fn(worker: LoopbackWorker) -> dict:
        train_view = _shard_loader_view(train_loader, world_size, worker.rank)
        val_view = _shard_loader_view(val_loader, world_size, worker.rank)
        history: dict = {"total_loss_train": [], "total_loss_val": []}

        def train_dispatch(stacked):
            cell["state"], m = step(cell["state"], stacked, rng)
            return m

        def eval_dispatch(stacked):
            m, _outputs = eval_step(cell["state"], stacked)
            return m

        for epoch in range(epochs):
            train_view.set_epoch(epoch)
            loss, _ = _reduce_epoch(worker, train_view, train_dispatch)
            vloss, _ = _reduce_epoch(worker, val_view, eval_dispatch)
            history["total_loss_train"].append(float(loss))
            history["total_loss_val"].append(float(vloss))
        worker.barrier("epochs_done")
        return {
            "rank": worker.rank,
            "world_size": world_size,
            "mesh": mesh_descriptor(mesh),
            "history": history,
            "final_loss": history["total_loss_train"][-1],
        }

    return run_workers(world_size, worker_fn)


# ------------------------------------------------------------ proxy rendezvous
class ProxyRendezvous:
    """The spawn-path rendezvous: the same named-barrier/allgather protocol
    over a localhost TCP socket, for workers that are separate OS processes.

    Rank 0 hosts the coordinator (``serve()``); every rank (0 included)
    connects a client. One round = every rank POSTs ``(tag, rank, payload)``
    and blocks until the coordinator has all ``world_size`` payloads, then
    receives the rank-ordered list — a barrier with data. Payloads are JSON
    (host metadata, shapes, health), NOT tensors: this coordinates hosts;
    device collectives still ride the backend (which is exactly why the
    spawned 2-process arm keeps its precise skip on CPU — see
    docs/DISTRIBUTED.md "Harness modes")."""

    def __init__(self, world_size: int, timeout_s: float = _BARRIER_TIMEOUT_S):
        self.world_size = int(world_size)
        self.timeout_s = float(timeout_s)
        self._server = None
        # One-way mailbox (the TCP twin of LoopbackRendezvous.post):
        # heartbeats and membership announcements from spawned workers,
        # drained by the supervisor's membership loop. Written by coordinator
        # handler threads, read by the supervisor.
        self._mail_lock = tsan.instrument_lock(
            threading.Lock(), "ProxyRendezvous._mail_lock"
        )
        self._mailbox: dict = {}  # guarded-by: self._mail_lock

    # ------------------------------------------------------------ coordinator
    def serve(self, host: str = "127.0.0.1", port: int = 0) -> int:
        """Start the coordinator (rank 0's process); returns the bound port."""
        import socketserver

        world = self.world_size
        lock = tsan.instrument_lock(threading.Lock(), "ProxyRendezvous._lock")
        # tag -> [generation, ...]; each generation is one round
        # ({"slots": {rank: payload}, "done": Event, "served": count}). Tags
        # are REUSABLE across rounds (a heartbeat loop barriers on the same
        # name forever): a post onto a completed generation starts a fresh
        # one, and a generation is evicted once every rank has received its
        # result — no stale payloads, no unbounded coordinator growth. The
        # client protocol guarantees no rank re-posts a tag before its
        # previous call returned (allgather blocks until the round is full),
        # so at most the newest generation is incomplete.
        rounds: dict = {}  # guarded-by: lock

        proxy = self

        class Handler(socketserver.StreamRequestHandler):
            timeout = self.timeout_s  # per-connection read deadline

            def handle(self) -> None:
                line = self.rfile.readline()
                if not line:
                    return
                if not line.endswith(b"\n"):
                    # A torn frame (client died mid-write, or a deadline cut
                    # the read): answer loudly instead of feeding half a JSON
                    # document to the decoder.
                    self.wfile.write(
                        b'{"error": "partial frame (no trailing newline)"}\n'
                    )
                    return
                try:
                    msg = json.loads(line.decode())
                except ValueError:
                    self.wfile.write(b'{"error": "undecodable frame"}\n')
                    return
                tag, rank, payload = msg["tag"], int(msg["rank"]), msg["payload"]
                if msg.get("mode") == "post":
                    # One-way mailbox post: store and ACK immediately — a
                    # heartbeat must never block on a barrier round.
                    with proxy._mail_lock:
                        proxy._mailbox.setdefault(tag, []).append(
                            (rank, payload)
                        )
                    self.wfile.write(b'{"result": "posted"}\n')
                    return
                with lock:
                    gens = rounds.setdefault(tag, [])
                    if not gens or gens[-1]["done"].is_set():
                        gens.append(
                            {
                                "slots": {},
                                "done": threading.Event(),
                                "served": 0,
                            }
                        )
                    rnd = gens[-1]
                    if rank in rnd["slots"]:
                        self.wfile.write(
                            b'{"error": "duplicate rank post before round '
                            b'completion"}\n'
                        )
                        return
                    rnd["slots"][rank] = payload
                    if len(rnd["slots"]) == world:
                        rnd["done"].set()
                if not rnd["done"].wait(timeout=self.server.proxy.timeout_s):
                    with lock:
                        # Evict the wedged generation so the tag is not
                        # poisoned: survivors' retries must start a FRESH
                        # round instead of bouncing off their own stale
                        # slots as duplicate posts.
                        if rnd in gens and not rnd["done"].is_set():
                            gens.remove(rnd)
                            if not gens:
                                rounds.pop(tag, None)
                    self.wfile.write(b'{"error": "proxy barrier timeout"}\n')
                    return
                with lock:
                    out = [rnd["slots"].get(r) for r in range(world)]
                    rnd["served"] += 1
                    if rnd["served"] == world:
                        gens.remove(rnd)
                        if not gens:
                            rounds.pop(tag, None)
                self.wfile.write(
                    (json.dumps({"result": out}) + "\n").encode()
                )

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._server = Server((host, port), Handler)
        self._server.proxy = self
        threading.Thread(
            target=self._server.serve_forever,
            name="proxy-rendezvous",
            daemon=True,
        ).start()
        return self._server.server_address[1]

    def close(self) -> None:
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None

    # ------------------------------------------------------- server-side drain
    def posts(self, tag: str = "post") -> List[tuple]:
        """Drain (and clear) the coordinator-side mailbox for ``tag`` — the
        supervisor's membership loop feeds these into a
        :class:`~hydragnn_tpu.parallel.elastic.MembershipTracker`."""
        with self._mail_lock:
            return self._mailbox.pop(tag, [])

    # ----------------------------------------------------------------- client
    @staticmethod
    def _round_trip(
        address: str,
        doc: dict,
        timeout_s: float,
        connect_retries: int = 2,
    ) -> dict:
        """One hardened request/reply frame: connect with capped-backoff
        retry (the ``DeviceFeed(transfer_retries=)`` transient-failure
        policy, applied to the wire — a coordinator still binding its socket
        must not fail the whole world), write+read under explicit deadlines,
        and a LOUD partial-frame error instead of a hang or a bare JSON
        decode crash when the peer dies mid-frame."""
        import socket
        import time as _time

        host, _, port = address.partition(":")
        what = doc.get("tag", "?")
        last_err: Optional[Exception] = None
        for attempt in range(connect_retries + 1):
            try:
                conn = socket.create_connection(
                    (host, int(port)), timeout=timeout_s
                )
                break
            except OSError as e:
                last_err = e
                if attempt >= connect_retries:
                    raise LoopbackError(
                        f"proxy rendezvous {what!r}: connect to {address} "
                        f"failed after {attempt + 1} attempt(s): {e}"
                    ) from e
                _time.sleep(min(0.05 * (2**attempt), 1.0))
        else:  # pragma: no cover - loop always breaks or raises
            raise LoopbackError(str(last_err))
        with conn as s:
            # Write AND read deadlines: a wedged coordinator surfaces as a
            # socket.timeout here, never an unbounded hang.
            s.settimeout(timeout_s)
            f = s.makefile("rwb")
            f.write((json.dumps(doc) + "\n").encode())
            f.flush()
            try:
                line = f.readline()
            except OSError as e:  # socket.timeout is an OSError subclass
                raise LoopbackError(
                    f"proxy rendezvous {what!r}: reply read from {address} "
                    f"timed out/failed after {timeout_s:g}s: {e}"
                ) from e
        if not line or not line.endswith(b"\n"):
            raise LoopbackError(
                f"proxy rendezvous {what!r}: partial frame from {address} "
                f"({len(line)} byte(s) without a trailing newline) — the "
                "coordinator died or a deadline cut the reply mid-frame"
            )
        try:
            return json.loads(line.decode())
        except ValueError as e:
            raise LoopbackError(
                f"proxy rendezvous {what!r}: undecodable reply frame from "
                f"{address}: {e}"
            ) from e

    @staticmethod
    def allgather(
        address: str, tag: str, rank: int, payload: Any,
        timeout_s: float = _BARRIER_TIMEOUT_S,
        connect_retries: int = 2,
    ) -> List[Any]:
        """Client side: post this rank's payload for ``tag``, block until all
        ranks posted, return the rank-ordered payload list."""
        reply = ProxyRendezvous._round_trip(
            address,
            {"tag": tag, "rank": rank, "payload": payload},
            timeout_s,
            connect_retries=connect_retries,
        )
        if "error" in reply:
            raise LoopbackError(f"proxy rendezvous {tag!r}: {reply['error']}")
        return reply["result"]

    @staticmethod
    def post(
        address: str, tag: str, rank: int, payload: Any,
        timeout_s: float = _POST_TIMEOUT_S,
        connect_retries: int = 2,
    ) -> None:
        """One-way mailbox post (heartbeats, membership announcements):
        ACKed by the coordinator immediately, never blocks on a barrier."""
        reply = ProxyRendezvous._round_trip(
            address,
            {"tag": tag, "rank": rank, "payload": payload, "mode": "post"},
            timeout_s,
            connect_retries=connect_retries,
        )
        if "error" in reply:
            raise LoopbackError(f"proxy rendezvous {tag!r}: {reply['error']}")

    @staticmethod
    def barrier(
        address: str, name: str, rank: int,
        timeout_s: float = _BARRIER_TIMEOUT_S,
    ) -> None:
        ProxyRendezvous.allgather(
            address, f"barrier:{name}", rank, None, timeout_s=timeout_s
        )


# --------------------------------------------------------------------- CLI
def main(argv: Optional[Sequence[str]] = None) -> int:
    """Loopback DP e2e from a JSON config — the run_suite_2proc fallback arm
    and the CI 4-device smoke. Prints one ``FINAL_LOSS <rank> <loss>`` line
    per worker (all must agree — psum-reduced) and a summary JSON."""
    import argparse
    import os

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("config")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--epochs", type=int, default=None)
    ap.add_argument("--grad-sync", default=None)
    ap.add_argument(
        "--thresholds",
        default=None,
        help='"rmse" convergence gate on the final reduced train loss',
    )
    args = ap.parse_args(argv)

    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={max(args.workers, 2)}"
    )
    import jax

    # Same accelerator opt-in as benchmarks/: HYDRAGNN_TPU_TESTS=1 leaves
    # the real backend so the harness can drive actual devices; default is
    # the hermetic virtual CPU topology pinned above.
    if os.environ.get("HYDRAGNN_TPU_TESTS") != "1":
        jax.config.update("jax_platforms", "cpu")
    with open(args.config) as f:
        config = json.load(f)
    results = loopback_train(
        config,
        world_size=args.workers,
        num_epochs=args.epochs,
        grad_sync=args.grad_sync,
    )
    for r in results:
        print(f"FINAL_LOSS {r['rank']} {r['final_loss']:.10f}", flush=True)
    finals = {r["final_loss"] for r in results}
    ok = len(finals) == 1
    if args.thresholds is not None:
        bound = float(args.thresholds.split()[0])
        ok = ok and all(r["final_loss"] < bound for r in results)
    print(
        json.dumps(
            {
                "mode": "loopback",
                "workers": args.workers,
                "mesh": results[0]["mesh"],
                "final_loss": results[0]["final_loss"],
                "workers_agree": len(finals) == 1,
                "ok": ok,
            }
        )
    )
    return 0 if ok else 1


if __name__ == "__main__":
    import sys

    sys.exit(main())
