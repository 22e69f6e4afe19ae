"""Async input pipeline: host collation and host->device transfer overlapped
with device compute (docs/INPUT_PIPELINE.md).

Round-5 hardware benches put the streamed production path at 770-808 graphs/s
against 926k graphs/s/chip on the pre-staged scan path (BENCH_r05_hw.json):
host->device transfer serialized with compute because the single prefetch
thread overlapped host collation only. The fix is the standard double-buffered
device feed (tf.data / flax.jax_utils.prefetch_to_device pattern):

    loader.__iter__            _Prefetcher             _Prefetcher
    (collation, thread 1) --> [host queue] --> transfer (device_put +
                                               block_until_ready, thread 2)
                                          --> [device queue, depth 2] --> step

While step *k* executes on device, batch *k+1* is already committed device
memory and batch *k+2* is in flight on the DMA engine — the steady-state step
never waits on H2D. The device queue depth of 2 is the double buffer: it
bounds in-flight HBM to (depth + one being transferred) batches.

Blocking on the transfer INSIDE the transfer thread is deliberate: transfers
land on the DMA engine, so the wait does not stall compute, it gives the
pipeline backpressure, and it makes the recorded H2D seconds the true wire
time rather than the (async) dispatch time. Those seconds land in
``FeedStats`` — the per-epoch transfer-vs-compute split surfaced through
``Timer``/``Profiler`` and reported by bench.py next to the throughput.

Consumers: every epoch-level TrainingDriver path (train_epoch, the chunked
scan, evaluate) AND the online inference engine (serve/engine.py), whose
micro-batcher generator runs as the host stage and whose dispatch thread is
the consumer. An out-of-core corpus composes transparently: a
``StreamingGraphLoader`` (datasets/stream.py, docs/DATA_PLANE.md) iterated
by thread 1 adds its shard-prefetch ring as a stage 0 — disk I/O + decode
overlap collation, which overlaps transfer, which overlaps compute — the
serving path gets the same batch-k+1-commits-while-k-
computes overlap as a training epoch.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterable, Optional

from ..analysis import tsan
from ..telemetry import graftel as telemetry


def transfer_error_is_transient(e: BaseException) -> bool:
    """Transfer failures worth retrying: only those explicitly marked
    ``transient`` (the fault layer's injected drill errors carry the mark).
    A ``device_put`` to a chip attached to this host has no transport that
    flaps. ``RESOURCE_EXHAUSTED`` there is HBM exhaustion, and it is NOT
    retried: the batch, bucket ladder or queue depth does not fit the chip,
    a retry only delays the error, and a run that passes on the second try is
    one allocation away from failing. Everything unmarked — shape/dtype
    mismatches, cancelled pipelines, out-of-memory — propagates on the first
    raise."""
    return bool(getattr(e, "transient", False))


def with_transfer_retries(
    transfer: Callable,
    retries: int = 2,
    backoff_s: float = 0.05,
    max_backoff_s: float = 2.0,
    transient: Callable = transfer_error_is_transient,
) -> Callable:
    """Wrap a transfer callable with capped exponential backoff on TRANSIENT
    failures (docs/FAULT_TOLERANCE.md). Runs on the pipeline's transfer
    thread, so the backoff sleep never stalls device compute — the device
    queue simply drains one slot deeper. Retries are counted
    (FaultCounters ``transfer_retries``); a non-transient error, or a
    transient one that survives every attempt, propagates to the consumer
    exactly like before."""
    if retries <= 0:
        return transfer

    def retrying(item):
        delay = backoff_s
        for attempt in range(retries + 1):
            try:
                return transfer(item)
            except Exception as e:  # noqa: BLE001 — classified below
                if attempt >= retries or not transient(e):
                    raise
                from ..faults.counters import FaultCounters

                FaultCounters.inc("transfer_retries")
                time.sleep(min(delay, max_backoff_s))
                delay *= 2.0

    return retrying


class _Prefetcher:
    """Background-thread batch producer: the stage boundary of the pipeline.
    Bounded queue; exceptions re-raised at the consumer; abandoning iteration
    (e.g. the train step raising) cancels the producer so neither the thread
    nor queued batches leak."""

    _SENTINEL = object()

    def __init__(self, iterable: Iterable, depth: int = 8, ctx=None):
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._err = None
        self._cancel = threading.Event()

        def _run():
            # Explicit telemetry context handoff (docs/OBSERVABILITY.md):
            # spans opened by the stage callable on THIS thread parent to the
            # epoch/pipeline span the consumer captured — thread-locals alone
            # cannot cross the stage boundary.
            telemetry.attach(ctx)
            try:
                for item in iterable:
                    while not self._cancel.is_set():
                        try:
                            self._q.put(item, timeout=0.1)
                            break
                        except queue.Full:
                            continue
                    if self._cancel.is_set():
                        return
            except BaseException as e:  # propagate to consumer
                self._err = e
            finally:
                # The sentinel must not be dropped: with the queue full (>=
                # depth batches and a momentarily slow consumer) put_nowait
                # would raise Full, the consumer would drain the items and
                # then block on get() forever. Block with cancel checks,
                # exactly like regular items.
                while not self._cancel.is_set():
                    try:
                        self._q.put(self._SENTINEL, timeout=0.1)
                        break
                    except queue.Full:
                        continue

        self._thread = threading.Thread(
            target=_run, name="hydragnn-prefetch", daemon=True
        )
        self._thread.start()

    def close(self):
        self._cancel.set()
        # Drain so a producer blocked on put() wakes and exits.
        try:
            while True:
                self._q.get_nowait()
        except Exception:
            pass
        # Wake a CONSUMER blocked on get(): when stages are chained, the
        # downstream stage's thread sits in this queue's get() — draining
        # alone could swallow the sentinel and leave it blocked forever.
        try:
            self._q.put_nowait(self._SENTINEL)
        except Exception:
            pass

    def __iter__(self):
        try:
            while True:
                item = self._q.get()
                if item is self._SENTINEL:
                    if self._err is not None:
                        raise self._err
                    return
                yield item
        finally:
            self.close()


class FeedStats:
    """Per-epoch transfer-vs-compute split of one epoch-level driver call.

    Written from two threads: the transfer thread records the ``h2d_*``
    fields while the consumer credits ``feed_wait_s``/``step_s`` and calls
    ``reset()`` at epoch start. The original lock-free disjoint-field design
    was safe only until ``reset()`` raced a late in-flight ``record_h2d``
    from the previous epoch's draining pipeline — graftrace flagged the
    pair, and one coarse lock (two uncontended acquisitions per batch)
    closes it for every field.

    - ``h2d_bytes`` / ``h2d_s``: payload bytes moved host->device and the
      true wire seconds (measured around a blocking device_put in the
      transfer thread — overlapped with compute, so this is NOT a share of
      epoch wall time unless the pipeline is transfer-bound).
    - ``feed_wait_s``: consumer seconds blocked on the device queue — where
      an input-bound pipeline actually stalls. Every consumer loop that pulls
      from a ``DeviceFeed`` credits it: the per-step loop, ``evaluate`` and
      the scan path's pull of its next chunk.
    - ``step_s``: consumer seconds in step dispatch + metrics readback (the
      readback blocks on the device computation, so this is compute-bound
      wall time).
    """

    def __init__(self):
        self._lock = tsan.instrument_lock(threading.Lock(), "FeedStats._lock")
        self.reset()

    def reset(self):
        with self._lock:
            self.h2d_bytes = 0  # guarded-by: self._lock
            self.h2d_s = 0.0  # guarded-by: self._lock
            self.h2d_transfers = 0  # guarded-by: self._lock
            self.feed_wait_s = 0.0  # guarded-by: self._lock
            self.step_s = 0.0  # guarded-by: self._lock

    def record_h2d(self, nbytes: int, seconds: float) -> int:
        """Add one transfer; returns its number in the epoch. The graftel
        ``h2d`` span is the caller's (``TrainingDriver._put_timed``), open
        while the transfer runs, so it is an event of a captured trace too."""
        with self._lock:
            self.h2d_bytes += int(nbytes)
            self.h2d_s += seconds
            self.h2d_transfers += 1
            idx = self.h2d_transfers
            tsan.shared_access("FeedStats.fields")
        return idx

    def credit(self, field: str, seconds: float) -> None:
        """Add consumer-side seconds to ``feed_wait_s``/``step_s``: the
        ``dur_s`` of the region's graftel span (``feed_wait``, ``device_step``,
        ``eval_step``), which is the region's one clock pair."""
        with self._lock:
            setattr(self, field, getattr(self, field) + seconds)
            tsan.shared_access("FeedStats.fields")

    def as_dict(self) -> dict:
        with self._lock:
            return {
                "h2d_bytes": self.h2d_bytes,
                "h2d_s": round(self.h2d_s, 4),
                "h2d_transfers": self.h2d_transfers,
                "feed_wait_s": round(self.feed_wait_s, 4),
                "step_s": round(self.step_s, 4),
            }


class DeviceFeed:
    """Two-stage bounded pipeline: a host stage runs ``iterable`` (collation)
    in one thread; a transfer stage applies ``transfer`` (device_put dispatch
    + completion wait) in a second thread; the consumer iterates committed
    device arrays. With ``transfer=None`` this degrades to the single-stage
    host prefetcher (the pre-round-6 behavior).

    Exceptions raised in either stage re-raise at the consumer; ``close()``
    (also triggered by abandoning iteration) cancels both threads, in
    downstream-first order so a transfer thread blocked on the host queue is
    woken by the host stage's close.

    Transient transfer failures (transfer_error_is_transient) are retried
    with capped exponential backoff on the transfer thread before
    propagating — ``transfer_retries=0`` restores fail-on-first-raise."""

    def __init__(
        self,
        iterable: Iterable,
        transfer: Optional[Callable] = None,
        host_depth: int = 8,
        device_depth: int = 2,
        transfer_retries: int = 2,
        transfer_backoff_s: float = 0.05,
        ctx=None,
    ):
        if transfer is not None and transfer_retries > 0:
            transfer = with_transfer_retries(
                transfer, retries=transfer_retries, backoff_s=transfer_backoff_s
            )
        # ``ctx`` is the caller's telemetry context (the epoch / serve
        # pipeline span): handed EXPLICITLY to both stage threads so their
        # spans parent to it (docs/OBSERVABILITY.md "context handoff").
        self._host = _Prefetcher(iterable, depth=host_depth, ctx=ctx)
        self._dev = (
            None
            if transfer is None
            else _Prefetcher(
                map(transfer, self._host), depth=device_depth, ctx=ctx
            )
        )

    def close(self):
        if self._dev is not None:
            self._dev.close()
        self._host.close()

    def join(self, timeout: float = 5.0) -> bool:
        """True when both stage threads have exited (tests/diagnostics)."""
        self._host._thread.join(timeout)
        if self._dev is not None:
            self._dev._thread.join(timeout)
        return not (
            self._host._thread.is_alive()
            or (self._dev is not None and self._dev._thread.is_alive())
        )

    def __iter__(self):
        src = self._dev if self._dev is not None else self._host
        try:
            yield from src
        finally:
            self.close()


def traced_batches(iterable: Iterable, name: str = "collate"):
    """Wrap a batch source so each pull becomes a graftel span (the host
    collation timeline of the flight recorder). Runs wherever the iterable
    is consumed — on the DeviceFeed host thread for the pipelined paths — so
    the spans parent to the context that thread attached."""
    it = iter(iterable)
    i = 0
    while True:
        with telemetry.span(name, index=i):
            try:
                b = next(it)
            except StopIteration:
                return
        yield b
        i += 1
