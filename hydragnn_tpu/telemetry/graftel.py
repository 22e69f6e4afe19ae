"""graftel — process-wide structured tracing, flight recorder, and metric
registry for the whole train/serve stack (docs/OBSERVABILITY.md).

Before this module the stack had five disconnected telemetry surfaces
(``Timer``, ``FeedStats``, ``ServeMetrics``, ``FaultCounters``,
``supervisor.json``), none of which could answer "what was happening across
the stack when step K went bad / request R breached its deadline?". graftel
is the hub they all emit into:

* **Spans and events.** ``span(name, **attrs)`` is a context manager timing a
  wall-clock region; ``event(name, **attrs)`` records an instant. Both carry
  a :class:`Context` (trace id, span id, optional request correlation id) and
  the emitting thread's name. A span is also a RUNNING TOTAL: on exit its
  seconds and one count go to ``span_s/<name>`` / ``span_n/<name>`` in the
  registry, under the lock acquisition the record already makes, so where a
  run's host seconds went is readable with collection off and after the ring
  has turned over (``span_totals()``). A span keeps its seconds as
  ``.dur_s``: the one clock pair of its region, which ``FeedStats`` and
  ``Timer`` are credited from. Its attributes may be set while it is open
  (``span.attrs[...] = ...``; ``span.elapsed_s()`` reads its clock): that is
  how a ``device_step`` record carries clock readings taken inside it with no
  child span. Same-thread nesting rides a thread-local
  context stack; CROSS-thread propagation is explicit — a producer captures
  ``current()`` (or a span's ``.ctx``) and the consumer thread calls
  ``attach(ctx)`` (the DeviceFeed pipeline and the serve dispatcher do this),
  because the stack's seven thread roots make thread-locals alone a dead end.

* **Flight recorder.** Every record also lands in a bounded ring
  (``deque(maxlen=...)``) that is ALWAYS on; ``flight_dump(trigger)`` writes
  the ring + counter/gauge snapshot to
  ``<run_dir>/flightrec_<pid>_<seq>_<trigger>.json``. Wired triggers:
  non-finite step-guard trips (faults/guard.py), engine poisoning
  (serve/engine.py), checkpoint-fallback loads (checkpoint/io.py),
  supervisor restarts (faults/supervisor.py), elastic dirty-shrink
  transitions (parallel/elastic.py — the timeline that led into a worker
  death, next to the checkpoint the shrunk world resumed from), and a
  stalled epoch (``epoch_stall``, train/train_validate_test.py: an epoch 1.5
  times the median of those before it, with each span name's seconds in it).

* **Metric registry.** ``counter``/``gauge``/``timer_credit`` feed one locked
  registry; ``Timer`` and ``FaultCounters`` delegate their storage here, so
  ``print_timers``, ``bench.py``, and the serve ``/metrics`` exposition all
  read the same numbers. ``render_prometheus()`` exports the registry in
  Prometheus text format — including the per-epoch training gauges
  (``hydragnn_train_*``) the epoch loop publishes.

* **jax bridges.** ``install_jax_hooks()`` registers a monitoring listener
  that folds JAX's own durations into the registry: every XLA backend
  compile (``jax/compiles`` + ``jax/compile_s``, also a ring event; JAX
  takes that duration round ``compile_or_get_cached``, so it HOLDS the
  persistent cache's loads), tracing (``jax/trace_s``), lowering
  (``jax/lower_s``) and the persistent cache's retrievals
  (``jax/cache_loads`` + ``jax/cache_load_s``);
  ``configure(jax_annotations=True)`` makes every span also open a
  ``jax.profiler.TraceAnnotation`` so host spans line up with device ops in
  a captured Perfetto trace.

* **The collector's pauses.** ``install_gc_hook()`` puts a ``gc.callbacks``
  entry in: every collection's seconds go to ``host/gc_pause_s``, and one of
  ``GC_RECORD_S`` (1 ms) or longer is a retroactive ``gc`` span
  (``record_span``: generation and collected count as attributes, no
  ``TraceAnnotation``, marked ``retro`` so that no reader takes it for a
  phase its thread opened).

Zero-surprise defaults: the ring and registry are always live (host-side,
one uncontended lock acquisition per record; what it costs on the chip, on
against off, is in PERF.md §6, PR 35); full span COLLECTION for the JSONL /
Chrome-trace exporters is opt-in (``configure(collect=True)``, the
``Telemetry`` config block, or ``HYDRAGNN_TRACE=1``). ``enabled=False``
silences span/event recording entirely while keeping the counter registry
(Timer/FaultCounters storage) functional.
"""

from __future__ import annotations

import functools
import gc
import itertools
import os
import threading
import time
import uuid
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

from ..analysis import tsan

try:  # the per-thread involuntary switches of ``thread_sched``
    import resource
except ImportError:  # a platform without it
    resource = None

SCHEMA_EVENTS = "hydragnn-graftel-events/v1"
SCHEMA_FLIGHT = "hydragnn-flightrec/v1"

_RING_CAPACITY = 4096

_lock = tsan.instrument_lock(threading.Lock(), "graftel._lock")
# The record stream: ring is the always-on flight-recorder window; collected
# is the unbounded export buffer, a list only while collect mode is on.
_ring: "deque" = deque(maxlen=_RING_CAPACITY)  # guarded-by: _lock
_collected: Optional[List[dict]] = None  # guarded-by: _lock
# Metric registry (one store for Timer / FaultCounters / train gauges).
_counters: Dict[str, float] = {}  # guarded-by: _lock
_gauges: Dict[str, float] = {}  # guarded-by: _lock
_dump_seq = 0  # guarded-by: _lock
# Span-id source: itertools.count.__next__ is a single C call (GIL-atomic),
# so id allocation never touches the registry lock — spans stay cheap on the
# per-batch hot paths even while another thread holds _lock for a dump.
_id_counter = itertools.count(1)
# Config flags. Hot-path readers (span/event fast paths) read these
# unlocked; writers hold the lock.
_enabled = True  # guarded-by: _lock, dirty-reads(bool flag flipped only by configure(); a stale read records or skips one extra record, never corrupts state)
_run_dir: Optional[str] = None  # guarded-by: _lock, dirty-reads(rebound only by configure(); a dump racing a reconfigure writes to the old run dir, which is correct for the events it holds)
_jax_annotations = False  # guarded-by: _lock, dirty-reads(bool flag flipped only by configure(); a stale read annotates or skips one span)
_jax_hooks_installed = False  # guarded-by: _lock

# Registry keys of a span name's running totals (seconds, count).
SPAN_SECONDS = "span_s/"
SPAN_COUNT = "span_n/"
# A collection this long or longer is a ``gc`` record, not only a count.
GC_RECORD_S = 1e-3
# JAX's own monitoring durations folded into counters beside jax/compile_s.
_JAX_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_JAX_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_JAX_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax/trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax/lower_s",
    _JAX_BACKEND_COMPILE: "jax/compile_s",
    _JAX_CACHE_LOAD: "jax/cache_load_s",
}
# Collections the gc callback saw, waiting to be folded in (``_fold_gc``). The
# callback may run between any two bytecodes of a thread that HOLDS ``_lock``,
# so it takes no lock: deque.append/popleft are single C calls.
_gc_pending: "deque" = deque(maxlen=_RING_CAPACITY)  # guarded-by: none(deque.append/popleft are GIL-atomic; the callback must never take _lock: it can fire inside a critical section of its own thread)
_gc_t0: Optional[float] = None  # guarded-by: none(the interpreter runs one collection at a time and both callback phases inside it)

# Per-process trace id — every record of this process shares it, so merged
# event logs from a supervised run's incarnations stay separable.
_TRACE_ID = uuid.uuid4().hex[:16]

_tls = threading.local()  # context stacks are thread-local (self-synced)


# ------------------------------------------------------------------ contexts
@dataclass(frozen=True)
class Context:
    """An explicit handoff token: (trace, parent span, request correlation).

    Producers capture one (``current()`` or ``span.ctx``) and hand it to the
    thread/callable that continues the work; the receiver either passes it as
    ``parent=`` or installs it as the thread's base with :func:`attach`."""

    trace_id: str
    span_id: str
    request_id: Optional[str] = None


def _new_span_id() -> str:
    return f"s{next(_id_counter):08x}"


def new_context(request_id: Optional[str] = None) -> Context:
    """Fresh root context (e.g. one per serve-pipeline incarnation)."""
    return Context(_TRACE_ID, _new_span_id(), request_id)


def new_request_id() -> str:
    """Serve correlation id: carried submit → pack bin → device batch →
    demux → response (+ echoed in the X-HydraGNN-Request-Id header)."""
    return "r-" + uuid.uuid4().hex[:12]


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def current() -> Optional[Context]:
    """This thread's innermost context (None outside any span/attach)."""
    st = _stack()
    return st[-1] if st else None


def attach(ctx: Optional[Context]) -> None:
    """Install ``ctx`` as this thread's base context — the explicit
    cross-thread handoff (DeviceFeed stage threads, the serve dispatcher)."""
    if ctx is not None:
        _stack().append(ctx)


def detach() -> None:
    st = _stack()
    if st:
        st.pop()


# ------------------------------------------------------------------- records
def _record(rec: dict) -> None:
    with _lock:
        _ring.append(rec)
        if _collected is not None:
            _collected.append(rec)
        if rec["kind"] == "span":
            seconds, count = SPAN_SECONDS + rec["name"], SPAN_COUNT + rec["name"]
            _counters[seconds] = _counters.get(seconds, 0.0) + rec["dur_s"]
            _counters[count] = _counters.get(count, 0.0) + 1.0
            tsan.shared_access("graftel.registry")


class span:
    """Timed region. Plain class (not contextlib): it sits in per-batch hot
    loops, so one small allocation per use. ``dur_s`` holds the region's
    seconds once it has closed (None before), recording on or off: the
    consumer loops credit ``FeedStats`` from it, so a region has ONE clock
    pair. ``attrs`` is the record's own dict: what is set on it while the
    span is open (``span.attrs["wait_s"] = ...``) is in the record."""

    __slots__ = (
        "name", "attrs", "ctx", "dur_s", "_parent", "_t0", "_wall0", "_jax",
        "_off",
    )

    def __init__(
        self,
        name: str,
        parent: Optional[Context] = None,
        request_id: Optional[str] = None,
        **attrs: Any,
    ):
        self.name = name
        self.attrs = attrs
        self._parent = parent
        self.ctx = Context(
            _TRACE_ID,
            _new_span_id(),
            request_id
            if request_id is not None
            else (parent.request_id if parent is not None else None),
        )
        self.dur_s = None
        self._jax = None
        self._off = False

    def __enter__(self):
        # Disabled fast path: no stack/annotation work, only the clock (the
        # .ctx is still real: callers hand it to DeviceFeed regardless), and
        # nothing records.
        if not _enabled:
            self._off = True
            self._t0 = time.perf_counter()
            return self
        parent = self._parent if self._parent is not None else current()
        if parent is not None and self.ctx.request_id is None and parent.request_id:
            self.ctx = Context(self.ctx.trace_id, self.ctx.span_id, parent.request_id)
        self._parent = parent
        _stack().append(self.ctx)
        if _jax_annotations:
            try:
                import jax

                self._jax = jax.profiler.TraceAnnotation(self.name)
                self._jax.__enter__()
            except Exception:
                self._jax = None
        self._wall0 = time.time()
        self._t0 = time.perf_counter()
        return self

    def elapsed_s(self) -> float:
        """Seconds since the span opened, on the clock ``dur_s`` is taken
        from: a reading taken inside the region splits it with no child span
        (the train loop's dispatch and wait, the engine's launch and wait)."""
        return time.perf_counter() - self._t0

    def __exit__(self, *exc):
        dur = self.dur_s = time.perf_counter() - self._t0
        if self._off:
            return
        if self._jax is not None:
            self._jax.__exit__(*exc)
        st = _stack()
        if st and st[-1] is self.ctx:
            st.pop()
        if not _enabled:
            return
        _fold_gc()
        rec = {
            "kind": "span",
            "name": self.name,
            "ts": self._wall0,
            "dur_s": dur,
            "thread": threading.current_thread().name,
            "trace_id": self.ctx.trace_id,
            "span_id": self.ctx.span_id,
            "parent_id": self._parent.span_id if self._parent else None,
        }
        if self.ctx.request_id:
            rec["request_id"] = self.ctx.request_id
        if self.attrs:
            rec["attrs"] = self.attrs
        _record(rec)


def record_span(
    name: str,
    dur_s: float,
    parent: Optional[Context] = None,
    request_id: Optional[str] = None,
    end_ts: Optional[float] = None,
    thread: Optional[str] = None,
    **attrs: Any,
) -> None:
    """Retroactive span for a region timed elsewhere (a garbage collection,
    timed by the interpreter's callback): it ended at ``end_ts`` (now) on
    ``thread`` (this one). The record is marked ``retro``: it was never open
    on its thread's stack and is no ``TraceAnnotation``, so it is nobody's
    child phase; its seconds go to the running totals like any span's."""
    if not _enabled:
        return
    ctx = parent if parent is not None else current()
    rec = {
        "kind": "span",
        "name": name,
        "ts": (time.time() if end_ts is None else end_ts) - dur_s,
        "dur_s": float(dur_s),
        "thread": thread or threading.current_thread().name,
        "trace_id": _TRACE_ID,
        "span_id": _new_span_id(),
        "parent_id": ctx.span_id if ctx else None,
        "retro": True,
    }
    rid = request_id or (ctx.request_id if ctx else None)
    if rid:
        rec["request_id"] = rid
    if attrs:
        rec["attrs"] = attrs
    _record(rec)


def event(name: str, request_id: Optional[str] = None, **attrs: Any) -> None:
    """Instant record (fault fired, request admitted, engine degraded...)."""
    if not _enabled:
        return
    ctx = current()
    rec = {
        "kind": "event",
        "name": name,
        "ts": time.time(),
        "thread": threading.current_thread().name,
        "trace_id": _TRACE_ID,
        "span_id": _new_span_id(),
        "parent_id": ctx.span_id if ctx else None,
    }
    rid = request_id or (ctx.request_id if ctx else None)
    if rid:
        rec["request_id"] = rid
    if attrs:
        rec["attrs"] = attrs
    _record(rec)


# --------------------------------------------------------- the thread's turn
_SCHEDSTAT = "/proc/thread-self/schedstat"
# The path once it was found missing: a kernel without the file does not grow
# it, and the failing ``open`` is not asked again. On a sandboxed kernel (the
# machine with the benchmark's chips) a system call is slow enough for the
# thread to lose the GIL while it is out, and to wait for a busy feed or
# client thread to hand it back: ~0.45 ms a call on the dispatching thread,
# 1% of the PNA train cell (PERF.md section 6, PR 50).
_no_schedstat: Optional[str] = None


def thread_sched():
    """``(run_delay_s, involuntary_switches)`` of the CALLING thread as the
    kernel counts them, both cumulative: the seconds it was runnable and not
    run (the second field of ``/proc/thread-self/schedstat``) and the times
    it was switched out against its will (``RUSAGE_THREAD``'s ``ru_nivcsw``).
    Two readings round a region tell a descheduled host thread from a slow
    program. A part the platform does not count is None, never 0 (a
    sandboxed kernel without ``schedstat`` gives ``(None, switches)``); None
    where it counts neither. One file read: for the two ends of a chunk, an
    evaluation step, an epoch or a flush, never a request or a batch."""
    global _no_schedstat
    delay = None
    if _SCHEDSTAT != _no_schedstat:
        try:
            with open(_SCHEDSTAT) as f:
                delay = int(f.read().split()[1]) * 1e-9
        except FileNotFoundError:
            _no_schedstat = _SCHEDSTAT
        except (OSError, IndexError, ValueError):
            pass
    switches = None
    try:
        switches = resource.getrusage(resource.RUSAGE_THREAD).ru_nivcsw
    except (AttributeError, OSError, ValueError):  # no module, no RUSAGE_THREAD
        pass
    if delay is None and switches is None:
        return None
    return delay, switches


def sched_since(before):
    """``(run_delay_s, nivcsw)`` the calling thread gained since ``before``
    (an earlier ``thread_sched()`` of the same thread); a part the platform
    does not count is None: not counted, which is not a count of nothing."""
    after = thread_sched()
    if before is None or after is None:
        return None, None
    return tuple(
        None if a is None or b is None else max(a - b, 0)
        for a, b in zip(after, before)
    )


# ----------------------------------------------------------- metric registry
def counter(name: str, n: float = 1.0) -> None:
    with _lock:
        _counters[name] = _counters.get(name, 0.0) + n
        tsan.shared_access("graftel.registry")


def timer_credit(name: str, seconds: float) -> None:
    """The Timer storage op: accumulate seconds under ``timer/<name>``."""
    counter("timer/" + name, float(seconds))


def gauge(name: str, value: float) -> None:
    with _lock:
        _gauges[name] = float(value)
        tsan.shared_access("graftel.registry")


def counter_value(name: str) -> float:
    _fold_gc()
    with _lock:
        return _counters.get(name, 0.0)


def counters_snapshot(prefix: str = "") -> Dict[str, float]:
    _fold_gc()
    with _lock:
        return {
            k: v for k, v in _counters.items() if k.startswith(prefix)
        }


def span_totals() -> Dict[str, float]:
    """{span name: seconds of its closed spans so far, all threads}: the
    always-live account of where the host's wall went (a nested span's
    seconds are in its parents' too). The difference of two copies is one
    epoch's account (the stall detector of train_validate_test.py)."""
    pre = len(SPAN_SECONDS)
    return {k[pre:]: v for k, v in counters_snapshot(SPAN_SECONDS).items()}


def jax_seconds() -> Dict[str, float]:
    """The four cumulative ``jax/*_s`` counters under one lock acquisition,
    keyed ``jax_trace_s`` ... : what each ``epoch`` span carries as
    attributes, so a reader knows how they stood when the epoch opened."""
    with _lock:
        return {
            key.replace("/", "_"): _counters.get(key, 0.0)
            for key in _JAX_DURATIONS.values()
        }


def gauges_snapshot() -> Dict[str, float]:
    with _lock:
        return dict(_gauges)


def timer_totals() -> Dict[str, float]:
    """{timer name: accumulated seconds} — the Timer.snapshot() payload."""
    pre = "timer/"
    with _lock:
        return {
            k[len(pre):]: v for k, v in _counters.items() if k.startswith(pre)
        }


def clear_counters(prefix: str) -> None:
    """Reset one delegated namespace (Timer.reset / FaultCounters.reset)."""
    with _lock:
        for k in [k for k in _counters if k.startswith(prefix)]:
            del _counters[k]


def snapshot_records() -> List[dict]:
    """Locked copy of the flight-recorder ring (newest last)."""
    _fold_gc()
    with _lock:
        return list(_ring)


def collected_records() -> List[dict]:
    """Locked copy of the export buffer ([] when collect mode is off)."""
    _fold_gc()
    with _lock:
        return list(_collected) if _collected is not None else []


# ----------------------------------------------------------------- lifecycle
def configure(
    run_dir: Optional[str] = None,
    collect: Optional[bool] = None,
    enabled: Optional[bool] = None,
    jax_annotations: Optional[bool] = None,
) -> None:
    """Process-wide setup. Omitted arguments keep their current value.
    ``run_dir`` is where flight-recorder dumps land (run_training points it
    at ``./logs/<name>``); ``collect=True`` buffers every record for the
    JSONL/Chrome exporters; ``enabled=False`` silences span/event recording
    (the counter registry stays live — Timer storage must keep working)."""
    global _run_dir, _collected, _enabled, _jax_annotations
    with _lock:
        if run_dir is not None:
            _run_dir = run_dir
        if enabled is not None:
            _enabled = bool(enabled)
        if jax_annotations is not None:
            _jax_annotations = bool(jax_annotations)
        if collect is not None:
            if collect and _collected is None:
                _collected = []
            elif not collect:
                _collected = None


def configured_run_dir() -> Optional[str]:
    with _lock:
        return _run_dir


def collecting() -> bool:
    with _lock:
        return _collected is not None


def jax_annotations() -> bool:
    """Whether spans open a ``jax.profiler.TraceAnnotation`` (the bridge
    ``utils/profile.Profiler`` switches on while its trace is open)."""
    with _lock:
        return _jax_annotations


def reset(keep_config: bool = False) -> None:
    """Clear records + registry (tests). ``keep_config`` keeps run_dir /
    collect / enabled; the default restores module defaults."""
    global _collected, _run_dir, _enabled, _jax_annotations
    _gc_pending.clear()
    with _lock:
        _ring.clear()
        _counters.clear()
        _gauges.clear()
        if _collected is not None:
            _collected = []
        if not keep_config:
            _collected = None
            _run_dir = None
            _enabled = True
            _jax_annotations = False


# ------------------------------------------------------------ flight recorder
def flight_dump(
    trigger: str, run_dir: Optional[str] = None, extra: Optional[dict] = None
) -> Optional[str]:
    """Dump the ring + registry snapshot to
    ``<run_dir>/flightrec_<pid>_<seq>_<trigger>.json``; returns the path, or
    None when no run dir is known (telemetry never configured — a library
    user exercising the engine standalone). Never raises: a failing dump must
    not take down the run it is documenting."""
    global _dump_seq
    target = run_dir if run_dir is not None else _run_dir
    if not target:
        return None
    _fold_gc()
    with _lock:
        _dump_seq += 1
        seq = _dump_seq
        records = list(_ring)
        counters = dict(_counters)
        gauges = dict(_gauges)
    doc = {
        "schema": SCHEMA_FLIGHT,
        "trigger": trigger,
        "ts_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "pid": os.getpid(),
        "trace_id": _TRACE_ID,
        "seq": seq,
        "records": records,
        "counters": counters,
        "gauges": gauges,
    }
    if extra:
        doc["extra"] = extra
    safe = "".join(c if c.isalnum() or c in "-_" else "_" for c in trigger)
    path = os.path.join(
        target, f"flightrec_{os.getpid()}_{seq:03d}_{safe}.json"
    )
    try:
        import json

        os.makedirs(target, exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1, default=str)
        os.replace(tmp, path)
    except OSError:
        return None
    return path


# ------------------------------------------------------------------ jax hooks
def install_jax_hooks() -> None:
    """Fold JAX's own monitoring durations into the registry: one event fires
    per real XLA backend compile (the recompile sentinel's mechanism,
    analysis/sentinel.py), so ``jax/compiles`` / ``jax/compile_s`` track
    compile count and seconds for ANY path (the training Prometheus compile
    gauge reads the per-epoch delta), and beside them the seconds of tracing
    (``jax/trace_s``; a jit traced inside another's trace is in both), of
    lowering to MLIR (``jax/lower_s``) and of retrieving executables from the
    persistent cache (``jax/cache_loads`` / ``jax/cache_load_s``: JAX takes
    the backend compile's duration round ``compile_or_get_cached``, so
    ``jax/compile_s`` holds these seconds too). Idempotent."""
    global _jax_hooks_installed
    with _lock:
        if _jax_hooks_installed:
            return
        _jax_hooks_installed = True
    import jax

    def _on_duration(name: str, duration: float, **kwargs) -> None:
        key = _JAX_DURATIONS.get(name)
        if key is None:
            return
        counter(key, float(duration))
        if name == _JAX_BACKEND_COMPILE:
            counter("jax/compiles", 1.0)
            event("jax/compile", duration_s=round(float(duration), 4))
        elif name == _JAX_CACHE_LOAD:
            counter("jax/cache_loads", 1.0)

    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def setup_phase(name: str, until_ready: bool = False):
    """Decorator: every call of the function runs under the span
    ``setup.<name>``; with ``until_ready`` the span closes when the result is
    on the device, as a clock round ``jax.block_until_ready(fn(...))`` would
    (the initializers: their seconds are the work, not its dispatch).
    The set-up account is opened INSIDE the functions every
    entry point calls before its first step (``run_training``, the
    benchmark's drivers, a library user), so no caller needs a clock of its
    own; whichever phase comes first installs the hooks, so the account is
    whole for a caller that installs them late or never."""

    def wrap(fn):
        @functools.wraps(fn)
        def in_phase(*args, **kwargs):
            install_jax_hooks()
            install_gc_hook()
            with span("setup." + name):
                result = fn(*args, **kwargs)
                if until_ready:
                    import jax

                    # Under an outer trace (``jax.eval_shape`` of the
                    # initializer) there is nothing to wait for, and a tracer
                    # refuses by printing the whole program it came from.
                    if not any(
                        isinstance(leaf, jax.core.Tracer)
                        for leaf in jax.tree_util.tree_leaves(result)
                    ):
                        result = jax.block_until_ready(result)
                return result

        return in_phase

    return wrap


# -------------------------------------------------------------------- gc hook
def _on_gc(phase: str, info: dict) -> None:
    global _gc_t0
    if phase == "start":
        _gc_t0 = time.perf_counter()
        return
    t0, _gc_t0 = _gc_t0, None
    if t0 is not None:
        _gc_pending.append((
            time.time(), time.perf_counter() - t0, info.get("generation"),
            info.get("collected"), threading.current_thread().name, current(),
        ))


def install_gc_hook() -> None:
    """Time every garbage collection from ``gc.callbacks``: all of them add
    to ``host/gc_pause_s`` (and ``host/gc_collections``), one of
    ``GC_RECORD_S`` or longer is a ``gc`` record too. The callback only
    queues; the registry's writers and readers fold the queue in.
    Idempotent (two threads racing here could leave two entries: the second
    finds no start time and queues nothing)."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def _fold_gc() -> None:
    """Book the queued collections. Called with ``_lock`` NOT held."""
    pause, n = 0.0, 0
    while _gc_pending:
        try:
            end_ts, dur, generation, collected, thread, ctx = _gc_pending.popleft()
        except IndexError:  # another thread took the last one
            break
        pause, n = pause + dur, n + 1
        if dur >= GC_RECORD_S:
            record_span(
                "gc", dur, parent=ctx, end_ts=end_ts, thread=thread,
                generation=generation, collected=collected,
            )
    if n:
        counter("host/gc_pause_s", pause)
        counter("host/gc_collections", float(n))


# ------------------------------------------------------------------ prom text
def _prom_name(prefix: str, key: str) -> str:
    return prefix + "_" + "".join(
        c if c.isalnum() or c == "_" else "_" for c in key
    )


def render_prometheus(prefix: str = "hydragnn") -> str:
    """Registry → Prometheus text exposition: every counter as
    ``<prefix>_<name>_total``, every gauge as ``<prefix>_<name>`` — this is
    where the TRAINING path's per-epoch step/h2d/compile gauges surface
    (docs/OBSERVABILITY.md catalogue). The serve front end appends this to
    its engine-scoped /metrics payload."""
    _fold_gc()
    with _lock:
        counters = dict(_counters)
        gauges = dict(_gauges)
    lines = []
    for key in sorted(counters):
        name = _prom_name(prefix, key) + "_total"
        lines.append(f"# TYPE {name} counter")
        lines.append(f"{name} {counters[key]}")
    for key in sorted(gauges):
        name = _prom_name(prefix, key)
        lines.append(f"# TYPE {name} gauge")
        lines.append(f"{name} {gauges[key]}")
    return "\n".join(lines) + ("\n" if lines else "")
