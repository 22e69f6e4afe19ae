"""Online inference engine: bucketed micro-batching over the padded-arena
collation contract, with a compiled-executable cache and bounded-queue
backpressure (docs/SERVING.md).

Why this shape: the repo's only inference surface before this module was the
offline ``run_prediction`` batch pass. Online traffic needs the same three
invariants that make the training path fast, re-assembled around a request
queue:

* **Static shapes.** Requests are collated into the exact padded
  ``(N_pad, E_pad, G_pad)`` buckets the training collator emits
  (graphs/collate.py: "XLA compiles once per bucket"), so steady-state
  traffic reuses a small set of AOT-compiled executables. The cache is
  explicit (``_executables``) — hits/misses/compile-seconds are serving
  metrics, and ``warmup()`` pre-compiles a declared bucket ladder so the
  first user request never pays a compile.

* **Overlap.** Batches flow through the PR-1 two-stage ``DeviceFeed``
  pipeline (train/pipeline.py): the micro-batcher generator runs on the
  feed's host thread (queue pop + deadline flush + the concatenation of
  requests that ``submit`` already made ready on their callers' threads), the
  transfer stage commits each batch with a blocking ``device_put`` on its
  own thread, and the dispatch thread only ever executes on
  already-committed device arrays — batch *k+1* transfers while batch *k*
  computes, exactly like a training epoch.

* **Bounded memory + honest failure.** The request queue is bounded;
  ``submit`` on a full queue raises :class:`BackpressureError` with a
  retry-after hint instead of queueing unboundedly (the caller — or the
  HTTP front end, as 429 — sheds the load). Any exception on the
  batcher/transfer/dispatch threads fails every pending future and poisons
  the engine (subsequent submits re-raise the original error): a worker
  crash is a loud caller-visible failure, never a silently wedged queue.

Numerical contract: the forward is ``_apply_model(model, ..., train=False)``
— the same function the offline eval step wraps — and padding is inert by
construction (masked BN/pool/heads, padding edges connect padding nodes), so
engine outputs are bit-identical to ``run_prediction`` on CPU for the same
checkpoint and graphs regardless of how requests are grouped into buckets
(locked by tests/test_serve_engine.py).

A token family (``models/families.py`` ``TOKEN_STACKS``: a document as a graph,
a token a node, no edges) is served on the same path: a request is the token
column and each token's place, the ladder's rungs are counted in tokens, a
class head answers with the log-probability of each next token instead of its
logits, and the routed layers' choices come out of the same executable
(docs/SERVING.md "Token families"). Its attention cores are of up to two
KINDS by layer, the complete causal graph of a document and the causal band
of ``sliding_window`` (which is which is the stack's sizes' to say:
``sliding(layer)``), and a flush's key blocks are counted a kind
(``_count_key_blocks``); a stack may route in no layer (``counts_routing``
false: the executable returns no choices and ``future.routing`` stays None)
and may hold selective-scan layers (``scans(layer)``), whose walk a flush is
counted by (``_count_scans``). Of the token layers the engine imports the shared
modules alone (``models/token_routed.py``: the sown collection and
``pass_rows``; ``models/token_attention.py``: the key-block counts), never a
family's file.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import queue
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..analysis import tsan
from ..cache import CacheKey, ExecutableRegistry, ExecutableStore, tree_signature
from ..graphs.collate import (
    GraphArena,
    PreparedGraph,
    collate_prepared,
    prepare_graph,
    round_up_pow2,
)
from ..graphs.packing import PackCaps, first_fit_decreasing
from ..graphs.sample import GraphSample
from ..telemetry import graftel as telemetry
from ..telemetry.stall import RUN_DELAY, StallAccount
from ..train.pipeline import DeviceFeed
from .metrics import ServeMetrics

_log = logging.getLogger(__name__)

# A flush's clock marks in the order they are read (``_BatchWork.marks``:
# ``time.perf_counter()`` readings, one clock, carried from thread to
# thread); the parts of its cycle follow from them by subtraction
# (``flush_parts``).
FLUSH_MARKS = (
    "first_queued", "taken", "collated", "h2d_start", "h2d_end", "exec_start",
    "launch_start", "launch_end", "ready", "d2h_end", "resolved",
)


class BackpressureError(RuntimeError):
    """Bounded request queue is full — retry after ``retry_after_s``."""

    def __init__(self, message: str, retry_after_s: float):
        super().__init__(message)
        self.retry_after_s = float(retry_after_s)


class EngineClosedError(RuntimeError):
    """The engine was shut down (close()) before the request resolved."""


class EngineFailedError(RuntimeError):
    """A worker thread died; the original exception is ``__cause__``."""


class NonFiniteOutputError(RuntimeError):
    """The model produced NaN/Inf for this request — the request fails, the
    engine keeps serving (the serving analog of the training step guard,
    docs/FAULT_TOLERANCE.md)."""


class PrecisionToleranceError(RuntimeError):
    """The quantized arm's outputs diverged from the f32 reference beyond the
    declared tolerance bound (docs/PRECISION.md "Tolerance gate"). Raised by
    :meth:`InferenceEngine.check_tolerance`; the full verdict rides on
    ``report``."""

    def __init__(self, message: str, report: dict):
        super().__init__(message)
        self.report = report


class SwapIdentityError(RuntimeError):
    """:func:`swap_from_checkpoint` rejected the checkpoint file: its
    verified content identity does not match the identity the caller pinned
    (``expected_identity``) — the file changed since it was staged. The
    engine keeps serving its current weights. A dedicated type so the /swap
    endpoint and orchestration callers classify the refusal structurally,
    never by parsing the message."""


class SwapFingerprintError(RuntimeError):
    """:meth:`InferenceEngine.swap_weights` rejected the incoming variables:
    their param-tree fingerprint (key paths/shapes/dtypes) does not match the
    tree the engine's executables were compiled against. The engine keeps
    serving its CURRENT weights — a wrong-architecture swap must never take
    the tier down (docs/SERVING.md "Live model lifecycle")."""


# The padded edge count of a token family's batches: such a request has no
# edges, and this is the smallest count the round-up ladder ever emits
# (graphs/collate.py ``round_up_pow2``'s minimum).
TOKEN_EDGE_PAD = 8


def _token_forward(model):
    """The executable of a token family: ``HydraGNN.score_tokens`` (a class
    head as ``[N, 1]`` log-probabilities of the next token) and, where the
    stack routes, the experts each node chose in every routed layer as ONE
    ``[N, routed layers x K]`` int32 array, in layer order."""
    import jax
    import jax.numpy as jnp

    from ..models.base import HydraGNN
    from ..models.token_routed import INTERMEDIATES, split_intermediates

    routes = model.counts_routing

    def forward(params, bstats, batch):
        out = model.apply(
            {"params": params, "batch_stats": bstats}, batch,
            method=HydraGNN.score_tokens,
            mutable=[INTERMEDIATES] if routes else False,
        )
        if not routes:
            return out, None
        outputs, sown = out
        per_layer, _ = split_intermediates(sown[INTERMEDIATES])
        layers = sorted(per_layer, key=lambda name: int(name.rsplit("_", 1)[1]))
        chosen = [per_layer[name]["chosen"].astype(jnp.int32) for name in layers]
        return outputs, jnp.concatenate(chosen, axis=1)

    return jax.jit(forward)


class _Outputs(list):
    """A flush's per-head host arrays; for a routed token family also the
    experts every node chose (``routing``), which ride along to the demux;
    ``clocks`` are ``_execute``'s three readings of its launch
    (``launch_start``, ``launch_end``, ``ready``: the flush's marks)."""

    routing: Optional[np.ndarray] = None
    clocks: Optional[Dict[str, float]] = None


class _Future:
    """Minimal thread-safe future.

    Deliberately NOT ``concurrent.futures.Future``: the engine's race
    closures (submit-vs-close rejection after enqueue, collation-failure
    rejection racing a normal resolve) rely on a second completion being a
    benign no-op-overwrite with at-most-one outcome visible to the waiter —
    the stdlib future raises InvalidStateError there, which inside
    ``_resolve`` would poison the whole engine. (And on this Python,
    ``concurrent.futures.TimeoutError`` is not the builtin ``TimeoutError``
    callers naturally catch.)"""

    __slots__ = (
        "_event", "_result", "_error", "request_id", "model_version", "routing",
    )

    def __init__(self, request_id: Optional[str] = None):
        self._event = threading.Event()
        self._result = None
        self._error = None
        # Correlation id (docs/OBSERVABILITY.md): assigned at submit, echoed
        # by the HTTP layer as X-HydraGNN-Request-Id.
        self.request_id = request_id
        # Model version the resolving batch executed against (set before
        # set_result; the lifecycle layer's per-response version tag —
        # docs/SERVING.md "Live model lifecycle").
        self.model_version: Optional[str] = None
        # A routed token family: the experts each of the request's tokens
        # chose, [tokens, routed layers x K] int32 (set before set_result);
        # None for every other family.
        self.routing: Optional[np.ndarray] = None

    def set_result(self, value) -> None:
        self._result = value
        self._event.set()

    def set_exception(self, exc: BaseException) -> None:
        self._error = exc
        self._event.set()

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None):
        if not self._event.wait(timeout):
            raise TimeoutError("inference request did not resolve in time")
        if self._error is not None:
            raise self._error
        return self._result


@dataclass
class _Request:
    """One admitted request. ``graph`` is the caller's sample made ready on
    the caller's thread (``prepare_graph``); the caller's own ``GraphSample``
    is read once, never written and not kept. ``t_submit`` is taken after
    ``_validate`` and BEFORE the preparation, ``t_queued`` after it, so
    ``prepare`` (``t_queued - t_submit``) + ``queue_wait`` (the flush -
    ``t_queued``) + the flush's ``collate`` + ``handoff`` + ``h2d`` +
    ``device`` + ``d2h`` add to ``e2e`` (``_resolve``'s start -
    ``t_submit``) with no second counted twice (serve/metrics.py)."""

    graph: PreparedGraph
    future: _Future
    t_submit: float
    t_queued: float
    request_id: str = ""


@dataclass
class _BatchWork:
    """One flushed micro-batch between the collation and dispatch stages.
    ``flush_id`` is what the spans of one flush share (as ``request_id`` is
    what the spans of one request share); ``marks`` are its clock readings
    (``FLUSH_MARKS``), each written once by the thread that holds the work."""

    requests: List[_Request]
    node_start: np.ndarray  # per-request node offsets into the padded batch
    batch: Any  # host GraphBatch
    fallback: bool  # shape came from pow2 fallback, not the ladder
    flush_id: int = 0
    marks: Dict[str, float] = field(default_factory=dict)


def flush_parts(marks: Dict[str, float]) -> Dict[str, float]:
    """Seconds of each part of one flush's cycle from its marks: ``handoff``
    is the two queues of the ``DeviceFeed`` (collated -> the transfer thread,
    transferred -> the dispatcher), ``lookup`` is ``_executable_for`` and the
    weights' read, ``launch`` the executable call's return, ``device_wait``
    the ``block_until_ready`` after it."""
    m = marks
    return {
        "fill": m["taken"] - m["first_queued"],
        "collate": m["collated"] - m["taken"],
        "handoff": (m["h2d_start"] - m["collated"]) + (m["exec_start"] - m["h2d_end"]),
        "h2d": m["h2d_end"] - m["h2d_start"],
        "lookup": m["launch_start"] - m["exec_start"],
        "launch": m["launch_end"] - m["launch_start"],
        "device_wait": m["ready"] - m["launch_end"],
        "d2h": m["d2h_end"] - m["ready"],
        "resolve": m["resolved"] - m["d2h_end"],
    }


_SHUTDOWN = object()


class InferenceEngine:
    """Micro-batching online inference over a HydraGNN model.

    Parameters
    ----------
    model, variables:
        The flax module (``create_model``/``create_model_config``) and its
        restored variables ({"params", "batch_stats"}).
    max_batch_graphs:
        Flush a micro-batch at this many graphs. Also fixes the padded graph
        dimension: every batch uses ``G_pad = max_batch_graphs + 1`` so the
        graph axis never contributes extra compiled shapes.
    max_delay_ms:
        Flush an open (non-full) batch this many ms after it opened — the
        bound on latency a lone request pays waiting for batch-mates.
    queue_limit:
        Bounded request-queue depth; beyond it ``submit`` raises
        :class:`BackpressureError`.
    bucket_ladder:
        Optional sequence of ``(N_pad, E_pad)`` shapes. A batch takes the
        smallest ladder entry it fits; only when none fits does it fall back
        to the round-up ladder (counted as ``ladder_fallback_total``). With
        ``warmup=True`` every ladder entry is compiled at construction, so
        steady-state traffic never recompiles. Fit one from observed traffic
        with ``graphs/packing.py fit_ladder`` (CLI: ``--bucket-ladder
        auto:<histogram-or-ladder.json>``).
    packing:
        Bin-pack each flushed micro-batch by first-fit-decreasing under the
        TOP ladder rung's (nodes, edges) capacity (graphs/packing.py): an
        over-capacity flush splits into several bins that each take their
        tightest rung instead of one batch falling back to a worst-case
        round-up shape. Per-request identity is preserved — every bin
        carries its own requests and node offsets through to response
        demux. No-op without a ladder.
    ladder_step:
        Round-up ladder for shapes that miss the bucket ladder: ``"pow2"``
        (historical) or ``"mult64"`` (multiples of 64 above 256 — a
        520-node batch pads to 576, not 1024).
    head_names, y_minmax:
        Optional per-head names and min-max pairs; with ``y_minmax`` set,
        outputs are denormalized (``v * (ymax - ymin) + ymin``, the
        postprocess.output_denormalize arithmetic) before futures resolve.
    guard_outputs:
        Check every resolved output for NaN/Inf on the host; a
        non-finite output fails THAT request with
        :class:`NonFiniteOutputError` instead of returning garbage with a
        200 (the serving reuse of the training non-finite guard).
    max_worker_restarts:
        Fatal worker errors within this budget RESTART the pipeline threads
        (pending/queued requests fail, the engine goes ``degraded`` but keeps
        accepting traffic) instead of poisoning the engine. 0 = the
        historical binary poisoning.
    precision, tolerance:
        Serving arm (docs/PRECISION.md): ``"f32"`` (default) keeps the
        bit-exactness contract against ``run_prediction``; ``"bf16"`` runs
        the forward in bf16 compute (f32 weights, cast in-executable);
        ``"int8"`` additionally snaps every weight matrix to a per-tensor
        symmetric int8 grid (precision/quantize.py). Both quantized arms
        REQUIRE a positive ``tolerance`` — the bit-exactness gate relaxes to
        :meth:`check_tolerance` (max-abs-diff vs a retained f32 reference,
        shared machinery with ops/certify.py) for quantized mode only. The
        arm is a CacheKey policy component: quantized executables can never
        hydrate an f32 entry or vice versa.
    compile_cache:
        Optional graftcache directory (docs/COMPILE_CACHE.md). With it set,
        ``warmup()`` and cache misses first try to HYDRATE the executable
        from the persistent store (a verified deserialize — seconds, zero
        XLA compiles) before paying a fresh compile, and fresh compiles are
        serialized back, so a restarted or newly spun-up replica warms its
        whole ladder from disk. ``None`` falls back to the
        ``HYDRAGNN_COMPILE_CACHE`` env var; empty/unset disables
        persistence (the historical in-memory-only cache).
    model_version:
        The version tag of the weights the engine boots with
        (docs/SERVING.md "Live model lifecycle"): tagged on every
        response (``fut.model_version``, the ``X-HydraGNN-Model-Version``
        header) and /healthz, and replaced atomically by
        :meth:`swap_weights`. ``from_config`` derives it from the
        checkpoint's verified content identity.
    autostart:
        Tests set False to exercise queue behavior without worker threads;
        call :meth:`start` to launch them later.
    """

    def __init__(
        self,
        model,
        variables: Dict[str, Any],
        *,
        max_batch_graphs: int = 32,
        max_delay_ms: float = 5.0,
        queue_limit: int = 256,
        bucket_ladder: Optional[Sequence[Tuple[int, int]]] = None,
        warmup: bool = False,
        packing: bool = False,
        ladder_step: str = "pow2",
        head_names: Optional[Sequence[str]] = None,
        y_minmax: Optional[Sequence] = None,
        metrics: Optional[ServeMetrics] = None,
        guard_outputs: bool = True,
        max_worker_restarts: int = 0,
        compile_cache: Optional[str] = None,
        precision: str = "f32",
        tolerance: Optional[float] = None,
        model_version: str = "v0",
        autostart: bool = True,
    ):
        import jax

        from ..precision import SERVE_PRECISIONS, fake_quantize_params
        from ..train.trainer import _apply_model

        # Precision arm resolution (docs/PRECISION.md) BEFORE anything reads
        # the model: quantized arms serve a bf16-compute clone (and, for
        # int8, grid-snapped weights) while the original f32 model+variables
        # are retained as the tolerance gate's reference.
        if precision not in SERVE_PRECISIONS:
            raise ValueError(
                f"precision {precision!r} is not one of {SERVE_PRECISIONS}"
            )
        self.precision = precision
        self.tolerance = None if tolerance is None else float(tolerance)
        # A token family (models/families.py TOKEN_STACKS): its sizes, else None.
        self._token_cfg = model.token_cfg
        # The window of its band layers, None for a stack with none.
        sliding = getattr(self._token_cfg, "sliding", None)
        self._band_window = (
            self._token_cfg.sliding_window
            if sliding and any(sliding(i) for i in range(model.num_conv_layers))
            else None
        )
        # Whether any of its layers is a selective scan (a stack with none
        # has no ``scans``).
        scans = getattr(self._token_cfg, "scans", None)
        self._scans = bool(
            scans and any(scans(i) for i in range(model.num_conv_layers))
        )
        if self._token_cfg is not None and precision != "f32":
            raise ValueError(
                f"{model.conv_type} reads token ids from a float32 node "
                f"column; the {precision!r} arm's cast would round them"
            )
        # Quantized-arm reference state: rebound only under _swap_lock
        # (created below; __init__ is pre-publication) — a swap and a
        # concurrent tolerance check must agree on which f32 reference
        # belongs to the published weights.
        self._quant_report: Optional[Dict[str, Any]] = None  # guarded-by: self._swap_lock
        self._ref_model = None  # guarded-by: self._swap_lock, dirty-reads(bound once in __init__, never rebound — swaps replace the reference VARIABLES, not the f32 module clone)
        self._ref_variables: Optional[Dict[str, Any]] = None  # guarded-by: self._swap_lock
        if precision != "f32":
            if self.tolerance is None or self.tolerance <= 0:
                raise ValueError(
                    f"quantized serving (precision={precision!r}) requires a "
                    "positive tolerance bound — the bit-exactness contract "
                    "is relaxed, never silently dropped (docs/PRECISION.md)"
                )
            # The gate's reference must be a REAL f32 forward: a checkpoint
            # whose Architecture already pins compute_dtype='bfloat16' would
            # otherwise be its own reference (max_abs_diff identically 0 —
            # a vacuous gate claiming a bound that was never measured).
            self._ref_model = (
                model
                if model.compute_dtype is None
                else model.clone(compute_dtype=None)
            )
            self._ref_variables = variables
            if model.compute_dtype != "bfloat16":
                model = model.clone(compute_dtype="bfloat16")
            if precision == "int8":
                variables = dict(variables)
                variables["params"], self._quant_report = fake_quantize_params(
                    variables["params"]
                )
        elif tolerance is not None:
            raise ValueError(
                "tolerance is a quantized-arm knob; precision='f32' serves "
                "under the bit-exactness contract and accepts none"
            )

        self.model = model
        self.max_batch_graphs = int(max_batch_graphs)
        self.max_delay_ms = float(max_delay_ms)
        self.queue_limit = int(queue_limit)
        self.metrics = metrics if metrics is not None else ServeMetrics()
        self.metrics.set_precision(self.precision, self.tolerance)
        self.head_names = (
            list(head_names)
            if head_names
            else [f"head_{i}" for i in range(len(model.output_dim))]
        )
        self._y_minmax = y_minmax
        self._g_pad = self.max_batch_graphs + 1
        self._edge_dim = model.edge_dim if model.use_edge_attr else 0
        # Node coordinates ride in the batch for the families that compute
        # their edge geometry in the step (PaiNN).
        self._with_positions = model.needs_positions
        # The bucket ladder is published like the weights: ONE sorted-list
        # reference, rebound atomically under _lock by warmup()'s merge and
        # swap_ladder() (the flywheel's drift-refit path). The batcher takes
        # a single locked snapshot per flush and threads it through
        # _pack_groups/_collate/_bucket_shape, so every batch — and
        # therefore every request — is planned against exactly one ladder
        # even while a swap lands mid-flush.
        self._ladder = self._rungs(bucket_ladder or ())  # guarded-by: self._lock, dirty-reads(status surfaces read the immutable list reference for display; consistency-bearing readers snapshot under the lock via _current_ladder)
        self._packing = bool(packing)
        self._ladder_step = ladder_step

        params = jax.device_put(variables["params"])
        bstats = jax.device_put(variables.get("batch_stats", {}))
        # Where the weights actually landed (/healthz reports it): the
        # default device of this process, out of however many it can see.
        dev = next(iter(jax.tree_util.tree_leaves(params)[0].devices()))
        self.device = {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "id": dev.id,
            "visible": jax.device_count(),
        }
        if self._token_cfg is None:
            self._jit = jax.jit(
                lambda params, bstats, batch: _apply_model(
                    model, params, bstats, batch, train=False
                )
            )
        else:
            self._jit = _token_forward(model)
        self._lock = tsan.instrument_lock(
            threading.Lock(), "InferenceEngine._lock"
        )
        # Serializes whole swaps (validate → quantize → gate → publish):
        # two concurrent swap_weights calls must publish in a total order,
        # and the quantized-arm reference state above must always describe
        # the published weights. Never held by the dispatch/feed threads —
        # request traffic only ever takes _lock. Lock order: _swap_lock
        # before _lock (the publish inside a swap).
        self._swap_lock = tsan.instrument_lock(
            threading.Lock(), "InferenceEngine._swap_lock"
        )
        # THE atomic weight reference (docs/SERVING.md "Live model
        # lifecycle"): (params, batch_stats, model_version) published as ONE
        # tuple — the dispatch thread reads it once per batch, so every
        # in-flight batch executes entirely against one version and every
        # response is tagged with exactly the version that produced it.
        # swap_weights() rebinds it under the lock; the compiled executables
        # take params/batch_stats as ARGUMENTS (and CacheKey fingerprints the
        # param TREE, not the values), so a same-architecture swap reuses
        # every compiled bucket with zero recompiles.
        self._weights: Tuple[Any, Any, str] = (  # guarded-by: self._lock
            params,
            bstats,
            str(model_version),
        )
        # Compiled-executable cache: filled by warmup() on the caller thread
        # AND by cache misses on the dispatch thread — since the graftcache
        # PR one shared ExecutableRegistry (cache/registry.py) whose single
        # locked lookup→(compile outside the lock)→store path replaced the
        # historical self._executables dict. With a compile_cache directory
        # bound, misses hydrate from the persistent store before compiling
        # fresh (docs/COMPILE_CACHE.md).
        cache_dir = (
            compile_cache
            if compile_cache is not None
            else os.environ.get("HYDRAGNN_COMPILE_CACHE", "")
        )
        self._registry = ExecutableRegistry(
            ExecutableStore(cache_dir) if cache_dir else None, name="serve"
        )
        # The serve half of the persistent key: model/weights identity from
        # the checkpoint layer's param-tree fingerprint plus the module's
        # field repr (hyperparameters without parameters — activation,
        # aggregation list — change the program but not the param tree).
        self._config_fingerprint = ""
        # Precision is BOTH a fingerprint component and a named CacheKey flag
        # (docs/PRECISION.md "Cache-key interaction"): the model repr already
        # separates f32 from the bf16-compute clone, but bf16 and int8 share
        # a module repr and a param-tree signature (int8 quantization moves
        # VALUES, not shapes/dtypes) — the explicit arm label is what makes
        # cross-precision hydration structurally impossible.
        self._key_flags: Tuple[str, ...] = (
            () if self.precision == "f32" else (f"precision={self.precision}",)
        )
        if self._registry.store is not None:
            from ..checkpoint.format import param_fingerprint

            self._config_fingerprint = hashlib.sha256(
                (
                    param_fingerprint(variables["params"])
                    + param_fingerprint(variables.get("batch_stats", {}))
                    + repr(model)
                    # Quantized arms only: the f32 digest must stay byte-
                    # identical to pre-graftprec stores — an upgraded replica
                    # fleet keeps hydrating its warm f32 entries.
                    + (
                        f"|precision={self.precision}"
                        if self.precision != "f32"
                        else ""
                    )
                ).encode()
            ).hexdigest()

        self._queue: "queue.Queue" = queue.Queue(maxsize=self.queue_limit)
        self._pending: set = set()  # guarded-by: self._lock
        self._closing = threading.Event()
        self._error: Optional[BaseException] = None  # guarded-by: self._lock, dirty-reads(set at most once before _closing; the submit fast path may read one poison late and is re-checked post-enqueue)
        self._feed: Optional[DeviceFeed] = None  # guarded-by: self._lock, dirty-reads(rebound only by start/_fail, serialized by the _closing/_gen_stop protocol; close() joins a possibly-stale feed harmlessly)
        self._dispatcher: Optional[threading.Thread] = None  # guarded-by: self._lock, dirty-reads(same lifecycle protocol as _feed)
        self._guard_outputs = bool(guard_outputs)
        self._restarts_left = int(max_worker_restarts)  # guarded-by: self._lock, dirty-reads(decremented only by _fail on the dispatch thread; budget off-by-one under a torn restart is acceptable degradation)
        self._degraded = False  # guarded-by: self._lock, dirty-reads(sticky monotonic bool; a stale False read only delays the /healthz downgrade by one scrape)
        # Bounded log of degraded-state transitions, correlation ids
        # included — surfaced by /healthz so "degraded: true" names the
        # requests that tripped it (docs/OBSERVABILITY.md).
        self._degraded_events: "deque" = deque(maxlen=16)  # guarded-by: self._lock
        # Telemetry context of the CURRENT pipeline incarnation, handed to
        # the feed threads + dispatcher (explicit cross-thread propagation).
        self._pipeline_ctx = None  # guarded-by: self._lock, dirty-reads(rebound only by start(); stage threads read the ctx they were constructed with)
        # Per-incarnation stop flag for the batcher generator: on a worker
        # restart the OLD batcher must stop consuming the shared request
        # queue before the new one starts (two live batchers would race).
        self._gen_stop: Optional[threading.Event] = None
        # Flush identifiers: drawn by the batcher alone, one a collated bin
        # (``serve/await`` and ``serve/fill`` carry the one drawn next).
        self._flush_seq = 0  # guarded-by: none(written by the batcher alone, in _collate; the _gen_stop protocol keeps ONE batcher on the queue at a time)
        # The engine's own seconds between two forwards and the wait for
        # the forward, against the rung's median (``serve/flush_stall``: the
        # rule is telemetry/stall.py's).
        self._flush_account = StallAccount(
            "flush", "serve/flush_stall", "serve/flush_stalls", "flush_stall",
            _log, dispatch=("lookup", "launch"), wait=("device_wait",),
        )

        if warmup and self._ladder:
            self.warmup()
        if autostart:
            self.start()

    # ------------------------------------------------------------ lifecycle
    def start(self) -> None:
        """Launch the batcher→transfer→dispatch pipeline (idempotent)."""
        if self._dispatcher is not None:
            return
        self._gen_stop = threading.Event()
        # One telemetry context per pipeline incarnation: the batcher /
        # transfer / dispatcher spans all parent here, so a flight-recorder
        # dump shows which incarnation served which requests.
        ctx = telemetry.new_context()
        feed = DeviceFeed(
            self._batch_source(self._gen_stop),
            transfer=self._transfer,
            host_depth=2,
            ctx=ctx,
        )
        dispatcher = threading.Thread(
            target=self._dispatch_loop, name="hydragnn-serve-dispatch",
            daemon=True,
        )
        with self._lock:
            self._feed = feed
            self._dispatcher = dispatcher
            self._pipeline_ctx = ctx
        dispatcher.start()

    @property
    def running(self) -> bool:
        return (
            self._dispatcher is not None
            and self._dispatcher.is_alive()
            and self._error is None
            and not self._closing.is_set()
        )

    @property
    def compiled_buckets(self) -> int:
        """Locked executable-cache size — /healthz and the serve CLI read
        this cross-thread (the registry's len() holds its own lock;
        callers must not reach through the registry's internals directly)."""
        return len(self._registry)

    def _rungs(self, ladder) -> List[Tuple[int, int]]:
        """``ladder`` as the sorted ``(N_pad, E_pad)`` rungs the batcher
        selects from. A token family's rungs are counted in TOKENS (a bare
        int, or a pair whose edge count is not read): every one takes
        ``TOKEN_EDGE_PAD`` edges."""
        if self._token_cfg is None:
            return sorted({(int(n), int(e)) for n, e in ladder})
        return sorted({
            (int(r if np.ndim(r) == 0 else r[0]), TOKEN_EDGE_PAD) for r in ladder
        })

    def _current_weights(self) -> Tuple[Any, Any, str]:
        """One locked read of the atomic (params, batch_stats, version)
        reference — the only way any consumer (dispatch, warmup, tolerance
        gate, status surfaces) may observe the weights."""
        with self._lock:
            return self._weights

    def _current_ladder(self) -> List[Tuple[int, int]]:
        """One locked read of the published bucket-ladder reference (the
        ladder analog of ``_current_weights``). The returned list is never
        mutated in place — swaps rebind the reference — so callers may hold
        the snapshot across a whole flush."""
        with self._lock:
            return self._ladder

    def variables_template(self) -> Dict[str, Any]:
        """THE variables template verified checkpoint loads restore onto
        (flax ``from_bytes``: structure used, values ignored). For quantized
        arms the retained f32 reference is the honest template — the served
        params carry the same tree either way. One definition shared by
        ``swap_from_checkpoint`` and ``LifecycleManager._template`` so the
        /swap path and the in-process lifecycle path can never diverge."""
        ref = getattr(self, "_ref_variables", None)
        if ref is not None:
            return ref
        params, bstats, _v = self._current_weights()
        return {"params": params, "batch_stats": bstats}

    @property
    def model_version(self) -> str:
        """The version the engine currently answers with (tagged on every
        response and /healthz — docs/SERVING.md "Live model lifecycle")."""
        return self._current_weights()[2]

    @property
    def degraded(self) -> bool:
        """Sticky health downgrade: the engine is serving, but it has seen
        batch-scoped failures, non-finite outputs, or a worker restart since
        construction — surfaced in /healthz next to the counters so operators
        see gray, not just green/black."""
        return self._degraded

    def close(self, timeout: float = 10.0) -> None:
        """Drain in-flight batches, stop the threads, fail stragglers."""
        if self._closing.is_set():
            return
        self._closing.set()
        # The shutdown marker must reach the batcher even under a full
        # queue: evict (and fail) queued requests until it fits.
        while True:
            try:
                self._queue.put_nowait(_SHUTDOWN)
                break
            except queue.Full:
                try:
                    req = self._queue.get_nowait()
                except queue.Empty:
                    continue
                if req is not _SHUTDOWN:
                    self._reject(req, EngineClosedError("engine closing"))
        if self._dispatcher is not None:
            self._dispatcher.join(timeout)
        if self._feed is not None:
            self._feed.close()
            self._feed.join(2.0)
        # Anything still unresolved (e.g. batches dropped by feed teardown).
        self._fail_pending(EngineClosedError("engine closed"))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # ------------------------------------------------------------- requests
    def submit(
        self, sample: GraphSample, request_id: Optional[str] = None
    ) -> _Future:
        """Enqueue one graph; returns a future resolving to the per-head
        output list ([dim] arrays for graph heads, [n, dim] for node heads).
        ``request_id`` is the correlation id carried end-to-end (submit →
        pack bin → device batch → demux → response; docs/OBSERVABILITY.md);
        one is generated when the caller brings none. The id is available on
        the returned future (``fut.request_id``).
        """
        if self._error is not None:
            raise EngineFailedError(
                "inference worker died; engine must be rebuilt"
            ) from self._error
        if self._closing.is_set():
            raise EngineClosedError("engine is shut down")
        self._validate(sample)
        rid = request_id or telemetry.new_request_id()
        telemetry.event(
            "serve/submit",
            request_id=rid,
            nodes=int(sample.num_nodes),
            edges=int(sample.num_edges),
        )
        # Made ready HERE, once, on the caller's thread, for every request
        # (keyed on nothing: a caller sends a new graph each time), so that
        # a flush is a concatenation (_collate).
        t_submit = time.perf_counter()
        with telemetry.span("serve/prepare", request_id=rid):
            graph = prepare_graph(
                sample,
                edge_dim=self._edge_dim,
                with_positions=self._with_positions,
            )
        req = _Request(
            graph=graph,
            future=_Future(request_id=rid),
            t_submit=t_submit,
            t_queued=time.perf_counter(),
            request_id=rid,
        )
        with self._lock:
            self._pending.add(req.future)
        # Annotated interleaving site: the window between pending-set entry
        # and enqueue is where a concurrent _fail must not strand the future
        # (tsan's seeded schedule fuzzing widens it deterministically).
        tsan.yield_point("serve.submit.pre_enqueue")
        try:
            self._queue.put_nowait(req)
        except queue.Full:
            with self._lock:
                self._pending.discard(req.future)
            self.metrics.count("rejected_total")
            telemetry.event("serve/reject", request_id=rid)
            hint = self._retry_after_hint()
            raise BackpressureError(
                f"request queue full ({self.queue_limit}); retry in "
                f"~{hint:.2f}s",
                retry_after_s=hint,
            ) from None
        # Close the check-then-act race with close()/_fail(): if shutdown or
        # a worker death landed BETWEEN the checks above and the enqueue, the
        # batcher may already be past its drain and never pop this request —
        # fail the future here. (If the batcher does still pop it, the caller
        # sees the rejection; at-most-one outcome is visible either way.)
        if self._closing.is_set() or self._error is not None:
            self._reject(
                req,
                EngineClosedError("engine closed during submit")
                if self._error is None
                else EngineFailedError("inference worker died"),
            )
            return req.future
        self.metrics.count("requests_total")
        self.metrics.observe("prepare", req.t_queued - t_submit)
        if graph.presorted:
            self.metrics.count("presorted_total")
        self.metrics.record_request(graph.num_nodes, graph.num_edges)
        return req.future

    def predict(
        self,
        samples: Sequence[GraphSample],
        timeout: Optional[float] = 60.0,
        request_id: Optional[str] = None,
    ) -> List[List[np.ndarray]]:
        """Synchronous convenience: submit all, wait all (results only; see
        :meth:`predict_versioned` for the per-graph model-version tags)."""
        results, _versions = self.predict_versioned(
            samples, timeout=timeout, request_id=request_id
        )
        return results

    def predict_versioned(
        self,
        samples: Sequence[GraphSample],
        timeout: Optional[float] = 60.0,
        request_id: Optional[str] = None,
    ) -> Tuple[List[List[np.ndarray]], List[Optional[str]]]:
        """Submit all, wait all → ``(results, versions)`` where versions[i]
        is the model version graph i's batch executed against. Returns one
        per-head output list per input graph. A multi-graph call shares one
        ``request_id`` base (the HTTP layer's correlation id); each graph
        gets ``<request_id>/<i>``. Per-request version consistency: each
        graph's version is exact; a multi-graph call racing a hot swap may
        legitimately span the old and new versions across its graphs.

        All samples are validated BEFORE any is admitted (a malformed graph
        rejects the call without consuming device work), and a multi-graph
        call that cannot fit the queue's free slots is rejected up front —
        so a 429 for the whole call does not leave a half-admitted batch
        computing results nobody will read (retry amplification)."""
        for s in samples:
            self._validate(s)
        if len(samples) > self.queue_limit:
            # Terminal, not transient: no amount of retrying fits this call.
            raise ValueError(
                f"predict() of {len(samples)} graphs exceeds queue_limit "
                f"{self.queue_limit}; split the call or raise the limit"
            )
        free = self.queue_limit - self._queue.qsize()
        if len(samples) > free:
            self.metrics.count("rejected_total")
            hint = self._retry_after_hint()
            raise BackpressureError(
                f"{len(samples)} graphs exceed the queue's ~{free} free "
                f"slots; retry in ~{hint:.2f}s",
                retry_after_s=hint,
            )
        rid = request_id or telemetry.new_request_id()
        futures = []
        try:
            for i, s in enumerate(samples):
                futures.append(self.submit(s, request_id=f"{rid}/{i}"))
        except BackpressureError:
            # Lost the capacity race to concurrent callers: the already-
            # admitted graphs will compute regardless — drain them so the
            # engine is quiescent for the caller's retry, then re-raise.
            for f in futures:
                try:
                    f.result(timeout)
                except Exception:
                    pass
            raise
        results = [f.result(timeout) for f in futures]
        return results, [f.model_version for f in futures]

    def _validate(self, sample: GraphSample) -> None:
        # Overlaps structurally with the loader-side quarantine validator
        # (preprocess/dataloader.py:invalid_sample_reason) but is a distinct
        # contract: request-facing errors, model input/edge width checks, no
        # y/y_loc (requests are unlabeled) and no finiteness (non-finite
        # OUTPUTS fail per-request in _resolve). Mirror changes to the
        # shared structural checks there.
        x = sample.x
        if x is None or np.ndim(x) != 2:
            raise ValueError("sample.x must be a [num_nodes, F] array")
        if x.shape[1] != self.model.input_dim:
            raise ValueError(
                f"sample.x feature width {x.shape[1]} != model input_dim "
                f"{self.model.input_dim}"
            )
        if self._token_cfg is not None:
            self._validate_tokens(sample)
        if sample.edge_index is not None:
            ei = np.asarray(sample.edge_index)
            if ei.ndim != 2 or ei.shape[0] != 2:
                raise ValueError("sample.edge_index must be [2, num_edges]")
            # Bounds matter for batch ISOLATION, not just this request: after
            # the flush's per-graph offset shift an out-of-range index would
            # alias this graph's edges onto a co-batched graph's nodes.
            if ei.size and (ei.min() < 0 or ei.max() >= sample.num_nodes):
                raise ValueError(
                    "sample.edge_index references nodes outside the graph"
                )
        if self._with_positions and (
            sample.pos is None or np.shape(sample.pos) != (sample.num_nodes, 3)
        ):
            raise ValueError(
                f"model reads node positions: sample.pos must be "
                f"[{sample.num_nodes}, 3]"
            )
        if self._edge_dim and sample.num_edges:
            # The model consumes per-edge features: a missing attr would
            # silently zero-fill (wrong predictions with a 200), a wrong
            # width would blow up collation mid-batch — reject here instead.
            ea = sample.edge_attr
            if ea is None:
                raise ValueError(
                    f"model expects edge_attr of width {self._edge_dim}; "
                    "request carries none"
                )
            # Row count too: the flush writes attr rows by edge_index counts,
            # so a mismatch corrupts (or crashes) co-batched requests.
            if np.ndim(ea) != 2 or np.shape(ea) != (
                sample.num_edges,
                self._edge_dim,
            ):
                raise ValueError(
                    f"sample.edge_attr must be [{sample.num_edges}, "
                    f"{self._edge_dim}], got shape {np.shape(ea)}"
                )
        # No size ceiling: a graph too large for every ladder rung is still
        # serveable through _bucket_shape's pow2 fallback (one compile,
        # counted as ladder_fallback_total).

    def _validate_tokens(self, sample: GraphSample) -> None:
        """A token family's request: the min-max-scaled token column with
        every id inside the rank's vocabulary slice, each token's place in
        ``pos[:, 0]`` counting 0 .. T-1, and no edges (the stack reads none,
        and a rung holds ``TOKEN_EDGE_PAD`` of them)."""
        cfg, n = self._token_cfg, sample.num_nodes
        lo, hi = cfg.token_minmax
        ids = np.asarray(sample.x, np.float64)[:, 0] * (hi - lo) + lo
        if n and not (ids.min() > -0.5 and ids.max() < cfg.vocab_size - 0.5):
            raise ValueError(
                f"sample.x holds a token id outside 0..{cfg.vocab_size - 1} "
                f"(scaled by token_minmax {list(cfg.token_minmax)})"
            )
        if sample.pos is None or np.shape(sample.pos) != (n, 3):
            raise ValueError(
                f"a token request carries each token's place: sample.pos "
                f"must be [{n}, 3] with the place in column 0"
            )
        if not np.array_equal(np.asarray(sample.pos)[:, 0], np.arange(n)):
            raise ValueError(
                "sample.pos[:, 0] must count the document's places 0..T-1 "
                "in order"
            )
        if sample.num_edges:
            raise ValueError(
                f"{self.model.conv_type} reads no edges; send edge_index=None"
            )

    def _retry_after_hint(self) -> float:
        """Seconds until the queue has likely drained one batch's worth:
        queued batches x per-batch service estimate (measured device latency
        when available, else the flush deadline)."""
        dev = self.metrics.latency["device"]
        per_batch = (
            dev.sum / dev.count if dev.count else self.max_delay_ms / 1000.0
        )
        batches_queued = max(1, self._queue.qsize() // self.max_batch_graphs)
        return max(0.05, batches_queued * max(per_batch, 1e-3))

    # ----------------------------------------------------------- the worker
    def _batch_source(self, stop: threading.Event):
        """Micro-batcher generator (runs on the DeviceFeed host thread):
        pop → deadline/size flush → concatenation → host batch. ``stop`` is
        this incarnation's kill switch — set by a worker restart so a stale
        batcher cannot keep consuming the shared queue."""
        q = self._queue
        while True:
            try:
                first = q.get_nowait()
            except queue.Empty:
                # The engine holds no request: ONE span from here to the
                # first one's arrival, however many polls that takes (the
                # callers' turn in a closed loop; closed on shutdown too).
                with telemetry.span("serve/await", flush_id=self._flush_seq + 1):
                    first = None
                    while first is None:
                        try:
                            first = q.get(timeout=0.05)
                        except queue.Empty:
                            if self._closing.is_set() or stop.is_set():
                                return
            if first is _SHUTDOWN:
                return
            entries = [first]
            saw_shutdown = False
            # From the first request to the flush, by size or by deadline.
            with telemetry.span("serve/fill", flush_id=self._flush_seq + 1) as fill:
                deadline = time.perf_counter() + self.max_delay_ms / 1000.0
                while len(entries) < self.max_batch_graphs:
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0:
                        break
                    try:
                        nxt = q.get(timeout=remaining)
                    except queue.Empty:
                        break
                    if nxt is _SHUTDOWN:
                        saw_shutdown = True
                        break
                    entries.append(nxt)
                fill.attrs.update(
                    requests=len(entries),
                    reason="shutdown" if saw_shutdown
                    else "size" if len(entries) >= self.max_batch_graphs
                    else "deadline",
                )
            # ONE ladder snapshot per flush: bin planning and bucket
            # selection below must agree on the rung set, even if
            # swap_ladder publishes a new ladder mid-flush.
            ladder = self._current_ladder()
            for group in self._pack_groups(entries, ladder):
                try:
                    work = self._collate(group, ladder)
                except Exception as e:  # noqa: BLE001
                    # A bad batch (collation failure past _validate's
                    # checks) fails ITS requests loudly but must not poison
                    # the engine — batch-mates and later traffic are
                    # innocent. Under packing the scope is one BIN: sibling
                    # bins of the same flush still serve.
                    for req in group:
                        self._reject(req, e)
                    self.metrics.count("errors_total")
                    self.metrics.count("bad_batches_total")
                    self._mark_degraded(
                        "collation_failure",
                        [r.request_id for r in group],
                    )
                    continue
                yield work
            if saw_shutdown:
                return

    def _pack_groups(
        self, entries: List[_Request], ladder: List[Tuple[int, int]]
    ) -> List[List[_Request]]:
        """Split one flush into arena-slot bins (first-fit-decreasing under
        the top ladder rung's capacity) when packing is on; otherwise the
        flush is one bin, the historical behavior. Every request of the
        flush appears in exactly one bin (demux identity is per-bin).
        ``ladder`` is the batcher's per-flush snapshot."""
        if not (self._packing and ladder):
            return [entries]
        top_n, top_e = ladder[-1]
        caps = PackCaps(
            nodes=top_n - 1, edges=top_e, graphs=self.max_batch_graphs
        )
        bins = first_fit_decreasing(
            [r.graph.num_nodes for r in entries],
            [r.graph.num_edges for r in entries],
            caps,
        )
        return [[entries[i] for i in members] for members in bins]

    def _bucket_shape(
        self,
        tot_nodes: int,
        tot_edges: int,
        ladder: Optional[List[Tuple[int, int]]] = None,
    ) -> Tuple[int, int, bool]:
        """Smallest ladder (N_pad, E_pad) the batch fits, else round-up
        fallback (``ladder_step`` mode). collate requires N_pad > tot_nodes
        (>=1 padding node) and E_pad >= tot_edges. The batcher passes its
        per-flush ladder snapshot; other callers default to a fresh one."""
        if ladder is None:
            ladder = self._current_ladder()
        for n, e in ladder:
            if n > tot_nodes and e >= tot_edges:
                return n, e, False
        return (
            round_up_pow2(tot_nodes + 1, mode=self._ladder_step),
            round_up_pow2(max(tot_edges, 1), mode=self._ladder_step),
            bool(ladder),
        )

    def _collate(
        self,
        entries: List[_Request],
        ladder: Optional[List[Tuple[int, int]]] = None,
    ) -> _BatchWork:
        self._flush_seq += 1
        flush_id = self._flush_seq
        t0 = time.perf_counter()
        # Queue wait ends at the FLUSH (now), before collation starts — the
        # stage decomposition must not double-count collate seconds.
        for r in entries:
            self.metrics.observe("queue_wait", t0 - r.t_queued)
        # "pack bin" stage of the correlation trail: this span names every
        # request collated into the bin (docs/OBSERVABILITY.md).
        with telemetry.span(
            "serve/collate", flush_id=flush_id,
            request_ids=[r.request_id for r in entries],
        ):
            graphs = [r.graph for r in entries]
            node_start = np.zeros(len(graphs) + 1, np.int64)
            np.cumsum([p.num_nodes for p in graphs], out=node_start[1:])
            tot_nodes = int(node_start[-1])
            tot_edges = sum(p.num_edges for p in graphs)
            n_pad, e_pad, fallback = self._bucket_shape(
                tot_nodes, tot_edges, ladder
            )
            batch = collate_prepared(
                graphs,
                num_nodes_pad=n_pad,
                num_edges_pad=e_pad,
                num_graphs_pad=self._g_pad,
                edge_dim=self._edge_dim,
                with_positions=self._with_positions,
            )
        collated = time.perf_counter()
        self.metrics.observe("collate", collated - t0)
        self.metrics.record_batch(
            len(entries), self.max_batch_graphs, tot_nodes, n_pad,
            tot_edges, e_pad,
        )
        if fallback:
            self.metrics.count("ladder_fallback_total")
        return _BatchWork(
            requests=entries,
            node_start=node_start[:-1],
            batch=batch,
            fallback=fallback,
            flush_id=flush_id,
            marks={
                "first_queued": min(r.t_queued for r in entries),
                "taken": t0, "collated": collated,
            },
        )

    def _transfer(self, work: _BatchWork):
        """DeviceFeed transfer stage: one blocking device_put per batch —
        batch k+1 commits over DMA while batch k executes."""
        import jax

        t0 = work.marks["h2d_start"] = time.perf_counter()
        with telemetry.span(
            "serve/h2d", flush_id=work.flush_id,
            request_ids=[r.request_id for r in work.requests],
        ):
            dev = jax.device_put(work.batch)
            jax.block_until_ready(dev)
        done = work.marks["h2d_end"] = time.perf_counter()
        self.metrics.observe("h2d", done - t0)
        self.metrics.count(
            "h2d_bytes_total",
            sum(
                getattr(leaf, "nbytes", 0)
                for leaf in jax.tree_util.tree_leaves(work.batch)
            ),
        )
        return work, dev

    def _cache_key(
        self, bucket: Tuple[int, int, int], batch, params, bstats
    ) -> Optional[CacheKey]:
        """Persistent-store key for one bucket shape, or None when no store
        is bound (in-memory misses then skip the fingerprint arithmetic).
        The args digest covers the FULL call signature (params, batch_stats,
        batch) — host and device copies of a batch share shapes/dtypes, so
        warmup (host dummy batch) and live traffic (device batch) agree —
        and ``tree_signature`` hashes STRUCTURE, so a hot weight swap of the
        same architecture keys identically (zero recompiles, zero
        cross-architecture hits)."""
        if self._registry.store is None:
            return None
        return CacheKey.for_environment(
            program="serve_forward",
            config_fingerprint=self._config_fingerprint,
            flags=self._key_flags,
            bucket=bucket,
            args_digest=tree_signature((params, bstats, batch)),
            devices=(self.device["id"],),
        )

    def _executable_for(self, dev_batch, params, bstats):
        key = (
            dev_batch.num_nodes_pad,
            dev_batch.num_edges_pad,
            dev_batch.num_graphs_pad,
        )
        # The registry's single lookup path: locked in-memory get; on miss
        # (outside the lock — a 10-50 s lowering must not block submit()'s
        # pending-set bookkeeping or /healthz reads) a persistent-store
        # hydrate, then a fresh compile + store-back. The CacheKey closure
        # is evaluated on misses only — steady-state hits never pay the
        # param-tree fingerprint arithmetic.
        exe, outcome, seconds = self._registry.lookup_or_compile(
            key,
            lambda: self._cache_key(key, dev_batch, params, bstats),
            lambda: self._jit.lower(params, bstats, dev_batch),
        )
        if outcome == "memory":
            self.metrics.count("cache_hits_total")
        elif outcome == "disk":
            self.metrics.record_hydrate(seconds)
        else:
            self.metrics.record_compile(seconds)
        return exe

    def no_recompile(self, allow: int = 0, action: str = "raise"):
        """Post-warmup steady-state assertion, generalized from this engine's
        executable-cache accounting into the shared recompile sentinel
        (analysis/sentinel.py): the wrapped region must not trigger ANY XLA
        compilation — not just engine cache misses, also stray jit traffic
        from co-resident code. Load tests and the serving benchmark wrap
        their measured windows with it."""
        from ..analysis import no_recompile as _no_recompile

        return _no_recompile(
            allow=allow, action=action, label="serve steady state"
        )

    def _execute(self, dev_batch) -> Tuple[List[np.ndarray], str]:
        """Run the (cached) compiled executable; host numpy outputs (for a
        routed token family with the experts every node chose beside them,
        ``_Outputs.routing``) plus the model version the batch executed
        against. The weight reference is
        read ONCE here, so the whole batch — and every response demuxed from
        it — belongs to exactly one version even while a swap publishes a
        new one concurrently. The launch is told from the wait for it by
        two clock readings round the executable's call (``_Outputs.clocks``:
        the train loop's pair, its ``_run_step``)."""
        import jax

        params, bstats, version = self._current_weights()
        exe = self._executable_for(dev_batch, params, bstats)
        launch_start = time.perf_counter()
        outputs = exe(params, bstats, dev_batch)
        launch_end = time.perf_counter()
        outputs = jax.block_until_ready(outputs)
        ready = time.perf_counter()
        self.metrics.observe("device", ready - launch_start)
        routing = None
        if self._token_cfg is not None:
            outputs, routing = outputs
        # (``serve/device``'s child: it is that span that carries the
        # flush's identifier.)
        with telemetry.span("serve/d2h"):
            host = _Outputs(np.asarray(o) for o in outputs)
            if routing is not None:
                host.routing = np.asarray(routing)
        host.clocks = {
            "launch_start": launch_start, "launch_end": launch_end, "ready": ready,
        }
        return host, version

    def _dispatch_loop(self) -> None:
        # Explicit context handoff: the dispatcher's device spans parent to
        # this incarnation's pipeline context (docs/OBSERVABILITY.md).
        telemetry.attach(self._pipeline_ctx)
        # The flush before this one, as ``_book_flush`` left it: a new
        # pipeline incarnation (a worker restart) starts with none.
        previous = None
        try:
            # The batcher's shutdown marker ends the feed iteration; every
            # batch flushed before it is still executed and resolved here.
            for work, dev_batch in self._feed:
                tsan.yield_point("serve.dispatch.pre_execute")
                # _execute failures (compile, device runtime) fall through to
                # _fail: the device's health is engine-scoped. Resolution
                # failures (per-request slicing/denormalization) are
                # BATCH-scoped: fail this batch's futures, keep serving.
                with telemetry.span(
                    "serve/device", flush_id=work.flush_id,
                    request_ids=[r.request_id for r in work.requests],
                ) as device:
                    # The dispatcher's turn across the program and its copy
                    # to the host (None for what the platform does not
                    # count); the launch and the wait are the flush's marks.
                    sched0 = telemetry.thread_sched()
                    work.marks["exec_start"] = time.perf_counter()
                    outputs, version = self._execute(dev_batch)
                    run_delay_s, nivcsw = telemetry.sched_since(sched0)
                    device.attrs.update(run_delay_s=run_delay_s, nivcsw=nivcsw)
                if run_delay_s is not None:
                    telemetry.counter("host/run_delay_s", run_delay_s)
                try:
                    with telemetry.span("serve/resolve", flush_id=work.flush_id):
                        self._resolve(work, outputs, version)
                except Exception as e:  # noqa: BLE001 — batch-scoped
                    previous = None
                    for req in work.requests:
                        self._reject(req, e)
                    self.metrics.count("errors_total")
                    self.metrics.count("bad_batches_total")
                    self._mark_degraded(
                        "resolution_failure",
                        [r.request_id for r in work.requests],
                    )
                else:
                    previous = self._book_flush(
                        work, getattr(outputs, "clocks", None), previous,
                        run_delay_s,
                    )
        except BaseException as e:  # noqa: BLE001 — re-raised at callers
            self._fail(e)

    def _book_flush(
        self, work: _BatchWork, clocks: Optional[Dict[str, float]],
        previous: Optional[Dict[str, float]], run_delay_s: Optional[float],
    ) -> Optional[Dict[str, float]]:
        """The account of one flush, once ``_resolve`` has set its last
        reply: ONE retroactive ``serve/flush`` record from its first
        request's entering the queue to now, whose ``marks`` are the offsets
        in seconds from its start (``t0``: that start on
        ``time.perf_counter()``, the marks' one clock; the record's ``ts`` is
        the same start on graftel's wall clock, so ``ts`` + a mark's offset
        places the mark among the real spans). With the flush before it
        (``previous``: its marks) come ``await_s`` (this flush's first request
        entered the queue that long after the last one's replies were set:
        the engine held no request) and ``turnaround_s`` (``launch_end`` - the previous
        ``ready``: the host time the chip sees as idle between two forwards);
        both None for an incarnation's first flush. The same marks feed the
        stage clocks an operator has without a trace (``handoff``: the two
        queues and the lookup, everything between the stages that had a clock
        and the ``device`` clock's start; ``d2h``; ``resolve``;
        ``turnaround``) and the rung's stall account. Returns the marks; None
        (no record, no account) where ``_execute`` gave no ``clocks``: a seam
        that put its own outputs in their place."""
        if clocks is None:
            return None
        marks = work.marks
        marks.update(clocks, resolved=time.perf_counter())
        resolved_wall = time.time()  # the same moment on the spans' clock
        parts = flush_parts(marks)
        observe = self.metrics.observe
        observe("handoff", parts["handoff"] + parts["lookup"])
        observe("d2h", parts["d2h"])
        observe("resolve", parts["resolve"])
        rung = f"{work.batch.num_nodes_pad}x{work.batch.num_edges_pad}"
        waited = turnaround = None
        if previous is not None:
            waited = max(marks["first_queued"] - previous["resolved"], 0.0)
            turnaround = max(marks["launch_end"] - previous["ready"], 0.0)
            observe("turnaround", turnaround)
        t0 = marks["first_queued"]
        telemetry.record_span(
            "serve/flush", marks["resolved"] - t0, parent=self._pipeline_ctx,
            end_ts=resolved_wall,
            flush_id=work.flush_id, rung=rung, requests=len(work.requests),
            t0=t0, marks={k: round(marks[k] - t0, 6) for k in FLUSH_MARKS},
            await_s=waited, turnaround_s=turnaround,
        )
        if previous is not None:
            # The account is over the ENGINE's seconds: of the turnaround,
            # not those in which it had set every reply and waited for its
            # next batch (the callers' turn, a pause in the traffic, a
            # deadline: an idle engine is not a stalled one). What is left
            # is the last flush's copy to the host and demux and this one's
            # way from the batcher to its launch.
            idle = max(marks["taken"] - previous["resolved"], 0.0)
            before = flush_parts(previous)
            seconds = {
                **{k: parts[k] for k in (
                    "collate", "handoff", "h2d", "lookup", "launch", "device_wait",
                )},
                "d2h": before["d2h"], "resolve": before["resolve"],
            }
            if run_delay_s is not None:
                seconds[RUN_DELAY] = run_delay_s
            self._flush_account.book(
                work.flush_id,
                max(turnaround - idle, 0.0) + parts["device_wait"], seconds,
                key=rung, flush_id=work.flush_id, rung=rung,
                requests=len(work.requests), turnaround_s=round(turnaround, 4),
                await_s=round(waited, 4), fill_s=round(parts["fill"], 4),
            )
        return marks

    def _count_routing(self, routing: np.ndarray, real: int) -> None:
        """A flush's routing in the engine's counters (serve/metrics.py) and
        as graftel gauges: over the flush's ``real`` tokens and each routed
        layer, the rows sent to held experts, the fullest held expert's rows
        and the layers whose rows passed the compact path's ``C`` and took a
        further pass (models/token_routed.py ``RoutedFFN``)."""
        from ..models.token_routed import pass_rows

        cfg, n_pad = self._token_cfg, routing.shape[0]
        k, held = cfg.num_experts_per_tok, cfg.num_experts_held
        local = routing[:real].reshape(real, -1, k) - cfg.experts_offset
        loads = np.stack([
            np.bincount(layer[(layer >= 0) & (layer < held)], minlength=held)
            for layer in np.moveaxis(local, 1, 0)
        ])  # [routed layers, held]
        cap = pass_rows(cfg, n_pad)
        self._count_flush({
            "moe_rows_held_total": int(loads.sum()),
            "moe_load_max_total": int(loads.max(axis=1).sum()),
            "moe_fallback_layers_total": int((loads.sum(axis=1) > cap).sum()),
        })

    def _count_key_blocks(self, node_graph: np.ndarray) -> None:
        """A flush's attention cores in the engine's counters and as graftel
        gauges, by the KIND of layer. A full layer (the complete causal
        graph): the (query block, key block) pairs ONE call of its core
        visits, a head, beside the pairs of the padded rung's whole causal
        triangle (models/token_attention.py ``attention_key_blocks``: the
        function that hands the TPU's kernel its block range, on the flush's
        own ``node_graph``); elsewhere than on a TPU the core walks the
        triangle, and the two are equal. A window layer (the causal band, where the
        stack has one): the pairs ONE call of the band's core visits at the
        flush's rung (``band_key_blocks``: the blocks the band's static mask
        holds, whatever the documents); 0 for a stack whose every layer is
        full."""
        from ..models.token_attention import attention_key_blocks, band_key_blocks

        visited, causal = attention_key_blocks(
            node_graph, ranged=self.device["platform"] == "tpu"
        )
        self._count_flush({
            "attn_key_blocks_visited_total": visited,
            "attn_key_blocks_causal_total": causal,
            "attn_window_key_blocks_total": (
                band_key_blocks(len(node_graph), self._band_window)
                if self._band_window else 0
            ),
        })

    def _count_scans(self, node_graph: np.ndarray, documents: int) -> None:
        """A flush's selective-scan layers in the engine's counters and as
        graftel gauges: the time chunks ONE scan call walks over the flush's
        rung, a layer and a channel block (ops/selective_scan.py
        ``scan_chunks``: the kernel's sequential grid axis), and the
        documents whose state the flush started (each a run of
        ``node_graph``; the padding rows' run is not a document); 0 and 0 for
        a stack with no such layer."""
        from ..ops.selective_scan import scan_chunks

        chunks, resets = (scan_chunks(len(node_graph)), documents) if self._scans else (0, 0)
        self._count_flush({"ssm_scan_chunks_total": chunks, "ssm_state_resets_total": resets})

    def _count_flush(self, counted: Dict[str, int]) -> None:
        for name, value in counted.items():
            self.metrics.count(name, value)
            telemetry.gauge("serve/" + name[: -len("_total")], value)

    def _resolve(
        self, work: _BatchWork, outputs: List[np.ndarray], version: str
    ) -> None:
        # ``e2e`` ends HERE, where the demux begins, and so does the flush's
        # ``d2h``: one reading for both, so the stages add to ``e2e``.
        now = work.marks["d2h_end"] = time.perf_counter()
        batch_had_nonfinite = False
        routing = getattr(outputs, "routing", None)
        if self._token_cfg is not None:
            self._count_key_blocks(work.batch.node_graph)
            self._count_scans(work.batch.node_graph, len(work.requests))
        if routing is not None:
            last = work.requests[-1]
            self._count_routing(
                routing, int(work.node_start[-1]) + last.graph.num_nodes
            )
        for i, req in enumerate(work.requests):
            per_head: List[np.ndarray] = []
            for ihead, htype in enumerate(self.model.output_type):
                out = outputs[ihead]
                if htype == "graph":
                    val = out[i]
                else:
                    start = int(work.node_start[i])
                    val = out[start : start + req.graph.num_nodes]
                per_head.append(self._denormalize(ihead, val))
            if self._guard_outputs and any(
                not np.isfinite(v).all() for v in per_head
            ):
                # The serving reuse of the non-finite guard: THIS request
                # fails; batch-mates and the engine are unaffected.
                self.metrics.count("nonfinite_total")
                batch_had_nonfinite = True
                telemetry.event(
                    "serve/nonfinite", request_id=req.request_id
                )
                self._reject(
                    req,
                    NonFiniteOutputError(
                        "model produced non-finite outputs for this request"
                    ),
                )
                continue
            with self._lock:
                self._pending.discard(req.future)
            # Version tag BEFORE set_result: a waiter woken by the event
            # must never observe a result without its version.
            req.future.model_version = version
            if routing is not None:
                start = int(work.node_start[i])
                req.future.routing = routing[start : start + req.graph.num_nodes]
            req.future.set_result(per_head)
            self.metrics.observe("e2e", now - req.t_submit)
            # Demux complete: the end of the correlation trail
            # (submit → pack bin → device batch → demux → response).
            telemetry.event(
                "serve/response",
                request_id=req.request_id,
                model_version=version,
                e2e_s=round(now - req.t_submit, 6),
            )
        if batch_had_nonfinite:
            self.metrics.count("bad_batches_total")
            self._mark_degraded(
                "nonfinite_output",
                [
                    r.request_id
                    for r in work.requests
                    if r.future._error is not None
                ],
            )

    def _denormalize(self, ihead: int, value: np.ndarray) -> np.ndarray:
        if self._y_minmax is None:
            return value
        ymin = np.asarray(self._y_minmax[ihead][0])
        ymax = np.asarray(self._y_minmax[ihead][1])
        return value * (ymax - ymin) + ymin

    def _mark_degraded(self, reason: str, request_ids: Sequence[str] = ()) -> None:
        """Sticky health downgrade + a bounded transition log: /healthz
        shows WHY the engine grayed out and which correlation ids were
        involved, and the transition lands in the telemetry stream (so a
        flight-recorder dump carries it too)."""
        entry = {
            "ts_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "reason": reason,
            "request_ids": [r for r in request_ids if r][:8],
        }
        with self._lock:
            self._degraded = True
            self._degraded_events.append(entry)
        telemetry.event(
            "serve/degraded",
            reason=reason,
            request_ids=entry["request_ids"],
        )

    @property
    def degraded_events(self) -> List[dict]:
        """Locked copy of the recent degraded-state transitions (newest
        last) — the /healthz payload's ``degraded_events`` field."""
        with self._lock:
            return list(self._degraded_events)

    def _reject(self, req: _Request, exc: BaseException) -> None:
        with self._lock:
            self._pending.discard(req.future)
        req.future.set_exception(exc)

    def _fail_pending(self, exc: BaseException) -> None:
        with self._lock:
            pending, self._pending = self._pending, set()
        for fut in pending:
            fut.set_exception(exc)

    def _fail(self, exc: BaseException) -> None:
        """A worker thread died. Within the ``max_worker_restarts`` budget:
        fail the in-flight/queued requests (their work is unrecoverable),
        mark the engine degraded, and RESTART the pipeline threads — the
        engine keeps serving. Budget exhausted (or 0, the default): poison
        the engine and fail every pending future so no caller blocks forever
        (the 'never wedge the queue' contract)."""
        if isinstance(exc, EngineClosedError) or (
            self._closing.is_set() and self._error is None
        ):
            self._fail_pending(EngineClosedError("engine closed"))
            return
        self.metrics.count("errors_total")
        restartable = self._restarts_left > 0 and not self._closing.is_set()
        if not restartable:
            # Poison FIRST so concurrent submits fail fast (their post-
            # enqueue re-check sees the error) before the queue drain below.
            with self._lock:
                self._error = exc
            self._closing.set()
            # Flight-recorder trigger (docs/OBSERVABILITY.md): the last
            # thing operators get from a poisoned engine is the timeline
            # that killed it.
            telemetry.event("serve/engine_poisoned", error=repr(exc))
            telemetry.flight_dump(
                "engine_poison", extra={"error": repr(exc)}
            )
        # Tear down this incarnation's pipeline either way: stop the batcher
        # FIRST (a stale batcher racing a successor on the shared queue would
        # strand whatever it popped), then cancel + join the feed threads.
        if self._gen_stop is not None:
            self._gen_stop.set()
        if self._feed is not None:
            self._feed.close()
            self._feed.join(2.0)
        # Drain queued requests that never reached a batch. (A request
        # admitted during this window may be failed here yet still sit in
        # the queue; the successor batcher then computes it and its
        # set_result is a benign no-op over the already-failed future.)
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            if req is not _SHUTDOWN:
                self._reject(req, exc)
        self._fail_pending(exc)
        if restartable:
            with self._lock:
                self._restarts_left -= 1
                self._degraded = True
                self._feed = None
                self._dispatcher = None
                self._degraded_events.append(
                    {
                        "ts_utc": time.strftime(
                            "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
                        ),
                        "reason": "worker_restart",
                        "request_ids": [],
                        "error": repr(exc),
                    }
                )
            self.metrics.count("engine_restarts_total")
            telemetry.event("serve/engine_restart", error=repr(exc))
            self.start()

    # -------------------------------------------------------------- warmup
    def warmup(self, ladder: Optional[Sequence[Tuple[int, int]]] = None) -> int:
        """AOT-compile every (declared or constructor) ladder bucket so
        steady-state traffic never pays a compile. An explicitly passed
        ladder is MERGED into the engine's bucket ladder — a warmed shape
        _bucket_shape can never select would be wasted compile time.
        Returns the number of executables compiled."""
        if ladder:
            with self._lock:
                self._ladder = sorted(set(self._ladder) | set(self._rungs(ladder)))
        compiled = 0
        params, bstats, _version = self._current_weights()
        # Iterate the MERGED ladder: constructor-declared buckets still cold
        # at this point must warm too, as the docstring promises. With a
        # persistent store bound, a rung found on disk HYDRATES (seconds,
        # zero XLA compiles — the replica-spin-up path docs/COMPILE_CACHE.md
        # exists for) and does not count toward the compile total.
        for n_pad, e_pad in self._current_ladder():
            key = (int(n_pad), int(e_pad), self._g_pad)
            if self._registry.get(key) is not None:
                continue
            batch = self._dummy_batch(int(n_pad), int(e_pad))
            _exe, outcome, seconds = self._registry.lookup_or_compile(
                key,
                self._cache_key(key, batch, params, bstats),
                lambda b=batch: self._jit.lower(params, bstats, b),
            )
            if outcome == "disk":
                self.metrics.record_hydrate(seconds)
            elif outcome == "compiled":
                self.metrics.record_compile(seconds)
                compiled += 1
        return compiled

    def _dummy_batch(self, n_pad: int, e_pad: int):
        """Structurally-real batch of one 1-node graph at the given pads —
        shape/dtype/pytree-identical to live traffic's batches."""
        s = GraphSample(
            x=np.zeros((1, self.model.input_dim), np.float32),
            pos=np.zeros((1, 3), np.float32),
            # A token family's batches hold no edge.
            edge_index=None if self._token_cfg is not None
            else np.zeros((2, 1), np.int32),
            edge_attr=np.zeros((1, max(self._edge_dim, 1)), np.float32)
            if self._edge_dim
            else None,
        )
        return GraphArena([s]).collate(
            np.array([0]),
            num_nodes_pad=n_pad,
            num_edges_pad=e_pad,
            num_graphs_pad=self._g_pad,
            edge_dim=self._edge_dim,
            with_positions=self._with_positions,
        )

    # ------------------------------------------------------ hot ladder swap
    def swap_ladder(
        self, ladder: Sequence[Tuple[int, int]], warm: bool = True
    ) -> Dict[str, Any]:
        """Atomic, per-request-consistent hot bucket-ladder swap — the data
        loop's analog of :meth:`swap_weights` (flywheel drift-refit,
        docs/FLYWHEEL.md).

        ``warm=True`` (the default, and what the flywheel uses) compiles or
        hydrates every rung of the NEW ladder through the shared executable
        registry BEFORE publishing, on the calling thread — so the batcher
        never selects a cold rung and rungs the old ladder already compiled
        (or a previous process persisted to the graftcache store) publish
        with ZERO XLA compiles. The publish itself rebinds the single sorted
        ladder reference under the engine lock; the batcher snapshots that
        reference once per flush, so every request is planned entirely
        against one ladder — no torn flush, no dropped request.

        Old-ladder executables stay in the registry (memory + store): a
        rollback swap re-publishes them without compiling, and oversized
        in-flight traffic still resolves through the pow2 fallback.

        Returns {ladder, previous, compiled, hydrated, wall_s}.
        """
        new = self._rungs(ladder)
        if not new:
            raise ValueError(
                "swap_ladder needs at least one (N_pad, E_pad) rung"
            )
        if self._error is not None:
            raise EngineFailedError(
                "inference worker died; engine must be rebuilt"
            ) from self._error
        if self._closing.is_set():
            raise EngineClosedError("engine is shut down")
        t0 = time.perf_counter()
        compiled = hydrated = 0
        # Same whole-swap mutex as weight swaps: a ladder swap racing a
        # weight swap must warm against a settled weight reference, and two
        # ladder swaps must publish in a total order.
        with self._swap_lock:
            if warm:
                params, bstats, _version = self._current_weights()
                for n_pad, e_pad in new:
                    key = (n_pad, e_pad, self._g_pad)
                    if self._registry.get(key) is not None:
                        continue
                    batch = self._dummy_batch(n_pad, e_pad)
                    _exe, outcome, seconds = self._registry.lookup_or_compile(
                        key,
                        self._cache_key(key, batch, params, bstats),
                        lambda b=batch: self._jit.lower(params, bstats, b),
                    )
                    if outcome == "disk":
                        self.metrics.record_hydrate(seconds)
                        hydrated += 1
                    elif outcome == "compiled":
                        self.metrics.record_compile(seconds)
                        compiled += 1
            # Annotated interleaving site: the publish races the batcher's
            # per-flush snapshot — the tsan flywheel drill perturbs exactly
            # this window (benchmarks/tsan_drill.py _flywheel_drill).
            tsan.yield_point("serve.ladder.pre_publish")
            with self._lock:
                previous = self._ladder
                self._ladder = new
        wall = time.perf_counter() - t0
        self.metrics.count("ladder_swaps_total")
        telemetry.event(
            "serve/ladder_swapped",
            rungs=len(new),
            compiled=compiled,
            hydrated=hydrated,
            wall_s=round(wall, 4),
        )
        return {
            "ladder": [list(r) for r in new],
            "previous": [list(r) for r in previous],
            "compiled": compiled,
            "hydrated": hydrated,
            "wall_s": round(wall, 4),
        }

    # ------------------------------------------------------ hot weight swap
    def swap_weights(self, variables: Dict[str, Any], version: str) -> Dict[str, Any]:
        """Atomic, per-request-consistent hot weight swap (docs/SERVING.md
        "Live model lifecycle"; ROADMAP item 4).

        Validates the incoming param-tree fingerprint against the tree the
        compiled executables take as arguments — a mismatch raises
        :class:`SwapFingerprintError` and the engine KEEPS SERVING its
        current weights. On a match, the new ``(params, batch_stats,
        version)`` triple is published as one reference under the engine
        lock: every in-flight batch executes entirely against one version
        (the dispatch thread reads the reference once per batch), versions
        observed by responses are monotonic, and — because ``CacheKey`` /
        ``tree_signature`` fingerprint the param TREE, not the values —
        every compiled bucket is reused with ZERO recompiles.

        Quantized arms (``precision != 'f32'``) re-apply their transform to
        the incoming f32 variables (int8 re-snaps the weight grid) and
        RE-RUN the PR-11 tolerance gate on the CANDIDATE weights before they
        publish; a gate failure raises :class:`PrecisionToleranceError` with
        the engine untouched — a candidate that cannot meet the declared
        bound never serves a single request. On success the new f32
        reference is retained for future gates.

        Returns a small report: {version, previous_version, wall_s, gate}.
        """
        import jax

        from ..checkpoint.format import param_fingerprint
        from ..precision import fake_quantize_params

        if self._error is not None:
            raise EngineFailedError(
                "inference worker died; engine must be rebuilt"
            ) from self._error
        if self._closing.is_set():
            raise EngineClosedError("engine is shut down")
        t0 = time.perf_counter()
        # Whole-swap mutex: concurrent swaps (a promote racing a rollback)
        # must validate against, gate against, and replace the SAME
        # predecessor in a total order — and the quantized-arm reference
        # state must always describe the published weights.
        with self._swap_lock:
            old_params, old_bstats, old_version = self._current_weights()
            want = param_fingerprint(old_params) + param_fingerprint(
                old_bstats
            )
            got = param_fingerprint(variables["params"]) + param_fingerprint(
                variables.get("batch_stats", {})
            )
            if got != want:
                self.metrics.count("swap_rejected_total")
                telemetry.event(
                    "serve/swap_rejected",
                    version=str(version),
                    reason="param-tree fingerprint mismatch",
                )
                raise SwapFingerprintError(
                    f"swap to version {version!r} rejected: its param-tree "
                    "fingerprint does not match the serving architecture — "
                    "the engine keeps serving version "
                    f"{old_version!r} (rebuild the engine for an "
                    "architecture change; a hot swap is weights-only)"
                )
            serve_params = variables["params"]
            quant_report = None
            if self.precision == "int8":
                serve_params, quant_report = fake_quantize_params(
                    serve_params
                )
            params = jax.device_put(serve_params)
            bstats = jax.device_put(variables.get("batch_stats", {}))
            jax.block_until_ready((params, bstats))
            gate_report = None
            if self.precision != "f32":
                # The tolerance gate runs on the CANDIDATE weights BEFORE
                # they publish: a candidate that cannot meet its declared
                # bound must never serve a single live request (and response
                # versions stay monotonic — no publish-then-revert flicker).
                try:
                    gate_report = self._tolerance_gate(
                        params, bstats, variables, quant_report
                    )
                except PrecisionToleranceError:
                    self.metrics.count("swap_gate_failures_total")
                    telemetry.event(
                        "serve/swap_gate_failed", version=str(version)
                    )
                    raise
            # Annotated interleaving site: the publish races the dispatch
            # thread's per-batch read — the tsan swap drill perturbs exactly
            # this window (benchmarks/tsan_drill.py _swap_drill).
            tsan.yield_point("serve.swap.pre_publish")
            with self._lock:
                self._weights = (params, bstats, str(version))
            if self.precision != "f32":
                self._ref_variables = variables
                if quant_report is not None:
                    self._quant_report = quant_report
        wall = time.perf_counter() - t0
        self.metrics.count("weight_swaps_total")
        telemetry.event(
            "serve/weights_swapped",
            version=str(version),
            previous_version=old_version,
            wall_s=round(wall, 4),
        )
        return {
            "version": str(version),
            "previous_version": old_version,
            "wall_s": round(wall, 4),
            "gate": gate_report,
        }

    def restore_weights(self, weights: Tuple[Any, Any, str]) -> None:
        """Republish a triple previously read from :meth:`_current_weights`
        — the manager's mid-fleet unwind (a swap that failed on replica k
        must not leave replicas 0..k-1 serving a version the registry never
        promoted). No fingerprint or gate re-run: the triple already served
        on this engine."""
        with self._swap_lock:
            with self._lock:
                self._weights = weights
        telemetry.event("serve/weights_restored", version=weights[2])

    # ------------------------------------------------------- tolerance gate
    def _calibration_samples(
        self, count: int = 4, seed: int = 0
    ) -> List[GraphSample]:
        """Deterministic random calibration graphs at the model's feature
        widths — the default probe batch for :meth:`check_tolerance` when the
        operator brings no representative samples. Seeded: the gate verdict
        is reproducible across restarts/replicas."""
        rng = np.random.default_rng(seed)
        out = []
        for _ in range(count):
            n = int(rng.integers(4, 9))
            ei = np.stack(
                [np.arange(n), (np.arange(n) + 1) % n]
            ).astype(np.int32)
            ei = np.concatenate([ei, ei[::-1]], axis=1)
            out.append(
                GraphSample(
                    x=rng.normal(size=(n, self.model.input_dim)).astype(
                        np.float32
                    ),
                    pos=np.zeros((n, 3), np.float32),
                    edge_index=ei,
                    edge_attr=rng.normal(
                        size=(ei.shape[1], self._edge_dim)
                    ).astype(np.float32)
                    if self._edge_dim
                    else None,
                )
            )
        return out

    def check_tolerance(self, samples: Optional[Sequence[GraphSample]] = None):
        """The quantized-arm gate (docs/PRECISION.md): collate one probe
        batch, run it through BOTH the serving executable (bf16/int8) and a
        retained f32 reference forward, and compare with the shared tolerance
        machinery (precision/tolerance.py — the same helpers ops/certify.py
        gates the aggregation arms with). Within the bound: returns the verdict report
        (also folded into ``hydragnn_serve_precision_*`` metrics). Beyond it:
        raises :class:`PrecisionToleranceError` — a quantized arm that cannot
        meet its declared tolerance must not take traffic.

        ``precision="f32"`` returns a trivial verdict: the f32 contract is
        bit-exactness against ``run_prediction`` (tests/test_serve_engine.py),
        not a tolerance."""
        if self.precision == "f32":
            return {
                "ok": True,
                "arm": "f32",
                "note": "bit-exactness contract — no tolerance gate",
            }
        # Consistent (weights, reference) pair: a swap completing after this
        # read yields a stale-but-self-consistent verdict, never a mixed one.
        with self._swap_lock:
            params, bstats, _version = self._current_weights()
            ref_vars = self._ref_variables
            quant_report = self._quant_report
        return self._tolerance_gate(params, bstats, ref_vars, quant_report, samples)

    def _tolerance_gate(
        self,
        params,
        bstats,
        ref_vars,
        quant_report,
        samples: Optional[Sequence[GraphSample]] = None,
    ):
        """The gate body over EXPLICIT weights + reference: shared by
        :meth:`check_tolerance` (the live weights) and :meth:`swap_weights`
        (candidate weights BEFORE they publish — a failing candidate must
        never serve a single live request)."""
        import jax

        from ..precision import tolerance_report
        from ..train.trainer import _apply_model

        if samples is None:
            samples = self._calibration_samples()
        else:
            samples = list(samples)
            if not samples:
                # An empty probe set is an upstream bug, not a request for
                # synthetic calibration — a verdict must never claim coverage
                # of data it did not see.
                raise ValueError(
                    "check_tolerance received an empty sample sequence; pass "
                    "None for the seeded synthetic calibration batch"
                )
        for s in samples:
            self._validate(s)
        arena = GraphArena(samples)
        n_pad, e_pad, _ = self._bucket_shape(
            int(arena.ns.sum()), int(arena.es.sum())
        )
        batch = arena.collate(
            np.arange(len(samples)),
            num_nodes_pad=n_pad,
            num_edges_pad=e_pad,
            num_graphs_pad=self._g_pad,
            edge_dim=self._edge_dim,
            with_positions=self._with_positions,
        )
        dev = jax.device_put(batch)
        quant = [
            np.asarray(o)
            for o in jax.block_until_ready(self._jit(params, bstats, dev))
        ]
        ref_model = self._ref_model
        assert ref_model is not None and ref_vars is not None
        ref_fn = jax.jit(
            lambda p, b, x: _apply_model(ref_model, p, b, x, train=False)
        )
        reference = [
            np.asarray(o)
            for o in jax.block_until_ready(
                ref_fn(
                    ref_vars["params"], ref_vars.get("batch_stats", {}), dev
                )
            )
        ]
        report = tolerance_report(
            quant, reference, self.tolerance, names=self.head_names
        )
        report["arm"] = self.precision
        report["probe_graphs"] = len(samples)
        if quant_report is not None:
            report["quantization"] = quant_report
        self.metrics.record_precision_gate(report)
        telemetry.event(
            "serve/precision_gate",
            arm=self.precision,
            ok=report["ok"],
            fwd_err=report["fwd_err"],
            tol=report["tol"],
        )
        if not report["ok"]:
            raise PrecisionToleranceError(
                f"{self.precision} arm diverges from the f32 reference by "
                f"{report['fwd_err']:.3e} (> tolerance {self.tolerance:g})",
                report,
            )
        return report

    # ------------------------------------------------------- checkpoint load
    @classmethod
    def from_config(
        cls,
        config,
        checkpoint: Optional[str] = None,
        checkpoint_format: str = "auto",
        logs_path: str = "./logs/",
        **options,
    ) -> "InferenceEngine":
        """Build an engine from a COMPLETED config (the snapshot
        ``run_training`` writes to ``logs/<name>/config.json`` — it must
        already carry input_dim/output_dim/output_type/pna_deg etc., since
        serving has no datasets to re-run config completion against).

        ``checkpoint`` is a path to either a native flax checkpoint
        (utils/model.save_model payload) or a reference torch ``.pk``
        (mapped through utils/torch_import); ``"auto"`` sniffs the format.
        ``checkpoint=None`` restores this framework's own
        ``logs/<log_name>/<log_name>.pk`` derived from the config. For torch
        checkpoints with ``num_sharedlayers > 1`` the model is built with the
        reference shared-MLP activation layout (models/layers.MLP
        ``inner_activation=False``) so imported forwards are exact.
        """
        from ..models.create import create_model_config, init_model_variables, make_example_batch
        from ..utils.config_utils import get_log_name_config
        from ..utils.model import load_checkpoint_file, load_existing_model

        if isinstance(config, str):
            with open(config) as f:
                config = json.load(f)
        arch = dict(config["NeuralNetwork"]["Architecture"])
        for required in ("input_dim", "output_dim", "output_type"):
            if required not in arch:
                raise ValueError(
                    f"config is not completed (missing Architecture."
                    f"{required}) — pass the logs/<name>/config.json "
                    "snapshot run_training wrote, not the raw input config"
                )

        fmt = checkpoint_format
        if fmt == "auto":
            fmt = "native" if checkpoint is None else cls._sniff_format(checkpoint)
        if fmt not in ("native", "torch"):
            raise ValueError(f"unknown checkpoint_format {fmt!r}")
        if fmt == "torch" and checkpoint is None:
            raise ValueError(
                "checkpoint_format='torch' requires an explicit checkpoint "
                "path (--ckpt); only native checkpoints can be derived from "
                "the config's log name"
            )
        if fmt == "torch":
            # The reference's shared-MLP Sequential has no ReLU between its
            # shared Linears; build the model with that exact layout so the
            # imported checkpoint serves bit-faithful outputs.
            heads = json.loads(json.dumps(arch["output_heads"]))
            if "graph" in heads:
                heads["graph"]["shared_layout"] = "reference"
            arch["output_heads"] = heads

        model = create_model_config(config=arch, verbosity=0)
        example = make_example_batch(
            arch["input_dim"],
            arch["output_dim"],
            arch["output_type"],
            edge_dim=arch.get("edge_dim"),
            num_nodes=arch.get("num_nodes") or 4,
            with_positions=model.needs_positions,
        )
        variables = init_model_variables(model, example)

        if fmt == "torch":
            from ..utils.torch_import import import_torch_checkpoint

            variables, report = import_torch_checkpoint(
                checkpoint, model, variables
            )
            if report["caveats"]:
                raise ValueError(
                    "torch checkpoint import is not exact for this config: "
                    + "; ".join(report["caveats"])
                )
        elif checkpoint is None:
            name = get_log_name_config(config)
            variables, _ = load_existing_model(variables, name, path=logs_path)
        else:
            variables, _, _ = load_checkpoint_file(variables, checkpoint)

        voi = config["NeuralNetwork"].get("Variables_of_interest", {})
        options.setdefault("head_names", voi.get("output_names"))
        if voi.get("denormalize_output") and voi.get("y_minmax"):
            options.setdefault("y_minmax", voi["y_minmax"])
        if "model_version" not in options:
            # The lifecycle layer's per-response version tag defaults to the
            # checkpoint's verified content identity (short form) so a
            # config-booted replica reports the same version id a
            # ModelRegistry would assign. v1/torch checkpoints carry no
            # verifiable identity — labeled, never guessed.
            if fmt == "native":
                path_name = checkpoint or os.path.join(
                    logs_path,
                    get_log_name_config(config),
                    get_log_name_config(config) + ".pk",
                )
                try:
                    from ..checkpoint.format import file_content_identity

                    options["model_version"] = file_content_identity(
                        path_name
                    )[0][:12]
                except Exception:  # noqa: BLE001 — v1 pickle, fallback load
                    options["model_version"] = "unverified"
            else:
                options["model_version"] = "torch-import"
        return cls(model, variables, **options)

    @staticmethod
    def _sniff_format(path: str) -> str:
        """Native v2 checkpoints carry the HGNN2 magic (sniffed WITHOUT
        executing any deserializer); torch.save writes a zip archive (PK
        magic). Legacy native v1 files are a plain pickle of
        {"params": bytes, ...} — the one remaining pickle sniff, kept through
        the v1 read-compat window (docs/CHECKPOINTING.md "Migration")."""
        from ..checkpoint import MAGIC

        try:
            with open(path, "rb") as f:
                head = f.read(max(len(MAGIC), 2))
        except OSError:
            return "torch"
        if head[: len(MAGIC)] == MAGIC:
            return "native"
        if head[:2] == b"PK":  # zip archive: torch.save
            return "torch"
        try:
            with open(path, "rb") as f:
                # graftlint: disable=pickle-load-outside-compat(format sniffer for v1 legacy checkpoints — classification only, result discarded, errors swallowed)
                payload = pickle.load(f)
            if isinstance(payload, dict) and "params" in payload:
                return "native"
        except Exception:
            pass
        return "torch"


# --------------------------------------------------------- checkpoint hot swap
def swap_from_checkpoint(
    engine: InferenceEngine,
    path: str,
    version: Optional[str] = None,
    expected_identity: Optional[str] = None,
) -> Dict[str, Any]:
    """Load a v2 checkpoint FILE and hot-swap it into ``engine`` — the shared
    implementation behind the ``/swap`` admin endpoint (serve/server.py) and
    ``Replica.swap_checkpoint`` (route/replica.py), so ``LifecycleManager``
    can drive spawned HTTP replicas with the exact semantics of an
    in-process ``engine.swap_weights`` (docs/SERVING.md "Live model
    lifecycle").

    ONE read: the bytes whose content identity is computed are the bytes
    deserialized (``checkpoint.io.load_checkpoint_bytes`` — the TOCTOU-free
    candidate-load contract from graftswap). ``expected_identity``, when
    given, must match the file's full content identity — the caller's staged
    version and the weights that publish provably attest the same bytes.
    ``version`` defaults to the identity's 12-hex short form (the registry's
    display convention). Returns the swap report plus ``identity``/``epoch``.
    """
    from ..checkpoint.format import content_identity
    from ..checkpoint.io import load_checkpoint_bytes

    with open(path, "rb") as f:
        blob = f.read()
    identity, _details = content_identity(blob, path)
    if expected_identity and identity != expected_identity:
        raise SwapIdentityError(
            f"{path}: content identity {identity[:12]} does not match the "
            f"expected {expected_identity[:12]} — the file changed since it "
            "was staged; the engine keeps serving its current version"
        )
    variables, _opt, meta = load_checkpoint_bytes(
        engine.variables_template(), blob, path
    )
    report = engine.swap_weights(variables, version or identity[:12])
    report["identity"] = identity
    report["epoch"] = (meta or {}).get("epoch")
    return report
