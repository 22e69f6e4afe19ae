"""Serving metrics: latency histograms, batch-shape counters, and a
Prometheus text exposition — the observability half of the online engine
(docs/SERVING.md "Metrics reference").

Everything here is host-side and lock-protected (observations arrive from the
engine's batcher/transfer/dispatch threads plus every caller thread). Seconds
observed into the latency histograms are ALSO credited into the existing
``Timer`` registry (utils/time_utils.py) under ``serve_*`` names, so a process
that both trains and serves prints one merged timer report.

Histogram design: fixed log-spaced bucket bounds (factor 2 from 100 µs to
~1638 s) — the standard Prometheus shape. Quantiles are estimated by linear
interpolation inside the first bucket whose cumulative count covers the
requested rank; with 2x-spaced bounds the estimate is within 2x of the true
value, which is the resolution serving SLOs are stated at.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

from ..analysis import tsan
from ..graphs.packing import SizeHistogram
from ..utils.time_utils import Timer

# 100 µs .. ~1638 s in 2x steps (25 bounds) — covers queue waits on an idle
# engine through multi-minute pathological stalls.
_DEFAULT_BOUNDS = tuple(1e-4 * (2.0**i) for i in range(25))

# Tolerance-diff bounds for the quantized precision arm (docs/PRECISION.md):
# 1e-9 .. ~275 in 4x steps — spans bf16 rounding noise on tiny heads through
# an unmistakably-broken quantization, at the 4x resolution tolerance bounds
# are stated at.
_DIFF_BOUNDS = tuple(1e-9 * (4.0**i) for i in range(20))


class LatencyHistogram:
    """Fixed-bound histogram of seconds with count/sum and quantile estimates."""

    def __init__(self, bounds: Sequence[float] = _DEFAULT_BOUNDS):
        self.bounds = tuple(float(b) for b in bounds)
        self._lock = tsan.instrument_lock(
            threading.Lock(), "LatencyHistogram._lock"
        )
        self._counts = [0] * (len(self.bounds) + 1)  # guarded-by: self._lock
        self.count = 0  # guarded-by: self._lock
        self.sum = 0.0  # guarded-by: self._lock

    def observe(self, seconds: float) -> None:
        seconds = float(seconds)
        i = 0
        for i, b in enumerate(self.bounds):
            if seconds <= b:
                break
        else:
            i = len(self.bounds)
        with self._lock:
            self._counts[i] += 1
            self.count += 1
            self.sum += seconds

    def mean(self) -> Optional[float]:
        """Locked mean seconds per observation (None when empty) — the
        per-request service estimate cross-thread readers (the router's
        admission check) must use instead of a torn sum/count pair."""
        with self._lock:
            return self.sum / self.count if self.count else None

    def quantile(self, q: float) -> Optional[float]:
        """Interpolated q-quantile in seconds (None when empty)."""
        with self._lock:
            counts = list(self._counts)
        return self.quantile_of(self.bounds, counts, q)

    @staticmethod
    def quantile_of(
        bounds: Sequence[float], counts: Sequence[int], q: float
    ) -> Optional[float]:
        """Interpolated q-quantile of an explicit per-bucket count vector
        (None when empty). Exposed so windowed readers — the router's
        rolling fleet-p99 sensor diffs successive ``counts_snapshot``
        vectors — estimate quantiles of a DELTA distribution with the same
        interpolation the cumulative :meth:`quantile` uses."""
        total = sum(counts)
        if total == 0:
            return None
        rank = q * total
        cum = 0
        for i, c in enumerate(counts):
            prev_cum = cum
            cum += c
            if cum >= rank and c > 0:
                hi = bounds[i] if i < len(bounds) else bounds[-1] * 2.0
                lo = bounds[i - 1] if i > 0 else 0.0
                frac = (rank - prev_cum) / c
                return lo + (hi - lo) * min(max(frac, 0.0), 1.0)
        return bounds[-1] * 2.0

    def counts_snapshot(self) -> List[int]:
        """One locked copy of the per-bucket counts (len(bounds) + 1 with
        the overflow bucket last) — the windowed-quantile reader's input."""
        with self._lock:
            return list(self._counts)

    def snapshot(self) -> Dict[str, Optional[float]]:
        with self._lock:
            count, total = self.count, self.sum
        out = {"count": count, "sum_s": round(total, 6)}
        for name, q in (("p50", 0.5), ("p95", 0.95), ("p99", 0.99)):
            v = self.quantile(q)
            out[name + "_ms"] = None if v is None else round(v * 1000.0, 3)
        return out

    def prometheus_lines(
        self, name: str, labels: str = "", le_fmt=None
    ) -> List[str]:
        """Cumulative-bucket exposition for one histogram. ``le_fmt`` formats
        bound labels; the default (6 decimal places, the historical latency
        rendering) COLLAPSES sub-1e-6 bounds to "0.0" — histograms with tiny
        bounds (the precision tolerance-diff family) must pass a
        significant-digit formatter instead, or strict parsers see duplicate
        le labels."""
        lab = f"{{{labels}}}" if labels else ""
        if le_fmt is None:
            le_fmt = lambda b: repr(round(b, 6))  # noqa: E731

        def with_le(le: str) -> str:
            inner = (labels + "," if labels else "") + f'le="{le}"'
            return f"{{{inner}}}"

        with self._lock:
            counts = list(self._counts)
            count, total = self.count, self.sum
        lines = []
        cum = 0
        for b, c in zip(self.bounds, counts):
            cum += c
            lines.append(f"{name}_bucket{with_le(le_fmt(b))} {cum}")
        lines.append(f"{name}_bucket{with_le('+Inf')} {count}")
        lines.append(f"{name}_sum{lab} {total}")
        lines.append(f"{name}_count{lab} {count}")
        return lines


class ServeMetrics:
    """All counters/histograms of one ``InferenceEngine``.

    Latency stages (per docs/SERVING.md), one observation a request:
      prepare    — submit(), on the caller's thread: the request made ready
                   for concatenation (float32 / int32 arrays, the edges
                   stable-sorted by receiver); one observation an ADMITTED
                   request, so its count is ``requests_total``;
      queue_wait — the prepared request entering the queue to its joining a
                   flushed micro-batch;
      e2e        — submit() to the moment ``_resolve`` begins to set the
                   flush's replies (the demux after it is ``resolve``);
    and one observation a FLUSH, from the flush's clock marks (serve/engine.py
    ``FLUSH_MARKS``; the same readings as its ``serve/flush`` record):
      collate    — host assembly of the micro-batch into its padded arrays;
      handoff    — what lies between the stages around it and under no other
                   clock: the two queues of the ``DeviceFeed`` (collated ->
                   the transfer thread, transferred -> the dispatcher) and
                   the executable's lookup before the ``device`` clock starts;
      h2d        — blocking device_put wire time (pipeline transfer thread);
      device     — compiled executable launch + ``block_until_ready``;
      d2h        — the outputs' copy to the host, to ``_resolve``'s start;
      resolve    — ``_resolve``: the flush's counters, each reply sliced and
                   set (on the dispatcher's thread, in series with the next
                   flush's launch);
      turnaround — the executable call's return less the PREVIOUS flush's
                   ``ready``: the host time the chip sees as idle between two
                   forwards (none for a pipeline incarnation's first flush).

    ``t_submit`` sits in ``submit()`` after validation and BEFORE the
    preparation (serve/engine.py ``_Request``): ``e2e`` holds ``prepare``,
    ``queue_wait`` starts where ``prepare`` ends, and for every request
    ``prepare + queue_wait`` + its flush's ``collate + handoff + h2d + device
    + d2h`` = ``e2e``, each second under exactly one clock
    (tests/test_telemetry.py holds it to a millisecond). ``resolve`` and
    ``turnaround`` are outside it: the first follows ``e2e``, the second
    overlaps the others.
    """

    _STAGES = (
        "prepare", "queue_wait", "collate", "handoff", "h2d", "device", "d2h",
        "resolve", "turnaround", "e2e",
    )

    def __init__(self):
        self._lock = tsan.instrument_lock(
            threading.Lock(), "ServeMetrics._lock"
        )
        # Observations arrive from the batcher (feed-host), transfer,
        # dispatch, and caller threads; every field below is declared
        # guarded (graftrace enforces the with-blocks mechanically).
        self.latency = {  # guarded-by: self._lock, dirty-reads(dict is immutable after construction; the leaf histograms carry their own lock)
            s: LatencyHistogram() for s in self._STAGES
        }
        # Counters (monotonic).
        self.requests_total = 0  # guarded-by: self._lock
        # Admitted requests whose edge list needed no sort in ``prepare``: it
        # arrived non-decreasing by receiver (an edgeless request does).
        self.presorted_total = 0  # guarded-by: self._lock
        self.rejected_total = 0  # guarded-by: self._lock
        self.errors_total = 0  # guarded-by: self._lock
        # Fault-tolerance split of errors (docs/FAULT_TOLERANCE.md):
        # batch-scoped failures keep the engine serving; worker restarts
        # consume the engine's restart budget; non-finite outputs fail the
        # REQUEST, not the engine.
        self.bad_batches_total = 0  # guarded-by: self._lock
        self.nonfinite_total = 0  # guarded-by: self._lock
        self.engine_restarts_total = 0  # guarded-by: self._lock
        # Live model lifecycle (graftswap, docs/SERVING.md): completed hot
        # weight swaps, fingerprint-rejected swap attempts, and post-swap
        # tolerance-gate reverts on quantized arms.
        self.weight_swaps_total = 0  # guarded-by: self._lock
        self.swap_rejected_total = 0  # guarded-by: self._lock
        self.swap_gate_failures_total = 0  # guarded-by: self._lock
        # Completed hot bucket-ladder swaps (the flywheel's drift-refit
        # path, serve/engine.py swap_ladder — docs/FLYWHEEL.md).
        self.ladder_swaps_total = 0  # guarded-by: self._lock
        self.batches_total = 0  # guarded-by: self._lock
        self.graphs_total = 0  # guarded-by: self._lock
        self.cache_hits_total = 0  # guarded-by: self._lock
        self.cache_misses_total = 0  # guarded-by: self._lock
        self.ladder_fallback_total = 0  # guarded-by: self._lock
        self.compile_seconds_total = 0.0  # guarded-by: self._lock
        # Persistent executable cache (graftcache, docs/COMPILE_CACHE.md):
        # disk hydrations — executables deserialized from the store instead
        # of compiled. A hydration is NOT a compile (no XLA compile event)
        # and NOT an in-memory hit; it gets its own pair so warmup cost is
        # attributable (exported as hydragnn_serve_exec_cache_*).
        self.exec_cache_hydrated_total = 0  # guarded-by: self._lock
        self.exec_cache_hydrate_seconds_total = 0.0  # guarded-by: self._lock
        self.h2d_bytes_total = 0  # guarded-by: self._lock
        # A routed token family (serve/engine.py _count_routing), summed over
        # the flushes and their routed layers: rows sent to held experts, the
        # fullest held expert's rows, and the layers whose rows passed the
        # compact path's capacity and took a further pass.
        self.moe_rows_held_total = 0  # guarded-by: self._lock
        self.moe_load_max_total = 0  # guarded-by: self._lock
        self.moe_fallback_layers_total = 0  # guarded-by: self._lock
        # A token family's attention cores (serve/engine.py _count_key_blocks),
        # summed over the flushes: the key blocks one call of a FULL layer's
        # core visits, a head, and those of the rung's whole causal triangle;
        # the key blocks one call of a WINDOW layer's core visits (0 for a
        # stack with no window layer).
        self.attn_key_blocks_visited_total = 0  # guarded-by: self._lock
        self.attn_key_blocks_causal_total = 0  # guarded-by: self._lock
        self.attn_window_key_blocks_total = 0  # guarded-by: self._lock
        # A token family's selective-scan layers (serve/engine.py
        # _count_scans), summed over the flushes: the time chunks ONE scan
        # call walks at the flush's rung, a layer, and the documents whose
        # state a flush started (0 for a stack with no such layer).
        self.ssm_scan_chunks_total = 0  # guarded-by: self._lock
        self.ssm_state_resets_total = 0  # guarded-by: self._lock
        # Occupancy / padding accumulators (averages derived in snapshot()).
        self._occupancy_sum = 0.0  # guarded-by: self._lock
        self._node_fill_sum = 0.0  # guarded-by: self._lock
        self._edge_fill_sum = 0.0  # guarded-by: self._lock
        # Per-bucket occupancy: the same accumulators keyed by the padded
        # (N_pad, E_pad) shape the batch compiled into, so a ladder's rungs
        # are individually observable (which rungs carry traffic, which
        # waste it) — docs/SERVING.md "Metrics reference".
        self._per_bucket: Dict[Tuple[int, int], Dict[str, float]] = {}  # guarded-by: self._lock
        # Observed request/batch sizes: the feedback record the ladder
        # fitter consumes (graphs/packing.py fit_ladder; dump via
        # histogram_json()). Guarded by the same lock as the counters.
        self.size_hist = SizeHistogram()  # guarded-by: self._lock
        # Precision arm (graftprec, docs/PRECISION.md): which arm this engine
        # serves, its tolerance bound, and the tolerance-gate record — the
        # hydragnn_serve_precision_* exposition family.
        self.precision_arm = "f32"  # guarded-by: self._lock, dirty-reads(set once at engine construction, before worker threads exist)
        self.precision_tolerance: Optional[float] = None  # guarded-by: self._lock, dirty-reads(same single-assignment lifecycle as precision_arm)
        self.precision_gate_checks_total = 0  # guarded-by: self._lock
        self.precision_gate_failures_total = 0  # guarded-by: self._lock
        self.precision_diff_max = 0.0  # guarded-by: self._lock
        # Per-head max-abs-diff observations.
        self.precision_diff = LatencyHistogram(bounds=_DIFF_BOUNDS)  # guarded-by: self._lock, dirty-reads(rebound never after construction; the leaf histogram carries its own lock, like the latency family)

    # ------------------------------------------------------------- recorders
    def observe(self, stage: str, seconds: float) -> None:
        self.latency[stage].observe(seconds)
        Timer.credit(f"serve_{stage}", seconds)

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, name, getattr(self, name) + n)
            tsan.shared_access("ServeMetrics.counters")

    def read_counters(self, *names: str) -> Dict[str, float]:
        """One locked copy of the named counters — cross-thread readers
        (/healthz) must not assemble their view field-by-field between the
        recorder's updates (torn pairs; same defect render_prometheus had)."""
        with self._lock:
            return {n: getattr(self, n) for n in names}

    def record_compile(self, seconds: float) -> None:
        with self._lock:
            self.cache_misses_total += 1
            self.compile_seconds_total += seconds
        Timer.credit("serve_compile", seconds)

    def record_hydrate(self, seconds: float) -> None:
        """One executable deserialized from the persistent store (a
        graftcache disk hit — docs/COMPILE_CACHE.md)."""
        with self._lock:
            self.exec_cache_hydrated_total += 1
            self.exec_cache_hydrate_seconds_total += seconds
        Timer.credit("serve_exec_cache_hydrate", seconds)

    def set_precision(self, arm: str, tolerance: Optional[float]) -> None:
        """Engine-construction registration of the serving arm."""
        with self._lock:
            self.precision_arm = str(arm)
            self.precision_tolerance = tolerance

    def record_precision_gate(self, report: Dict) -> None:
        """Fold one check_tolerance verdict into the precision family: gate
        counters, running max diff, and the per-head diff histogram."""
        with self._lock:
            self.precision_gate_checks_total += 1
            if not report.get("ok"):
                self.precision_gate_failures_total += 1
            self.precision_diff_max = max(
                self.precision_diff_max, float(report.get("fwd_err", 0.0))
            )
        for head in report.get("per_head", ()):
            self.precision_diff.observe(float(head["max_abs_diff"]))

    def record_request(self, num_nodes: int, num_edges: int) -> None:
        """One admitted request's graph size — the serve half of the size
        histogram (the training half lives on GraphDataLoader)."""
        with self._lock:
            self.size_hist.record_graph(num_nodes, num_edges)

    def record_batch(
        self,
        num_graphs: int,
        max_batch_graphs: int,
        real_nodes: int,
        n_pad: int,
        real_edges: int,
        e_pad: int,
    ) -> None:
        with self._lock:
            tsan.shared_access("ServeMetrics.counters")
            self.batches_total += 1
            self.graphs_total += num_graphs
            self._occupancy_sum += num_graphs / max(max_batch_graphs, 1)
            self._node_fill_sum += real_nodes / max(n_pad, 1)
            self._edge_fill_sum += real_edges / max(e_pad, 1)
            bucket = self._per_bucket.setdefault(
                (int(n_pad), int(e_pad)),
                {"batches": 0, "graphs": 0, "node_fill": 0.0, "edge_fill": 0.0},
            )
            bucket["batches"] += 1
            bucket["graphs"] += num_graphs
            bucket["node_fill"] += real_nodes / max(n_pad, 1)
            bucket["edge_fill"] += real_edges / max(e_pad, 1)
            self.size_hist.record_batch(real_nodes, real_edges, num_graphs)

    # -------------------------------------------------------------- reporters
    def snapshot(self) -> Dict:
        with self._lock:
            batches = self.batches_total
            out = {
                "requests_total": self.requests_total,
                "presorted_total": self.presorted_total,
                "rejected_total": self.rejected_total,
                "errors_total": self.errors_total,
                "bad_batches_total": self.bad_batches_total,
                "nonfinite_total": self.nonfinite_total,
                "engine_restarts_total": self.engine_restarts_total,
                "weight_swaps_total": self.weight_swaps_total,
                "swap_rejected_total": self.swap_rejected_total,
                "swap_gate_failures_total": self.swap_gate_failures_total,
                "ladder_swaps_total": self.ladder_swaps_total,
                "batches_total": batches,
                "graphs_total": self.graphs_total,
                "bucket_cache": {
                    "hits": self.cache_hits_total,
                    "misses": self.cache_misses_total,
                    "compile_seconds": round(self.compile_seconds_total, 4),
                    "ladder_fallbacks": self.ladder_fallback_total,
                    "hydrated": self.exec_cache_hydrated_total,
                    "hydrate_seconds": round(
                        self.exec_cache_hydrate_seconds_total, 4
                    ),
                },
                "h2d_bytes_total": self.h2d_bytes_total,
                "moe_rows_held_total": self.moe_rows_held_total,
                "moe_load_max_total": self.moe_load_max_total,
                "moe_fallback_layers_total": self.moe_fallback_layers_total,
                "attn_key_blocks_visited_total": self.attn_key_blocks_visited_total,
                "attn_key_blocks_causal_total": self.attn_key_blocks_causal_total,
                "attn_window_key_blocks_total": self.attn_window_key_blocks_total,
                "ssm_scan_chunks_total": self.ssm_scan_chunks_total,
                "ssm_state_resets_total": self.ssm_state_resets_total,
                # Precision arm + tolerance-gate record (docs/PRECISION.md).
                "precision": {
                    "arm": self.precision_arm,
                    "tolerance": self.precision_tolerance,
                    "gate_checks": self.precision_gate_checks_total,
                    "gate_failures": self.precision_gate_failures_total,
                    "max_abs_diff": self.precision_diff_max,
                },
                "batch_occupancy_mean": round(
                    self._occupancy_sum / batches, 4
                )
                if batches
                else None,
                # Padding waste = 1 - fill: the share of padded rows that
                # carried no real node/edge (compiled FLOPs spent on padding).
                "padding_waste_nodes_mean": round(
                    1.0 - self._node_fill_sum / batches, 4
                )
                if batches
                else None,
                "padding_waste_edges_mean": round(
                    1.0 - self._edge_fill_sum / batches, 4
                )
                if batches
                else None,
                # Per compiled (N_pad, E_pad) shape: which ladder rungs carry
                # the traffic and how full they run.
                "per_bucket": {
                    f"{n}x{e}": {
                        "batches": int(b["batches"]),
                        "graphs": int(b["graphs"]),
                        "node_fill_mean": round(
                            b["node_fill"] / b["batches"], 4
                        ),
                        "edge_fill_mean": round(
                            b["edge_fill"] / b["batches"], 4
                        ),
                    }
                    for (n, e), b in sorted(self._per_bucket.items())
                },
            }
        out["latency_ms"] = {s: h.snapshot() for s, h in self.latency.items()}
        out["precision"]["diff"] = self.precision_diff.snapshot()
        return out

    def histogram_json(self) -> Dict:
        """The observed-size record (requests + collated batch totals) in the
        ``fit-ladder`` CLI's input schema — the production feedback loop of
        docs/SERVING.md "Fitting a ladder from production histograms"."""
        with self._lock:
            return self.size_hist.to_json()

    # Counter attr -> exported Prometheus metric name. Exposition reads the
    # whole set in ONE locked copy — graftrace flagged the original
    # field-by-field unlocked reads (a scrape mid-record saw torn pairs,
    # e.g. batches_total incremented but graphs_total not yet).
    _PROM_COUNTERS = (
        ("requests_total", "requests_total"),
        ("presorted_total", "presorted_total"),
        ("rejected_total", "rejected_total"),
        ("errors_total", "errors_total"),
        ("bad_batches_total", "bad_batches_total"),
        ("nonfinite_total", "nonfinite_total"),
        ("engine_restarts_total", "engine_restarts_total"),
        # Hot-swap lifecycle counters (docs/OBSERVABILITY.md catalogue).
        ("weight_swaps_total", "weight_swaps_total"),
        ("swap_rejected_total", "swap_rejected_total"),
        ("swap_gate_failures_total", "swap_gate_failures_total"),
        ("ladder_swaps_total", "ladder_swaps_total"),
        ("batches_total", "batches_total"),
        ("graphs_total", "graphs_total"),
        ("cache_hits_total", "bucket_cache_hits_total"),
        ("cache_misses_total", "bucket_cache_misses_total"),
        ("ladder_fallback_total", "ladder_fallback_total"),
        ("compile_seconds_total", "compile_seconds_total"),
        # graftcache exposition (docs/COMPILE_CACHE.md): the persistent
        # executable store's view of this engine — hits/misses alias the
        # bucket-cache pair (one registry serves both), hydrations are the
        # disk-restore half only this family carries.
        ("cache_hits_total", "exec_cache_hits_total"),
        ("cache_misses_total", "exec_cache_misses_total"),
        ("exec_cache_hydrated_total", "exec_cache_hydrated_total"),
        ("exec_cache_hydrate_seconds_total", "exec_cache_hydrate_seconds_total"),
        ("h2d_bytes_total", "h2d_bytes_total"),
        ("moe_rows_held_total", "moe_rows_held_total"),
        ("moe_load_max_total", "moe_load_max_total"),
        ("moe_fallback_layers_total", "moe_fallback_layers_total"),
        ("attn_key_blocks_visited_total", "attn_key_blocks_visited_total"),
        ("attn_key_blocks_causal_total", "attn_key_blocks_causal_total"),
        ("attn_window_key_blocks_total", "attn_window_key_blocks_total"),
        ("ssm_scan_chunks_total", "ssm_scan_chunks_total"),
        ("ssm_state_resets_total", "ssm_state_resets_total"),
    )

    def render_prometheus(self) -> str:
        """Prometheus text-format exposition (the /metrics payload)."""
        p = "hydragnn_serve"
        with self._lock:
            counters = {
                attr: getattr(self, attr) for attr, _ in self._PROM_COUNTERS
            }
        lines = []
        for attr, metric in self._PROM_COUNTERS:
            lines.append(f"# TYPE {p}_{metric} counter")
            lines.append(f"{p}_{metric} {counters[attr]}")
        snap = self.snapshot()
        for gauge in (
            "batch_occupancy_mean",
            "padding_waste_nodes_mean",
            "padding_waste_edges_mean",
        ):
            v = snap[gauge]
            if v is not None:
                lines.append(f"# TYPE {p}_{gauge} gauge")
                lines.append(f"{p}_{gauge} {v}")
        if snap.get("per_bucket"):
            # One contiguous sample group per metric family (the exposition
            # format requires all of a metric's samples directly under its
            # TYPE line — interleaving families breaks strict parsers).
            lines.append(f"# TYPE {p}_bucket_batches_total counter")
            for key, b in snap["per_bucket"].items():
                lines.append(
                    f'{p}_bucket_batches_total{{bucket="{key}"}} '
                    f"{b['batches']}"
                )
            lines.append(f"# TYPE {p}_bucket_node_fill_mean gauge")
            for key, b in snap["per_bucket"].items():
                lines.append(
                    f'{p}_bucket_node_fill_mean{{bucket="{key}"}} '
                    f"{b['node_fill_mean']}"
                )
        # Precision family (docs/PRECISION.md "Telemetry"): which arm serves
        # (info-style gauge with the arm label), the gate counters, and the
        # per-head tolerance-diff histogram — empty (all-zero buckets) on
        # the f32 arm, where no gate runs.
        prec = snap["precision"]
        lines.append(f"# TYPE {p}_precision_info gauge")
        lines.append(f'{p}_precision_info{{arm="{prec["arm"]}"}} 1')
        lines.append(f"# TYPE {p}_precision_gate_checks_total counter")
        lines.append(
            f"{p}_precision_gate_checks_total {prec['gate_checks']}"
        )
        lines.append(f"# TYPE {p}_precision_gate_failures_total counter")
        lines.append(
            f"{p}_precision_gate_failures_total {prec['gate_failures']}"
        )
        if prec["tolerance"] is not None:
            lines.append(f"# TYPE {p}_precision_tolerance_bound gauge")
            lines.append(
                f"{p}_precision_tolerance_bound {prec['tolerance']}"
            )
        lines.append(f"# TYPE {p}_precision_tolerance_diff histogram")
        lines.extend(
            self.precision_diff.prometheus_lines(
                f"{p}_precision_tolerance_diff",
                labels=f'arm="{prec["arm"]}"',
                # Significant digits, not decimal places: the 1e-9-scale
                # bounds would otherwise all collapse to le="0.0".
                le_fmt=lambda b: f"{b:.3g}",
            )
        )
        lines.append(f"# TYPE {p}_latency_seconds histogram")
        for stage, hist in self.latency.items():
            lines.extend(
                hist.prometheus_lines(
                    f"{p}_latency_seconds", labels=f'stage="{stage}"'
                )
            )
        return "\n".join(lines) + "\n"
