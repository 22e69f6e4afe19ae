"""Step-windowed profiler (reference /root/reference/hydragnn/utils/
profile.py:9-68 wraps torch.profiler with a wait=1/warmup=1/active=3 step
schedule inside a target epoch; here jax.profiler traces to TensorBoard).

Config surface is a superset of the reference's:
``"Profile": {"enable": 1, "target_epoch": N, "wait": 1, "warmup": 1,
"active": 3}`` — within the target epoch, ``wait + warmup`` train steps run
untraced (compile/cache effects settle), then exactly ``active`` steps are
captured. ``active: 0`` falls back to tracing the whole epoch. The trace
lands under ./logs/<name>/profiler_output for TensorBoard / Perfetto.

One annotation path (docs/OBSERVABILITY.md): while its trace is open the
profiler switches graftel's ``jax.profiler.TraceAnnotation`` bridge on, so the
host events of a captured trace ARE the program's graftel spans, under the
names every other reader uses (``train_epoch``, ``collate``, ``h2d``,
``feed_wait``, ``device_step``, ``evaluate``, ``eval_step``); the training
loop opens no annotation of its own. ``annotate(name)`` (torch
``record_function`` analog) is a graftel span too, for a caller's own region.
Device time by named scope of such a trace:
``python3 -m graftbench.xplane_scopes <trace dir>``."""

from __future__ import annotations

import os
from typing import Optional

import jax

from ..telemetry import graftel as telemetry


class Profiler:
    def __init__(self, prefix: str = "./logs/profile"):
        self.enabled = False
        self.target_epoch: Optional[int] = None
        self.trace_dir = os.path.join(prefix, "profiler_output")
        # Step schedule within the target epoch (reference profile.py:23).
        self.wait = 1
        self.warmup = 1
        self.active_steps = 3
        self._armed = False  # inside the target epoch
        self._tracing = False  # jax trace window open
        self._bridge_was = False  # graftel's annotation bridge before _start
        self._step = 0

    def setup(self, config: Optional[dict]) -> None:
        """config = the optional "Profile" block of the run config."""
        if not config:
            return
        self.enabled = bool(config.get("enable", 0))
        self.target_epoch = config.get("target_epoch", 0)
        self.wait = int(config.get("wait", 1))
        self.warmup = int(config.get("warmup", 1))
        self.active_steps = int(config.get("active", 3))

    def set_current_epoch(self, epoch: int) -> None:
        if not self.enabled:
            return
        if epoch == self.target_epoch and not self._armed:
            self._armed = True
            self._step = 0
            # Whole-epoch window, or a schedule with no wait/warmup: the
            # trace must open before the first step runs.
            if self.active_steps <= 0 or self.wait + self.warmup == 0:
                self._start()
        elif self._armed and epoch != self.target_epoch:
            self.stop()

    @property
    def active(self) -> bool:
        """True inside the target epoch (drives the per-step train path —
        scanned epochs would hide step boundaries from the trace)."""
        return self._armed

    def step(self) -> None:
        """Per-train-step hook: advances the wait/warmup/active schedule."""
        if not self._armed or self.active_steps <= 0:
            return
        self._step += 1
        skip = self.wait + self.warmup
        if self._step == skip and not self._tracing:
            self._start()
        elif self._step == skip + self.active_steps and self._tracing:
            self._stop_trace()

    def annotate(self, name: str):
        """Named region (record_function analog): a graftel span, which is a
        host event of the trace while one is open."""
        return telemetry.span(name)

    def _start(self) -> None:
        os.makedirs(self.trace_dir, exist_ok=True)
        jax.profiler.start_trace(self.trace_dir)
        self._bridge_was = telemetry.jax_annotations()
        telemetry.configure(jax_annotations=True)
        self._tracing = True

    def _stop_trace(self) -> None:
        telemetry.configure(jax_annotations=self._bridge_was)
        jax.profiler.stop_trace()
        self._tracing = False

    def stop(self) -> None:
        if self._tracing:
            self._stop_trace()
        self._armed = False
