"""The stall rule, once: a cycle far over the median of those before it says
so itself, profiler or none (docs/OBSERVABILITY.md "The host's timeline").

A cycle is whatever its keeper books: an epoch (``EpochAccount``,
train/train_validate_test.py: the wall of the ``epoch`` span and each span
name's seconds in it) or a flush of the serving engine (serve/engine.py: the
engine's own seconds between two forwards, not those it waited for
requests, plus the wait for the forward, keyed by rung). The rule takes a wall and a dict of named seconds and knows nothing
else of either loop.
"""

from __future__ import annotations

import logging
import statistics
from collections import deque
from typing import Dict, Hashable, Optional, Sequence, Tuple

from . import graftel

# A cycle this many times the median of the cycles of its key before it (at
# least STALL_HISTORY of them), and at least STALL_MIN_S longer, is a stall:
# tiny cycles swing by more than half of themselves.
STALL_RATIO = 1.5
STALL_HISTORY = 3
STALL_MIN_S = 0.1
CYCLES_KEPT = 32
# The named second every keeper may book beside its own: how long the
# dispatching thread was runnable and not run (``graftel.thread_sched``).
# A keeper books it only where the platform counts it: a cycle without it
# says "not counted", and its verdict never answers ``host_thread``.
RUN_DELAY = "run_delay_s"
# The verdict's three answers, and what the warning line calls them.
HELD_BY = {
    "host_thread": "the host thread was runnable and not run",
    "dispatch": "the dispatch was slow",
    "wait": "the program or its wake-up was slow (the wait)",
    None: "neither the thread's turn, the dispatch nor the wait grew",
}


class StallAccount:
    """The last cycles' walls and named seconds, by key. ``book`` adds one
    and, where it stalled, counts it (``counter``), emits ``event`` with
    where its seconds went, dumps the flight recorder (``dump``) and logs ONE
    warning line on ``logger``. ``dispatch`` and ``wait`` name which of the
    booked seconds are a program's launch and the wait for it; with
    ``run_delay_s`` they give the line its verdict: the host thread, the
    dispatch or the wait held the excess. Where the cycle books no run delay,
    because the platform counts none, the event's ``run_delay_s`` is None,
    the line says "not counted", and a thread that was not run reads as the
    dispatch or the wait it was in. ``containers`` are names whose seconds
    hold other names' (never the part that grew)."""

    def __init__(
        self,
        what: str,
        event: str,
        counter: str,
        dump: str,
        logger: logging.Logger,
        dispatch: Sequence[str] = (),
        wait: Sequence[str] = (),
        containers: Sequence[str] = (),
    ):
        self.what, self.event, self.counter, self.dump = what, event, counter, dump
        self.logger = logger
        self.dispatch, self.wait = tuple(dispatch), tuple(wait)
        self.containers = tuple(containers)
        # key -> its kept cycles, oldest first: (wall_s, seconds)
        self._cycles: Dict[Hashable, deque] = {}  # guarded-by: external(an account has ONE keeper thread: the epoch loop's, the engine's dispatcher)

    def book(
        self, label, wall_s: float, seconds: Dict[str, float],
        key: Hashable = None, **fields,
    ) -> Optional[dict]:
        """Book one cycle (``label`` names it on the warning line, ``fields``
        go onto the event as they are); returns the stall's attributes where
        it stalled, else None."""
        cycles = self._cycles.setdefault(key, deque(maxlen=CYCLES_KEPT))
        stall = None
        if len(cycles) >= STALL_HISTORY:
            median = statistics.median(wall for wall, _ in cycles)
            if wall_s > STALL_RATIO * median and wall_s - median >= STALL_MIN_S:
                stall = self._report(label, wall_s, median, seconds, cycles, fields)
        cycles.append((wall_s, seconds))
        return stall

    def _extra(self, seconds, usual) -> Tuple[dict, str]:
        """A keeper's own attributes of a stall and its clause of the line."""
        return {}, ""

    def _report(self, label, wall_s, median, seconds, cycles, fields) -> dict:
        usual = {
            name: statistics.median(s.get(name, 0.0) for _, s in cycles)
            for name in seconds
        }
        over = {name: seconds[name] - usual[name] for name in seconds}
        excess = sorted(
            (
                (over[name], name) for name in seconds
                if name not in self.containers and name != RUN_DELAY
            ),
            reverse=True,
        )[:3]
        held_by, held_s = self._verdict(wall_s - median, over)
        more, clause = self._extra(seconds, usual)
        delay = seconds.get(RUN_DELAY)
        stall = dict(
            fields, wall_s=round(wall_s, 4), median_s=round(median, 4), **more,
            run_delay_s=None if delay is None else round(delay, 4),
            held_by=held_by, held_s=round(held_s, 4),
            seconds={k: round(v, 4) for k, v in sorted(seconds.items())},
            excess=[
                [name, round(grew, 4), round(usual[name], 4)]
                for grew, name in excess
            ],
        )
        graftel.counter(self.counter)
        graftel.event(self.event, **stall)
        graftel.flight_dump(self.dump, extra=stall)
        self.logger.warning(
            "%s %s took %.3f s against a median of %.3f s%s; largest excesses "
            "over their own medians: %s; %s (%+.3f s; %s)",
            self.what, label, wall_s, median, clause,
            ", ".join(
                f"{name} {grew:+.3f} s ({seconds[name]:.3f} against {usual[name]:.3f})"
                for grew, name in excess
            ),
            HELD_BY[held_by], held_s,
            "the thread's run delay is not counted on this host"
            if delay is None
            else f"the thread's run delay {delay:.3f} s against {usual[RUN_DELAY]:.3f}",
        )
        return stall

    def _verdict(self, excess_s: float, over: Dict[str, float]):
        """Which of the three held the excess, and its seconds. The run delay
        lies INSIDE the dispatch's or the wait's seconds (the thread was not
        run while one of them was open), so it is asked first, where the
        cycle books one: half the excess or more. Else the larger of dispatch
        and wait, where it holds a quarter of the excess; else none of the
        three (a feed)."""
        delay = over.get(RUN_DELAY)
        if delay is not None and delay >= 0.5 * excess_s:
            return "host_thread", delay
        dispatch = sum(over.get(name, 0.0) for name in self.dispatch)
        wait = sum(over.get(name, 0.0) for name in self.wait)
        if max(dispatch, wait) < 0.25 * excess_s:
            return None, max(dispatch, wait, 0.0)
        return ("dispatch", dispatch) if dispatch >= wait else ("wait", wait)
