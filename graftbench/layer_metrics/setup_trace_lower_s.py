"""Seconds of set-up JAX spent tracing and lowering (``jax/trace_s`` +
``jax/lower_s``, its own monitoring durations folded into graftel's
counters), as they stood when the window's first ``epoch`` span opened: that
span carries the cumulative counters as attributes, so the warm-up is in and
whatever the benchmark lowers after the window is out. A jit traced inside
another's trace is in both. None for a program whose epochs carry no such
attributes."""

from graftbench import host_phases


def read(run):
    return host_phases.at_first_epoch(run.spans, "jax_trace_s", "jax_lower_s")
