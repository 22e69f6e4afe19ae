"""Device time a train step: seconds of the programs that ran under the
program's ``device_step`` span (a train step, or a scan chunk of steps, up to
its blocking readback), from the device trace, mean over the chips, over the
steps of the window. The trace carries no named scope, so the host span is
what tells a train step from an evaluation step (``trace_reduce.py``)."""


def read(run):
    seconds = run.trace["by_span"].get("device_step", {}).get("seconds")
    steps = run.facts.get("steps")
    return 1e3 * seconds / steps if steps and seconds else None
