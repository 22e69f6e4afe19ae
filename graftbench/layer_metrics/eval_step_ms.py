"""Device time an evaluation step: seconds of the programs that ran under
the program's ``eval_step`` span (``trace_reduce``'s ``by_span``, mean over
the chips) over the window's ``eval_step`` spans."""

from graftbench import host_phases


def read(run):
    seconds = run.trace["by_span"].get("eval_step", {}).get("seconds")
    steps = len(host_phases.dispatching(run.spans, "eval_step"))
    return 1e3 * seconds / steps if steps and seconds else None
