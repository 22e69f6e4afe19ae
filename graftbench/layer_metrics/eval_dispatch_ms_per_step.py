"""``dispatch_ms_per_chunk`` of the evaluation steps: host milliseconds from
an ``eval_step`` span's opening to the return of the call that launches its
program, mean over the window's ``eval_step`` records on the dispatching
thread. None for a program whose records carry no ``dispatch_s``."""

from graftbench.layer_metrics.dispatch_ms_per_chunk import mean_attr_ms


def read(run):
    return mean_attr_ms(run, "eval_step", "dispatch_s")
