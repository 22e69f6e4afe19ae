"""Host milliseconds a flush under no stage's work: ``handoff`` (the two
queues of the ``DeviceFeed``: collated -> the transfer thread, transferred
-> the dispatcher; a thread's wake-up, or the dispatcher still busy with the
flush before) + ``lookup`` (``_executable_for``) + ``launch`` (the executable
call's return), from the ``serve/flush`` record's marks, mean over the
window. None without such records."""

from graftbench.layer_metrics.serve_turnaround_ms_per_flush import mean_ms


def read(run):
    return mean_ms(run, "handoff", "lookup", "launch")
