"""Share of the epochs' wall spent in the evaluations, from inside: the
program's ``evaluate`` spans over its ``epoch`` spans in the window.
``eval_wall_share``'s twin (that one is the benchmark's clock round the
epoch less the program's ``train/epoch_wall_s``)."""

from graftbench import host_phases


def read(run):
    epochs = host_phases.seconds(host_phases.dispatching(run.spans, "epoch"))
    evaluations = host_phases.dispatching(run.spans, "evaluate")
    if not epochs or not evaluations:
        return None
    return 100.0 * host_phases.seconds(evaluations) / epochs
