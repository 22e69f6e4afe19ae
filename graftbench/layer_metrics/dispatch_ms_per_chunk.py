"""Host milliseconds from a ``device_step`` span's opening to the return of
the call that launches its program (``dispatch_s``: the executable's lookup,
argument handling, the launch; the blocking readback after it is ``wait_s``),
mean over the window's ``device_step`` records on the dispatching thread: a
train step on the per-batch paths, a scan chunk of steps on the scan path.
While it runs the chip has nothing new to do unless the program before is
still running. None for a program whose records carry no ``dispatch_s``."""

from graftbench import host_phases


def mean_attr_ms(run, span: str, attr: str):
    values = [
        r["attrs"][attr] for r in host_phases.dispatching(run.spans, span)
        if attr in (r.get("attrs") or {})
    ]
    return 1e3 * sum(values) / len(values) if values else None


def read(run):
    return mean_attr_ms(run, "device_step", "dispatch_s")
