"""Device milliseconds of the forward program a flush: the seconds of every
program the device ran inside the window (``trace_reduce``'s ``XLA Modules``
events; in a serving window the engine's forward is the only one), over the
flushes the engine counted. None where the trace holds no program.

``scope_ms`` is what the serving cell's two roofline readers share: device
self time a flush of the operations whose innermost scope starts with a
prefix, WHATEVER their root (the engine's forward opens no root scope, so
``xplane_scopes.step_ms`` would book it to ``other``)."""

from graftbench import xplane_scopes


def device_s(run):
    programs = (run.trace or {}).get("programs") or {}
    return sum(row["seconds"] for row in programs.values()) or None


def read(run):
    seconds, flushes = device_s(run), run.facts.get("flushes")
    return 1e3 * seconds / flushes if seconds and flushes else None


def scope_ms(run, prefix: str):
    result, flushes = xplane_scopes.table(run), run.facts.get("flushes")
    if result is None or not flushes:
        return None
    seconds = sum(
        row["seconds"] for row in result["rows"] if row["scope"].startswith(prefix)
    )
    return 1e3 * seconds / flushes or None


def roofline(run, prefix: str, bytes_class: str):
    """The counted bytes a flush of a class (``flops.forward``: float32,
    real rows) over what the HBM could move in ``scope_ms``, in percent; not
    clamped."""
    ms = scope_ms(run, prefix)
    counted = (run.facts.get("flush_bytes") or {}).get(bytes_class)
    if not ms or not counted or not run.peaks:
        return None
    return 100.0 * counted / (ms * 1e-3 * run.peaks["hbm_bytes_per_s"])
