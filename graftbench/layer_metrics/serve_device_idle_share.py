"""1 - union of device-operation intervals over the serving window (trace),
as ``device_idle_share`` takes it, in percent. In a closed loop the engine's
collation, transfer and reply stages run in series with the forward, so most
of it is theirs (``breakdown.idle_gaps`` names each by its host span)."""


def read(run):
    v = (run.trace or {}).get("idle_share_worst")
    return None if v is None else 100.0 * v
