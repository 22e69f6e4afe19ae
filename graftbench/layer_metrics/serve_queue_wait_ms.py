"""Milliseconds a request waited between ``submit`` and the flush that took
it, mean over the window's requests: the engine's own clock pair
(``serve/metrics.py`` ``queue_wait``: its sum and count, as they moved over
the window). In a closed loop of as many clients as a flush holds this is
the time the LAST client of a flush takes to ask again, not a backlog. None
where no flush was taken."""


def read(run):
    n = run.facts.get("queue_wait_n")
    return 1e3 * run.facts["queue_wait_s"] / n if n else None
