"""Seconds of set-up in the program's eager initializer, from inside: the
running totals of the spans ``setup.init_variables`` and
``setup.create_state``, which close when their results are on the device.
``setup_init_s``'s twin (that one is the benchmark's clock round the two
calls). The totals are live with collection off, so the spans need not have
been collected. None for a program without them."""


def read(run):
    from hydragnn_tpu import telemetry

    totals = telemetry.counters_snapshot("span_s/setup.")
    parts = [
        totals.get("span_s/setup." + name)
        for name in ("init_variables", "create_state")
    ]
    return sum(parts) if None not in parts else None
