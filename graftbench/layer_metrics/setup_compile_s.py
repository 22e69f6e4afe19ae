"""Seconds of XLA compilation during set-up (``jax.monitoring``'s
backend_compile durations, persistent-cache loads included)."""


def read(run):
    return run.setup["compile_s"]
