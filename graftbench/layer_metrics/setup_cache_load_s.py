"""Seconds of set-up spent retrieving executables from JAX's persistent
cache (``jax/cache_load_s``), as it stood when the window's first ``epoch``
span opened. JAX takes ``backend_compile_duration`` round
``compile_or_get_cached``, so ``setup_compile_s`` HOLDS these seconds: this
says how much of it is loading."""

from graftbench import host_phases


def read(run):
    return host_phases.at_first_epoch(run.spans, "jax_cache_load_s")
