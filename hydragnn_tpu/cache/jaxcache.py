"""Where JAX's own persistent compilation cache lives (docs/COMPILE_CACHE.md).

This is XLA's cache, keyed and read by JAX itself — a different store from
graftcache (store.py / registry.py), which keeps whole executables under keys
this package computes. Every entry point (run_training, run_prediction, the
serve and route CLIs, bench.py, chip_smoke.py) calls :func:`place_jax_cache`
before its first compile, so the rule lives in one place:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; the program sets
  no cache directory in code.
* unset — the cache goes to ONE fixed path inside the checkout. The path is
  part of nothing's key but a directory that moves never hits, so it is not
  derived from a run name, a store directory or a temporary directory.

JAX decides once per process, at its first compile, whether the cache is in
use: a call after that is too late to matter.

The key is JAX's, with one addition. JAX leaves operation metadata out of the
key (``jax_compilation_cache_include_metadata_in_key`` is off, and turning it
on would make every source line number a new program), so an executable
compiled before a named scope changed is, but for its names, what is compiled
after, and the cache serves it WITH THE OLD NAMES: measured on the chip, a
traced run of the program with ``hydragnn.agg.*`` scopes on a cache warmed by
the program without them showed none (PERF.md §6, PR 23). So the version of
the scope vocabulary (``telemetry/scopes.py`` ``VERSION``) goes into the key
through ``cache_key.custom_hook``, JAX's own hook for "any addition to the
cache key": programs compiled under another vocabulary are other entries.
"""

from __future__ import annotations

import os

JAX_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def place_jax_cache() -> str:
    """Put JAX's persistent compilation cache where the rule above says and
    return the directory in effect. Idempotent; touches no backend."""
    _key_scopes_version()
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    if jax.config.jax_compilation_cache_dir != JAX_CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir", JAX_CACHE_DIR)
    return JAX_CACHE_DIR


def _key_scopes_version() -> None:
    """Fold the scope vocabulary's version into every persistent-cache key of
    this process (module docstring). Idempotent; an AttributeError here means
    JAX moved its hook, and the stale-names fault is back until this is."""
    from jax._src import cache_key

    from ..telemetry import scopes

    tag = f"hydragnn-scopes-v{scopes.VERSION}"
    before = cache_key.custom_hook
    if getattr(before, "hydragnn_tag", None) == tag:
        return

    def hook() -> str:
        return before() + tag

    hook.hydragnn_tag = tag
    cache_key.custom_hook = hook
