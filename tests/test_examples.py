"""Example smoke tests (reference tests/test_examples.py:18-26): run each
example script in a subprocess and require exit 0. The wrapper forces JAX onto
host CPU before the example imports jax (the env pins an external platform that
can only be overridden in-process).

The cases live in one file an example (``tests/test_examples_<name>.py``):
``--dist loadfile`` gives a file to ONE worker and starts the files with the
fewest tests last, so five examples in one file were one worker's 400-700 s
at the end of the run with five workers idle. This module holds what they
share and no test of its own."""

import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_WRAPPER = """
import os
os.environ['XLA_FLAGS'] = os.environ.get('XLA_FLAGS','') + ' --xla_force_host_platform_device_count=8'
import jax
jax.config.update('jax_platforms', 'cpu')
import runpy
runpy.run_path({script!r}, run_name='__main__')
"""
# Under the test's own limit (tests/conftest.py TIME_LIMIT_S), so that a stuck
# example is killed and its output shown before the limit fails the test.
_WAIT_S = 270


def run_example(example):
    if os.sep not in example:
        example = os.path.join(example, example)
    script = os.path.join(_REPO, "examples", example + ".py")
    code = _WRAPPER.format(script=script)
    result = subprocess.run(
        [sys.executable, "-c", code],
        cwd=_REPO,
        capture_output=True,
        text=True,
        timeout=_WAIT_S,
    )
    assert result.returncode == 0, (
        f"{example} failed:\n{result.stdout[-2000:]}\n{result.stderr[-2000:]}"
    )
