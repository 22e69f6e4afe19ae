"""Guard the driver entry points (__graft_entry__.py): the multichip dryrun —
the artifact gate the driver runs with N virtual CPU devices — must stay green
from a clean process, and entry() must stay jittable."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.mpi_skip
def pytest_dryrun_multichip_clean_process():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = subprocess.run(
        [
            sys.executable, "-c",
            "import __graft_entry__ as g; g.dryrun_multichip(8)",
        ],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=420,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "dryrun_multichip OK" in out.stdout


def pytest_entry_jittable():
    import jax

    import __graft_entry__ as g

    fn, args = g.entry()
    loss, rmses = jax.jit(fn)(*args)
    assert bool(jax.numpy.isfinite(loss))
