"""Test-session setup: force JAX onto a virtual 8-device CPU platform.

Tests run hermetically on the host CPU with 8 virtual devices, so the
distributed (data-parallel mesh) paths are exercised the way the reference CI
exercises DDP with 2 MPI ranks (/root/reference/.github/workflows/CI.yml:47-52).
On a machine with a chip, HYDRAGNN_TPU_TESTS=1 leaves the chip as the default
backend (no suite is gated on it now; the arms are certified on the chip by
chip_smoke.py's kernels stage).

JAX's persistent compilation cache is OFF for the session, and for every
child process a test starts: the entry points place it at a fixed path in the
checkout (hydragnn_tpu/cache/jaxcache.py), which would turn the cold compiles
that tests/test_compile_cache.py times into hits left by an earlier run.

Every test has a time limit of its own (``TIME_LIMIT_S``, or what its
``@pytest.mark.time_limit(seconds)`` says): a test that runs into it FAILS by
name with every thread's stack on stderr and the run goes on, where a test
that ran for ever used to cost the whole run its clock.

Python's bytecode cache is ON for the session and its children, under the
temporary directory and never in a checkout.

``program`` and ``forward`` are how a test runs a whole stack: under ``jit``,
traced where the test's switches stand (``from tests.conftest import ...``).
"""

import faulthandler
import os
import signal
import sys
import tempfile
import threading

# Python's bytecode cache, for this process, the workers and every interpreter
# a test starts: where the environment turns it off (PYTHONDONTWRITEBYTECODE)
# each of them compiles every module of jax, flax and this package from source,
# 8-9 s of CPU an interpreter. Kept outside the checkout, under each source's
# own path, so no tree gains a ``__pycache__``.
os.environ.pop("PYTHONDONTWRITEBYTECODE", None)
os.environ.setdefault(
    "PYTHONPYCACHEPREFIX", os.path.join(tempfile.gettempdir(), "hydragnn_tpu_pycache")
)
sys.dont_write_bytecode = False
sys.pycache_prefix = os.environ["PYTHONPYCACHEPREFIX"]

if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    # (a test file that imports this module's helpers runs it a second time)
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count="
        + os.environ.get("HYDRAGNN_HOST_DEVICES", "8")
    )
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax
import pytest

# HYDRAGNN_TPU_TESTS=1 leaves the real accelerator as the default backend.
if os.environ.get("HYDRAGNN_TPU_TESTS") != "1":
    jax.config.update("jax_platforms", "cpu")


def program(function, **jit_options):
    """``jax.jit`` of ``function`` through a function object of its own: a
    program traced NOW, under whatever the test has set. JAX keys its traces
    by the function OBJECT and by nothing the trace read on its way (an
    environment variable, ``platform_override``, a monkeypatched attribute):
    the same object jitted on both sides of such a switch runs the first
    side's executable twice, and a comparison of the two compares nothing.
    Op by op outside ``jit`` every primitive of every shape compiles alone,
    which is why whole stacks are not run that way here."""
    return jax.jit(lambda *args, **kwargs: function(*args, **kwargs), **jit_options)


def forward(model, variables, batch):
    """The evaluation forward as ONE program, traced at this call."""
    return program(lambda v, b: model.apply(v, b, train=False))(variables, batch)


# One test's seconds, its function-scoped fixtures included. A subprocess a
# test waits for gets a shorter wait, so that the child is killed and its
# output shown before this fires.
TIME_LIMIT_S = 300.0


@pytest.fixture(autouse=True)
def _time_limit(request):
    """Arm ``ITIMER_REAL`` round the test. The handler runs on the main
    thread, between two bytecodes of whatever the test is doing there (an
    xdist worker runs its tests on its main thread; a wait on a lock, a queue
    or a child process is interrupted): it dumps every thread's stack and
    raises pytest's own failure, so the test fails by name and the next one
    runs."""
    marker = request.node.get_closest_marker("time_limit")
    seconds = float(marker.args[0]) if marker else TIME_LIMIT_S
    if threading.current_thread() is not threading.main_thread():
        yield  # signals are the main thread's: nothing to arm here
        return

    def ran_out(signum, frame):
        try:
            faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        except (AttributeError, OSError, ValueError):  # a stderr with no descriptor
            faulthandler.dump_traceback(file=sys.__stderr__, all_threads=True)
        pytest.fail(
            f"{request.node.nodeid} ran into its time limit of {seconds:g} s "
            "(tests/conftest.py TIME_LIMIT_S; @pytest.mark.time_limit(seconds) "
            "for a test that needs more); every thread's stack is on stderr",
            pytrace=False,
        )

    before = signal.signal(signal.SIGALRM, ran_out)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


def pytest_collection_modifyitems(config, items):
    """Skip ``mpi_skip``-marked tests under a multi-process launcher — the
    analog of the reference's ``@pytest.mark.mpi_skip`` under ``mpirun -n 2``
    (.github/workflows/CI.yml:47-52): those tests race on shared ./logs and
    ./serialized_dataset paths when every rank runs them."""
    world = int(
        os.environ.get("HYDRAGNN_WORLD_SIZE")
        or os.environ.get("OMPI_COMM_WORLD_SIZE")
        or os.environ.get("SLURM_NPROCS")
        or jax.process_count()
    )
    if world <= 1:
        return
    skip = pytest.mark.skip(reason="serial-only test under multi-process run")
    for item in items:
        if "mpi_skip" in item.keywords:
            item.add_marker(skip)

    # DIVERGENCE from the reference's mpirun model (where every CPU unit test
    # harmlessly runs twice): JAX's runtime is process-global — once
    # jax.distributed initializes, jax.devices() is the GLOBAL device set, so
    # unit tests that build their own single-process virtual meshes are
    # inherently serial. Under a multi-process launch only the world-agnostic
    # end-to-end suites run (the high-level API auto-shards over the global
    # mesh); distributed unit coverage lives in tests/test_distributed.py and
    # the rendezvous harness in tests/test_multiprocess.py.
    # World-safe = the whole flow rides the high-level API (auto-sharding over
    # the global mesh, rank-0 file writes behind barriers): the convergence
    # matrix AND checkpoint-reload/predict (train → save → fresh model →
    # load_existing_model → evaluate under 2 ranks).
    world_safe = {
        "test_graphs_pna.py",
        "test_graphs_pna_multihead.py",
        "test_graphs_cgcnn.py",
        "test_graphs_sage.py",
        "test_graphs_gin.py",
        "test_graphs_gat.py",
        "test_graphs_mfc.py",
        "test_model_loadpred.py",
        "test_resume_2proc.py",
        "test_predict_2proc.py",
    }
    skip_local = pytest.mark.skip(
        reason="single-process test (local virtual mesh) under multi-process run"
    )
    for item in items:
        if os.path.basename(str(item.fspath)) not in world_safe:
            item.add_marker(skip_local)
