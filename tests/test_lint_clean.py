"""Tier-1 static-health gate: ``python -m hydragnn_tpu.analysis`` over the
package must report a clean graftlint run (zero unsuppressed violations, and
an EMPTY committed baseline — ISSUE 4's satellite requires the baseline stay
empty for host-sync-in-step/cond-in-guard; the shipped state is stronger:
empty entirely, so every surviving suppression is inline with a reason).

ruff + mypy have pinned configs in pyproject.toml; when the tools are
present in the environment they must also pass over the configured scope
(hydragnn_tpu/analysis + hydragnn_tpu/utils). The container this repo grows
in does not ship them, so those halves gate on availability instead of
failing the tier-1 run on a missing binary.

Two cases hold the SUITE to its clock: no test file is a long pole (read with
``ast``, nothing compiled), and a test past its time limit fails by name while
the run goes on (tests/conftest.py ``_time_limit``)."""

import ast
import glob
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_ENV = dict(os.environ, JAX_PLATFORMS="cpu")


@pytest.mark.mpi_skip()
def pytest_graftlint_clean_over_package():
    proc = subprocess.run(
        [sys.executable, "-m", "hydragnn_tpu.analysis", "--json"],
        capture_output=True,
        text=True,
        cwd=_REPO,
        env=_ENV,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["ok"]
    assert doc["new_violations"] == []
    assert doc["violations"] == [], "unsuppressed violations: " + "\n".join(
        doc["violations"]
    )
    assert doc["baseline_entries"] == 0  # fully clean, nothing grandfathered
    # The run actually analyzed the package, not an empty directory.
    assert doc["files"] > 50 and doc["traced_functions"] > 50
    # Surviving suppressions all carry inline justifications (the engine
    # enforces this; the report surfaces each reason for review).
    for line in doc["suppressed"]:
        assert "reason:" not in line  # formatted reasons live in text mode


def pytest_pinned_lint_configs_exist():
    """The ruff/mypy configuration is pinned in pyproject.toml with explicit
    scope and rule selection — config drift is a test failure even where the
    tools themselves are absent."""
    with open(os.path.join(_REPO, "pyproject.toml")) as f:
        text = f.read()
    for needle in (
        "[tool.ruff]",
        "required-version",
        "[tool.ruff.lint]",
        '"I"',  # import sorting
        "[tool.mypy]",
        "hydragnn_tpu/analysis",
        "hydragnn_tpu/utils",
    ):
        assert needle in text, f"pyproject.toml lost pinned lint config: {needle}"


@pytest.mark.mpi_skip()
def pytest_ruff_clean_when_available():
    if shutil.which("ruff") is None:
        pytest.skip("ruff not installed in this environment")
    proc = subprocess.run(
        ["ruff", "check", "hydragnn_tpu/analysis", "hydragnn_tpu/utils"],
        capture_output=True,
        text=True,
        cwd=_REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


@pytest.mark.mpi_skip()
def pytest_mypy_clean_when_available():
    if shutil.which("mypy") is None:
        pytest.skip("mypy not installed in this environment")
    proc = subprocess.run(
        ["mypy", "--config-file", "pyproject.toml"],
        capture_output=True,
        text=True,
        cwd=_REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


# A whole program a call: a training through the high-level API, or a child
# process (and a module's helper that makes such a call, its own or one it
# imports from a sibling).
_WHOLE_PROGRAMS = {"run_training", "unittest_train_model"}
_MOST_A_FILE = 6


def _called(node):
    """Names a function's body calls: ``f(...)`` and ``x.f(...)`` as ``f``,
    anything of ``subprocess`` as ``subprocess``."""
    for call in ast.walk(node):
        if not isinstance(call, ast.Call):
            continue
        f = call.func
        if isinstance(f, ast.Name):
            yield f.id
        elif isinstance(f, ast.Attribute):
            owner = f.value.id if isinstance(f.value, ast.Name) else None
            yield "subprocess" if owner == "subprocess" else f.attr


def _cases(function, tree):
    """How many tests a function is: the product of its literal
    ``parametrize`` lists (a name is looked up among the module's own
    assignments; what cannot be read counts once). 0 if marked ``slow``."""
    literals = {
        t.id: a.value for a in tree.body if isinstance(a, ast.Assign)
        for t in a.targets if isinstance(t, ast.Name)
    }
    count = 1
    for mark in function.decorator_list:
        text = ast.unparse(mark)
        if text.startswith("pytest.mark.slow"):
            return 0
        if text.startswith("pytest.mark.parametrize") and len(mark.args) > 1:
            values = mark.args[1]
            if isinstance(values, ast.Call) and values.args:  # sorted(NAME)
                values = values.args[0]
            if isinstance(values, ast.Name):
                values = literals.get(values.id)
            if isinstance(values, (ast.List, ast.Tuple, ast.Set)):
                count *= len(values.elts)
            elif isinstance(values, ast.Dict):
                count *= len(values.keys)
    return count


def pytest_no_test_file_is_a_long_pole():
    """``--dist loadfile`` gives a file to ONE worker, and the scheduler hands
    files out by their count of tests, largest first: a file of few tests,
    each a whole program, starts last and one worker runs it alone while the
    others idle. At most six such tests a file."""
    trees = {}
    for path in sorted(glob.glob(os.path.join(_REPO, "tests", "*.py"))):
        with open(path) as f:
            trees[os.path.basename(path)[:-3]] = ast.parse(f.read())
    functions = {
        name: [n for n in ast.walk(tree) if isinstance(n, ast.FunctionDef)]
        for name, tree in trees.items()
    }
    # What a module takes from a sibling: ``from tests.<sibling> import name``.
    borrowed = {
        name: {
            (n.module.split(".")[-1], a.name)
            for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)
            and (n.module or "").startswith("tests.") for a in n.names
        }
        for name, tree in trees.items()
    }
    # A module's helper that calls a whole program is one, and so is the
    # sibling's that borrows it.
    heavy = {name: set(_WHOLE_PROGRAMS) | {"subprocess"} for name in trees}
    grew = True
    while grew:
        grew = False
        for name, defs in functions.items():
            found = {a for sibling, a in borrowed[name] if a in heavy.get(sibling, ())}
            found |= {
                d.name for d in defs
                if not d.name.startswith("pytest_") and heavy[name] & set(_called(d))
            }
            if not found <= heavy[name]:
                heavy[name] |= found
                grew = True
    over = {}
    for name, defs in functions.items():
        whole = sum(
            _cases(d, trees[name]) for d in defs
            if d.name.startswith("pytest_") and heavy[name] & set(_called(d))
        )
        if name.startswith("test_") and whole > _MOST_A_FILE:
            over[f"tests/{name}.py"] = whole
    assert not over, (
        f"{over}: more than {_MOST_A_FILE} tests of a file call run_training / "
        "unittest_train_model or start a subprocess. Split this file: "
        "`--dist loadfile` gives a file to one worker, and the files with the "
        "fewest tests start last"
    )


def pytest_a_test_past_its_time_limit_fails_by_name_and_the_next_one_runs(tmp_path):
    """The limit at work, in a pytest of its own so that this run stays
    green: a body that sleeps 60 s under a limit of 1 s set by the marker
    fails within seconds, by its own name, with the stacks dumped; the test
    after it in the same process runs and passes."""
    case = tmp_path / "test_limit_at_work.py"
    case.write_text(
        "import time\n\nimport pytest\n\n\n"
        "@pytest.mark.time_limit(1)\n"
        "def pytest_sleeps_past_its_limit():\n    time.sleep(60)\n\n\n"
        "def pytest_the_one_after_it():\n    pass\n"
    )
    start = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", str(case), "-q", "-p", "tests.conftest",
         "-c", os.path.join(_REPO, "pytest.ini"), "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly"],
        capture_output=True, text=True, cwd=_REPO, env=_ENV, timeout=120,
    )
    took = time.monotonic() - start
    out = proc.stdout + proc.stderr
    assert proc.returncode == 1 and "1 failed, 1 passed" in out, out[-3000:]
    assert "pytest_sleeps_past_its_limit ran into its time limit of 1 s" in out, out[-3000:]
    assert "FAILED" in out and "pytest_sleeps_past_its_limit" in out.split("FAILED")[-1]
    assert "time.sleep(60)" in out or "most recent call first" in out  # the stacks
    assert took < 45, took  # well before the sleep would have ended
