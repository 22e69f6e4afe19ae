"""The ``ising_model`` example's smoke test (tests/test_examples.py has the why of
one file an example)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tests.test_examples import run_example  # noqa: E402


@pytest.mark.parametrize("example", [os.path.join("ising_model", "ising_model")])
@pytest.mark.mpi_skip()
def pytest_examples(example):
    run_example(example)
