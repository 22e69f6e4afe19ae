"""The convergence matrix's MFC cases (tests/test_graphs.py has the
training cell, the thresholds and the why of one file a conv family)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tests.test_graphs import unittest_train_model  # noqa: E402


@pytest.mark.parametrize("model_type", ["MFC"])
@pytest.mark.parametrize("ci_input", ["ci.json", "ci_multihead.json"])
def pytest_train_model(model_type, ci_input, overwrite_data=False):
    unittest_train_model(model_type, ci_input, False, overwrite_data)
