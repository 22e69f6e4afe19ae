"""The large-graph story on the chip's (sorted) aggregation arm
(tests/test_largegraph.py has the story, the other arm and the why of a file
an arm)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tests.test_largegraph import graph_axis_equivalence  # noqa: E402


@pytest.mark.mpi_skip
@pytest.mark.parametrize("agg_arm", ["sorted"])
def pytest_largegraph_graph_axis_equivalence(tmp_path, monkeypatch, agg_arm):
    graph_axis_equivalence(tmp_path, monkeypatch, agg_arm)
