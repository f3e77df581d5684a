"""Fixtures of the benchmark harness's tests."""

import pytest

from benchmark_testkit import make_tiny_root


@pytest.fixture
def tiny_root(tmp_path):
    """A temporary copy of the benchmark with tiny configurations, mixes and
    cells added as new files and entries."""
    return make_tiny_root(tmp_path)
