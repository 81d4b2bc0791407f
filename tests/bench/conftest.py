"""Fixtures of the benchmark's CPU tests (helpers are in benchutil.py)."""
import pytest

from benchutil import make_root


@pytest.fixture()
def tiny_root(tmp_path):
    return make_root(tmp_path)
