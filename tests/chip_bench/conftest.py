"""Fixtures for the benchmark's tests (helpers in ``benchfix.py``)."""
import pytest

from benchfix import make_tiny_root


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)
