"""Fixtures of the benchmark's own tests."""

from __future__ import annotations

import pytest
from _tiny import make_tiny_root


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)
