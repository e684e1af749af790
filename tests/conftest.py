"""Test-suite bootstrap: registers the ``slow`` marker so long-running
integration tests (the serving engine end-to-end) can be excluded from
quick CI runs with ``-m "not slow"`` (see tools/ci_check.sh) while still
running under the full tier-1 command.
"""

from __future__ import annotations


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running integration test; excluded by tools/ci_check.sh "
        "quick runs via -m 'not slow'",
    )
