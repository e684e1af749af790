"""The chip benchmark of NITRO-D: see ``bench/harness.py`` and ``PERF.md``."""
