"""The chip benchmark of this repository: see ``run.py`` and ``PERF.md``."""
