"""On-chip benchmark of the repository (see ``bench/run.py`` and ``PERF.md``)."""
