"""The chip benchmark: harness, traffic, reference and yardstick (see
``bench/run.py`` and ``PERF.md``)."""
