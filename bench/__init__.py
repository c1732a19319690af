"""The chip benchmark: cells, traffic, per-layer metric readers, the trace
reduction and the plain reference that decides ``correct``.

Everything that belongs to one model configuration, traffic mix, cell or
per-layer metric is a file of its own under this directory, found by the
name ``BENCHMARK.json`` gives it (:mod:`bench.catalog`). ``bench/run.py`` is
the command.
"""
