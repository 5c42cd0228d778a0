"""Chip benchmark harness for the WC-Index serving path.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration, traffic mix and metrics, and the harness reads
``bench/configs/<config>.json``, ``bench/traffic/<mix>.json`` and
``bench/metrics/<metric>.py``. Adding a cell, a mix or a metric is a new
file plus a new ``BENCHMARK.json`` entry; no harness code changes.

The harness imports the program under test (``src/repro``) only to build
and serve the index. Traffic, the graph generators, the reference
distances, the trace reduction and the table of peaks live here, so the
yardstick does not move when the program does.
"""
