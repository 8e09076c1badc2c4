"""On-chip benchmark harness: traffic, weights, the served run, the trace
reduction, the rooflines and the correctness check.  ``run.py`` beside
this package is the entry point; everything a cell needs is found by name
from ``BENCHMARK.json`` (see ``README.md``)."""
