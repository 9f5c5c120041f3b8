"""Metrics of the benchmark, found by name."""
