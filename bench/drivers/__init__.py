"""Drivers of the benchmark, found by name."""
