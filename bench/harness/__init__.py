"""Shared code of the benchmark: loading, weights, timing, trace reduction."""
