"""The chip benchmark: one command, cells named in BENCHMARK.json."""
