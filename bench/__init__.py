"""The chip benchmark: see bench/harness.py and PERF.md."""
