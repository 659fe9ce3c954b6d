"""Tests of the benchmark (run with pytest bench/tests)."""
