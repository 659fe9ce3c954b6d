"""Benchmark of the COBRA binary transformer system (see run.py)."""
