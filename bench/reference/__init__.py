"""Plain references of the served models (see binary_lm.py)."""
