"""The on-chip serving benchmark (see bench/run.py)."""
