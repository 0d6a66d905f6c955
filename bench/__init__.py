"""The benchmark: one command (run.py), driven by the files under configs/,
traffic/ and metrics/."""
