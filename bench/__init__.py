"""The on-chip benchmark of the in situ coupling: ``python3 bench/run.py``."""
