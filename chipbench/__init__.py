"""On-chip benchmark of the served path; see ``bench.py``."""
