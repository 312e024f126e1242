"""On-chip benchmark of the graph engine: see ``bench/run.py``."""
