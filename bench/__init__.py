"""On-chip benchmark of the multi-model server: see ``bench/run.py``."""
