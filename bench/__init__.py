"""The benchmark: see ``bench/run.py``."""
