"""Chip benchmark of the SSSP serving path: ``python3 bench/run.py --help``."""
