"""Chip benchmark of the LUT serving path; ``python3 bench/run.py --help``."""
