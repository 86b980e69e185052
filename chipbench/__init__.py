"""Chip benchmark of the serving stack: ``python3 chipbench/run.py --help``."""
