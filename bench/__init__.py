"""Chip benchmark of the scheduling service: ``python3 -m bench.run``."""
