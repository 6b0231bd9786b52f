"""Chip benchmark of the pilot's distributed join and sort (see run.py)."""
