"""Chip benchmark of the served scheduling path (see ``run.py``)."""
