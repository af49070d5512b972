"""Chip benchmark of the served NAV verify path (see ``bench/run.py``)."""
