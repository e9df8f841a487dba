"""Chip benchmark of the NOMAD trainer and top-k server (``run.py``)."""
