"""Chip benchmark of the edge-cloud serving system (see ``run.py``)."""
