"""Chip benchmark of the Graft serving system (see ``run.py``)."""
