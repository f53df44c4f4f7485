"""Chip benchmark of the async multi-app FL path (see ``bench/run.py``)."""
