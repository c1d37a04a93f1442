"""Chip benchmark of the serving and training paths (see ``bench/run.py``)."""
