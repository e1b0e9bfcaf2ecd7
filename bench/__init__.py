"""Chip benchmark of the served top-10 search (see ``bench/run.py``)."""
