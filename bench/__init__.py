"""Chip benchmark of the what-if replay (see ``bench/run.py``)."""
