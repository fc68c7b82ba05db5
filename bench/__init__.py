"""The on-chip benchmark of the served P²M path (see ``bench/run.py``)."""
