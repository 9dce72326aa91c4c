"""On-chip benchmark of the in-filter serving path (see ``run.py``)."""
