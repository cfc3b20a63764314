"""On-chip benchmark of the served decode path (see ``run.py``)."""
