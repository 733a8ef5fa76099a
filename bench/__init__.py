"""The chip benchmark: one cell per process (``python3 bench/run.py``)."""
