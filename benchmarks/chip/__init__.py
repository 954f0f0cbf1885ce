"""The chip benchmark of the broker's served selection path (``run.py``)."""
