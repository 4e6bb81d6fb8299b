"""The chip benchmark of the SpliDT serving path (see ``run.py``)."""
