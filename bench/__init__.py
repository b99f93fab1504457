"""The on-chip benchmark of the served Layered-LSH path (see run.py)."""
