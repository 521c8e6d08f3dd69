"""On-chip benchmark of the STCO design-space sweep (see bench.py)."""
