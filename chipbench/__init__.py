"""On-chip benchmark of the CIM serving path; see ``harness.py``."""
