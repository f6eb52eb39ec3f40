"""The on-chip benchmark of the Kron-Matmul engine (see ``run.py``)."""
