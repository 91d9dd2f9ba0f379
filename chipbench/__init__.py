"""On-chip benchmark of the ForkKV server (see ``chipbench/run.py``)."""
