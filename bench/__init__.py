"""On-chip benchmark of the retrieval engine (see ``bench/run.py``)."""
