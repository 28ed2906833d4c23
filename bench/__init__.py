"""Chip benchmark of exact betweenness centrality (see ``bench/run.py``)."""
