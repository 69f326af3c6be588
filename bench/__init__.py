"""The on-chip benchmark of the predictive-indexing engine.

``bench/run.py`` runs one cell; ``BENCHMARK.json`` at the repository
root names the cells, their configuration and traffic files here, and
the metrics, each read by a file of its own.
"""
