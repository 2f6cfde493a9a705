"""The repository's benchmark: cells, traffic drivers, references and the
reduction from traces to metrics.  ``python3 bench/run.py --help``."""
