"""The chip benchmark: one cell (a model configuration under a traffic
mix) run once per process on a TPU; see ``run.py`` and ``harness.py``."""
