"""The chip benchmark: one command runs one cell (a configuration under a
traffic mix) on the TPU and prints its metrics; see ``run.py``."""
