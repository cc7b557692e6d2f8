"""Chip benchmark of the Clairvoyant sidecar: see ``chipbench/run.py``."""
