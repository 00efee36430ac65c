"""Idle share of the device in the serving cells (see idle_share.py)."""
from bench.metrics.idle_share import read  # noqa: F401
