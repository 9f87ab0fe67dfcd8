"""Utilisation over the whole serving window: operations of the circuits
served (bench/workcount.py) over the window's seconds x chips x peak."""
from bench.metrics_common import mfu as read  # noqa: F401
