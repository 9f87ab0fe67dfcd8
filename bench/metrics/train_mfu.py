"""Whole-step utilisation: operations of the window's bank circuits
(bench/workcount.py) over the window's seconds x chips x the chip's peak."""
from bench.metrics_common import mfu as read  # noqa: F401
