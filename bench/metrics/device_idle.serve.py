"""Share of the traced window in which the device ran no operation."""
from bench.metrics_common import device_idle as read  # noqa: F401
