"""Device time of the min-plus sweep kernel per decision (ms)."""
from bench.readers import kernel_ms_per_decision as read  # noqa: F401
