"""Host time inside launch.fetch spans, each instant counted once, per decision (ms)."""
from bench.program_trace import fetch_ms as read  # noqa: F401
