"""Host time inside the decision spans, each instant counted once, per decision (ms)."""
from bench.readers import decide_ms_per_decision as read  # noqa: F401
