"""Host time of decide.prep spans less their child spans, per decision (ms)."""
from bench.program_trace import prep_ms as read  # noqa: F401
