"""XLA module executions in the device trace per decision."""
from bench.readers import programs_per_decision as read  # noqa: F401
