"""Device self time of the ops under the decide.rows scope (COST-row build) per decision (ms)."""
from bench.program_trace import rows_device_ms as read  # noqa: F401
