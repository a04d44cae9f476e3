"""Device self time of the ops under the decide.dp scope (min-plus DP) per decision (ms)."""
from bench.program_trace import dp_device_ms as read  # noqa: F401
