"""Mean host time of one stream_advance span (ms per slot with arrivals)."""
from bench.readers import advance_ms as read  # noqa: F401
