"""Mean host time of one price.commit span (ms per accepted job)."""
from bench.readers import commit_ms as read  # noqa: F401
