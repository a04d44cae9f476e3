"""launch.dispatch spans (program launches at the decide and commit sites) per decision."""
from bench.program_trace import dispatches as read  # noqa: F401
