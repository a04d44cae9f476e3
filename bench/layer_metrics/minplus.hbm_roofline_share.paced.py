"""Share of the min-plus sweep's time its HBM bytes need at the chip's peak (%)."""
from bench.readers import hbm_roofline_share as read  # noqa: F401
