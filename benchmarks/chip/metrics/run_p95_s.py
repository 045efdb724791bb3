"""Run latency, 95th percentile, seconds: scheduled send to terminal state."""
from benchmarks.chip.readers import run_p95_s as read  # noqa: F401
