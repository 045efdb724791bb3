"""Run latency, median, seconds: scheduled send to terminal state."""
from benchmarks.chip.readers import run_p50_s as read  # noqa: F401
