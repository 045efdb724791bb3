"""Engine loop latency, ms: launch minus the latest harvest of the producers."""
from benchmarks.chip.readers import engine_gap_ms as read  # noqa: F401
