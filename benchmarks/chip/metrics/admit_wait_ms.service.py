"""Service admission wait, ms: mean of started_at - submitted_at."""
from benchmarks.chip.readers import admit_wait_ms as read  # noqa: F401
