"""Engine loop time (``sf:tick`` spans) per completed invocation, ms."""
from benchmarks.chip.spans import engine_self_ms as read  # noqa: F401
