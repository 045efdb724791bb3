"""Time an invocation spends in the engine outside its tool body, ms."""
from benchmarks.chip.readers import outside_body_ms as read  # noqa: F401
