"""Workflow makespan, seconds: mean of submit to terminal state."""
from benchmarks.chip.readers import makespan_s as read  # noqa: F401
