"""Harvest lag, ms: mean of an invocation's harvest minus the end of its
worker (``sf:work``)."""
from benchmarks.chip.spans import harvest_lag_ms as read  # noqa: F401
