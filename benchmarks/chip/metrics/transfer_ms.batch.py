"""Data-plane transfer time per completed workflow, ms."""
from benchmarks.chip.readers import transfer_ms as read  # noqa: F401
