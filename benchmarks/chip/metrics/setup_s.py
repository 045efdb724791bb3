"""Process start to window start, seconds, compilation included."""
from benchmarks.chip.readers import setup_s as read  # noqa: F401
