"""Share of the traced window in which the chip was idle while a host
thread was in a data-plane span (decode, encode, CAS put, transfer), %."""
from benchmarks.chip.spans import idle_dataplane_pct as read  # noqa: F401
