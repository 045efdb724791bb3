"""Share of the traced window in which no operation ran on the chip, %."""
from benchmarks.chip.readers import idle_share_pct as read  # noqa: F401
