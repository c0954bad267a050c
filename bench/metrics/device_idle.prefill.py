"""device_idle.prefill: share of the traced window, in %, in which no
operation ran on the chip (1 - busy / window, busy being the union of
the device operations' intervals)."""
from bench.metrics._program import idle_percent as read  # noqa: F401
