"""idle_share.decode: share of the decode cell's traced window in which no
operation ran on the device (1 - busy union / window)."""
from bench.harness.trace import idle_share as read  # noqa: F401
