"""idle_pct.saturated: Share of the traced slice in which no operation ran
on the device (the saturated cell's)."""
from harness.readers import idle_pct as read  # noqa: F401
