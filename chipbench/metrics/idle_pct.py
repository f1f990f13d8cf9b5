"""idle_pct: Share of the traced slice in which no operation ran on the
device."""
from harness.readers import idle_pct as read  # noqa: F401
