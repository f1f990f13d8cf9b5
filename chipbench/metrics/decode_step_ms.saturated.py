"""decode_step_ms.saturated: Mean device time of one fused decode step in
the traced slice (the saturated cell's, moving its throughput)."""
from harness.readers import decode_step_ms as read  # noqa: F401
