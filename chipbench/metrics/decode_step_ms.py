"""decode_step_ms: Mean device time of one fused decode step in the traced
slice."""
from harness.readers import decode_step_ms as read  # noqa: F401
