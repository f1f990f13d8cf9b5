"""step_mfu_pct: Model FLOPs of the tokens the fused steps of the traced
slice decoded, over those steps' device time and the chip's bf16 peak,
in %."""
from harness.readers import step_mfu_pct as read  # noqa: F401
