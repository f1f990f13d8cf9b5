"""step_mfu_pct.saturated: Model FLOPs of every prompt and output token
processed in the traced slice, over the slice times the chips and one
chip's bf16 peak, in %."""
from harness.readers import slice_mfu_pct as read  # noqa: F401
