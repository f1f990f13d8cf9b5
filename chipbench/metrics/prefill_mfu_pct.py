"""prefill_mfu_pct: Model FLOPs of the prompt tokens processed in the
traced slice over the prefill programs' device time and the chip's bf16
peak, in %."""
from harness.readers import prefill_mfu_pct as read  # noqa: F401
