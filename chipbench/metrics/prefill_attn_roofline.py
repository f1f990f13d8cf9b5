"""prefill_attn_roofline: Least time of the prefill attention the packed
prefills of the traced slice needed (causal same-prompt pairs), at the
chip's peaks, over the prefill kernel's device time, in %."""
from harness.readers import prefill_attn_roofline as read  # noqa: F401
