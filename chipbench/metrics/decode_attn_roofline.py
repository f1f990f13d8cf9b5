"""decode_attn_roofline: Least time of the decode attention the fused steps
of the traced slice needed (each decoding slot's live KV entries), at
the chip's peaks, over the decode kernel's device time, in %."""
from harness.readers import decode_attn_roofline as read  # noqa: F401
