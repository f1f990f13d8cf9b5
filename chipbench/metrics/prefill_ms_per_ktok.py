"""prefill_ms_per_ktok: Device time a chip spends in the packed prefill
and chunk step programs in the traced slice, per 1,000 prompt tokens they
processed there (``engine.prefill_tokens``)."""
from harness.readers import prefill_ms_per_ktok as read  # noqa: F401
