"""batch_occupancy_pct.saturated: Mean live slots per decode step over the
window (the engine's ``active_slot_hist``), over ``max_batch``, in %."""
from harness.readers import batch_occupancy_pct as read  # noqa: F401
