"""kv_live_pct.saturated: Mean over the traced slice's decode steps of the
live KV entries of occupied slots (prompt and output so far), over
``max_batch x kv_len``, in %."""
from harness.readers import kv_live_pct as read  # noqa: F401
