"""queue_wait_p50_ms: Median over requests due in the window, before the
profiler stopped, of the wait from the due time to ``Request.t_admit``
(the program's stamp when a slot is assigned); a request never admitted
counts with its whole wait."""
from harness.readers import queue_wait_p50_ms as read  # noqa: F401
