"""Peak bytes of live buffers (``peak_bytes_in_use``: state, batches) on
the fullest chip after the window, in GB (1e9).  The step program's scratch
is not in it: ``device.memory_peak_bytes`` of the result line adds that."""


def read(facts):
    peak = facts.get("live_peak_bytes")
    return peak / 1e9 if peak else None
