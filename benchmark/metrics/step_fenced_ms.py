"""The program's own fenced step time: host wall between two read-backs
that drained the device, over the train steps between them (``fence_ms``
and ``fence_steps`` of the telemetry step records that closed such a window,
``train/loop._DispatchClock``), summed over the window's records, in
milliseconds.  No profiler needed.  None where no record carries a fence."""


def read(facts):
    rows = [r for r in facts["records"] if r.get("fence_steps")]
    steps = sum(r["fence_steps"] for r in rows)
    return sum(r["fence_ms"] for r in rows) / steps if steps else None
