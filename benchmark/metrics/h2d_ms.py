"""Mean host time a step of the window spent inside ``put_fn``, staging a
later batch onto the device: ``h2d_ms`` of the program's telemetry step
records (``data/loader.DevicePrefetch``'s clock round ``put_fn``; a part of
``data_ms``), in milliseconds.  None where the program writes no such field."""


def read(facts):
    rows = [r["h2d_ms"] for r in facts["records"]
            if not r.get("compile") and "h2d_ms" in r]
    return sum(rows) / len(rows) if rows else None
