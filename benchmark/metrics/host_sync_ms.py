"""Host time a step of the window spent blocked in a device->host read (the
``--log_every`` read-back): the sum of ``sync_ms`` of the program's
telemetry step records, written only where it is not zero, over the number
of records, in milliseconds.  None where the program writes no ``h2d_ms``
either: a program from before these fields, whose silence is no zero."""


def read(facts):
    rows = [r for r in facts["records"] if not r.get("compile")]
    if not rows or not any("h2d_ms" in r for r in rows):
        return None
    return sum(r.get("sync_ms", 0.0) for r in rows) / len(rows)
