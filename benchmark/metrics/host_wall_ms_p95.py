"""95th percentile of the host's wall time per loop iteration (``wall_ms`` of
the window's telemetry step records: data wait + enqueue + hooks), in
milliseconds.  The host runs ahead of the device, so this is the tail of the
HOST loop (a stall shows here), not a step time; the count of records is on
an earlier line of the run."""


def read(facts):
    rows = sorted(r["wall_ms"] for r in facts["records"]
                  if not r.get("compile"))
    if len(rows) < 20:
        return None
    return rows[min(len(rows) - 1, int(0.95 * len(rows)))]
