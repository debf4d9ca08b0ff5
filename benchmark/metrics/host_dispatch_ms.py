"""Median host time of the step call (the enqueue, not the step):
``dispatch_ms`` of the window's telemetry step records, in milliseconds."""

import statistics


def read(facts):
    rows = [r["dispatch_ms"] for r in facts["records"]
            if not r.get("compile")]
    return statistics.median(rows) if rows else None
