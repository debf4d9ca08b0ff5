"""The program's set-up spans, for the ``setup_*_s`` readers.

A span record (``telemetry/spans.py``) carries ``name``, ``dur_ms``,
``t0_s`` (its start on the monotonic clock ``setup_s`` is taken on) and
``parent``.  ``facts`` holds none of them: they are read from the
recorder's own file, ``benchmark_out/<cell>/telemetry/host_00000.jsonl``
(the training runner's ``--telemetry_dir``; the cell is the command line's
``--workload``, as ``run.py`` reads it), up to the window's first step
record (``facts["records"][0]``).  A program from before the spans had a
start writes none with ``t0_s``: then there is nothing to read, and a
reader returns None.  Nothing here raises."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _cell():
    argv = sys.argv[1:]
    for i, arg in enumerate(argv):
        if arg == "--workload" and i + 1 < len(argv):
            return argv[i + 1]
        if arg.startswith("--workload="):
            return arg.split("=", 1)[1]
    return None


def spans(facts):
    """The set-up's span records that carry a start, or None."""
    cell = _cell()
    if cell is None:
        return None
    path = os.path.join(ROOT, "benchmark_out", cell, "telemetry",
                        "host_00000.jsonl")
    records = facts.get("records") or []
    first = records[0]["step"] if records else None
    found = []
    try:
        with open(path) as f:
            for line in f:
                r = json.loads(line)
                if r.get("kind") == "step" and r.get("step") == first:
                    break
                if r.get("kind") == "span" and "t0_s" in r:
                    found.append(r)
    except (OSError, ValueError):
        return None
    return found or None


def total_s(facts, names):
    """Seconds in the set-up spans named ``names``; None where there are
    none (a program that writes no such span)."""
    found = [r["dur_ms"] for r in spans(facts) or ()
             if r.get("name") in names]
    return sum(found) / 1e3 if found else None
