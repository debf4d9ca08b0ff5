"""Seconds of set-up in ``program_observe`` spans: the compile
observatory's own work for each program the set-up acquires (module text
and fingerprint, cache listings, memory analysis, the compiled text's op
counts, the executable cache's store), which is what the program's
tracing costs in set-up (``telemetry/programs.py``; ``_setup_spans.py``
says where they are read)."""

from benchmark.metrics._setup_spans import total_s


def read(facts):
    return total_s(facts, ("program_observe",))
