"""Seconds of set-up in ``program_lower`` spans: ``jitted.lower`` of each
program the set-up acquires, the Python trace and the lowering
(``telemetry/programs.py``; ``_setup_spans.py`` says where they are
read)."""

from benchmark.metrics._setup_spans import total_s


def read(facts):
    return total_s(facts, ("program_lower",))
