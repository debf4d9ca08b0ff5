"""Seconds of set-up in ``program_compile`` spans: XLA's compile, the
persistent cache's load or the executable cache's deserialize of each
program the set-up acquires (``telemetry/programs.py``;
``_setup_spans.py`` says where they are read)."""

from benchmark.metrics._setup_spans import total_s


def read(facts):
    return total_s(facts, ("program_compile",))
