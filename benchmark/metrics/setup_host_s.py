"""Seconds of set-up in the program's own set-up spans: ``setup_platform``
(``cli.setup_platform``), ``setup_data`` (``cli.make_loaders``),
``setup_model`` (``cli.build_model``), ``setup_state`` (``run_training``'s
state creation and placement) and ``restore`` (``telemetry/spans.py``;
``_setup_spans.py`` says where they are read)."""

from benchmark.metrics._setup_spans import total_s

NAMES = ("setup_platform", "setup_data", "setup_model", "setup_state",
         "restore")


def read(facts):
    return total_s(facts, NAMES)
