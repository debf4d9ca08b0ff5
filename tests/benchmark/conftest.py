"""Shared by the benchmark's tests: the repository on the path; what the
parametrised tests hold (``HELD``: ``BENCHMARK.json``'s entries and the
``lm_toy`` fixture's, a cut language model with AdamW, so that a second
configuration goes through every one of them on every PR); and each
configuration's CPU rehearsal, a file of its own found by the
configuration's name (``tiny/<config>.py``).  What a test needs of a
configuration comes from that file, ``configuration.sizes(config)`` and the
traffic's ``data``: sizes are overridden HERE, inside the tests — the
benchmark has no option for it — and no test builds a configuration at the
sizes of its own file."""

import importlib
import importlib.util
import json
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "benchmark")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = os.path.join(ROOT, "tests", "benchmark", "tiny")
FIXTURE = os.path.join(ROOT, "tests", "benchmark", "fixtures", "lm_toy")
FIXTURE_CONFIG, FIXTURE_TRAFFIC = "encoder_lm_toy", "lm_toy_tokens"
FIXTURE_CELL = "encoder_lm_toy.bs8_seq16"


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def module_from(path):
    name = os.path.basename(path)[:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def with_fixture(bench):
    """``bench`` and the fixture's two entries, where it has not got them
    (a tree in which the fixture was entered as a real configuration)."""
    config = {"name": FIXTURE_CONFIG,
              "source": load(FIXTURE, FIXTURE_CONFIG + ".json")["source"],
              "file": f"benchmark/configs/{FIXTURE_CONFIG}.json",
              "reduced": ["num_hidden_layers", "vocab_size"],
              "why": "rehearsal: a cut language model with AdamW"}
    cell = {"name": FIXTURE_CELL, "config": FIXTURE_CONFIG,
            "traffic": FIXTURE_TRAFFIC, "chips": 1,
            "why": "rehearsal: 8 packed rows of 16 ids, causal, AdamW"}

    def entered(entries, new):
        held = new["name"] in {e["name"] for e in entries}
        return entries if held else entries + [new]
    return dict(bench, configs=entered(bench["configs"], config),
                workloads=entered(bench["workloads"], cell))


BENCH = load("BENCHMARK.json")
FIRST_CELL = BENCH["workloads"][0]
HELD = with_fixture(BENCH)


def copy_harness(dest):
    """The tree's ``benchmark/`` as it is, under ``dest``."""
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(dest, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "*.gz"))


def enter_fixture(dest):
    """The fixture entered in a copy of the harness the way the next
    configuration will arrive: three files and two entries, no edit."""
    bench = os.path.join(dest, "benchmark")
    for name, folder in ((FIXTURE_CONFIG + ".json", "configs"),
                         (FIXTURE_CONFIG + "_reference.py", "configs"),
                         (FIXTURE_TRAFFIC + ".json", "traffic")):
        shutil.copy(os.path.join(FIXTURE, name), os.path.join(bench, folder))
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(HELD, f)


@pytest.fixture(scope="session")
def tree(tmp_path_factory):
    """The root of such a copy, where ``run.resolve`` finds every cell of
    ``HELD``.  ``benchmark`` is a namespace package, so with the copy LAST
    on the path every module the tree has stays the tree's own and the
    fixture's reference imports as ``benchmark.configs.<name>_reference``,
    as the runner and the configuration's ``flops`` ask for it."""
    dest = str(tmp_path_factory.mktemp("held"))
    copy_harness(dest)
    enter_fixture(dest)
    sys.path.append(dest)
    importlib.invalidate_caches()
    yield dest
    sys.path.remove(dest)


def resolve(tree, cell):
    from benchmark import run
    return run.resolve(cell, root=tree,
                       bench_dir=os.path.join(tree, "benchmark"))


def rehearsal_path(config_name):
    return os.path.join(TINY, config_name + ".py")


def rehearsal(config_name):
    """The configuration's rehearsal module.  A test of a configuration
    that brings none is skipped: one test fails for it by name
    (``test_configuration_brings_its_rehearsal_file``)."""
    path = rehearsal_path(config_name)
    if not os.path.exists(path):
        pytest.skip(f"{config_name} has no rehearsal file "
                    f"{os.path.relpath(path, ROOT)}")
    return module_from(path)


def tiny(config_name, config, traffic):
    """Cut ``config`` and ``traffic`` (the caller's own copies) to the size
    of the configuration's rehearsal file; returns that module."""
    from benchmark import configuration
    r = rehearsal(config_name)
    configuration.sizes(config).update(r.SIZES)
    config["argv"] = config["argv"] + r.ARGV
    argv = list(traffic["argv"])
    for flag, value in r.SHRINK.items():
        if flag not in argv:
            raise KeyError(f"{rehearsal_path(config_name)}: SHRINK names "
                           f"{flag}, which the traffic's argv {argv} has "
                           f"not got")
        argv[argv.index(flag) + 1] = value
    traffic["argv"] = argv
    traffic["data"].update(r.DATA)
    return r


def batch_and_length(config, traffic):
    """``batch_size`` and ``seq_len`` as the runner passes them to the
    reference: the program's own parse of the two argv."""
    from benchmark.runners import train
    cfg, _ = train.parse_cfg(config, traffic, seed=0, out_dir="")
    return cfg.batch_size, cfg.seq_len


def first_batches(data, seed, batch, seq_len):
    """The first three host batches of a traffic's ``data``, by its own
    kind: packed or padded rows through ``encode_batch``, images as they
    are (the runner tells the two apart the same way)."""
    from benchmark.traffic.generate import generate
    made = generate(dict(data, rows=3 * batch), seed)
    starts = range(0, 3 * batch, batch)
    if hasattr(made, "encode_batch"):
        return [made.encode_batch(np.arange(i, i + batch), seq_len)
                for i in starts]
    x, y = made
    return [{"image": x[i:i + batch], "label": y[i:i + batch]}
            for i in starts]


def tiny_cell(tree, monkeypatch, name=FIRST_CELL["name"]):
    """(bench, cell, config, traffic) of a cell of ``HELD`` at its
    configuration's rehearsal size, the program cut with it."""
    bench, entry, config, traffic = resolve(tree, name)
    r = tiny(entry["config"], config, traffic)
    if hasattr(r, "program"):    # where the argv cannot say the sizes
        r.program(monkeypatch)
    batch, _ = batch_and_length(config, traffic)
    traffic["data"]["rows"] = 6 * batch
    traffic["warmup_steps"] = 4
    # a name, and so a directory under benchmark_out/, of this process's own:
    # every run empties its directory first, and the workers run side by side
    cell = dict(entry, name=f"tiny.cell.{os.getpid()}", traffic="tiny",
                why="CPU rehearsal")
    return bench, cell, config, traffic


@pytest.fixture(autouse=True)
def _jax_config_as_it_was():
    """A configuration's ``jax_config`` is process-wide; give it back."""
    import jax
    before = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", before)


@pytest.fixture
def tiny_run(tree, monkeypatch, capsys):
    """Drive ``run.main`` on a tiny cell, the look for a chip skipped;
    returns (exit code, parsed last line, standard error)."""
    from benchmark import run

    def go(cell=FIRST_CELL["name"], seconds=0.5, trace=0, seed=2100000123):
        resolved = tiny_cell(tree, monkeypatch, cell)
        monkeypatch.setattr(run, "resolve", lambda w, **kw: resolved)
        monkeypatch.setattr(run, "check_devices", lambda chips: {
            "platform": "cpu", "kind": "cpu", "count": 1})
        rc = run.main(["--workload", "tiny.cell", "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)])
        captured = capsys.readouterr()
        last = captured.out.strip().splitlines()[-1]
        return rc, json.loads(last), captured.err
    return go
