"""Shared by the benchmark's tests: the repository on the path, and the one
tiny cell (the ResNet cut to two bottlenecks, batch 16, float32) that
rehearses ``run.main`` on the CPU.  Sizes are overridden HERE, inside the
tests — the benchmark has no option for it."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "benchmark")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_ARGV = ["--device", "cpu", "--precision", "fp32", "--log_every", "2"]
TINY_SIZES = dict(stage_sizes=[1, 1], widths=[64, 128], strides=[1, 2])
TINY_BATCH = 16


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


BENCH = load("BENCHMARK.json")
FIRST_CELL = BENCH["workloads"][0]


def tiny_resnet(monkeypatch):
    """The program's ``resnet50`` cut to the two bottlenecks of
    ``TINY_SIZES``."""
    from faster_distributed_training_tpu import models
    from faster_distributed_training_tpu.models import resnet
    monkeypatch.setitem(models._RESNETS, "resnet50",
                        resnet._factory(resnet.BottleNeck, (1, 1)))


def tiny_cell(limits=None, rows=TINY_BATCH * 6):
    """(bench, cell, config, traffic): the benchmark's first cell, its own
    configuration and traffic files, at the tiny size."""
    entry = {c["name"]: c for c in BENCH["configs"]}[FIRST_CELL["config"]]
    config = load(entry["file"])
    traffic = load("benchmark", "traffic", FIRST_CELL["traffic"] + ".json")
    config["sizes"].update(TINY_SIZES)
    config["argv"] = config["argv"] + TINY_ARGV
    traffic["argv"] = ["--bs", str(TINY_BATCH), "--mesh", "dp=1"]
    traffic["data"].update(rows=rows)
    traffic["warmup_steps"] = 4
    if limits is not None:
        traffic["limits"] = limits
    # a name, and so a directory under benchmark_out/, of this process's own:
    # every run empties its directory first, and the workers run side by side
    cell = dict(FIRST_CELL, name=f"tiny.cell.{os.getpid()}", traffic="tiny",
                why="CPU rehearsal")
    return BENCH, cell, config, traffic


@pytest.fixture(autouse=True)
def _jax_config_as_it_was():
    """A configuration's ``jax_config`` is process-wide; give it back."""
    import jax
    before = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", before)


@pytest.fixture
def tiny_run(monkeypatch, capsys):
    """Drive ``run.main`` on the tiny cell, the look for a chip skipped;
    returns (exit code, parsed last line, standard error)."""
    from benchmark import run

    def go(seconds=0.5, trace=0, limits=None, seed=2100000123):
        tiny_resnet(monkeypatch)
        monkeypatch.setattr(run, "resolve",
                            lambda w, **kw: tiny_cell(limits))
        monkeypatch.setattr(run, "check_devices", lambda chips: {
            "platform": "cpu", "kind": "cpu", "count": 1})
        rc = run.main(["--workload", "tiny.cell", "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)])
        captured = capsys.readouterr()
        last = captured.out.strip().splitlines()[-1]
        return rc, json.loads(last), captured.err
    return go
