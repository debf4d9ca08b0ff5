"""The harness is driven by data: every entry of BENCHMARK.json, and of the
language-model fixture entered beside them (``conftest.HELD``), resolves to
files by name and brings its CPU rehearsal; names keep to the contract's
characters, and ``run.py`` prints the contract's line at a tiny size — and
nothing without a chip."""

import importlib
import json
import os
import re
import subprocess
import sys

import pytest

from conftest import BENCH, HELD, ROOT, load, rehearsal_path, resolve

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def cells_of(metric):
    return metric.get("workloads", CELLS)


@pytest.mark.parametrize("cell", [w["name"] for w in HELD["workloads"]])
def test_cell_resolves_to_its_files_by_name(cell, tree):
    from benchmark import configuration
    from benchmark.traffic import generate
    bench, entry, config, traffic = resolve(tree, cell)
    assert entry["chips"] in (1, 4) and len(entry["why"]) <= 200
    assert config["name"] == entry["config"]
    assert traffic["name"] == entry["traffic"]
    assert traffic["data"]["kind"] in generate.KINDS
    assert os.path.exists(os.path.join(
        tree, "benchmark", "runners", config["runner"] + ".py"))
    ref = importlib.import_module(f"benchmark.configs.{config['reference']}")
    assert callable(ref.init_params) and callable(ref.loss_fn)
    from benchmark import flops
    assert flops.resolve(config["flops"])(
        dict(configuration.sizes(config)), 8, 16) > 0
    # every number the comparison prints has an entry, held or null
    assert set(traffic["limits"]) == {
        "loss1_gap", "loss2_gap", "loss3_gap", "grad_norm_gap",
        "stats_gap", "change_norm_gap", "change_worst_gap"}


CONFIGS = [c["name"] for c in HELD["configs"]]


@pytest.mark.parametrize("config", CONFIGS)
def test_configuration_is_used_and_its_file_is_under_paths(config, tree):
    entry = {c["name"]: c for c in HELD["configs"]}[config]
    assert any(w["config"] == config for w in HELD["workloads"])
    assert any(entry["file"].startswith(p + "/") for p in HELD["paths"])
    assert load(tree, entry["file"])["source"] == entry["source"]
    # the contract of a cut configuration (benchmark/configuration.py): what
    # ``reduced`` names is a size of the file, which then states the
    # published values, the deployment and what it assumed, cuts no width
    # and keeps the guide's floors; an uncut one lists nothing
    from benchmark import configuration
    assert configuration.cut_faults(entry, load(tree, entry["file"])) == []


@pytest.mark.parametrize("config", CONFIGS)
def test_configuration_brings_its_rehearsal_file(config):
    """The one test that fails for a configuration without one; every
    other test of it is skipped, and none builds it at its own sizes."""
    path = rehearsal_path(config)
    assert os.path.exists(path), (
        f"configuration {config!r} brings no CPU rehearsal: add "
        f"{os.path.relpath(path, ROOT)} "
        f"(ARGV, SIZES, SHRINK, DATA, CONTROL and, where the program's "
        f"argv cannot say the sizes, program(monkeypatch); "
        f"benchmark/README.md, 'Add a cell, a configuration, a metric')")


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_names_and_units_use_only_the_allowed_characters(metric):
    m = {m["name"]: m for m in METRICS}[metric]
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")


@pytest.mark.parametrize("name", [w["name"] for w in HELD["workloads"]]
                         + CONFIGS
                         + [w["traffic"] for w in HELD["workloads"]])
def test_cell_configuration_and_traffic_names_are_names(name):
    assert NAME.match(name)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metric_moves_a_metric_its_cells_report(metric):
    m = {m["name"]: m for m in BENCH["per_layer"]}[metric]
    moved = {e["name"]: e for e in BENCH["end_to_end"]}[m["moves"]]
    assert set(cells_of(m)) <= set(cells_of(moved)) <= set(CELLS)
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    reader = importlib.import_module(f"benchmark.metrics.{m['name']}")
    assert callable(reader.read)
    if "roofline" in m["name"] or "mfu" in m["name"]:
        assert m["unit"] == "%"


def test_benchmark_json_keeps_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    # no cell, configuration or metric name in the harness's own code
    for f in ("run.py", os.path.join("runners", "train.py"),
              "trace_reduce.py", "configuration.py",
              os.path.join("traffic", "generate.py"),
              os.path.join("reference", "optim.py"),
              os.path.join("reference", "steps.py")):
        text = open(os.path.join(ROOT, "benchmark", f)).read()
        for name in CELLS + [m["name"] for m in METRICS
                             if m["name"] not in ("setup_s",
                                                  "examples_per_s_per_chip")]:
            assert name not in text, (f, name)


def test_a_four_chip_cell_is_new_files_and_entries_only(tmp_path):
    """A dp=4 cell (PERF.md's first open question asks for the encoder's;
    the mechanism is shown on the configuration that is here): a traffic
    file with its mesh and an entry with ``chips: 4``, found from a
    directory the harness has never seen; no file that is there changes."""
    from benchmark import run
    from benchmark.runners import train
    first = BENCH["workloads"][0]
    bench = dict(BENCH, workloads=BENCH["workloads"] + [{
        "name": "resnet50_cifar.dp4_bs4096", "config": first["config"],
        "traffic": "cifar_dp4_bs4096", "chips": 4,
        "why": "scaling efficiency"}])
    traffic = dict(load("benchmark", "traffic", first["traffic"] + ".json"),
                   name="cifar_dp4_bs4096",
                   argv=["--bs", "4096", "--mesh", "dp=4"])
    os.makedirs(tmp_path / "bench" / "traffic")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "bench" / "traffic" / "cifar_dp4_bs4096.json").write_text(
        json.dumps(traffic))
    entry = {c["name"]: c for c in BENCH["configs"]}[first["config"]]
    os.makedirs(tmp_path / os.path.dirname(entry["file"]))
    (tmp_path / entry["file"]).write_text(json.dumps(load(entry["file"])))
    _, cell, config, got = run.resolve(
        "resnet50_cifar.dp4_bs4096", root=str(tmp_path),
        bench_dir=str(tmp_path / "bench"))
    assert cell["chips"] == 4
    cfg, _ = train.parse_cfg(config, got, seed=5, out_dir=str(tmp_path))
    assert tuple(cfg.mesh_shape) == (4,) and cfg.batch_size == 4096


TRAFFIC_KINDS = {
    "images": {"kind": "images", "rows": 64, "classes": 10,
               "shape": [32, 32, 3], "signal": 0.6, "noise_std": 40.0},
    "texts": {"kind": "texts", "rows": 64, "classes": 4, "vocab": 2000,
              "length": [4, 15], "buckets": [16, 32]},
    "tokens": {"kind": "tokens", "rows": 64, "seq_len": 32, "vocab": 2000,
               "eod": 2, "doc_len": {"median": 12, "sigma": 1.0},
               "zipf": 1.1},
}


@pytest.mark.parametrize("kind", sorted(TRAFFIC_KINDS))
def test_traffic_follows_the_seed_and_takes_a_large_one(kind):
    """The same seed gives the same rows, another seed other rows, and two
    seeds 2**31 apart are two seeds (a plain modulo made them one)."""
    import numpy as np
    from benchmark.traffic.generate import generate

    def rows(seed):
        data = generate(TRAFFIC_KINDS[kind], seed)
        if kind == "images":
            return np.concatenate([data[0].ravel(), data[1]])
        batch = data.encode_batch(np.arange(8), 32)
        # texts: the smallest bucket; tokens: exactly seq_len, nothing padded
        assert batch["tokens"].shape == (8, 16 if kind == "texts" else 32)
        return np.concatenate([batch["tokens"].ravel(), batch["label"]])
    big = 2 ** 31 + 99
    assert np.array_equal(rows(big), rows(big))
    assert not np.array_equal(rows(big), rows(big + 1))
    assert not np.array_equal(rows(101), rows(big + 2))   # 101 + 2**31 - 2


def test_run_prints_exactly_the_contracts_keys_at_a_tiny_size(tiny_run):
    rc, line, err = tiny_run()          # under the cell's own limits
    assert rc == 0
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"setup_s", "examples_per_s_per_chip"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # each number compared beside its limit: last in the line, and the last
    # lines of standard error
    tail = [r for r in err.strip().splitlines() if r.startswith("compared ")]
    assert len(tail) == len(line["compared"]) == 7
    assert err.strip().splitlines()[-1].startswith("compared ")
    for c in line["compared"].values():
        assert set(c) == {"value", "limit"}


def test_without_a_chip_run_exits_non_zero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, cwd=ROOT, capture_output=True, text=True,
        timeout=120)
    assert out.returncode != 0
    assert "metrics" not in out.stdout
    assert "tpu" in out.stderr and "no" in out.stderr
