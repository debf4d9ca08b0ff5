"""The hand-assembled runner cannot drift from ``cli.run_training``
unnoticed: at a tiny size on the CPU the train program the runner's Trainer
lowers is the program ``cli.main`` lowers for the same argv — equal
observatory fingerprint of ``train:host:k1`` — for every configuration of
``BENCHMARK.json``, with the same data-set size on both sides (the schedule
bakes ``steps_per_epoch`` into the program)."""

import importlib
import json
import os

import pytest

from conftest import BENCH, TINY_ARGV, TINY_SIZES, load, tiny_resnet

ROWS, BATCH = 4096, 16    # the program's --dataset synthetic has 4096


def fingerprint_from_manifest(telemetry_dir):
    with open(os.path.join(telemetry_dir, "manifest.json")) as f:
        programs = json.load(f)["compile"]["programs"]
    (entry,) = [p for p in programs if p["name"] == "train:host:k1"]
    assert entry["lowerings"] == 1
    return entry["variants"][0]["fingerprint"]


CASES = {w["config"]: w["traffic"] for w in BENCH["workloads"]}


@pytest.mark.parametrize("name", sorted(CASES))
def test_runner_lowers_the_program_cli_main_lowers(name, monkeypatch,
                                                   tmp_path):
    from faster_distributed_training_tpu import cli
    from benchmark.runners import train

    config = load("benchmark", "configs", name + ".json")
    traffic = load("benchmark", "traffic", CASES[name] + ".json")
    tiny_resnet(monkeypatch)
    config["argv"] = config["argv"] + TINY_ARGV
    config["sizes"].update(TINY_SIZES)
    traffic["argv"] = ["--bs", str(BATCH), "--mesh", "dp=1"]
    # the same split size on both sides (--subset_stride would change the
    # ResNet's schedule, so the benchmark's side grows to the program's)
    traffic["data"].update(rows=ROWS)

    reference = importlib.import_module(
        f"benchmark.configs.{config['reference']}")
    session = train.Session(config, traffic, seed=11,
                            out_dir=str(tmp_path / "bench"),
                            reference=reference, log=lambda *_: None)
    try:
        session.run(limit=1)
        ours = session.programs()["train:host:k1"]
        assert len(ours) == 1
        argv = [a for a in session.argv]
    finally:
        session.close()

    # the same argv through cli.main, on the program's own synthetic split
    i = argv.index("--telemetry_dir")
    argv[i + 1] = str(tmp_path / "cli_telemetry")
    i = argv.index("--checkpoint_dir")
    argv[i + 1] = str(tmp_path / "cli_ckpt")
    entry = importlib.import_module(config["entry"])
    argv += ["--dataset", "synthetic"]
    # one step is enough: stop the program's epoch loop after the first
    real = cli.make_loaders

    def one_batch_loaders(*a, **kw):
        train_loader, eval_loader, steps = real(*a, **kw)

        def first(epoch):
            it = iter(train_loader(epoch))
            batch = next(it)
            getattr(it, "close", lambda: None)()
            return [batch]
        return first, eval_loader, steps
    monkeypatch.setattr(cli, "make_loaders", one_batch_loaders)
    monkeypatch.setattr(
        "faster_distributed_training_tpu.train.loop.Trainer.fit",
        lambda self, state, train_loader, eval_loader, **kw:
        self.run_epoch(state, train_loader(0))[0])
    out = cli.main(argv, defaults=entry.DEFAULTS, prog=config["entry"])
    theirs = fingerprint_from_manifest(out["telemetry_dir"])
    assert ours[0]["fingerprint"] and ours[0]["fingerprint"] == theirs
