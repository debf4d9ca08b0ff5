"""The hand-assembled runner cannot drift from ``cli.run_training``
unnoticed: at a tiny size on the CPU the train program the runner's Trainer
lowers is the program ``cli.main`` lowers for the same argv — equal
observatory fingerprint of ``train:host:k1`` — for every held configuration
(``BENCHMARK.json``'s and the language-model fixture's), each cut by its
rehearsal file, over the same data on both sides (the schedule bakes
``steps_per_epoch`` into the program, the vocabulary sets the tables)."""

import importlib
import json
import os

import pytest

from conftest import HELD, tiny_cell

SEED = 11


def fingerprint_from_manifest(telemetry_dir):
    with open(os.path.join(telemetry_dir, "manifest.json")) as f:
        programs = json.load(f)["compile"]["programs"]
    (entry,) = [p for p in programs if p["name"] == "train:host:k1"]
    assert entry["lowerings"] == 1
    return entry["variants"][0]["fingerprint"]


CASES = {w["config"]: w["name"] for w in HELD["workloads"]}


@pytest.mark.parametrize("name", sorted(CASES))
def test_runner_lowers_the_program_cli_main_lowers(name, tree, monkeypatch,
                                                   tmp_path):
    from faster_distributed_training_tpu import cli
    from benchmark.runners import train
    from benchmark.traffic.generate import generate

    _, _, config, traffic = tiny_cell(tree, monkeypatch, CASES[name])

    reference = importlib.import_module(
        f"benchmark.configs.{config['reference']}")
    session = train.Session(config, traffic, seed=SEED,
                            out_dir=str(tmp_path / "bench"),
                            reference=reference, log=lambda *_: None)
    try:
        session.run(limit=1)
        ours = session.programs()["train:host:k1"]
        assert len(ours) == 1
        argv = [a for a in session.argv]
    finally:
        session.close()

    # the same argv through cli.main, over the rows the session was given
    i = argv.index("--telemetry_dir")
    argv[i + 1] = str(tmp_path / "cli_telemetry")
    i = argv.index("--checkpoint_dir")
    argv[i + 1] = str(tmp_path / "cli_ckpt")
    entry = importlib.import_module(config["entry"])

    def the_sessions_rows(cfg, train):
        return (generate(traffic["data"], SEED) if train else
                generate(dict(traffic["data"], rows=cfg.batch_size),
                         SEED + 1))
    monkeypatch.setattr(cli, "load_dataset", the_sessions_rows)
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
