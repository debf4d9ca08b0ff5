"""The next configuration enters as new files and new entries only: a
language model (the program's own encoder at a toy size, ``--task lm
--lm_causal``) with a non-empty ``reduced``, ``tokens`` traffic and
``training.optimizer: adamw``, from files the harness has never seen.  The
test copies the tree's ``benchmark/`` as it is, ADDS the fixture's four
files and two entries of ``BENCHMARK.json``, and runs the copy's
``run.main`` on the CPU in a process of its own: through
``cli.make_loaders`` and ``Trainer.run_epoch``, compared with the fixture's
plain reference, the contract's line printed with ``correct`` true."""

import json
import os
import shutil
import subprocess
import sys

from conftest import (FIXTURE, FIXTURE_CELL as CELL, ROOT, copy_harness,
                      enter_fixture)

FIVE = {"BENCHMARK.json", "run_without_a_chip.py",
        "benchmark/configs/encoder_lm_toy.json",
        "benchmark/configs/encoder_lm_toy_reference.py",
        "benchmark/traffic/lm_toy_tokens.json"}


def files(root):
    return {os.path.join(d, f) for d, _, fs in os.walk(root) for f in fs}


def copied_tree(tmp_path):
    """The harness as committed plus the new files (``conftest.py``'s
    ``enter_fixture``, which every parametrised test holds the fixture
    through); returns the files of the copy before and after them."""
    copy_harness(tmp_path)
    before = files(tmp_path)
    enter_fixture(tmp_path)
    shutil.copy(os.path.join(FIXTURE, "run_without_a_chip.py"), tmp_path)
    return before, files(tmp_path)


def test_a_cut_language_model_with_adamw_is_new_files_and_entries_only(
        tmp_path):
    before, after = copied_tree(tmp_path)
    # four files and BENCHMARK.json (fewer only in a tree whose benchmark/
    # holds the fixture's files already: they are then copied with it)
    assert {os.path.relpath(p, tmp_path) for p in after - before} == {
        f for f in FIVE if f == "BENCHMARK.json"
        or not os.path.exists(os.path.join(ROOT, f))}
    for path in before:                      # no file that was there changed
        with open(path, "rb") as a, open(
                os.path.join(ROOT, os.path.relpath(path, tmp_path)),
                "rb") as b:
            assert a.read() == b.read(), path
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(tmp_path), ROOT]))
    out = subprocess.run(
        [sys.executable, "run_without_a_chip.py", "--workload", CELL,
         "--seed", str(2 ** 31 + 2600), "--seconds", "0.5", "--trace", "0"],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:] + out.stdout[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "examples_per_s_per_chip"}
    # the language model keeps no running statistics: six numbers compared,
    # every one held to a limit of the new traffic file
    assert set(line["compared"]) == {
        "loss1_gap", "loss2_gap", "loss3_gap", "grad_norm_gap",
        "change_norm_gap", "change_worst_gap"}
    assert all(c["limit"] is not None and c["value"] <= c["limit"]
               for c in line["compared"].values())
    assert "--optimizer adamw" in out.stdout and "--task lm" in out.stdout
    # it ran from the copy, and wrote only there
    assert os.path.isdir(tmp_path / "benchmark_out" / CELL)
