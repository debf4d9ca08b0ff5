"""``correct`` can fail.  Under every held cell's limits (``BENCHMARK.json``'s
and the language-model fixture's) the control (the plain reference computed
in the nearest precision below the one the configuration states: float8
operands under bfloat16, bfloat16 under float32) and the planted fault (half
of the batch left out, the mean over the rest), each put in the program's
place, come out NOT correct, and so does a run of the harness whose timed
path is broken underneath: a step that returns its state unchanged, and a
step that leaves half of the batch out.  Tiny sizes on the CPU, each
configuration's from its rehearsal file (``tiny/<config>.py``); the chip
readings at the cells' own sizes are in PERF.md."""

import functools
import importlib

import jax
import numpy as np
import pytest

from conftest import HELD, batch_and_length, first_batches, resolve, tiny

from benchmark import configuration, correct
from benchmark.reference import steps

CELLS = sorted(w["name"] for w in HELD["workloads"])
SEED = 5


@functools.lru_cache(maxsize=None)
def tiny_problem(tree, cell, control=False):
    """A cell at its rehearsal's size, or at the size its control is shown
    at: the reference module, ``sizes``, ``training``, the first three
    ``batches``, the cell's ``limits`` and the one the control ``breaks``."""
    _, entry, config, traffic = resolve(tree, cell)
    r = tiny(entry["config"], config, traffic)
    ref = importlib.import_module(f"benchmark.configs.{config['reference']}")
    batch, seq_len = batch_and_length(config, traffic)
    sizes = dict(configuration.sizes(config), batch_size=batch,
                 seq_len=seq_len, **(r.CONTROL["sizes"] if control else {}))
    batches = first_batches(traffic["data"], SEED, batch, seq_len)
    return dict(ref=ref, sizes=sizes, training=config["training"],
                batches=batches, limits=traffic["limits"],
                breaks=r.CONTROL["breaks"])


@functools.lru_cache(maxsize=None)
def readings(tree, cell, control=False, **kw):
    p = tiny_problem(tree, cell, control)
    return steps.first_steps(p["ref"], p["sizes"], p["training"], SEED,
                             p["batches"], 100, 7, **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_planted_fault_in_the_programs_place_comes_out_not_correct(
        cell, tree):
    sound = readings(tree, cell)
    half = readings(tree, cell, fault="half_batch")
    limits = tiny_problem(tree, cell)["limits"]
    same = correct.compare(sound, sound, limits)
    assert correct.verdict(same)
    assert all(c["value"] == 0 for c in same.values())
    if jax.tree.leaves(sound["stats"]):      # running statistics are held
        assert same["stats_gap"]["limit"] is not None
    compared = correct.compare(half, sound, limits)
    assert not correct.verdict(compared), compared


@pytest.mark.parametrize("cell", CELLS)
def test_control_at_lower_precision_comes_out_not_correct(cell, tree):
    """The control, in the program's place at the sizes the rehearsal file
    shows it at, breaks the limit that file names.  ``stats_gap`` is the
    forward pass layer by layer and needs no step: the statistics of the
    control's first forward pass.  Any other number is read from the three
    steps followed at the control's precision."""
    p = tiny_problem(tree, cell, control=True)
    ref, sizes, training = p["ref"], p["sizes"], p["training"]
    limits, name = p["limits"], p["breaks"]
    limit = limits[name]
    assert limit is not None, name
    if name == "stats_gap":
        params = ref.init_params(sizes, SEED)
        batch = {k: jax.numpy.asarray(v) for k, v in p["batches"][0].items()}
        forward = jax.jit(lambda low: ref.loss_fn(
            params, batch, sizes, training, 7, 0, low, "")[1],
            static_argnums=0)
        sound, control = forward(False), forward(True)
        start = steps.stats_start(ref, sound)
        assert correct.stats_gap(sound, sound, start) == 0
        value = correct.stats_gap(control, sound, start)
    else:
        sound = readings(tree, cell, control=True)
        compared = correct.compare(
            readings(tree, cell, control=True, precision="fp8"), sound,
            limits)
        assert correct.compare(sound, sound, limits)[name]["value"] == 0
        value = compared[name]["value"]
    assert value > limit
    assert not correct.verdict({name: {"value": value, "limit": limit}})


def broken_step(kind):
    """The program's own step factory, broken underneath."""
    from faster_distributed_training_tpu.train import steps as program_steps
    real = program_steps.make_train_step

    def make(cfg, *a, **kw):
        step = real(cfg, *a, **kw)

        def unchanged(state, batch):
            new, metrics = step(state, batch)
            return state.replace(step=new.step), metrics

        def half_batch(state, batch):
            half = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
            return step(state, half)
        return {"state_unchanged": unchanged, "half_batch": half_batch}[kind]
    return make


@pytest.mark.parametrize("fault", ["sound", "state_unchanged", "half_batch"])
@pytest.mark.parametrize("cell", CELLS)
def test_run_with_the_timed_path_broken_comes_out_not_correct(
        cell, fault, tiny_run, monkeypatch):
    if fault != "sound":
        monkeypatch.setattr(
            "faster_distributed_training_tpu.train.loop.make_train_step",
            broken_step(fault))
    rc, line, _ = tiny_run(cell)        # under the cell's own limits
    assert rc == 0
    assert line["correct"] is (fault == "sound"), line["compared"]


def test_leaves_with_fewer_rows_than_rank_are_not_well_determined():
    from benchmark.reference import optim
    hp = {"max_dim": 8192}
    # rank = min((dim + 1) // 2, 80); rows = the other elements
    assert optim.well_determined((3, 3, 512, 512), hp)
    assert optim.well_determined((1, 1, 64, 64), hp)       # 64 rows, rank 32
    assert not optim.well_determined((1, 1, 64, 256), hp)  # 64 rows, rank 80
    assert not optim.well_determined((2048, 10), hp)       # 10 rows, rank 80
    assert not optim.well_determined((256,), hp)           # one row
    assert optim.well_determined((9000,), hp)              # over max_dim


def test_a_distance_is_the_norm_of_the_difference_over_the_larger_norm():
    ref = {"a": np.array([3.0, 4.0]), "b": np.array([0.0, 1.0]),
           "c": np.array([6.0, 8.0])}
    prog = {"a": np.array([4.0, 3.0]), "b": np.array([1.0, 0.0]),
            "c": np.array([6.0, 8.0])}
    norms = {"a": 5.0, "b": 1.0, "c": 10.0}
    assert correct.diff_norms(prog, ref) == pytest.approx(
        [2 ** 0.5, 2 ** 0.5, 0.0])
    # equal norms on both sides: a gap of norms sees nothing
    assert correct.worst_leaf_gap(norms, norms)[0] == 0
    # leaf b against the median leaf's norm 5, leaf a against its own
    gap, at = correct.worst_leaf_gap(norms, norms, [False, True, True],
                                     correct.diff_norms(prog, ref))
    assert gap == pytest.approx(2 ** 0.5 / 5) and at == 1


def test_worst_leaf_gap_is_a_gap_of_norms_over_the_larger_norm():
    prog = {"a": 1.0, "b": 0.011, "c": 5.0}
    ref = {"a": 1.1, "b": 0.001, "c": 5.0}
    # median leaf of the reference is 1.1: leaf b's gap 0.01 is measured
    # against it, not against its own 0.001
    gap, at = correct.worst_leaf_gap(prog, ref)
    assert gap == pytest.approx(0.1 / 1.1) and at == 0
    gap, _ = correct.worst_leaf_gap(prog, ref, keep=[False, True, True])
    assert gap == pytest.approx(0.01 / 1.1)


def test_a_number_without_a_limit_is_printed_and_not_compared():
    out = {"x": {"value": 9.0, "limit": None},
           "y": {"value": 0.1, "limit": 0.2}}
    assert correct.verdict(out)
    assert not correct.verdict({"x": {"value": 9.0, "limit": None}})
    assert not correct.verdict(dict(out, z={"value": float("inf"),
                                            "limit": 1.0}))
