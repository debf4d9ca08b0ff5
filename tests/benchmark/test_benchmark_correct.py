"""``correct`` can fail.  Under every cell's limits the control (the plain
reference computed with float8 operands: the nearest precision below the
bfloat16 the configuration states) and the planted fault (half of the batch
left out, the mean over the rest), each put in the program's place, come out
NOT correct, and so does a run of the harness whose timed path is broken
underneath: a step that returns its state unchanged, and a step that leaves
half of the batch out.  Tiny sizes on the CPU; the chip readings at the
cells' own sizes are in PERF.md."""

import functools
import importlib

import numpy as np
import pytest

from conftest import TINY_BATCH, TINY_SIZES, load

from benchmark import correct
from benchmark.reference import steps
from benchmark.traffic.generate import generate

BENCH = load("BENCHMARK.json")
CELLS = {w["name"]: w for w in BENCH["workloads"]}


def cell_limits(cell):
    return load("benchmark", "traffic",
                CELLS[cell]["traffic"] + ".json")["limits"]


def tiny_problem(config_name):
    """(reference module, sizes, training, batches) at a tiny size."""
    config = load("benchmark", "configs", config_name + ".json")
    ref = importlib.import_module(f"benchmark.configs.{config['reference']}")
    sizes = dict(config["sizes"], batch_size=TINY_BATCH, **TINY_SIZES)
    n = TINY_BATCH
    x, y = generate({"kind": "images", "rows": 3 * n, "classes": 10,
                     "shape": [32, 32, 3], "signal": 0.6,
                     "noise_std": 40.0}, 5)
    batches = [{"image": x[i:i + n], "label": y[i:i + n]}
               for i in (0, n, 2 * n)]
    return ref, sizes, config["training"], batches


@functools.lru_cache(maxsize=None)
def readings(config_name):
    ref, sizes, training, batches = tiny_problem(config_name)
    args = (ref, sizes, training, 5, batches, 100, 7)
    return (steps.first_steps(*args),
            steps.first_steps(*args, fault="half_batch"))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_planted_fault_in_the_programs_place_comes_out_not_correct(cell):
    sound, half = readings(CELLS[cell]["config"])
    limits = cell_limits(cell)
    same = correct.compare(sound, sound, limits)
    assert correct.verdict(same)
    assert all(c["value"] == 0 for c in same.values())
    assert same["stats_gap"]["limit"] is not None
    compared = correct.compare(half, sound, limits)
    assert not correct.verdict(compared), compared


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_at_lower_precision_comes_out_not_correct(cell):
    """By the number that is there for it, the forward pass layer by layer:
    the statistics of the control's first step put in the program's place,
    at all four stages with one bottleneck each (the rounding adds up with
    depth: at two stages the control reads 0.026, at the cell's own size
    0.058 on the chip, PERF.md section 2)."""
    import jax
    ref, sizes, training, batches = tiny_problem(CELLS[cell]["config"])
    sizes = dict(sizes, stage_sizes=[1, 1, 1, 1],
                 widths=[64, 128, 256, 512], strides=[1, 2, 2, 2])
    params = ref.init_params(sizes, 5)
    batch = {k: jax.numpy.asarray(v) for k, v in batches[0].items()}
    forward = jax.jit(lambda low: ref.loss_fn(
        params, batch, sizes, training, 7, 0, low, "")[1],
        static_argnums=0)
    sound, control = forward(False), forward(True)
    start = steps.stats_start(ref, sound)
    limit = cell_limits(cell)["stats_gap"]
    assert correct.stats_gap(sound, sound, start) == 0
    value = correct.stats_gap(control, sound, start)
    assert value > limit
    assert not correct.verdict({"stats_gap": {"value": value,
                                              "limit": limit}})


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
def test_run_with_the_timed_path_broken_comes_out_not_correct(
        fault, tiny_run, monkeypatch):
    if fault != "sound":
        monkeypatch.setattr(
            "faster_distributed_training_tpu.train.loop.make_train_step",
            broken_step(fault))
    rc, line, _ = tiny_run(limits=cell_limits(sorted(CELLS)[0]))
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
