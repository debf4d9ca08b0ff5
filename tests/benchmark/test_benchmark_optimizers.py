"""The optimizer by name, on both sides.  The reference's ``adamw`` and
``sgd`` written out (``benchmark/reference/optim.py``) against optax's on a
small tree for three steps; the schedule's ``constant``; the runner's table
of "the first gradient as the optimizer kept it" on hand-made optimizer
states; an optimizer in neither table fails with the table's name; and the
language-model fixture's control and planted fault come out NOT correct
under its limits."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from conftest import ROOT, load

from benchmark import correct
from benchmark.reference import optim, steps
from benchmark.runners import train
from benchmark.traffic.generate import generate

ADAMW = {"optimizer": "adamw", "lr": 3e-3, "schedule": "constant",
         "weight_decay": 0.05, "clip_norm": 1.0,
         "adamw": {"b1": 0.9, "b2": 0.999, "eps": 1e-8}}
SGD = {"optimizer": "sgd", "lr": 0.1, "schedule": "constant",
       "weight_decay": 1e-3, "clip_norm": 1.0, "momentum": 0.9}


def small_tree(seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return [jax.random.normal(k[0], (7, 5)), jax.random.normal(k[1], (5,)),
            jax.random.normal(k[2], (3, 2, 4))]


def gradients(step):
    # the first is clipped (norm far over 1), the later ones are not
    scale = [40.0, 0.02, 0.01][step]
    return [scale * g for g in small_tree(100 + step)]


def follow(training, tx):
    """(reference's, optax's) parameters after each of three steps."""
    ours, theirs = small_tree(), small_tree()
    state, opt_state = optim.start(ours, training), tx.init(theirs)
    out = []
    for step in range(3):
        lr = optim.learning_rate(training, 10, step)
        ours, state = optim.update(ours, gradients(step), state, lr,
                                   step == 0, training)
        updates, opt_state = tx.update(gradients(step), opt_state, theirs)
        theirs = optax.apply_updates(theirs, updates)
        out.append((ours, theirs, state, opt_state))
    return out


@pytest.mark.parametrize("name", ["adamw", "sgd"])
def test_reference_optimizer_follows_optax_for_three_steps(name):
    training = {"adamw": ADAMW, "sgd": SGD}[name]
    inner = (optax.adamw(training["lr"], b1=0.9, b2=0.999, eps=1e-8,
                         weight_decay=training["weight_decay"])
             if name == "adamw" else
             optax.chain(optax.add_decayed_weights(training["weight_decay"]),
                         optax.trace(decay=0.9),
                         optax.scale_by_learning_rate(training["lr"])))
    tx = optax.chain(optax.clip_by_global_norm(training["clip_norm"]), inner)
    start = small_tree()
    for ours, theirs, _, _ in follow(training, tx):
        for a, b, s in zip(ours, theirs, start):
            assert not np.allclose(a, s)         # it moved
            np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-7)


@pytest.mark.parametrize("name", ["adamw", "sgd"])
def test_kept_gradient_is_the_clipped_first_gradient_on_both_sides(name):
    """After ONE step, from the reference's state and, through the
    runner's table, from optax's: the first gradient as clipped (adamw) or
    clipped and decayed (sgd), leaf by leaf."""
    training = {"adamw": ADAMW, "sgd": SGD}[name]
    inner = (optax.adamw(training["lr"],
                         weight_decay=training["weight_decay"])
             if name == "adamw" else
             optax.chain(optax.add_decayed_weights(training["weight_decay"]),
                         optax.trace(decay=0.9),
                         optax.scale_by_learning_rate(training["lr"])))
    tx = optax.chain(optax.clip_by_global_norm(training["clip_norm"]), inner)
    _, _, state, opt_state = follow(training, tx)[0]
    raw = gradients(0)
    norm = np.sqrt(sum(float(jnp.sum(g * g)) for g in raw))
    want = [g / norm * training["clip_norm"] for g in raw]
    if name == "sgd":
        want = [g + training["weight_decay"] * p
                for g, p in zip(want, small_tree())]
    for held, over in (optim.kept_gradient(state, training),
                       train.kept_gradient(opt_state, training)):
        for a, b in zip(held, want):
            np.testing.assert_allclose(a / over, b, rtol=1e-5, atol=1e-7)
        # and as the runner reads it: norms, divided inside the reduction
        np.testing.assert_allclose(
            steps.leaf_norms(held, over),
            [np.linalg.norm(np.ravel(b)) for b in want], rtol=1e-5)


def test_kept_gradient_from_a_hand_made_adam_state():
    mu = {"model": {"w": jnp.asarray([0.1, -0.2]), "b": jnp.asarray([0.3])}}
    state = (optax.EmptyState(),
             (optax.ScaleByAdamState(count=jnp.asarray(1), mu=mu, nu=mu),
              optax.EmptyState()))
    held, over = train.kept_gradient(state, {"optimizer": "adamw",
                                             "adamw": {"b1": 0.75}})
    assert held is mu and over == 0.25       # the state's own tree, no copy
    got = steps.leaf_norms(held, over)
    np.testing.assert_allclose(got["model"]["w"], 0.8 * 5 ** 0.5 / 2,
                               rtol=1e-6)
    np.testing.assert_allclose(got["model"]["b"], 1.2, rtol=1e-6)
    trace = (optax.TraceState(trace=mu), optax.EmptyState())
    for name in ("sgd", "ngd"):
        held, over = train.kept_gradient(trace, {"optimizer": name})
        assert held is mu and over == 1.0
    with pytest.raises(ValueError, match="one Adam state"):
        train.kept_gradient(trace, {"optimizer": "adamw",
                                    "adamw": {"b1": 0.9}})
    with pytest.raises(ValueError, match="one momentum trace"):
        train.kept_gradient(state, {"optimizer": "sgd"})


@pytest.mark.parametrize("table,call", [
    ("KEPT_GRADIENT", lambda t: train.kept_gradient((), t)),
    ("OPTIMIZERS", lambda t: optim.start([], t)),
])
def test_an_optimizer_in_no_table_fails_with_the_tables_name(table, call):
    with pytest.raises(ValueError, match=table):
        call({"optimizer": "madgrad"})


def test_schedules_and_determined_leaves_outside_ngd():
    assert optim.learning_rate(ADAMW, 48, 0) == 3e-3
    assert optim.learning_rate(ADAMW, 48, 5000) == 3e-3
    shapes = [(2048, 10), (256,), (1, 1, 64, 256)]
    assert optim.well_determined_leaves(shapes, ADAMW) == [True] * 3
    assert optim.well_determined_leaves(shapes, SGD) == [True] * 3
    ngd = {"optimizer": "ngd", "ngd": {"max_dim": 8192}}
    assert optim.well_determined_leaves(shapes, ngd) == [False] * 3


# -- the language-model fixture: its limits can fail ---------------------------

FIXTURE = os.path.join(ROOT, "tests", "benchmark", "fixtures", "lm_toy")


@pytest.fixture(scope="module")
def toy_readings():
    spec = importlib.util.spec_from_file_location(
        "encoder_lm_toy_reference",
        os.path.join(FIXTURE, "encoder_lm_toy_reference.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    config = load("tests", "benchmark", "fixtures", "lm_toy",
                  "encoder_lm_toy.json")
    traffic = load("tests", "benchmark", "fixtures", "lm_toy",
                   "lm_toy_tokens.json")
    data = generate(traffic["data"], 5)
    batches = [data.encode_batch(np.arange(i, i + 8), 16)
               for i in (0, 8, 16)]
    sizes = dict({k: v for k, v in config.items() if isinstance(v, int)},
                 batch_size=8, seq_len=16)
    args = (ref, sizes, config["training"], 5, batches, 8, 0)
    return traffic["limits"], {
        "sound": steps.first_steps(*args),
        "control": steps.first_steps(*args, precision="fp8"),
        "half_batch": steps.first_steps(*args, fault="half_batch")}


@pytest.mark.parametrize("side", ["sound", "control", "half_batch"])
def test_language_model_fixtures_limits_can_fail(side, toy_readings):
    limits, readings = toy_readings
    compared = correct.compare(readings[side], readings["sound"], limits)
    assert "stats_gap" not in compared           # no running statistics
    assert all(c["limit"] is not None for c in compared.values())
    assert correct.verdict(compared) is (side == "sound"), compared
