"""The comparison costs a training cell no memory of its own.  On the
language-model fixture, for ``sgd``, ``ngd`` and ``adamw``:

(a) outside the step program the harness never holds more than the training
    state, one step's gradient and one leaf: the bytes of ``jax.live_arrays``
    sampled at every call of the reference's programs and of the session's
    ``run`` (the loops as they were held a copy of the starting weights and
    a tree of the gradient as kept beside the state);
(b) ``first_steps`` on both sides returns, to the last bit, what an
    undonated, copy-keeping loop written out here returns (two readings to
    float32's rounding: ``rounded``);
(c) the weights the reference makes, and makes again for its change, are
    bit-equal to the ones a session's ``_make_state`` placed;
(d) the reference's ``update`` deleted the parameters and the state it was
    given (donation took), and not the gradient.

And the 0.67B rehearsal's copy (``fixtures/lm_0p67b/rehearse.py``) is new
files and entries only.  CPU, toy sizes: the chip readings at 0.67B are in
PERF.md."""

import functools
import gc
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ROOT, load, module_from

from benchmark.reference import optim, steps
from benchmark.runners import train
from benchmark.traffic.generate import generate, seed32

FIXTURES = os.path.join(ROOT, "tests", "benchmark", "fixtures")
SEED = 2 ** 31 + 2700
PROGRAM_SEED, STEPS_PER_EPOCH = 0, 8
OPTIMIZERS = ["sgd", "ngd", "adamw"]
MOMENTUM = {"momentum": 0.9, "ngd": {"alpha": 4.0, "eta": 0.1,
                                     "update_period": 4, "max_dim": 8192}}
SLACK = 16 * 1024         # scalars: seeds, step counts, losses, rng roots


REF = module_from(os.path.join(FIXTURES, "lm_toy",
                               "encoder_lm_toy_reference.py"))


def toy(name):
    """(configuration, traffic, sizes) of the fixture under optimizer
    ``name``."""
    config = load("tests", "benchmark", "fixtures", "lm_toy",
                  "encoder_lm_toy.json")
    traffic = load("tests", "benchmark", "fixtures", "lm_toy",
                   "lm_toy_tokens.json")
    at = config["argv"].index("--optimizer")
    config["argv"][at + 1] = name
    config["training"] = dict(config["training"], optimizer=name, **MOMENTUM)
    sizes = dict({k: v for k, v in config.items() if isinstance(v, int)},
                 batch_size=8, seq_len=16)
    return config, traffic, sizes


def toy_batches(traffic):
    data = generate(traffic["data"], SEED)
    return [data.encode_batch(np.arange(i, i + 8), 16) for i in (0, 8, 16)]


def live() -> int:
    """Bytes of the live device buffers, each counted once (a leaf whose
    shards were looked at is listed twice: itself and its shard's view).
    By addressable shard: under ``--dist loadfile`` the worker still holds
    the sharded arrays of the files it ran before, and a sharded array has
    no one buffer to point at."""
    gc.collect()
    return sum({s.data.unsafe_buffer_pointer(): s.data.nbytes
                for a in jax.live_arrays() if not a.is_deleted()
                for s in a.addressable_shards}.values())


def nbytes(tree) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(tree) if hasattr(x, "dtype"))


def floats(tree):
    return [float(v) for v in jax.tree.leaves(tree)]


def rounded(numbers):
    """For the two readings whose squares are now added in another order:
    Adam's moment over 1 - b1 (divided inside the reduction's own loop,
    which the CPU backend then vectorises otherwise) and the session's
    change (taken on the host, in float64).  The same operations on the
    same bits; apart by the rounding of a float32 sum over a leaf (some
    1e-6 of it at these sizes), and no more."""
    return pytest.approx(numbers, rel=1e-5)


# -- the reference ------------------------------------------------------------

def programs(sizes, training):
    return steps._programs(REF, json.dumps(sizes, sort_keys=True),
                           json.dumps(training, sort_keys=True), False, "")


@pytest.mark.parametrize("name", OPTIMIZERS)
def test_reference_holds_the_state_one_gradient_and_one_leaf(
        name, monkeypatch):
    config, traffic, sizes = toy(name)
    training, batches = config["training"], toy_batches(traffic)
    samples, real = [], steps._programs

    def wrap(fn, what):
        def call(*a, **kw):
            samples.append((what, live() - base))
            return fn(*a, **kw)
        return call

    def sampled(*args):
        init, treedef, value_and_grad, update = real(*args)
        return (wrap(init, "init"), treedef,
                wrap(value_and_grad, "value_and_grad"),
                wrap(update, "update"))
    monkeypatch.setattr(steps, "_programs", sampled)
    monkeypatch.setattr(steps, "leaf_norms", wrap(steps.leaf_norms, "norms"))
    base = live()
    steps.first_steps(REF, sizes, training, SEED, batches, STEPS_PER_EPOCH,
                      PROGRAM_SEED)
    assert [w for w, _ in samples] == (
        ["init", "value_and_grad", "norms", "update", "norms"]
        + ["value_and_grad", "update"] * 2 + ["init", "norms"])
    params = jax.eval_shape(lambda: jax.tree.leaves(
        REF.init_params(sizes, 0)))
    after_a_step = jax.eval_shape(
        lambda p: optim.update(p, p, optim.start(p, training), 0.1, True,
                               training)[1], params)
    per_parameter = {"sgd": 12, "ngd": 12, "adamw": 16}[name]
    factors = nbytes(after_a_step[0]) if name == "ngd" else 0
    assert (2 * nbytes(params) + nbytes(after_a_step)
            == per_parameter * nbytes(params) // 4 + factors
            + (4 if name == "adamw" else 0))          # Adam's step count
    bound = (2 * nbytes(params) + nbytes(after_a_step)
             + max(nbytes(p) for p in params) + nbytes(batches[0]) + SLACK)
    worst = max(samples, key=lambda s: s[1])
    assert worst[1] <= bound, (worst, bound, samples)
    # the bound is the state's size, not a loose one: one more tree of the
    # parameters' size (the copy of the starting weights) would pass it
    assert worst[1] + nbytes(params) > bound
    assert worst[0] == "update"      # parameters, gradient and state alive


def written_out_reference(sizes, training, batches):
    """The loop as it was: nothing donated, the starting weights kept, the
    gradient as kept and the change made as whole trees."""
    own_seed = jnp.asarray(seed32(SEED), jnp.int32)
    flat = jax.tree.leaves(jax.jit(
        lambda s: REF.init_params(sizes, s))(own_seed))
    treedef = jax.tree.structure(REF.init_params(sizes, 0))

    @jax.jit
    def value_and_grad(leaves, batch, key_seed, step):
        (loss, _), grads = jax.value_and_grad(REF.loss_fn, has_aux=True)(
            treedef.unflatten(leaves), batch, sizes, training, key_seed,
            step, False, "")
        return loss, jax.tree.leaves(grads)

    @functools.partial(jax.jit, static_argnames=("first",))
    def update(leaves, grads, state, lr, first):
        return optim.update(leaves, grads, state, lr, first, training)

    norms = jax.jit(lambda leaves: [jnp.sqrt(jnp.sum(jnp.square(x)))
                                    for x in leaves])
    start, state = flat, optim.start(flat, training)
    losses = []
    for step, batch in enumerate(batches):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        loss, grads = value_and_grad(
            flat, batch, jnp.asarray(PROGRAM_SEED, jnp.int32),
            jnp.asarray(step, jnp.int32))
        lr = jnp.asarray(optim.learning_rate(training, STEPS_PER_EPOCH,
                                             step), jnp.float32)
        flat, state = update(flat, grads, state, lr, step == 0)
        losses.append(float(loss))
        if step == 0:
            raw = floats(norms(grads))
            held, over = optim.kept_gradient(state, training)
            kept = floats(norms([h / over for h in held]))
    change = floats(norms([a - b for a, b in zip(flat, start)]))
    return {"loss": losses, "raw_grad_norm": raw, "grad_norm": kept,
            "change_norm": change}


@pytest.mark.parametrize("name", OPTIMIZERS)
def test_reference_reads_what_the_copy_keeping_loop_reads(name):
    config, traffic, sizes = toy(name)
    batches = toy_batches(traffic)
    got = steps.first_steps(REF, sizes, config["training"], SEED, batches,
                            STEPS_PER_EPOCH, PROGRAM_SEED)
    want = written_out_reference(sizes, config["training"], batches)
    for key, numbers in want.items():
        if key == "grad_norm" and name == "adamw":
            numbers = rounded(numbers)
        assert floats(got[key]) == numbers, key
    assert all(v > 0 for v in want["change_norm"])


@pytest.mark.parametrize("name", OPTIMIZERS)
def test_update_deleted_the_parameters_and_the_state_it_was_given(name):
    config, _, sizes = toy(name)
    training = config["training"]
    init, _, _, update = programs(sizes, training)
    flat = jax.tree.leaves(init(jnp.asarray(7, jnp.int32)))
    state = optim.start(flat, training)
    grads = [jnp.full_like(p, 0.01) for p in flat]
    lr = jnp.asarray(0.1, jnp.float32)
    for first in (True, False):      # ngd's factors exist from the second
        given = flat + jax.tree.leaves(state)
        flat, state = update(flat, grads, state, lr, first)
        assert all(x.is_deleted() for x in given)
        assert not any(g.is_deleted() for g in grads)
    assert not any(x.is_deleted() for x in flat + jax.tree.leaves(state))


# -- the session --------------------------------------------------------------

@pytest.fixture(scope="module", params=OPTIMIZERS)
def session(request, tmp_path_factory):
    """One session an optimizer (one compiled step), reseeded by each test
    as ``calibrate.py`` reseeds it; with the live bytes from before it."""
    config, traffic, sizes = toy(request.param)
    before = jax.config.jax_default_matmul_precision
    base = live()
    s = train.Session(config, traffic, SEED,
                      str(tmp_path_factory.mktemp("session")), REF,
                      log=lambda msg: None)
    yield s, base, sizes
    s.close()
    jax.config.update("jax_default_matmul_precision", before)


def test_session_holds_the_state_and_one_leaf_at_every_run(session):
    s, base, _ = session
    s._seed_data_and_state(SEED)
    samples, real = [], s.run

    def run(**kw):
        samples.append(live() - base)
        out = real(**kw)
        samples.append(live() - base)
        return out
    s.run = run
    try:
        program = s.first_steps(3, 5)
    finally:
        del s.run
    assert len(samples) == 2 * 4         # three checked steps, the warm-up
    leaves = jax.tree.leaves(s.state.params)
    # at most 8 batches: the kept host rows are numpy, the loader's
    # prefetch holds a few on the device
    bound = (nbytes(s.state) + max(nbytes(p) for p in leaves)
             + 8 * nbytes(program["batches"][0]) + SLACK)
    assert max(samples) <= bound, (samples, bound)
    assert max(samples) >= nbytes(s.state)
    # one more tree of the parameters' size would not pass
    assert max(samples) + nbytes(leaves) > bound


def test_session_reads_what_the_copy_keeping_loop_reads(session):
    s, _, _ = session
    training = s._config["training"]
    s._seed_data_and_state(SEED)
    got = s.first_steps(3, 3)

    # the loop as it was, on the same object from the same seed
    s._seed_data_and_state(SEED)
    diff_norms = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b))
    start = jax.tree.map(jnp.copy, s.state.params["model"])
    losses = []
    for i in range(3):
        _, _, summary = s.run(limit=1, keep=1)
        losses.append(float(summary["loss"]))
        if i == 0:
            held, over = train.kept_gradient(s.state.opt_state, training)
            kept = jax.tree.map(lambda m: m / over, held)
            grad = floats(steps.leaf_norms(kept["model"]))
    change = floats(diff_norms(s.state.params["model"], start))
    assert got["loss"] == losses
    adam = training["optimizer"] == "adamw"
    assert floats(got["grad_norm"]) == (rounded(grad) if adam else grad)
    assert floats(got["change_norm"]) == rounded(change)
    assert all(v > 0 for v in change)
    for a, b in zip(got["batches"], s.feed.kept):
        np.testing.assert_array_equal(a["tokens"], b["tokens"])


def test_weights_made_again_are_the_ones_make_state_placed(session):
    s, _, sizes = session
    s._seed_data_and_state(SEED)
    init = programs(sizes, s._config["training"])[0]
    again = init(jnp.asarray(seed32(SEED), jnp.int32))
    placed = s.state.params["model"]
    assert jax.tree.structure(again) == jax.tree.structure(placed)
    for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(placed)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and a second call of the same program gives the same bits again
    for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(
            init(jnp.asarray(seed32(SEED), jnp.int32)))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- the rehearsal's copy -----------------------------------------------------

def test_the_0p67b_rehearsal_is_new_files_and_entries_only(tmp_path):
    rehearse = module_from(os.path.join(FIXTURES, "lm_0p67b", "rehearse.py"))
    dest = str(tmp_path / "copy")
    added = rehearse.make_copy(dest)
    # three, or two in a tree whose benchmark/ holds the shared reference
    assert len(added) == 3 - os.path.exists(os.path.join(
        ROOT, "benchmark", "configs", os.path.basename(rehearse.REFERENCE)))
    for folder, _, files in os.walk(os.path.join(dest, "benchmark")):
        for f in files:
            rel = os.path.relpath(os.path.join(folder, f), dest)
            if rel not in added:             # no file that was there changed
                with open(os.path.join(dest, rel), "rb") as a, open(
                        os.path.join(ROOT, rel), "rb") as b:
                    assert a.read() == b.read(), rel
    from benchmark import run
    bench, cell, config, traffic = run.resolve(
        rehearse.CELL, root=dest, bench_dir=os.path.join(dest, "benchmark"))
    assert bench["workloads"][:-1] == load("BENCHMARK.json")["workloads"]
    assert cell["chips"] == 1 and config["training"]["optimizer"] == "adamw"
    cfg, _ = train.parse_cfg(config, traffic, seed=5, out_dir=str(tmp_path))
    assert (cfg.batch_size, cfg.seq_len) == (8, 512)
    assert cfg.seq_len == traffic["data"]["seq_len"]
    # 0.67B parameters: 16 bytes of each under AdamW are 10.7 GB
    sizes = dict({k: v for k, v in config.items() if isinstance(v, int)},
                 batch_size=cfg.batch_size, seq_len=cfg.seq_len)
    n = nbytes(jax.eval_shape(lambda: REF.init_params(sizes, 0))) // 4
    assert 0.66e9 < n < 0.68e9 and 10.6e9 < 16 * n < 10.9e9
    assert config["flops"].endswith("encoder_lm_toy_reference:train_flops")
    assert REF.train_flops(sizes, 8, 512) > 1.6e13       # 17 TFLOP a step
