"""Follow a configuration's first training steps with its plain reference.

A configuration's reference module (``benchmark/configs/<name>_reference.py``)
gives ``init_params(sizes, seed)``, ``loss_fn(params, batch, sizes,
training, seed, step, low, fault)`` -> (loss, running statistics after the
step) and the statistics' ``STATS_START``; this file differentiates the loss and
applies the optimizer of ``benchmark/reference/optim.py``, one step at a
time, and returns what ``benchmark/correct.py`` compares.  Imports nothing
of the program.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from benchmark.reference import optim
from benchmark.traffic.generate import seed32


@functools.lru_cache(maxsize=None)
def _programs(ref, sizes_json: str, training_json: str, low: bool,
              fault: str):
    """The reference's three programs (weights, loss + gradient, update),
    built once for a problem: seed and step are arguments, so one
    compilation serves every seed."""
    sizes, training = json.loads(sizes_json), json.loads(training_json)
    init = jax.jit(lambda s: ref.init_params(sizes, s))
    treedef = jax.tree.structure(jax.eval_shape(init, jnp.int32(0)))

    @jax.jit
    def value_and_grad(leaves, batch, key_seed, step):
        (loss, stats), grads = jax.value_and_grad(ref.loss_fn, has_aux=True)(
            treedef.unflatten(leaves), batch, sizes, training, key_seed,
            step, low, fault)
        return loss, jax.tree.leaves(grads), stats

    @functools.partial(jax.jit, static_argnames=("first",))
    def update(leaves, grads, factors, trace, lr, first):
        return optim.update(leaves, grads, factors, trace, lr, first,
                            training)

    norms = jax.jit(lambda leaves: [jnp.sqrt(jnp.sum(jnp.square(x)))
                                    for x in leaves])
    return init, treedef, value_and_grad, update, norms


def stats_start(ref, stats):
    """The value each running statistic started from, as a tree like
    ``stats`` (the last key of a path names the statistic)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: ref.STATS_START[path[-1].key], stats)


def first_steps(ref, sizes: dict, training: dict, seed: int, batches: list,
                steps_per_epoch: int, program_seed: int,
                precision: str = "f32", fault: str = "") -> dict:
    """Each step's loss; per leaf, the norm of the first gradient as the
    optimizer kept it (its momentum trace after one step), of the raw first
    gradient and of the parameters' change after the steps, and whether the
    leaf's natural-gradient direction is well determined
    (``optim.well_determined``); the normalisations' running statistics
    after the first step (``stats``) and the value each started from
    (``stats_start``).  Trees carry the program's names.
    ``seed`` makes the weights, ``program_seed`` is the configuration's
    own seed of the step's random draws.
    ``precision="fp8"`` is the control, ``fault="half_batch"`` the planted
    fault."""
    if len(batches) > 10:
        raise ValueError("the reference follows the first steps only: from "
                         "the tenth on the factors are not refreshed on "
                         "every step")
    init, treedef, value_and_grad, update, norms = _programs(
        ref, json.dumps(sizes, sort_keys=True),
        json.dumps(training, sort_keys=True), precision == "fp8", fault)
    flat = jax.tree.leaves(init(jnp.asarray(seed32(seed), jnp.int32)))
    host = lambda leaves: [float(v) for v in jax.device_get(norms(leaves))]  # noqa
    start = flat
    factors, trace = {}, [jnp.zeros_like(p) for p in flat]
    losses, raw, kept, stats = [], None, None, None
    for step, batch in enumerate(batches):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        loss, grads, stats_now = value_and_grad(
            flat, batch, jnp.asarray(program_seed, jnp.int32),
            jnp.asarray(step, jnp.int32))
        lr = jnp.asarray(optim.learning_rate(training, steps_per_epoch,
                                             step), jnp.float32)
        flat, factors, trace = update(flat, grads, factors, trace, lr,
                                      step == 0)
        losses.append(float(loss))
        if step == 0:
            raw, kept = host(grads), host(trace)
            stats = jax.device_get(stats_now)
    change = host([a - b for a, b in zip(flat, start)])
    well = [optim.well_determined(p.shape, training["ngd"]) for p in flat]
    return {"loss": losses, "grad_norm": treedef.unflatten(kept),
            "raw_grad_norm": treedef.unflatten(raw),
            "change_norm": treedef.unflatten(change),
            "well_determined": treedef.unflatten(well), "stats": stats,
            "stats_start": stats_start(ref, stats)}
