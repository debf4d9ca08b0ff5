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
    compilation serves every seed.  ``update`` donates the parameters and
    the optimizer's state and writes the new ones in their place; the
    gradient has no output to give its memory to, so the caller unbinds
    it."""
    sizes, training = json.loads(sizes_json), json.loads(training_json)
    init = jax.jit(lambda s: ref.init_params(sizes, s))
    treedef = jax.tree.structure(jax.eval_shape(init, jnp.int32(0)))

    @jax.jit
    def value_and_grad(leaves, batch, key_seed, step):
        (loss, stats), grads = jax.value_and_grad(ref.loss_fn, has_aux=True)(
            treedef.unflatten(leaves), batch, sizes, training, key_seed,
            step, low, fault)
        return loss, jax.tree.leaves(grads), stats

    @functools.partial(jax.jit, static_argnames=("first",),
                       donate_argnames=("leaves", "state"))
    def update(leaves, grads, state, lr, first):
        return optim.update(leaves, grads, state, lr, first, training)
    return init, treedef, value_and_grad, update


@functools.partial(jax.jit, static_argnames=("over",))
def leaf_norms(tree, over: float = 1.0):
    """Per leaf, the norm of ``tree / over``: both sides' one reduction.
    The division happens inside it, so ``tree / over`` is never a second
    tree in memory."""
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) / over))), tree)


def stats_start(ref, stats):
    """The value each running statistic started from, as a tree like
    ``stats`` (the last key of a path names the statistic)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: ref.STATS_START[path[-1].key], stats)


def allocator() -> str:
    """The fullest device's allocator, for a log line: bytes in use now
    and their peak so far (a process's peak never falls).  The TPU's
    allocator holds a running program's scratch apart, as reserved."""
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return ", ".join(
        f"{name} {max(int(s.get(key, 0)) for s in stats)} B"
        for name, key in (("in use", "bytes_in_use"),
                          ("peak", "peak_bytes_in_use"),
                          ("reserved peak", "peak_bytes_reserved")))


def first_steps(ref, sizes: dict, training: dict, seed: int, batches: list,
                steps_per_epoch: int, program_seed: int,
                precision: str = "f32", fault: str = "", log=None) -> dict:
    """Each step's loss; per leaf, the norm of the first gradient as the
    optimizer kept it (``optim.kept_gradient`` of its state after one
    step), of the raw first gradient and of the parameters' change after
    the steps, and whether the leaf's direction is well determined
    (``optim.well_determined_leaves``: a matter of ``ngd`` alone); the
    normalisations' running statistics after the first step (``stats``) and
    the value each started from (``stats_start``).  Trees carry the
    program's names.
    ``seed`` makes the weights, ``program_seed`` is the configuration's
    own seed of the step's random draws.
    ``precision="fp8"`` is the control, ``fault="half_batch"`` the planted
    fault.

    Held on the device, outside ``value_and_grad``'s own scratch: at most
    the parameters, the optimizer's state and one step's gradient (16 bytes
    a parameter under ``adamw``, 12 and ``ngd``'s factors otherwise).  The
    reference module bounds the activations of its ``loss_fn`` itself
    (layer by layer under ``jax.checkpoint``, or rows in blocks)."""
    if len(batches) > 10:
        raise ValueError("the reference follows the first steps only: from "
                         "the tenth on ngd's factors are not refreshed on "
                         "every step")
    init, treedef, value_and_grad, update = _programs(
        ref, json.dumps(sizes, sort_keys=True),
        json.dumps(training, sort_keys=True), precision == "fp8", fault)
    own_seed = jnp.asarray(seed32(seed), jnp.int32)
    host = lambda values: [float(v) for v in jax.device_get(values)]  # noqa

    def lap(what):
        if log is not None:
            log(f"[bench] reference: {what}; {allocator()}")
    flat = jax.tree.leaves(init(own_seed))
    state = optim.start(flat, training)
    losses, raw, kept, stats = [], None, None, None
    for step, batch in enumerate(batches):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        loss, grads, stats_now = value_and_grad(
            flat, batch, jnp.asarray(program_seed, jnp.int32),
            jnp.asarray(step, jnp.int32))
        if step == 0:
            raw = host(leaf_norms(grads))
            stats = jax.device_get(stats_now)
        lap(f"step {step + 1}: loss and gradient")
        lr = jnp.asarray(optim.learning_rate(training, steps_per_epoch,
                                             step), jnp.float32)
        flat, state = update(flat, grads, state, lr, step == 0)
        # unbound, and the update that still reads it waited for, before
        # the next value_and_grad is given room for its own
        del grads, stats_now
        jax.block_until_ready(flat)
        losses.append(float(loss))
        if step == 0:
            kept = host(leaf_norms(*optim.kept_gradient(state, training)))
        lap(f"step {step + 1}: update in place")
    # the change from the starting weights, which are made again (same
    # program, same seed: the same bits) now that the state is gone: 12
    # bytes a parameter with the differences, under what ``update`` held
    del state
    change = host(leaf_norms([a - b for a, b in zip(
        flat, jax.tree.leaves(init(own_seed)))]))
    lap(f"{len(batches)} steps followed, the change read")
    well = optim.well_determined_leaves([p.shape for p in flat], training)
    return {"loss": losses, "grad_norm": treedef.unflatten(kept),
            "raw_grad_norm": treedef.unflatten(raw),
            "change_norm": treedef.unflatten(change),
            "well_determined": treedef.unflatten(well), "stats": stats,
            "stats_start": stats_start(ref, stats)}
