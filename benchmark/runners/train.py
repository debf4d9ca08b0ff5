"""The training runner: the simple case of ``cli.run_training`` (one data
mesh, host loaders, no pipeline, no resilience) assembled from the
program's own pieces, with a time limit round ``Trainer.run_epoch``.

``Session`` builds ONE Trainer with ONE state; set-up drives it through its
first steps (the readings ``correct`` is decided from, then the warm-up) and
the window drives the same object.  Every step of every phase goes through
``Trainer.run_epoch`` over one ``Feed``, which chains the program's
``train_loader(0)``, ``train_loader(1)``, ... and stops yielding at a count
or a deadline.  No cell, configuration or metric name appears here.

What the runner reads of a configuration's file: ``entry`` (the entry
script whose DEFAULTS and parser make the program's config), ``argv``,
``reference``, ``flops``, the sizes (``benchmark/configuration.py::sizes``:
the group ``sizes``, or the file's own top level where it is a catalog
model's ``config``), ``training`` (the reference's hyper-parameters;
``training.optimizer`` also names the row of ``KEPT_GRADIENT`` below),
``jax_config``, and for the traced run ``kernels`` and ``scopes`` (a
group's name -> patterns searched in the operations' op_names, first match
in the file's order; ``benchmark/trace_reduce.py``).  A cut configuration
also holds ``published``, ``deployment`` and ``assumed``
(``configuration.check_cut``); the runner reads none of the three.  Text or
image, and the sample the state is built from, follow the traffic's
``data``, not the model's name.
"""

from __future__ import annotations

import importlib
import json
import math
import os
import shutil
import time
from typing import Callable, Optional

import numpy as np


def _one_state(opt_state, kind, what: str):
    """The one state of ``kind`` inside the program's optimizer state."""
    import jax
    found = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda s: isinstance(s, kind))
        if isinstance(s, kind)]
    if len(found) != 1:
        raise ValueError(f"expected one {what} in the optimizer state, "
                         f"found {len(found)}")
    return found[0]


def _momentum_trace(opt_state, training: dict):
    import optax
    return _one_state(opt_state, optax.TraceState,
                      "momentum trace").trace, 1.0


def _adam_first_moment(opt_state, training: dict):
    import optax
    mu = _one_state(opt_state, optax.ScaleByAdamState, "Adam state").mu
    return mu, 1.0 - float(training["adamw"]["b1"])


# training.optimizer -> where the program's optimizer state after ONE step
# keeps the first gradient, as (tree, over): the momentum trace over 1 (it
# started from zero), or Adam's first moment over 1 - b1 (likewise).  The
# tree is the state's own; ``reference/steps.py::leaf_norms`` divides inside
# its reduction, so the gradient as kept is never a second tree in memory
KEPT_GRADIENT = {"sgd": _momentum_trace, "ngd": _momentum_trace,
                 "adamw": _adam_first_moment}


def kept_gradient(opt_state, training: dict) -> tuple:
    name = training["optimizer"]
    if name not in KEPT_GRADIENT:
        raise ValueError(f"training.optimizer {name!r} is not in "
                         f"benchmark/runners/train.py's KEPT_GRADIENT "
                         f"({sorted(KEPT_GRADIENT)})")
    return KEPT_GRADIENT[name](opt_state, training)


class Feed:
    """One iterator over the program's epochs.  ``arm`` sets where the
    next ``run_epoch`` call ends: after ``limit`` batches, or at the first
    ``next()`` past ``deadline``.  ``keep`` > 0 keeps that many of the next
    host batches (the reference follows them)."""

    def __init__(self, train_loader: Callable, annotate=None):
        self._loader_fn = train_loader
        self._annotate = annotate
        self.epoch = 0
        self.restarts = 0
        self._loader = None
        self._it = None
        self.kept = []
        self._keep = 0
        self.arm()

    def arm(self, limit: Optional[int] = None,
            deadline: Optional[float] = None, keep: int = 0) -> None:
        self._limit, self._deadline = limit, deadline
        self._keep = keep
        self.count = 0
        self.t_first = None
        self.t_stop = None

    def __iter__(self):
        return self

    def __next__(self):
        now = time.monotonic()
        if self.t_first is None:
            self.t_first = now
        if ((self._limit is not None and self.count >= self._limit)
                or (self._deadline is not None and now >= self._deadline)):
            self.t_stop = now
            raise StopIteration
        if self._annotate is not None:
            with self._annotate("bench/next"):
                batch = self._next_batch()
        else:
            batch = self._next_batch()
        self.count += 1
        if self._keep > 0:
            self._keep -= 1
            self.kept.append({k: np.array(v) for k, v in batch.items()})
        return batch

    def _next_batch(self):
        while True:
            if self._it is None:
                self._loader = self._loader_fn(self.epoch)
                self._it = iter(self._loader)
            try:
                return next(self._it)
            except StopIteration:
                self._close_loader()
                self.epoch += 1
                self.restarts += 1

    def _close_loader(self):
        closer = getattr(self._loader, "close", None)
        if closer is not None:
            closer()
        self._loader = self._it = None

    def close(self):
        # run_epoch calls this on an abnormal exit; the runner at the end
        if self._loader is not None:
            self._close_loader()


def parse_cfg(config: dict, traffic: dict, seed: int, out_dir: str):
    """argv -> TrainConfig through the entry script's own DEFAULTS and the
    CLI's own parser, as ``cli.main`` does."""
    from faster_distributed_training_tpu.config import (build_parser,
                                                        config_from_args)
    entry = importlib.import_module(config["entry"])
    argv = (list(config["argv"]) + list(traffic["argv"])
            + ["--checkpoint_dir", os.path.join(out_dir, "ckpt"),
               "--telemetry_dir", os.path.join(out_dir, "telemetry")])
    parser = build_parser(prog=config["entry"], defaults=entry.DEFAULTS)
    cfg = config_from_args(parser.parse_args(argv), defaults=entry.DEFAULTS)
    return cfg, argv


class Session:
    def __init__(self, config: dict, traffic: dict, seed: int, out_dir: str,
                 reference, log: Callable[[str], None] = print,
                 annotate=None):
        import jax

        from faster_distributed_training_tpu import cli
        from faster_distributed_training_tpu.parallel import make_mesh
        from faster_distributed_training_tpu.parallel.placement import (
            dp_size, make_put_batch)
        from faster_distributed_training_tpu.telemetry import (
            build_telemetry, programs, spans)
        from faster_distributed_training_tpu.train import Trainer

        self.log = log
        self._lap_t = time.monotonic()
        os.makedirs(out_dir, exist_ok=True)
        for key, value in config.get("jax_config", {}).items():
            jax.config.update(key, value)
            log(f"[bench] jax.config {key} = {value!r} (the configuration's)")
        self.cfg, self.argv = parse_cfg(config, traffic, seed, out_dir)
        cfg = self.cfg
        log(f"[bench] argv: {' '.join(self.argv)}")
        log(cli.setup_platform(cfg))
        self.lap("imports, argv, platform")
        self.mesh = make_mesh(cfg.mesh_axes, cfg.mesh_shape)
        self.dp = dp_size(self.mesh)

        self._config, self._traffic = config, traffic
        self._reference, self._annotate = reference, annotate
        self._make_state = None
        self.feed = self.trainer = None
        self._seed_data_and_state(seed)

        self.telemetry = build_telemetry(cfg, log=log)
        self._prev = (spans.set_recorder(self.telemetry.recorder),
                      programs.set_observatory(self.telemetry.observatory))
        self._mesh_ctx = self.mesh
        self._mesh_ctx.__enter__()
        put_train = make_put_batch(self.mesh)
        self.trainer = Trainer(cfg, put_batch=put_train,
                               put_eval_batch=put_train, log=log,
                               telemetry=self.telemetry)
        self.lap("telemetry, Trainer")

    def _seed_data_and_state(self, seed: int) -> None:
        """Data, loaders, feed and a fresh state from ``seed``; everything
        that does not follow the seed (mesh, model, Trainer, the compiled
        step) is kept.  ``calibrate.py`` calls this once a seed."""
        import jax
        import jax.numpy as jnp

        from faster_distributed_training_tpu import cli
        from faster_distributed_training_tpu.optim import build_optimizer
        from faster_distributed_training_tpu.parallel.placement import (
            shard_train_state)
        from faster_distributed_training_tpu.train import create_train_state

        from benchmark import configuration
        from benchmark.traffic.generate import generate, seed32

        config, traffic = self._config, self._traffic
        reference = self._reference
        cfg = self.cfg
        if self.feed is not None:
            self.feed.close()
        self.state = None       # the seed before's, freed before the next
        train_ds = generate(traffic["data"], seed)
        eval_ds = generate(dict(traffic["data"], rows=cfg.batch_size),
                           seed + 1)
        # rows of token ids or images: what the program's own loaders ask
        # of a data set (data/loader.py), not the model's name
        is_text = hasattr(train_ds, "encode_batch")
        self.lap(f"data from seed {seed}")
        train_loader, _, self.steps_per_epoch = cli.make_loaders(
            cfg, train_ds, eval_ds, dp=self.dp)
        if self._make_state is None:
            vocab = train_ds.vocab_size() if is_text else None
            self.model = cli.build_model(cfg, vocab_size=vocab,
                                         mesh=self.mesh)
            tx, _ = build_optimizer(cfg, self.steps_per_epoch,
                                    lr_scale=float(self.dp))
            sample = (jnp.zeros((cfg.batch_size, cfg.seq_len), jnp.int32)
                      if is_text else
                      jnp.zeros((cfg.batch_size,
                                 *traffic["data"]["shape"]), jnp.float32))
            sizes = dict(configuration.sizes(config),
                         batch_size=cfg.batch_size, seq_len=cfg.seq_len)

            # one jitted call: the program's own constructor for the
            # state's structure (optimizer state, statistics, rng root),
            # and the benchmark's own weights from the seed put in its
            # place — the reference makes the same weights itself and
            # takes nothing from the program
            def make_state(key, own_seed):
                st = create_train_state(self.model, tx, sample, key,
                                        init_kwargs={"train": True})
                own = {"model": reference.init_params(sizes, own_seed)}
                same = (jax.tree.structure(own)
                        == jax.tree.structure(st.params))
                shapes = jax.tree.map(lambda a, b: a.shape == b.shape
                                      and a.dtype == b.dtype, own,
                                      st.params)
                if not (same and all(jax.tree.leaves(shapes))):
                    raise ValueError(
                        "the reference's parameter tree is not the "
                        f"program's: {jax.tree.structure(own)} vs "
                        f"{jax.tree.structure(st.params)}")
                return st.replace(params=own)
            lowered = jax.jit(make_state).lower(
                jax.random.PRNGKey(cfg.seed), jnp.asarray(0, jnp.int32))
            self.lap("state: traced and lowered")
            self._make_state = lowered.compile()
            self.lap("state: compiled or loaded")
        state = self._make_state(
            jax.random.PRNGKey(cfg.seed),
            jnp.asarray(seed32(seed), jnp.int32))
        self.state = shard_train_state(state, self.mesh, cfg)
        del state               # its placed twin is the one state there is
        jax.block_until_ready(self.state.params)
        self.lap("state: made on the device in one call, placed")
        self.feed = Feed(train_loader, annotate=self._annotate)
        self.steps_done = 0
        if self.trainer is not None:
            self.trainer.global_step = 0

    def lap(self, what: str) -> None:
        from benchmark.reference.steps import allocator
        now = time.monotonic()
        self.log(f"[bench] set-up: {what} {now - self._lap_t:.2f} s; "
                 f"{allocator()}")
        self._lap_t = now

    # -- driving ----------------------------------------------------------

    def run(self, limit=None, deadline=None, keep=0):
        """One ``Trainer.run_epoch`` call; returns (steps, seconds from the
        first ``next()`` to run_epoch's own fence, its summary)."""
        self.feed.arm(limit=limit, deadline=deadline, keep=keep)
        self.state, summary, _ = self.trainer.run_epoch(
            self.state, self.feed, epoch=self.feed.epoch)
        t_end = time.monotonic()
        n = self.feed.count
        self.steps_done += n
        return n, t_end - self.feed.t_first, summary

    def first_steps(self, n_checked: int, warmup: int) -> dict:
        """The readings ``correct`` is decided from, through the window's
        own call and feed: the loss of each of the first steps; after the
        first, the normalisations' running statistics and the norm of every
        leaf of the gradient as the optimizer kept it (``KEPT_GRADIENT``);
        and the norm of every leaf's change after ``n_checked`` steps.  Then
        the rest of the warm-up.

        On the device, set-up holds the state the window holds and no
        tree more: the starting weights are kept on the HOST (one read
        before the first step, one after the last checked one, the
        difference taken there), and the gradient as kept is read as norms
        from the optimizer's own state."""
        import jax

        from benchmark import correct
        from benchmark.reference.steps import leaf_norms

        training = self._config["training"]
        start = jax.device_get(self.state.params["model"])
        self.lap("starting weights read to the host")
        losses, grad, stats = [], None, None
        for i in range(n_checked):
            _, _, summary = self.run(limit=1, keep=1)
            losses.append(float(summary["loss"]))
            self.lap(f"step {i + 1} through run_epoch"
                     + (" (compiles or loads the step program)" if i == 0
                        else ""))
            if i == 0:
                kept, over = kept_gradient(self.state.opt_state, training)
                grad, stats = jax.device_get(
                    (leaf_norms(kept["model"], over),
                     self.state.batch_stats))
        change = jax.tree.unflatten(
            jax.tree.structure(start), correct.diff_norms(
                jax.device_get(self.state.params["model"]), start))
        del start
        self.lap("weights read again, change taken on the host")
        if warmup > n_checked:
            self.run(limit=warmup - n_checked)
            self.lap(f"warm-up to {warmup} steps")
        return {"loss": losses, "stats": stats, "grad_norm": grad,
                "change_norm": change, "batches": self.feed.kept}

    def window(self, seconds: float) -> dict:
        t0 = time.monotonic()
        steps, elapsed, summary = self.run(deadline=t0 + seconds)
        overshoot = elapsed - (self.feed.t_stop - self.feed.t_first)
        return {"steps": steps, "seconds": elapsed, "summary": summary,
                "t_first": self.feed.t_first, "overshoot_s": overshoot,
                "first_step": self.steps_done - steps,
                "loader_restarts": self.feed.restarts}

    # -- what the metrics read ---------------------------------------------

    def step_records(self, first_step: int, steps: int) -> list:
        """The telemetry step records of the ``steps`` steps after
        ``first_step`` (global step numbers), read back from the recorder's
        JSONL."""
        rec = self.telemetry.recorder
        rec.flush(wait=True)
        out = []
        with open(rec.path) as f:
            for line in f:
                r = json.loads(line)
                if (r.get("kind") == "step"
                        and first_step < r["step"] <= first_step + steps):
                    out.append(r)
        return out

    def programs(self) -> dict:
        """{program name: [one record a lowering]} from the observatory."""
        return {k: list(v)
                for k, v in self.telemetry.observatory.programs.items()}

    def close(self) -> None:
        """Free the program's state (the reference runs after this) and
        give the process-global sinks back."""
        from faster_distributed_training_tpu.telemetry import programs, spans
        self.feed.close()
        self._mesh_ctx.__exit__(None, None, None)
        self.telemetry.close()
        spans.set_recorder(self._prev[0])
        programs.set_observatory(self._prev[1])
        self.state = None
        self.trainer = None


def run(cell, config, traffic, seed, seconds, trace, out_dir, t0, device,
        log) -> dict:
    """One run of a training cell: set-up (state, first steps, warm-up), the
    window, then — with the program's state freed — the plain reference over
    the same first steps and the comparison."""
    import jax

    from benchmark import configuration, correct, flops, trace_reduce
    from benchmark.reference import steps as reference_steps

    reference = importlib.import_module(
        f"benchmark.configs.{config['reference']}")
    annotate = jax.profiler.TraceAnnotation if trace else None
    s = Session(config, traffic, seed, out_dir, reference, log=log,
                annotate=annotate)
    cfg = s.cfg
    program = s.first_steps(int(traffic["checked_steps"]),
                            int(traffic["warmup_steps"]))
    log(f"[bench] first steps: loss {program['loss']}")
    compiled_before = sum(len(v) for v in s.programs().values())

    trace_dir = os.path.join(out_dir, "trace")
    if trace:
        # the step call's own span, from outside the program: with the
        # feed's bench/next it says what the host did in an idle gap
        inner = s.trainer.train_step

        def step_call(state, batch):
            with jax.profiler.TraceAnnotation("bench/step_call"):
                return inner(state, batch)
        s.trainer.train_step = step_call
    setup_s = time.monotonic() - t0
    traced_s = float(traffic["trace_seconds"]) if trace else 0.0
    asked = max(seconds - traced_s, 0.5 * seconds)
    w = s.window(asked)
    if trace:
        # a second run_epoch call under the profiler: starting and stopping
        # it takes seconds and stays outside both stretches; every number
        # that is not read from the trace comes from the untraced stretch
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1     # annotations, not every runtime span
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        traced = s.window(traced_s)
        jax.profiler.stop_trace()
        log(f"[bench] traced stretch: {traced['steps']} steps in "
            f"{traced['seconds']:.3f} s")
    log(f"[bench] memory_stats: {jax.local_devices()[0].memory_stats()}")
    compiled_in_window = (sum(len(v) for v in s.programs().values())
                          - compiled_before)
    chips = int(cell["chips"])
    rate = w["steps"] * cfg.batch_size / w["seconds"] / chips
    log(f"[bench] window: {w['steps']} steps of {cfg.batch_size} in "
        f"{w['seconds']:.3f} s ({rate:.1f} examples/s/chip); the host's loop "
        f"saw the deadline {w['seconds'] - w['overshoot_s'] - asked:.3f} s "
        f"late (it runs ahead of the device up to the read-back every "
        f"{cfg.log_every} steps, so about "
        f"{(w['seconds'] - asked) * w['steps'] / w['seconds']:.0f} steps "
        f"were in flight at the deadline) and the closing fence took "
        f"{w['overshoot_s']:.3f} s; loader restarts so far "
        f"{w['loader_restarts']}, loss {w['summary'].get('loss')}, set-up "
        f"{setup_s:.2f} s")
    records = s.step_records(w["first_step"], w["steps"])
    table = s.programs()
    for name, entries in table.items():
        for e in entries:
            log(f"[bench] program {name}: compile {e['compile_ms']} ms, "
                f"cache {e['cache']}, hlo_ops {e.get('hlo_ops')}")
    peak = _memory_peak(table["train:host:k1"][0], log)
    sizes = dict(configuration.sizes(config), batch_size=cfg.batch_size,
                 seq_len=cfg.seq_len)
    batches = program.pop("batches")
    steps_per_epoch = s.steps_per_epoch
    s.close()
    del s

    t = time.monotonic()
    ref = reference_steps.first_steps(
        reference, sizes, config["training"], seed, batches,
        steps_per_epoch, program_seed=cfg.seed, log=log)
    log(f"[bench] reference over {len(batches)} steps: "
        f"{time.monotonic() - t:.2f} s, loss {ref['loss']}")
    compared = correct.compare(program, ref, traffic["limits"])
    ok = correct.verdict(compared)
    if compiled_in_window:
        log(f"[bench] {compiled_in_window} program(s) compiled INSIDE the "
            f"window: the run does not count")
        ok = False
    failed = 0 if math.isfinite(float(w["summary"].get("loss", math.nan))) \
        else w["steps"]

    facts = {"records": records, "steps": w["steps"],
             "seconds": w["seconds"], "chips": chips,
             "batch_size": cfg.batch_size, "seq_len": cfg.seq_len,
             "sizes": sizes,
             "flops_per_step": flops.resolve(config["flops"])(
                 sizes, cfg.batch_size, cfg.seq_len),
             "peaks": flops.peaks(device["kind"])
             if device["platform"] == "tpu" else None,
             "memory_peak_bytes": peak["total"],
             "live_peak_bytes": peak["live"], "traffic": traffic}
    if trace:
        facts["trace"] = trace_reduce.reduce_dir(
            trace_dir, chips=chips, kernels=config.get("kernels", {}),
            scopes=config.get("scopes"), log=log)
        shutil.rmtree(trace_dir, ignore_errors=True)   # write little
    return {"correct": ok, "attempted": w["steps"], "failed": failed,
            "end_to_end": {"setup_s": setup_s,
                           "examples_per_s_per_chip": rate},
            "facts": facts, "compared": compared}


def _memory_peak(step_program: dict, log) -> dict:
    """Peak bytes on the fullest chip: the peak of live buffers
    (``peak_bytes_in_use``: state, batches) plus the scratch of the step
    program that ran (``temp_size_in_bytes`` of its own
    ``memory_analysis()``, as the compile observatory recorded it).  On
    this TPU runtime the allocator's ``peak_bytes_in_use`` leaves a
    program's scratch out: it is held apart as ``bytes_reserved``, and the
    largest free block is the limit less both (PERF.md section 4 has the
    readings).  The compiler assigns the scratch at compile time and the
    program cannot run without all of it, so the sum is what the step
    holds while it runs; the allocator's own ``peak_bytes_reserved`` is
    printed beside it."""
    import jax
    temp = int(step_program.get("temp_bytes", 0))
    live, reserved = 0, 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        live = max(live, int(stats.get("peak_bytes_in_use", 0)))
        reserved = max(reserved, int(stats.get("peak_bytes_reserved", 0)))
    log(f"[bench] memory: live buffers' peak {live} B, the step program's "
        f"scratch (memory_analysis temp) {temp} B, the allocator's reserved "
        f"peak {reserved} B; arguments "
        f"{step_program.get('argument_bytes')} B, outputs "
        f"{step_program.get('output_bytes')} B, aliased "
        f"{step_program.get('alias_bytes')} B")
    return {"live": live, "total": live + temp}
