"""Inference engine: AOT-compiled, donation-enabled predict programs
with params frozen.

The serving step deliberately does NOT reuse the training step family:

  * **params are frozen** — no optimizer state exists at all
    (:class:`ServingState` carries params + batch_stats and an EMPTY
    opt_state group, so the r15 memory attribution
    (``telemetry.programs.state_bytes_table``) reads serving HBM =
    params (+ quant scales) only; pinned by tests/test_serve.py);
  * **no mutable collections** — the model applies with
    ``train=False`` and immutable ``batch_stats``; under ``--quant``
    the r13 ``QuantDense`` scale state is additionally FROZEN at load
    (``QuantPolicy.frozen_scales`` via ``cli.build_model(serving=
    True)``), so serving N requests is state-free and two identical
    requests return bitwise-identical logits;
  * **the batch is donated, not the state** — the training step donates
    the train state (its carry); a serving step's only dead buffer is
    the REQUEST batch it just consumed, so the predict program donates
    exactly that (``donate_argnums`` on the batch argument) and the
    params buffers are never at risk.  The scheduler always hands the
    engine fresh host (numpy) arrays, so donation can never invalidate
    a buffer a retry still needs;
  * **AOT-compiled per (bucket, batch) cell** — one explicit
    ``lower()``/``compile()`` per bucket length at warmup, routed
    through the r15 program observatory when one is active (program
    name ``serve:predict:L<bucket>``), so serving compiles are
    accounted like every other program and steady-state calls go
    straight to the executable.

Checkpoint loading (:func:`load_serving_state`) routes through the r14
``StorageBackend`` + checkpoint manager walk, so the serving tier
restores from exactly the artifacts training wrote — step-cadence
(sharded or single-file) checkpoints first, the epoch checkpoint as the
fallback — on posix, the fake object store, or GCS alike.
"""

from __future__ import annotations

import os
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

# jax warns once per compiled program when a donated buffer cannot be
# aliased into an output (a logits output never matches the token
# buffer's shape/dtype).  Donation here is about FREEING the consumed
# request batch early, not aliasing — the warning is expected, so the
# engine filters exactly it at compile time.
_DONATION_WARNING = "Some donated buffers were not usable"


class ServingState:
    """The serving-side state bundle: params + batch_stats, NO optimizer
    state.  The ``opt_state`` attribute exists (empty) so the r15
    ``state_bytes_table`` attribution applies unchanged — its
    ``opt_state_bytes_per_chip`` reading 0 for a serving process is the
    pinned memory contract."""

    def __init__(self, params: Any, batch_stats: Any, step: int = 0):
        self.params = params
        self.batch_stats = batch_stats
        self.opt_state: dict = {}
        self.step = int(step)

    def variables(self) -> Dict[str, Any]:
        return {"params": self.params, "batch_stats": self.batch_stats}


def make_predict_fn(apply_fn: Callable) -> Callable:
    """The pure serving step: variables + batch -> logits.  Mirrors
    steps.make_eval_step's forward (deterministic, running stats) but
    returns RAW logits — response shaping (masked-row drop, argmax,
    softmax) is the caller's business, and the bitwise batched-vs-single
    contract is stated on logits."""

    def predict(variables: Dict[str, Any],
                batch: Dict[str, Any]):
        return apply_fn({"params": variables["params"]["model"],
                         "batch_stats": variables["batch_stats"]},
                        batch["tokens"],
                        token_types=batch.get("token_types"),
                        mask=batch.get("mask"), train=False)

    return predict


def pad_batch(requests: Sequence, bucket: int, batch_size: int,
              pad_id: int = 0) -> Tuple[Dict[str, np.ndarray], int]:
    """Assemble a (batch_size, bucket) batch from <= batch_size
    requests; returns (batch, n_real).  Rows past n_real are PAD rows:
    copies of row 0 (a real request — the same any-real-sample padding
    BatchLoader's pad_last uses, so the model only ever sees
    in-distribution rows) whose outputs the scheduler DROPS.  Per-row
    independence of the transformer forward (no cross-example op; quant
    scales are per-tensor constants under frozen_scales) is what makes
    the pad content unobservable in the real rows — pinned bitwise by
    scripts/serve_smoke.py."""
    if not requests:
        raise ValueError("pad_batch needs at least one request")
    if len(requests) > batch_size:
        raise ValueError(f"{len(requests)} requests > batch_size "
                         f"{batch_size}")
    tokens = np.full((batch_size, bucket), pad_id, np.int32)
    mask = np.zeros((batch_size, bucket), np.int32)
    for i, req in enumerate(requests):
        t = np.asarray(req.tokens, np.int32)[:bucket]
        tokens[i, :len(t)] = t
        mask[i, :len(t)] = 1
    n_real = len(requests)
    for i in range(n_real, batch_size):
        tokens[i] = tokens[0]
        mask[i] = mask[0]
    return {"tokens": tokens, "token_types": np.zeros_like(tokens),
            "mask": mask}, n_real


class InferenceEngine:
    """Per-bucket AOT predict programs over one frozen variable bundle.

    ``device``: pin this engine's params (and every call's batch) to one
    chip — the replicated-per-chip layout (SNIPPETS [3]).  ``mesh``: the
    model-sharded fallback — compiles/executes under the mesh context
    with the variables wherever the caller placed them.

    ``donate``: None = donate the batch argument; True/False force.
    Donated or
    not, callers passing device arrays must treat them as CONSUMED.
    """

    def __init__(self, apply_fn: Callable, state: ServingState,
                 batch_size: int, buckets: Sequence[int],
                 donate: Optional[bool] = None, device=None, mesh=None,
                 name: str = "serve",
                 log: Callable[[str], None] = print):
        import jax

        self.batch_size = int(batch_size)
        self.buckets = tuple(sorted({int(b) for b in buckets}))
        self.name = name
        self.device = device
        self.mesh = mesh
        self._log = log
        self.donate = True if donate is None else bool(donate)
        variables = state.variables()
        if device is not None:
            variables = jax.device_put(variables, device)
        self._variables = variables
        self._jit = jax.jit(make_predict_fn(apply_fn),
                            donate_argnums=(1,) if self.donate else ())
        self._compiled: Dict[int, Any] = {}
        self.calls = 0

    # -- compilation -------------------------------------------------------

    def _dummy_batch(self, bucket: int) -> Dict[str, np.ndarray]:
        z = np.zeros((self.batch_size, bucket), np.int32)
        return {"tokens": z, "token_types": z,
                "mask": np.ones_like(z)}

    def compile_bucket(self, bucket: int) -> None:
        """Explicit AOT lower+compile of the (bucket, batch_size) cell,
        observed by the process-global program observatory when one is
        active; any observe failure falls back to a plain
        lower/compile (and any AOT failure to plain jit dispatch)."""
        if bucket in self._compiled:
            return
        from faster_distributed_training_tpu.telemetry import programs
        args = (self._variables, self._dummy_batch(bucket))
        pname = f"{self.name}:predict:L{bucket}"
        compiled = None
        with warnings.catch_warnings():
            warnings.filterwarnings("ignore", message=_DONATION_WARNING)
            with self._mesh_ctx():
                obs = programs.get_observatory()
                if obs is not None:
                    sig = programs.args_signature(args, (1,))
                    compiled = obs.observe_compile(pname, self._jit, args,
                                                   sig=sig)
                if compiled is None:
                    try:
                        compiled = self._jit.lower(*args).compile()
                    except Exception as e:
                        self._log(f"[serve] AOT compile of {pname} failed "
                                  f"({e!r}); plain jit dispatch serves it")
                        compiled = self._jit
        self._compiled[bucket] = compiled

    def warmup(self, buckets: Optional[Sequence[int]] = None) -> float:
        """Compile every (bucket, batch) cell BEFORE the queue opens —
        steady-state serving never pays a compile (and the replica
        heartbeat timeout never has to cover one).  Returns wall
        seconds."""
        t0 = time.monotonic()
        for b in (buckets if buckets is not None else self.buckets):
            self.compile_bucket(int(b))
        return time.monotonic() - t0

    def _mesh_ctx(self):
        import contextlib
        return self.mesh if self.mesh is not None \
            else contextlib.nullcontext()

    # -- the hot path ------------------------------------------------------

    def predict_batch(self, batch: Dict[str, Any]) -> np.ndarray:
        """Logits [batch_size, n_class] for one assembled batch.  The
        batch arrays are CONSUMED when donation is on (the scheduler
        always hands fresh host arrays, so a re-dispatch after a
        replica death re-uploads from the same numpy)."""
        import jax

        tokens = batch["tokens"]
        bs, bucket = tokens.shape
        if bs != self.batch_size:
            raise ValueError(f"batch rows {bs} != engine batch_size "
                             f"{self.batch_size} (the scheduler pads)")
        if bucket not in self._compiled:
            self.compile_bucket(bucket)
        if self.device is not None:
            batch = jax.device_put(batch, self.device)
        with self._mesh_ctx():
            logits = self._compiled[bucket](self._variables, batch)
        self.calls += 1
        return np.asarray(logits)


# -- checkpoint loading ----------------------------------------------------

def load_serving_state(cfg, mesh=None, log: Callable[[str], None] = print,
                       ckpt_name: Optional[str] = None
                       ) -> Tuple[Any, ServingState, dict]:
    """(model, ServingState, meta) from ``cfg.checkpoint_dir`` through
    the configured r14 StorageBackend.

    Walk order = the training side's own restore preference: newest
    VALID step-cadence checkpoint (sharded or single-file, via the
    manager's committed-entry walk) first, the epoch checkpoint
    (``<dir>/<workload>``) as the fallback.  The restored train state's
    opt_state/loss_scale/rng are DROPPED — serving holds params +
    batch_stats only.  The model is built with
    ``cli.build_model(serving=True)``: identical param tree to
    training (checkpoints interchange), quant scale state frozen at the
    restored amax history."""
    import jax
    import jax.numpy as jnp

    from faster_distributed_training_tpu.cli import (build_model,
                                                     load_dataset)
    from faster_distributed_training_tpu.optim import build_optimizer
    from faster_distributed_training_tpu.resilience.manager import (
        AsyncCheckpointManager)
    from faster_distributed_training_tpu.resilience.storage import (
        build_backend)
    from faster_distributed_training_tpu.train import create_train_state
    from faster_distributed_training_tpu.train.checkpoint import (
        has_checkpoint, read_checkpoint_meta, restore_checkpoint)

    if cfg.model != "transformer":
        raise ValueError(f"serving is wired for the transformer text "
                         f"workload; got model={cfg.model!r}")
    ckpt_name = ckpt_name or "transformer"
    ds = load_dataset(cfg, train=False)
    vocab = ds.vocab_size() if hasattr(ds, "vocab_size") else None
    model = build_model(cfg, vocab_size=vocab, mesh=mesh, serving=True)
    # the template the checkpoint restores into: same creation path as
    # training (param tree identity is the interchange contract); the
    # throwaway optimizer state is dropped right after the restore
    tx, _ = build_optimizer(cfg, steps_per_epoch=1)
    sample = jnp.zeros((max(cfg.batch_size, 1), cfg.seq_len), jnp.int32)
    template = create_train_state(model, tx, sample,
                                  jax.random.PRNGKey(cfg.seed),
                                  init_kwargs={"train": True})
    backend = build_backend(getattr(cfg, "storage_backend", "posix"),
                            cfg.checkpoint_dir, log=log)
    # same prefix the training side's build_resilience used — its
    # step-cadence dirs are <dir>/<workload>_step_<N>
    mgr = AsyncCheckpointManager(cfg.checkpoint_dir, prefix=ckpt_name,
                                 backend=backend, log=log)
    try:
        got = mgr.restore_latest(template)
    finally:
        mgr.close()
    meta: dict
    if got is not None:
        restored, meta = got
        log(f"[serve] restored step-cadence checkpoint: step "
            f"{int(meta.get('step', 0))}")
    elif has_checkpoint(cfg.checkpoint_dir, ckpt_name, backend=backend):
        # the orbax ARRAY read is posix by design (the documented
        # single-file exception — non-posix backends force the sharded
        # step-cadence path above), but the meta markers routed through
        # the backend, so read them back the same way instead of
        # restore_checkpoint's posix-default read
        restored, epoch, best = restore_checkpoint(cfg.checkpoint_dir,
                                                   ckpt_name, template)
        bmeta = read_checkpoint_meta(cfg.checkpoint_dir, ckpt_name,
                                     backend=backend)
        meta = {"epoch": int(bmeta.get("epoch", epoch)),
                "best_acc": float(bmeta.get("best_acc", best)),
                "step": int(np.asarray(restored.step))}
        log(f"[serve] restored epoch checkpoint {ckpt_name!r} "
            f"(epoch {meta['epoch']})")
    else:
        raise FileNotFoundError(
            f"no serveable checkpoint under {cfg.checkpoint_dir!r} "
            f"(neither a committed step-cadence checkpoint nor "
            f"{ckpt_name!r})")
    meta = dict(meta)
    meta["vocab"] = vocab
    state = ServingState(params=restored.params,
                         batch_stats=restored.batch_stats,
                         step=int(np.asarray(restored.step)))
    if mesh is not None:
        # model-sharded serving: place params/batch_stats on the same
        # overlay training used (train_state_shardings), so the tp/sp
        # program contracts local shards instead of gathered copies
        from faster_distributed_training_tpu.parallel.placement import (
            train_state_shardings)
        sh = train_state_shardings(restored, mesh, cfg)
        state.params = jax.tree.map(jax.device_put, state.params,
                                    sh.params)
        state.batch_stats = jax.tree.map(jax.device_put,
                                         state.batch_stats,
                                         sh.batch_stats)
    return model, state, meta
