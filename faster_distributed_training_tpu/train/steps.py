"""Jitted train/eval step factories.

One compiled function per workload replaces the reference's per-batch
Python orchestration (resnet50_test.py:521-566,
transformer_test.py:241-271): mixup, forward, loss, backward, gradient
clipping (inside the optax chain), optimizer update, BN-stat update,
loss-scale bookkeeping and the metric accumulation all trace into a
single XLA program — zero host round-trips per step.

Under a Mesh with the batch sharded on the data axes, XLA inserts the
gradient psums automatically (DDP's bucketed all-reduce,
resnet50_test.py:716, becomes a compiler decision); with params sharded
on an ``fsdp`` axis the same code becomes ZeRO-3
(reduce-scatter + all-gather), matching transformer_test.py:387-392.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from faster_distributed_training_tpu.config import (TrainConfig,
                                                    is_token_model)
from faster_distributed_training_tpu.telemetry import spans
from faster_distributed_training_tpu.train import mixup as mx
from faster_distributed_training_tpu.train.amp import (
    scale_loss, unscale_and_check, update_loss_scale)
from faster_distributed_training_tpu.train.losses import (
    cross_entropy, per_sample_cross_entropy)
from faster_distributed_training_tpu.train.state import TrainState

Metrics = Dict[str, jax.Array]


def resolve_mixup_mode(cfg: TrainConfig) -> str:
    if cfg.mixup_mode:
        return cfg.mixup_mode
    if cfg.meta_learning:
        return "meta"               # --meta_learning (resnet50_test.py:525)
    return "static" if cfg.alpha != 0 else "none"


def _tree_where(pred, a, b):
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def _sentinel_ok(loss, grads, finite) -> jax.Array:
    """The sentinel's fused bad-step verdict: ONE bit over the
    unscaled per-step loss, the global grad norm, and the loss-scale
    overflow check it rides on (amp.unscale_and_check — already True
    outside fp16).  Both operands are global scalars inside the jitted
    program (the loss is psum-reduced by GSPMD, the norm spans the
    whole grad tree), so the bit is identical on every (dp, tp, pp)
    host BY CONSTRUCTION — no host round-trip, no agreement protocol.
    An fp32-overflowing grad norm reports inf -> not finite, which is
    the right verdict for a gradient that large."""
    import optax
    gnorm = optax.global_norm(grads)
    return (jnp.isfinite(loss) & jnp.isfinite(gnorm)
            & jnp.asarray(finite, bool))


def _sentinel_metrics(metrics: Metrics, ok: jax.Array) -> Metrics:
    """Mask a guarded step's contribution out of the epoch sums via
    ``where`` (NOT multiplication: 0 * NaN is NaN, and the whole point
    is that the bad step's loss may be NaN).  ``loss_total`` is
    materialized first so the accumulator's exact-weighted epoch loss
    (loss_total/total) spans only the steps that actually updated;
    gauges (loss_scale) pass through unmasked.  ``bad_steps`` is the
    counted verdict — summed by the scan reduction and the epoch
    accumulator into ``bad_steps_sum``, which the Trainer forwards to
    the ``skipped_steps`` goodput counter with NO extra device sync
    (it rides the one summary fetch per epoch)."""
    out = dict(metrics)
    if "loss_total" not in out:
        out["loss_total"] = out["loss"] * out["total"]
    for kk in ("loss", "loss_total", "correct", "total"):
        out[kk] = jnp.where(ok, out[kk], jnp.zeros_like(out[kk]))
    out["bad_steps"] = 1.0 - ok.astype(jnp.float32)
    return out


def lm_shift_metrics(logits: jax.Array, tokens: jax.Array,
                     tok_mask: Optional[jax.Array] = None,
                     sample_valid: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Shifted next-token objective over ``logits [B, L, V]`` /
    ``tokens [B, L]``: position t predicts token t+1.  Returns
    ``(loss_total, correct, total)`` where ``total`` counts VALID target
    positions — a target is valid when both its context position and the
    target token itself are real (``tok_mask`` row-wise; packed LM rows
    carry all-ones masks so every position counts), optionally crossed
    with the per-SAMPLE ``valid`` mask of a padded final eval batch.
    Per-token fp32 cross-entropy; the epoch summary recovers the exact
    token-weighted loss from loss_total/total (MetricAccumulator), and
    perplexity = exp(loss) rides on top (train/metrics.perplexity)."""
    lg = logits[:, :-1].astype(jnp.float32)
    tgt = tokens[:, 1:]
    if tok_mask is not None:
        valid = (tok_mask[:, :-1] * tok_mask[:, 1:]).astype(jnp.float32)
    else:
        valid = jnp.ones(tgt.shape, jnp.float32)
    if sample_valid is not None:
        valid = valid * sample_valid.astype(jnp.float32)[:, None]
    import optax
    losses = optax.softmax_cross_entropy_with_integer_labels(lg, tgt)
    loss_total = jnp.sum(losses * valid)
    correct = jnp.sum((jnp.argmax(lg, axis=-1) == tgt) * valid)
    total = jnp.sum(valid)
    return (loss_total.astype(jnp.float32), correct.astype(jnp.float32),
            total.astype(jnp.float32))


def _offload_transfers(state_shardings):
    """(fetch, stash) for --host_offload: params/optimizer state live in
    pinned_host between steps (CPUOffload(offload_params=True) analog,
    transformer_test.py:46-48); XLA cannot compute on host-placed operands
    directly, so the step fetches the state into device memory on entry
    and stashes the update back to host before returning — both transfers
    are in-graph (jax.device_put under jit), so XLA schedules/overlaps
    them."""
    if state_shardings is None:
        return (lambda s: s), (lambda s: s)

    def device_kind(sh):
        # only host-pinned leaves transfer; the rest keep their sharding
        # (the partial --offload_opt_state tier, and backends like CPU
        # whose only memory kind IS the host) — device_put on an
        # unchanged sharding is a cheap placement pin
        if getattr(sh, "memory_kind", None) == "pinned_host":
            return sh.with_memory_kind("device")
        return sh

    to_dev = jax.tree.map(device_kind, state_shardings)

    def fetch(state):
        return jax.tree.map(jax.device_put, state, to_dev)

    def stash(state):
        return jax.tree.map(jax.device_put, state, state_shardings)

    return fetch, stash


def step_counters(mutated) -> Dict[str, jax.Array]:
    """What the model sowed into ``spans.COUNTERS`` this step, each name
    meaned over the layers that wrote it; {} where no layer wrote any."""
    found: Dict[str, list] = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            mutated.get(spans.COUNTERS, {})):
        # a sown leaf sits in a tuple under its name: .../<name>/0
        name = next(k.key for k in reversed(path) if hasattr(k, "key"))
        found.setdefault(name, []).append(leaf)
    return {k: jnp.mean(jnp.stack(v)) for k, v in found.items()}


def make_train_step(cfg: TrainConfig, state_shardings=None, pipeline=None
                    ) -> Callable[[TrainState, Any],
                                  Tuple[TrainState, Metrics]]:
    """Build the jitted train step for cfg.model ('resnet*', or a token
    model: 'transformer', 'decoder').

    state_shardings: pass the TrainState-shaped sharding tree when
    cfg.host_offload is on — the step then round-trips the state
    host->device->host per _offload_transfers.

    Image augmentation happens IN-STEP when the batch carries raw uint8
    images (the loaders' native dtype): the crop/flip/normalize key is
    derived from the CHECKPOINTED device step counter
    (``fold_in(PRNGKey(seed+1), state.step)``) instead of a host-side
    counter, so (a) a resumed run's augmentation stream is bitwise-
    identical to an uninterrupted one (ROADMAP "augmentation-stream
    resume"), and (b) the fused K-step dispatch can advance the stream
    on device with zero host involvement.  Pre-normalized float batches
    (synthetic probes, the eval staging path) pass through
    untouched.

    pipeline: a parallel.pipeline.PipelineSpec on a pp>1 mesh — the
    transformer forward then runs the staged 1F1B microbatch rotation
    (models/transformer.py).  None (every pp=1 config) adds NOTHING to
    the apply call, so those programs stay byte-identical to r21."""
    fp16 = cfg.precision == "fp16"
    # --sentinel guard|full: arm the in-graph bad-step guard.  A
    # TRACE-time Python flag, so --sentinel none programs stay
    # byte-identical to the unguarded build (pinned by
    # tests/test_sentinel.py); when armed, the fp16 GradScaler skip
    # below generalizes to every precision with the fused verdict.
    sentinel_on = getattr(cfg, "sentinel", "none") not in ("none", None)
    # FDT_FAULT_NAN_AT_STEP: the poison multiplier is baked into the
    # program at trace time (lazy import — faults.py pulls in the
    # resilience package, which train.steps must not need at import)
    from faster_distributed_training_tpu.resilience.faults import (
        graph_nan_at)
    nan_at = graph_nan_at()
    is_text = is_token_model(cfg)
    lm = getattr(cfg, "task", "cls") == "lm"
    if lm and not is_text:
        raise ValueError(f"--task lm needs a token model, the "
                         f"transformer or the decoder (next-token "
                         f"prediction over token ids); got model="
                         f"{cfg.model!r}")
    if cfg.model == "decoder" and not lm:
        raise ValueError("--model decoder is a language model: --task lm")
    mode = resolve_mixup_mode(cfg)
    # non-offload shardings (a tp/2D mesh): pin the UPDATED state to the
    # placement policy — without the constraint XLA's propagation is
    # free to replicate the optimizer update's outputs, silently undoing
    # the 1/tp per-param footprint the sharding exists for.  Offload
    # runs pin through stash() instead (different memory kinds).
    offload = cfg.host_offload or getattr(cfg, "offload_opt_state", False)
    if offload and state_shardings is None:
        # the placement layer pins params/opt state to pinned_host for this
        # cfg; a step without the fetch would compile against host-placed
        # operands (TPU: compile error; worse, a silent contract violation)
        raise ValueError("cfg.host_offload/offload_opt_state requires "
                         "state_shardings (see parallel.placement."
                         "train_state_shardings)")
    if offload and not any(
            getattr(s, "memory_kind", None) == "pinned_host"
            for s in jax.tree.leaves(
                state_shardings, is_leaf=lambda x: hasattr(x, "mesh"))):
        # backend without a pinned_host tier (CPU): the placement layer
        # already degraded every pin to plain device sharding, so the
        # fetch/stash round-trip would be pure no-op plumbing — but
        # flipping constrain_out still changes GSPMD's partitioning and
        # with it fp32 reduction order.  Treat the flag as fully off so
        # --offload_opt_state on a host-only backend is BITWISE inert
        # (pinned by test_offload_opt_state_degrades_bitwise_on_cpu).
        offload = False
    # constrain_out is also what makes r23 per-stage residency STICK:
    # the updated state is pinned to the train_state_shardings tree
    # (which carries the pp specs from sharding.pp_residency_specs), so
    # the partitioner cannot drift a stage-owned leaf back to
    # replicated between donated steps — the same pin that already
    # protects the tp/sp layouts below.
    constrain_out = state_shardings is not None and not offload
    fetch, stash = _offload_transfers(
        state_shardings if offload else None)
    # --overlap_grad_reduce: reshard grads through byte-bounded 1-D
    # buckets constrained to the zero axis, so GSPMD lowers the gradient
    # psum as bucketed reduce-scatter it can overlap with the next
    # microbatch's compute inside the K-dispatch scan.  Value-identity.
    reduce_grads = lambda g: g                                 # noqa: E731
    if getattr(cfg, "overlap_grad_reduce", False) \
            and state_shardings is not None:
        from faster_distributed_training_tpu.parallel.sharding import (
            bucketed_grad_reduce)
        _mesh = jax.tree.leaves(
            state_shardings,
            is_leaf=lambda x: hasattr(x, "mesh"))[0].mesh
        _bucket = int(getattr(cfg, "overlap_bucket_mb", 4)) << 20
        reduce_grads = lambda g: bucketed_grad_reduce(      # noqa: E731
            g, _mesh, bucket_bytes=_bucket)
    # the augmentation stream root — the same seed+1 derivation
    # cli.run_training used for the host-counter stream it replaces
    aug_root = jax.random.PRNGKey(cfg.seed + 1)
    # pp>1 only: the staged-encoder selector, absent (not None-valued —
    # ABSENT) from every pp=1 apply call so those traces don't change
    pp_kwargs = {} if pipeline is None else {"pp_spec": pipeline}

    def step(state: TrainState, batch: Dict[str, jax.Array]
             ) -> Tuple[TrainState, Metrics]:
        state = fetch(state)
        if (not is_text and "image" in batch
                and batch["image"].dtype == jnp.uint8):
            from faster_distributed_training_tpu.data.augment import (
                augment_batch)
            k_aug = jax.random.fold_in(aug_root, state.step)
            with jax.named_scope("fdt/augment"):
                batch = dict(batch, image=augment_batch(
                    k_aug, batch["image"], train=True))
        step_key = jax.random.fold_in(state.rng, state.step)
        k_mix, k_drop = jax.random.split(step_key)
        if cfg.dropout_rng_impl == "rbg" and cfg.dropout_impl == "xla":
            # Opt-in: dropout masks through the rbg PRNG (XLA
            # RngBitGenerator — the TPU's hardware-RNG path) instead of
            # threefry, which costs ~100 vector ops per draw and was
            # measured to eat 34% of the transformer step in round 3.
            # Only meaningful with cfg.dropout_impl == "xla": the
            # default hash dropout (ops/dropout.py) never draws mask
            # bits from this key at all (it derives one u32 seed per
            # site), is faster than the rbg path AND bit-reproducible,
            # which is why threefry is back as the rng default
            # (ADVICE r3 #2).  Only the DROPOUT stream switches:
            # mixup/init stay threefry, and the attention-prob dropout
            # keeps its placement-independent index hash
            # (ops.attention.dropout_keep).
            k_drop = jax.random.wrap_key_data(
                jax.random.bits(k_drop, (4,), jnp.uint32), impl="rbg")
        if lm:
            # next-token LM objective (--task lm, r18): per-position
            # vocab logits, targets = tokens shifted left.  mask=None to
            # the model — the streamed LM rows are PACKED (format.
            # pack_lm_rows: no padding), so there is nothing to mask in
            # attention and the one program serves every data path
            # identically; padded-target validity is handled in the LOSS
            # (lm_shift_metrics' tok_mask term) for datasets that do pad.
            # No mixup: a dense token objective has no sentence-embedding
            # to mix (the k_mix rng is threaded for stream parity but
            # the lm model path never draws from it).
            def loss_fn(params):
                variables = {"params": params["model"],
                             "batch_stats": state.batch_stats}
                with jax.named_scope("fdt/model"):
                    logits, mutated = state.apply_fn(
                        variables, batch["tokens"],
                        token_types=batch.get("token_types"),
                        mask=None, train=True,
                        rngs={"dropout": k_drop, "mixup": k_mix},
                        mutable=["batch_stats", spans.COUNTERS],
                        **pp_kwargs)
                with jax.named_scope("fdt/loss"):
                    loss_total, correct, total = lm_shift_metrics(
                        logits, batch["tokens"], batch.get("mask"))
                    loss = loss_total / jnp.maximum(total, 1.0)
                    scaled = scale_loss(loss, state.loss_scale, fp16)
                if nan_at is not None:
                    # multiplicative poison: the NaN flows through the
                    # backward pass, so every gradient leaf is NaN too —
                    # exactly the shape of a real overflow/bad batch
                    scaled = scaled * jnp.where(state.step == nan_at,
                                                jnp.nan, 1.0)
                new_stats = mutated.get("batch_stats", state.batch_stats)
                counters = step_counters(mutated)
                return scaled, (loss, loss_total, correct, total, new_stats,
                                counters)

            grads, (loss, loss_total, correct, total, new_stats,
                    counters) = jax.grad(loss_fn, has_aux=True)(state.params)
            with jax.named_scope("fdt/grad_reduce"):
                grads = reduce_grads(grads)
                grads, finite = unscale_and_check(grads, state.loss_scale,
                                                  fp16)
            ok = _sentinel_ok(loss, grads, finite) if sentinel_on \
                else finite
            with jax.named_scope("fdt/optimizer"):
                updated = state.apply_gradients(grads)
            updated = updated.replace(
                batch_stats=new_stats,
                loss_scale=update_loss_scale(state.loss_scale, finite,
                                             fp16))
            if fp16 or sentinel_on:
                # the loss-scale ladder keys off the overflow bit
                # (finite), the sentinel skip off the fused verdict (ok)
                skipped = state.replace(
                    step=state.step + 1,
                    loss_scale=update_loss_scale(state.loss_scale, finite,
                                                 fp16))
                updated = _tree_where(ok, updated, skipped)
            # loss = per-TOKEN mean (perplexity's log); total counts
            # target tokens, so the accumulator's loss_total/total is
            # the exact token-weighted epoch loss and "accuracy" is
            # next-token accuracy
            metrics = {"loss": loss.astype(jnp.float32),
                       "loss_total": loss_total,
                       "correct": correct, "total": total}
            if counters:
                metrics["counters"] = counters
            if fp16:
                metrics["loss_scale"] = updated.loss_scale.scale
            if sentinel_on:
                metrics = _sentinel_metrics(metrics, ok)
            if constrain_out:
                updated = jax.tree.map(jax.lax.with_sharding_constraint,
                                       updated, state_shardings)
            return stash(updated), metrics
        y = batch["label"]

        def loss_fn(params):
            model_params = params["model"]
            variables = {"params": model_params,
                         "batch_stats": state.batch_stats}
            if is_text:
                with jax.named_scope("fdt/model"):
                    out, mutated = state.apply_fn(
                        variables, batch["tokens"],
                        token_types=batch.get("token_types"),
                        mask=batch.get("mask"), train=True,
                        rngs={"dropout": k_drop, "mixup": k_mix},
                        mutable=["batch_stats"], **pp_kwargs)
                logits, index, lam = out       # in-forward mixup triplet
                y_a, y_b = y, y[index]
            else:
                x = batch["image"]
                with jax.named_scope("fdt/mixup"):
                    if mode == "meta":
                        x, y_a, y_b, lam = mx.meta_mixup_apply(
                            params["mixup_lambda"], k_mix, x, y)
                    elif mode == "attn":
                        x, y_a, y_b, lam = mx.attn_mixup_apply(
                            params["mixup_lambda"], k_mix, x, y)
                    elif mode == "static":
                        x, y_a, y_b, lam = mx.mixup_data(k_mix, x, y,
                                                         cfg.alpha)
                    elif mode == "intra":
                        x, y_a, y_b, lam = mx.mixup_data(
                            k_mix, x, y, cfg.alpha, intra_only=True)
                    else:
                        x, y_a, y_b, lam = x, y, y, jnp.asarray(1.0)
                with jax.named_scope("fdt/model"):
                    logits, mutated = state.apply_fn(
                        variables, x, train=True,
                        rngs={"dropout": k_drop, "mixup": k_mix},
                        mutable=["batch_stats"])
            with jax.named_scope("fdt/loss"):
                if mode in ("meta", "attn") and not is_text:
                    loss = mx.mixup_criterion_meta(
                        per_sample_cross_entropy, logits, y_a, y_b, lam)
                else:
                    loss = mx.mixup_criterion(cross_entropy, logits, y_a,
                                              y_b, lam)
                scaled = scale_loss(loss, state.loss_scale, fp16)
            if nan_at is not None:
                scaled = scaled * jnp.where(state.step == nan_at,
                                            jnp.nan, 1.0)
            new_stats = mutated.get("batch_stats", state.batch_stats)
            return scaled, (loss, logits, y_a, y_b, lam, new_stats)

        grads, (loss, logits, y_a, y_b, lam, new_stats) = jax.grad(
            loss_fn, has_aux=True)(state.params)
        with jax.named_scope("fdt/grad_reduce"):
            grads = reduce_grads(grads)
            grads, finite = unscale_and_check(grads, state.loss_scale, fp16)
        ok = _sentinel_ok(loss, grads, finite) if sentinel_on else finite

        with jax.named_scope("fdt/optimizer"):
            updated = state.apply_gradients(grads)
        updated = updated.replace(
            batch_stats=new_stats,
            loss_scale=update_loss_scale(state.loss_scale, finite, fp16))
        if fp16 or sentinel_on:
            # skip the whole update on non-finite grads (GradScaler policy,
            # resnet50_test.py:547-548) — but still advance step & scale
            skipped = state.replace(
                step=state.step + 1,
                loss_scale=update_loss_scale(state.loss_scale, finite, fp16))
            updated = _tree_where(ok, updated, skipped)

        # mixup-weighted train accuracy (resnet50_test.py:550-558)
        pred = jnp.argmax(logits, axis=-1)
        if lam.ndim == 0:
            correct = (lam * jnp.sum(pred == y_a)
                       + (1.0 - lam) * jnp.sum(pred == y_b))
        else:
            correct = jnp.sum(lam * (pred == y_a)
                              + (1.0 - lam) * (pred == y_b))
        metrics = {"loss": loss.astype(jnp.float32),
                   "correct": correct.astype(jnp.float32),
                   "total": jnp.asarray(y.shape[0], jnp.float32)}
        if fp16:
            metrics["loss_scale"] = updated.loss_scale.scale
        if sentinel_on:
            metrics = _sentinel_metrics(metrics, ok)
        if constrain_out:
            updated = jax.tree.map(jax.lax.with_sharding_constraint,
                                   updated, state_shardings)
        return stash(updated), metrics

    return step


def _reduce_scanned_metrics(ms: Metrics) -> Metrics:
    """Per-step metrics stacked [K] by lax.scan -> one on-device dict.

    ``loss_total``/``total`` let MetricAccumulator.summary() recover the
    EXACT sample-weighted epoch loss (identical to K=1's mean over equal-
    sized steps); ``loss`` (mean over the dispatch) feeds the live
    log-line and the non-finite epoch check — any non-finite step
    poisons the mean, so divergence detection keeps per-step acuity."""
    out = {"loss": jnp.mean(ms["loss"]),
           # the LM step emits an exact loss_total (token-weighted sum);
           # reduce it directly instead of re-deriving loss*total, so a
           # K>1 LM dispatch's epoch loss is the same float the K=1
           # path accumulates
           "loss_total": (jnp.sum(ms["loss_total"]) if "loss_total" in ms
                          else jnp.sum(ms["loss"] * ms["total"])),
           "correct": jnp.sum(ms["correct"]),
           "total": jnp.sum(ms["total"])}
    if "bad_steps" in ms:
        # the sentinel's counted verdicts (one 0/1 per scanned step) —
        # summed here and again by the epoch accumulator into
        # bad_steps_sum, the Trainer's skipped_steps feed
        out["bad_steps"] = jnp.sum(ms["bad_steps"])
    if "loss_scale" in ms:
        out["loss_scale"] = jax.tree.map(lambda x: x[-1], ms["loss_scale"])
    if "counters" in ms:
        out["counters"] = jax.tree.map(jnp.mean, ms["counters"])
    return out


def make_fused_train_step(cfg: TrainConfig, k: int, state_shardings=None,
                          resident=None, mesh=None,
                          pipeline=None) -> Callable:
    """K steps in ONE device dispatch: ``lax.scan`` over the single-step
    body (Kumar et al. 2021's loop-inside-the-program fix for dispatch-
    bound small-model training).  The scan compiles the body ONCE and
    calls it K times, so each iteration runs the same XLA program as the
    standalone jitted step — which is what makes a K=4 run bitwise-equal
    to a K=1 run at the same global step (pinned by
    tests/test_fused_dispatch.py).  State is donated across the carry;
    loss-scale/NGD/mixup state threads through unchanged (it all lives
    in the carry); metrics are stacked by the scan and reduced on device
    (_reduce_scanned_metrics).

    Two batch sources:
      * host (``resident=None``): ``step_k(state, batches)`` where every
        batch leaf carries a leading K axis (the Trainer stacks K host
        batches and stages them with ONE transfer);
      * device-resident (``resident=DeviceResidentData``):
        ``step_k(state, data, order, start)`` — batch ``start + i`` is
        gathered from the resident split *inside* the scan body
        (``order`` is the epoch's index array, ``start`` the dispatch's
        first step-in-epoch), so the steady-state loop moves no batch
        bytes from the host at all.  A ``resident`` with
        ``batch_major=True`` (per-host sharded residency,
        ``data.device_resident.ShardedDeviceResidentData``) hands the
        dispatch this epoch's ``[steps, batch, ...]`` view instead: the
        permutation was applied by the once-per-epoch re-shard, so the
        in-graph "gather" is a ``dynamic_index`` on the UNsharded
        leading axis — every device reads only its own rows of batch
        ``start + i`` from local HBM (``order`` is carried for
        signature uniformity but never indexed through).

    k == 1 is valid (one-step scan) but the Trainer keeps the plain
    ``make_train_step`` path for it — the default behavior stays
    byte-for-byte today's.

    pipeline (r22): on a pp>1 mesh the scan BODY is the staged
    1F1B-microbatched step, so the pipeline's tick loop nests inside
    the K-dispatch scan — the pipeline bubble and the K-ladder share
    one dispatch accounting (the donated carry, the exact stacked-
    metric reduction and the loss-scale/NGD/mixup threading are the
    scan's, unchanged)."""
    base = make_train_step(cfg, state_shardings, pipeline=pipeline)
    k = int(k)
    if k < 1:
        raise ValueError(f"steps_per_dispatch must be >= 1, got {k}")

    if resident is None:
        def step_k(state: TrainState, batches: Dict[str, jax.Array]
                   ) -> Tuple[TrainState, Metrics]:
            state, ms = lax.scan(base, state, batches, length=k)
            return state, _reduce_scanned_metrics(ms)
        return step_k

    bs = resident.batch_size
    constraint = None
    if mesh is not None:
        from jax.sharding import NamedSharding
        from faster_distributed_training_tpu.parallel.sharding import (
            batch_spec)
        constraint = NamedSharding(mesh, batch_spec(mesh))

    batch_major = getattr(resident, "batch_major", False)

    def gather_batch(data: Dict[str, jax.Array], order: jax.Array,
                     step_in_epoch: jax.Array) -> Dict[str, jax.Array]:
        if batch_major:
            # order was pre-applied by the per-epoch re-shard: just
            # index the unsharded leading step axis (local-HBM reads)
            batch = {kk: lax.dynamic_index_in_dim(v, step_in_epoch, 0,
                                                  keepdims=False)
                     for kk, v in data.items()}
        else:
            idx = lax.dynamic_slice_in_dim(order, step_in_epoch * bs, bs)
            # indices come from a host-built permutation of [0, n) —
            # always in bounds, so skip jnp.take's clamp/fill index
            # normalization
            batch = {kk: v.at[idx].get(mode="promise_in_bounds")
                     for kk, v in data.items()}
        if constraint is not None:
            batch = {kk: lax.with_sharding_constraint(v, constraint)
                     for kk, v in batch.items()}
        return batch

    def step_k(state: TrainState, data: Dict[str, jax.Array],
               order: jax.Array, start: jax.Array
               ) -> Tuple[TrainState, Metrics]:
        def body(s, i):
            return base(s, gather_batch(data, order, start + i))
        state, ms = lax.scan(body, state, jnp.arange(k))
        return state, _reduce_scanned_metrics(ms)

    return step_k


def make_eval_step(cfg: TrainConfig) -> Callable[[TrainState, Any],
                                                 Metrics]:
    """Eval: deterministic forward (running BN stats, no dropout, no mixup —
    fixing the reference's always-on eval mixup, transformer_test.py:321).

    No offload fetch here: under --host_offload the Trainer transfers the
    state to device ONCE per eval epoch (Trainer.evaluate), not per batch —
    the state never changes inside an eval loop."""
    is_text = is_token_model(cfg)
    lm = getattr(cfg, "task", "cls") == "lm"

    def step(state: TrainState, batch: Dict[str, jax.Array]) -> Metrics:
        variables = {"params": state.params["model"],
                     "batch_stats": state.batch_stats}
        if lm:
            # next-token eval: same shifted objective as training, with
            # the padded-final-batch per-sample `valid` mask crossed in
            # (pad rows contribute zero target tokens — full-split
            # perplexity is exact at any batch size)
            logits = state.apply_fn(variables, batch["tokens"],
                                    token_types=batch.get("token_types"),
                                    mask=None, train=False)
            loss_total, correct, total = lm_shift_metrics(
                logits, batch["tokens"], batch.get("mask"),
                batch.get("valid"))
            return {"loss": (loss_total / jnp.maximum(total, 1.0)
                             ).astype(jnp.float32),
                    "loss_total": loss_total, "correct": correct,
                    "total": total}
        if is_text:
            logits = state.apply_fn(variables, batch["tokens"],
                                    token_types=batch.get("token_types"),
                                    mask=batch.get("mask"), train=False)
        else:
            logits = state.apply_fn(variables, batch["image"], train=False)
        y = batch["label"]
        hit = (jnp.argmax(logits, axis=-1) == y).astype(jnp.float32)
        losses = per_sample_cross_entropy(logits, y)
        valid = batch.get("valid")
        if valid is None:
            loss_total = jnp.sum(losses)
            correct = jnp.sum(hit)
            total = jnp.asarray(y.shape[0], jnp.float32)
        else:
            # padded final batch (BatchLoader pad_last): padding samples
            # carry valid=0 and contribute to nothing
            loss_total = jnp.sum(losses * valid)
            correct = jnp.sum(hit * valid)
            total = jnp.sum(valid)
        return {"loss": (loss_total / jnp.maximum(total, 1.0)
                         ).astype(jnp.float32),
                "loss_total": loss_total.astype(jnp.float32),
                "correct": correct.astype(jnp.float32),
                "total": total}

    return step
