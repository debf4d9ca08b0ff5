"""Span API: one vocabulary, ``fdt/<name>``, on the profiler's clock.

Two entry points write it:

``with spans.span("restore"):`` — for BOUNDARY events (set-up,
checkpoint, restore, eval, upload).  It does two things at once:

  * records the HOST wall time of the block into the active
    :class:`~faster_distributed_training_tpu.telemetry.recorder.
    TelemetryRecorder` (a ``{"kind": "span", ...}`` JSONL event), so
    ordinary runs get a span breakdown without any profiler attached.
    The record carries ``t0_s``, ``time.monotonic()`` at entry (the
    clock the benchmark's ``setup_s`` is taken on), and ``parent``, the
    innermost span still open on the same thread (absent at top level);
  * wraps the block in ``jax.profiler.TraceAnnotation`` under the same
    ``fdt/<name>`` label, so when a trace IS being captured (``--profile``
    or the windowed ``--profile_steps A:B``) the identical names appear
    on the XLA timeline — the JSONL numbers and the trace annotate each
    other instead of living in two vocabularies.

``with spans.phase("dispatch", step=n):`` — for the HOT LOOP.  The bare
trace annotation and nothing else: no lock, no ``_OPEN`` entry, no JSONL
record (the step record carries the loop's host times; a TraceMe costs a
level check when no profiler session is open).  The dispatching thread's
iteration is TILED by sibling phases, none nested in another ``fdt/*``
span, so a reduction that gives a device-idle gap to the host span
covering most of it names exactly one phase.

Set-up runs before any recorder exists: :func:`open_setup`
(``cli.setup_platform`` calls it) starts a SET-UP STRETCH, in which a span
that closes while no recorder is installed is HELD (at most
:data:`HELD_MAX`; any beyond are counted and dropped) until
:func:`hand_over` gives the held spans, in order, to the recorder
``build_telemetry`` made and ends the stretch.  Outside a stretch nothing
is held.

The recorder is installed process-globally (:func:`set_recorder`) rather
than threaded through every constructor: the instrumented seams live in
modules that predate telemetry (resilience/manager.py's background
writer thread, data/device_resident.py's upload path) and must stay
usable — at zero overhead beyond two clock reads and the trace
annotation — when no recorder is active (telemetry-off runs, library use).
The recorder's buffer is lock-guarded, so spans may be recorded from
any thread (the checkpoint background writer does).

Span names in use (append-only — new names may be added, existing ones
are never renamed; README "Observability" documents them):

  ``h2d_upload``            device_resident split upload (once per run)
  ``epoch_reshard``         per-epoch order upload / batch-major re-shard
  ``ckpt_snapshot``         blocking device->host state fetch of a save
  ``ckpt_commit``           background serialize + two-phase commit
  ``ckpt_sync_save``        blocking (sync/emergency) collective save
  ``restore``               checkpoint restore walk (manager)
  ``rendezvous``            pod restore-agreement barrier (coordinator)
  ``eval``                  the per-epoch eval pass
  ``first_dispatch_compile`` first call of an observed program for an
                            input signature (``ObservedJit``): its
                            acquisition and first execution; ``step`` =
                            the dispatch's first train step where the
                            caller gives one
  ``program_lower``         inside it: ``jitted.lower`` (Python trace +
                            lowering)
  ``program_compile``       inside it: XLA's compile, the persistent
                            cache's load or the executable cache's
                            deserialize
  ``program_observe``       inside it (twice, before and after
                            ``program_compile``): the observatory's own
                            work — module text and fingerprint, cache
                            listings, memory analysis, compiled text for
                            the op counts, the executable cache's store
  ``setup_platform``        ``cli.setup_platform`` (opens the set-up
                            stretch)
  ``setup_data``            ``cli.make_loaders``
  ``setup_model``           ``cli.build_model``
  ``setup_state``           ``cli.run_training``'s state creation and
                            placement

Hot-loop phases (:data:`PHASES`; trace only; ``step`` = the first train
step of the dispatch the phase belongs to, which is the step record's
``step`` at K=1 and ``step - k + 1`` under K-step dispatch):

  ``data_wait``    the loader's ``next()`` (data/loader.device_prefetch;
                   the fused-host group read; the stream window swap)
  ``h2d``          ``put_fn``: host->device staging of a LATER batch
  ``dispatch``     the jitted train-step call (the enqueue; it blocks
                   when the runtime's queue of in-flight steps is full)
  ``hooks``        ``Trainer._resilience_hooks`` (only with resilience)
  ``readback``     ``float(metrics["loss"])`` at a ``--log_every``
                   boundary: the loop's only device->host sync
  ``epoch_fence``  ``run_epoch``'s closing ``block_until_ready``

Device scopes (``jax.named_scope`` in train/steps.py, optim/ngd.py,
ops/quant.py, ops/conv_bn.py, ops/ssd.py, models/decoder.py,
models/hybrid.py, models/mamba.py and models/moe.py;
metadata only: they live in the HLO's ``op_name`` debug locations, never
in ``lowered.as_text()``, so program fingerprints and compile-cache keys
do not move).  A transform wraps ONE path element (``jvp(fdt/model)``,
``transpose(jvp(fdt/model))``), so readers match a scope as a substring
of the op's ``op_name`` (telemetry/trace_report.py).  Enter them by
``with``, never as a decorator or through a wrapper that makes the call:
a Python frame more between the epoch loop and traced code cost 20 s of
set-up on the chip's host (PERF.md section 6, PR 24):

  ``fdt/augment``      in-graph crop/flip/normalize of uint8 images
  ``fdt/mixup``        the image-space mixup variants
  ``fdt/model``        ``state.apply_fn`` inside ``loss_fn``: forward =
                       ``jvp(fdt/model)``, backward = its ``transpose``
  ``fdt/conv1x1_bn_stats``  inside the model's forward: ops/conv_bn.py's
                       batch statistics of the expanding 1x1 conv+BN
                       from the convolution's input (the K x K Gram
                       matrix and the column sums: two passes over
                       ``x``, so the convolution's output is never
                       written); the same 16 layers as the next row
  ``fdt/conv1x1_bn_bwd``  inside the model's backward: ops/conv_bn.py's
                       backward of the expanding 1x1 conv+BN from the
                       convolution's input (16 of ResNet-50's 46
                       FusedConvBNLayers, none in ResNet-18/34); the
                       scope's presence in a program is the path's
                       counter
  ``fdt/attention``    inside the decoder's forward and backward
                       (models/decoder.py): attention proper — scores,
                       softmax, values — through
                       ``ops/flash_attention.banded_attention``; not the
                       projections, norms or the output gate
  ``fdt/mamba``        a Mamba-2 mixer's work outside its two projections
                       and its scan (models/mamba.py): the causal conv, the
                       step and decays, ``D x``, the gate and the grouped
                       norm
  ``fdt/ssd``          the mixer's chunked scan (ops/ssd.py): the products
                       inside each chunk, each chunk's end state, the scan
                       across chunks, the states' outputs
  ``fdt/moe_route``    an expert layer's router (models/moe.py): sigmoid
                       scores over the router's whole width, top-k,
                       weights
  ``fdt/moe_dispatch`` the token-slots sorted by held expert and the
                       tokens' rows gathered into that order
  ``fdt/moe_experts``  the held experts' grouped products
                       (ops/grouped_matmul.py): SwiGLU, or a gateless
                       relu2 pair
  ``fdt/moe_combine``  the slots' results back in token order and each
                       token's weighted sum
  ``fdt/moe_latent``   an expert layer with latent experts: the tokens'
                       projection down to the latent width before the
                       dispatch and the weighted sum's back up after the
                       combine
  ``fdt/loss``         the (mixup) criterion
  ``fdt/grad_reduce``  ``reduce_grads`` + ``unscale_and_check``
  ``fdt/optimizer``    ``state.apply_gradients``; inside it the bare
                       children ``ngd`` (scale_by_ngd's update) and,
                       inside that, ``fisher_update`` (the
                       every-``update_period`` Fisher refresh)
  ``fdt/quant_scale_refresh``  ops/quant.py's delayed-scaling roll

Pallas kernel names (``name=`` on every ``pl.pallas_call``; a trace shows
them in the custom call's name):

  ``fdt_flash_fwd`` / ``fdt_flash_fwd_lse``  monolithic flash forward
                       (without / with the saved row statistics)
  ``fdt_flash_fwd_kblocked``   k-blocked flash forward (beyond the
                       monolithic envelope)
  ``fdt_flash_bwd_dq`` / ``fdt_flash_bwd_dkv``  k-blocked backward pair
  ``fdt_flash_fwd_banded`` / ``fdt_flash_bwd_dq_banded`` /
  ``fdt_flash_bwd_dkv_banded``  the same three over a causal band
                       (optionally a window) with grouped key-value
                       heads: the decoder's attention.  The grouped
                       products run megablox's kernels, which carry the
                       library's names (``gmm``, ``tgmm``)
  ``fdt_flash_bwd_fused``      one-kernel backward from saved statistics
  ``fdt_flash_bwd_recompute``  one-kernel backward that recomputes them
  ``fdt_fused_ffn_fwd`` / ``fdt_fused_ffn_fwd_general``  the encoder's
                       fused FFN forward: plain / quantized-or-tp-partial
                       (its backward is XLA's)
  ``fdt_fused_mlp``    the classifier head's fused MLP
  ``fdt_quant_matmul`` the int8/fp8 quantized matmul
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Iterator, List, Optional

_ACTIVE = None   # the installed TelemetryRecorder (or None)

# the flax collection a model sows its own per-step counters into (device
# scalars, whatever their names).  train/steps.py makes it mutable, means
# each name over the layers that wrote it and hands them on as ONE entry,
# ``metrics["counters"]``; the loop keeps them to the read-back it makes
# anyway and the recorder writes each as a step-record field of the same
# name.  Only the model and TELEMETRY_SCHEMA name a counter.
COUNTERS = "counters"

# the hot loop's phases, labels built once (phase() is called several
# times per dispatch)
PHASES = ("data_wait", "h2d", "dispatch", "hooks", "readback",
          "epoch_fence")
_PHASE_LABEL = {name: f"fdt/{name}" for name in PHASES}

# the set-up stretch (module docstring): None when no stretch is open,
# else the spans held for the recorder build_telemetry makes
HELD_MAX = 64
_held: Optional[list] = None
_held_dropped = 0

# the names of the spans open on this thread, innermost last (parent)
_STACK = threading.local()

# spans currently OPEN, any thread ({token: {name, t0, step, thread}}):
# the crash flight recorder (telemetry/flight.py) reads this so a host
# that dies inside restore/ckpt_commit/rendezvous names the phase it
# died in.  One lock-guarded dict add/remove per span — spans live at
# checkpoint/epoch boundaries, never per dispatch.
_OPEN: dict = {}
_OPEN_LOCK = threading.Lock()


def active_spans() -> List[dict]:
    """[{name, elapsed_ms, step?, thread}] of every span open right now
    (the flight-dump payload; empty when nothing is in flight)."""
    now = time.monotonic()
    with _OPEN_LOCK:
        out = []
        for info in _OPEN.values():
            rec = {"name": info["name"],
                   "elapsed_ms": round((now - info["t0"]) * 1e3, 3),
                   "thread": info["thread"]}
            if info["step"] is not None:
                rec["step"] = info["step"]
            out.append(rec)
        return out


def set_recorder(recorder) -> Optional[object]:
    """Install `recorder` as the process-global span sink; returns the
    previously installed one so callers can restore it (tests nest)."""
    global _ACTIVE
    prev, _ACTIVE = _ACTIVE, recorder
    return prev


def get_recorder():
    return _ACTIVE


def open_setup() -> None:
    """Start a set-up stretch (clearing anything an earlier one held)."""
    global _held, _held_dropped
    with _OPEN_LOCK:
        _held, _held_dropped = [], 0


def hand_over(recorder) -> int:
    """End the set-up stretch: record the held spans, in order, into
    ``recorder`` (dropped when it is None, a telemetry-off run) and
    return how many spans the stretch dropped beyond :data:`HELD_MAX`."""
    global _held, _held_dropped
    with _OPEN_LOCK:
        held, dropped = _held or [], _held_dropped
        _held, _held_dropped = None, 0
    if recorder is not None:
        for h in held:
            recorder.record_span(**h)
    return dropped


@contextlib.contextmanager
def span(name: str, step: Optional[int] = None) -> Iterator[None]:
    """Record `name`'s host wall time, start and parent to the active
    recorder (or hold it in a set-up stretch) AND label the same region
    ``fdt/<name>`` in any in-flight profiler trace.  Exception-safe: a
    span that raises still records its duration (a failed restore's cost
    is exactly the kind of time MTTR wants)."""
    global _held_dropped
    import jax

    stack = getattr(_STACK, "names", None)
    if stack is None:
        stack = _STACK.names = []
    parent = stack[-1] if stack else None
    stack.append(name)
    t0 = time.monotonic()
    token = object()
    with _OPEN_LOCK:
        _OPEN[token] = {"name": name, "t0": t0, "step": step,
                        "thread": threading.current_thread().name}
    try:
        with jax.profiler.TraceAnnotation(f"fdt/{name}"):
            yield
    finally:
        dur_ms = (time.monotonic() - t0) * 1e3
        stack.pop()
        rec = _ACTIVE
        with _OPEN_LOCK:
            _OPEN.pop(token, None)
            if rec is None and _held is not None:
                if len(_held) < HELD_MAX:
                    _held.append({"name": name, "dur_ms": dur_ms,
                                  "step": step, "t0_s": t0,
                                  "parent": parent})
                else:
                    _held_dropped += 1
        if rec is not None:
            rec.record_span(name, dur_ms, step=step, t0_s=t0, parent=parent)


def phase(name: str, step: Optional[int] = None):
    """``fdt/<name>`` on the profiler's timeline and nowhere else: the
    hot loop's annotation (module docstring).  ``name`` is one of
    :data:`PHASES`; ``step`` lands as the event's ``step`` stat."""
    import jax

    if step is None:
        return jax.profiler.TraceAnnotation(_PHASE_LABEL[name])
    return jax.profiler.TraceAnnotation(_PHASE_LABEL[name], step=step)
