"""Run-scoped telemetry: per-dispatch records + run manifest as JSONL.

Every ordinary training run emits machine-readable evidence: a
:class:`TelemetryRecorder` buffers one small host-side record per
dispatch (step, wall ms, examples/s, data-wait ms, checkpoint-blocking
ms, K, epoch) plus span/epoch/goodput events, and a single background
writer appends them as JSONL to
``<telemetry_dir>/host_<pi>.jsonl`` — the r7 off-critical-path idiom
(one worker thread, the step thread only appends to a list under a
lock).  A run manifest (config, mesh, jax/jaxlib versions, device kind)
is written once at startup (:func:`write_manifest`) so a telemetry
directory is self-describing.

Cost accounting (target <1% of median step; not measured on the
chip): the hot-path cost per dispatch is a few ``time.monotonic``
reads, one dict construction, and one lock-guarded list append; JSON
encoding and file IO happen on the background thread.  The buffer is a
RING in spirit — bounded, never a backlog: when ``capacity`` records
accumulate they are handed to the writer as one batch, and if the
writer falls more than a few batches behind (a wedged filesystem) new
batches are DROPPED and counted (``dropped_records``) rather than
queued — observability must never grow unbounded host memory or stall
the step loop.  ``--no_telemetry`` kills the whole subsystem
(telemetry.build_telemetry).

Schema (APPEND-ONLY — fields may be added, never renamed; consumers
must ignore unknown fields).  One JSON object per line, discriminated
by ``"kind"``:

  ``run_start``  {t, process_index, process_count, schema}
  ``step``       {step, epoch, n, k, wall_ms, dispatch_ms, data_ms,
                  block_ms, examples, ex_s, h2d_ms, compile?, sync_ms?,
                  fence_steps?, fence_ms?}
                 step = global step AFTER the dispatch; n = step in
                 epoch; wall_ms = the loop iteration's whole host wall
                 (data wait + dispatch + resilience hooks + the
                 --log_every read-back when it fell here);
                 dispatch_ms = the jitted call alone (the ENQUEUE: it
                 returns at once or blocks for a whole device step,
                 see the caveat below); data_ms = loader wait + H2D
                 staging, of which h2d_ms inside put_fn; ex_s =
                 examples / wall; compile=true marks a first execution
                 (compile time — aggregation excludes these from
                 step-time percentiles); sync_ms = host ms blocked in a
                 device->host read (written only when non-zero);
                 fence_steps / fence_ms = only on a dispatch that ended
                 in the read-back, which drains the device: train steps
                 and host wall ms since the previous such fence of this
                 run_epoch call (or since its first dispatch began);
                 the block_ms in between are NOT taken out (the device
                 works through its queue while the host blocks) —
                 fence_ms / fence_steps is the program's own FENCED
                 step time
  ``epoch_fence`` {step, epoch, fence_steps, fence_ms, sync_ms}
                 run_epoch's closing fence closed the epoch's last
                 fenced window (same meaning as the step record's
                 fence fields; sync_ms = the wait of the fence itself)
  ``span``       {name, dur_ms, step?, t0_s?, parent?}
                 (telemetry/spans.py) t0_s = time.monotonic() at the
                 span's entry; parent = the innermost span open on the
                 same thread when it began (absent at top level)
  ``epoch``      {epoch, steps, trained_steps, loss?, accuracy?,
                  wall_s, ex_s, peak_mem_bytes?, eval_loss?,
                  eval_accuracy?}
  ``goodput``    {… GoodputTracker.summary() …}  (per-epoch snapshot)
  ``goodput_event`` {counter, total}             (restart/preemption/
                  peer-failure counters as they happen — the MTTR
                  story rides the same stream)
  ``flush_stats``  {dropped_records}             (emitted at close when
                  any batch was dropped)
  ``program``    {name, variant, lowerings, compile_ms, lower_ms,
                  fingerprint, cache, cache_method, avals,
                  argument_bytes, output_bytes, temp_bytes,
                  generated_code_bytes, alias_bytes}
                 one per observed compile (telemetry/programs.py)
  ``retrace``    {name, reason, lowerings, avals, prev_avals}
                 an accidental re-lowering was detected (loud WARNING
                 beside it)
  ``memory``     {scope, ...} — scope "state": the per-chip
                 params/opt_state/batch_stats byte table
                 (programs.state_bytes_table — opt_state_bytes_per_chip
                 is ROADMAP's ZeRO-sizing number, opt_state_tiers the
                 per-tier sharded/replicated/offloaded split the ZeRO
                 overlay is audited by); scope "epoch":
                 device memory watermarks; scope "sharding_drift": the
                 guard fired (expected/got fingerprints + changed
                 leaves under --debug)
  ``flight``     {path, reason}                  (a crash flight dump
                  was written — telemetry/flight.py)
  ``serve_batch``   {bucket, size, real, pad, replica, dispatch_ms,
                  attempts}                      (one per dispatched
                  serving batch — serve/scheduler.py)
  ``serve_request`` {bucket, len, queue_ms, total_ms, replica}
                 (one per fulfilled request; len is the raw
                  pre-truncation length)
  ``spare``      {event, spare, seat, slice, generation, step}
                 (r17 warm-spare lifecycle: parked / claimed — the
                  swap duration rides the goodput stream as
                  warm_spare_swap_s)
  ``decode_admit`` {replica, slot, bucket, len, queue_ms}
                 (one per mid-stream admission: a prompt prefilled and
                  its K/V swapped into a running decode batch —
                  serve/decode/scheduler.py)
  ``decode_step``  {replica, pages, active, batch, step_ms}
                 (one per decode step over the slot batch; pages is
                  the page-count program that served it)
  ``slot_evict``   {replica, slot, tokens, reason}
                 (one per reclaimed cache slot; reason "budget" =
                  token budget met, "capacity" = cache/position
                  ceiling)

r17 append-only field addition: ``program`` records grew
``cache_source`` ({deserialized, persistent_dir, compiled} — which
tier served the executable; resilience/executable_cache.py).

The machine-checkable registry of the above is TELEMETRY_SCHEMA below;
``scripts/check_telemetry_schema.py`` AST-scans every emission site in
the package against it (tier-1), so a renamed kind/field fails CI
instead of silently breaking telemetry_report.py consumers.

Run scoping: the host file is opened in APPEND mode — a supervised
relaunch of the same run (same checkpoint_dir) continues the same
story, pre-crash records included.  A fresh run wants a fresh
directory, exactly like the checkpoint dir (cli.attempt's docstring);
the aggregation barrier is additionally time-scoped
(telemetry/aggregate.py) so a reused directory's markers can't lie.

Wall-time caveat, documented rather than hidden: ``wall_ms`` and
``dispatch_ms`` are HOST times, and under async dispatch they are not
step times.  The host runs ahead of the device until the runtime's
queue of in-flight executions is full; from then on a dispatch either
returns at once or blocks for a whole device step (on the v5e, ResNet-50
bs1024 at 160 ms a step: median ``dispatch_ms`` 3.8, mean 46.9, about a
third of the calls blocked inside ``ExecutePrepare`` — PERF.md section
5), so no percentile of them tracks the device.  The step time the
program itself can vouch for is the FENCED one: ``fence_ms /
fence_steps`` of the records that carry them (one per ``--log_every``
window, closed by the read-back that drains the device).
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, Optional, Tuple

SCHEMA_VERSION = 1
MANIFEST = "manifest.json"

# -- APPEND-ONLY schema registry (scripts/check_telemetry_schema.py) ------
# kind -> the complete set of fields records of that kind may carry.
# Fields are ADDED here when an emitter grows one and NEVER removed or
# renamed (consumers parse by literal name; old entries document
# history).  A kind mapped to None is OPEN: its fields come from a
# runtime dict the lint resolves separately ("goodput" =
# GoodputTracker.summary()'s keys, dynamic per-segment).
TELEMETRY_SCHEMA: Dict[str, Optional[frozenset]] = {
    "run_start": frozenset({"t", "process_index", "process_count",
                            "schema"}),
    # h2d_ms / sync_ms / fence_steps / fence_ms (PR 24, append-only):
    # the loop's host phases and its fenced step time (module docstring)
    "step": frozenset({"step", "epoch", "n", "k", "wall_ms",
                       "dispatch_ms", "data_ms", "block_ms", "examples",
                       "ex_s", "compile", "h2d_ms", "sync_ms",
                       "fence_steps", "fence_ms",
                       # PR 33, append-only: a model's own counters
                       # (spans.COUNTERS; these two are models/moe.py's),
                       # on the records that close a fenced window: means
                       # over the window's steps
                       "moe_slots", "moe_load_max"}),
    # PR 24, append-only: the epoch's last fenced window, closed by
    # run_epoch's own fence (train/loop._DispatchClock.fence)
    "epoch_fence": frozenset({"step", "epoch", "fence_steps", "fence_ms",
                              "sync_ms"}),
    # t0_s / parent (append-only): the span's start on the
    # monotonic clock and the span it nests in (telemetry/spans.py)
    "span": frozenset({"name", "dur_ms", "step", "t0_s", "parent"}),
    # perplexity/eval_perplexity (r18 LM workload, append-only): only
    # emitted on --task lm runs (exp of the token-weighted epoch loss)
    "epoch": frozenset({"epoch", "steps", "trained_steps", "loss",
                        "accuracy", "wall_s", "ex_s", "peak_mem_bytes",
                        "eval_loss", "eval_accuracy", "perplexity",
                        "eval_perplexity"}),
    "goodput": None,
    "goodput_event": frozenset({"counter", "total"}),
    "rollback": frozenset({"epoch", "restored_epoch", "step"}),
    "flush_stats": frozenset({"dropped_records"}),
    # cache_source (r17 instant restart, append-only): where the
    # executable came from — "deserialized" (persistent executable
    # cache, resilience/executable_cache.py), "persistent_dir" (XLA's
    # compilation-cache dir served the compile), "compiled" (full price)
    "program": frozenset({"name", "variant", "lowerings", "compile_ms",
                          "lower_ms", "fingerprint", "cache",
                          "cache_method", "cache_source", "avals",
                          "argument_bytes", "output_bytes", "temp_bytes",
                          "generated_code_bytes", "alias_bytes"}),
    "retrace": frozenset({"name", "reason", "lowerings", "avals",
                          "prev_avals"}),
    "memory": frozenset({"scope", "epoch", "step",
                         "params_bytes_per_chip", "params_leaves",
                         "opt_state_bytes_per_chip", "opt_state_leaves",
                         "batch_stats_bytes_per_chip",
                         "batch_stats_leaves", "total_bytes_per_chip",
                         "top_leaves", "opt_state_tiers", "pp_residency",
                         "peak_bytes",
                         "bytes_in_use", "expected", "got",
                         "changed_leaves"}),
    "flight": frozenset({"path", "reason"}),
    # r16 serving tier (serve/scheduler.py) — append-only additions:
    # one record per dispatched batch, one per fulfilled request
    "serve_batch": frozenset({"bucket", "size", "real", "pad", "replica",
                              "dispatch_ms", "attempts"}),
    "serve_request": frozenset({"bucket", "len", "queue_ms", "total_ms",
                                "replica"}),
    # r18 streaming data plane (data/stream/window.py) — append-only:
    # one stream_refill per background buffer fill (disk read + H2D
    # split out), one stream_stall per buffer swap the consumer had to
    # wait for (the numerator of Trainer.stream_stall_pct, <1% target)
    "stream_refill": frozenset({"epoch", "base", "batches", "bytes",
                                "read_ms", "h2d_ms"}),
    "stream_stall": frozenset({"epoch", "step", "wait_ms"}),
    # r17 warm-spare slices (cli._run_warm_spare) — append-only: one
    # record when a spare parks (event="parked") and one when it claims
    # a failed seat (event="claimed", with the adopted seat/slice/
    # generation); the swap duration itself lands in the goodput stream
    # (warm_spare_swap_s)
    "spare": frozenset({"event", "spare", "seat", "slice", "generation",
                        "step"}),
    # r21 decode serving tier (serve/decode/scheduler.py) — append-only:
    # one decode_admit per mid-stream admission (prefill + K/V swap into
    # the running batch), one decode_step per slot-batch decode step
    # (pages = the page-count program that served it), one slot_evict
    # per reclaimed cache slot (reason: budget | capacity)
    "decode_admit": frozenset({"replica", "slot", "bucket", "len",
                               "queue_ms"}),
    "decode_step": frozenset({"replica", "pages", "active", "batch",
                              "step_ms"}),
    "slot_evict": frozenset({"replica", "slot", "tokens", "reason"}),
    # r22 pipeline parallelism (parallel/pipeline.py; emitted once at
    # startup by cli.run_training on pp>1 meshes) — append-only: one
    # pp_bubble with the schedule's analytic accounting (the executed
    # program pays exactly this — fill/drain ticks compute on recycled
    # (discarded) microbatch data, never zeros: see the 0*inf
    # constant-fold note in pipeline.py), one pp_stage per stage with
    # its layer block and idle/active slot-tick split (what
    # pp_stage_idle_ms scales by measured tick time)
    "pp_bubble": frozenset({"n_stages", "n_microbatches", "n_ticks",
                            "schedule", "bubble_pct"}),
    "pp_stage": frozenset({"stage", "layers", "idle_ticks",
                           "active_ticks"}),
}
# kinds that once existed but are no longer emitted (none today): the
# lint's staleness rule consults this instead of forcing removal from
# the append-only registry above
RETIRED_KINDS: frozenset = frozenset()

# background-writer backlog bound (batches, not records): beyond this
# the recorder drops instead of queueing — a wedged shared fs must not
# grow snapshots of the run in host memory
_MAX_PENDING_BATCHES = 4


def _write_json_atomic(path: str, obj) -> None:
    # local tmp+replace+fsync copy (the coordinator/checkpoint idiom) so
    # a reader never observes a torn manifest/summary
    tmp = f"{path}.tmp{os.getpid()}.{threading.get_ident()}"
    with open(tmp, "w") as f:
        json.dump(obj, f, indent=1, default=str)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def write_manifest(directory: str, cfg=None, mesh=None,
                   extra: Optional[dict] = None) -> str:
    """``<directory>/manifest.json``, written once at startup (process
    0): everything needed to interpret the host JSONL files without the
    process that wrote them — config, mesh, jax/jaxlib versions, device
    kind/count.  Returns the path."""
    import dataclasses

    import jax

    man: dict = {"schema": SCHEMA_VERSION,
                 "unix_time": round(time.time(), 3)}
    try:
        import jaxlib
        man["jaxlib_version"] = getattr(jaxlib, "__version__", "?")
    except ImportError:
        man["jaxlib_version"] = ""
    man["jax_version"] = jax.__version__
    try:
        dev = jax.local_devices()[0]
        man["backend"] = jax.default_backend()
        man["device_kind"] = getattr(dev, "device_kind", str(dev))
        man["device_count"] = jax.device_count()
        man["process_count"] = jax.process_count()
    except Exception:
        pass  # an uninitializable backend must not kill the run
    if mesh is not None:
        try:
            man["mesh"] = {str(k): int(v) for k, v in dict(mesh.shape).items()}
        except Exception:
            man["mesh"] = str(mesh)
    if cfg is not None:
        man["config"] = (dataclasses.asdict(cfg)
                         if dataclasses.is_dataclass(cfg) else dict(cfg))
    if extra:
        man.update(extra)
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, MANIFEST)
    _write_json_atomic(path, man)
    return path


def update_manifest(directory: str, extra: dict) -> Optional[str]:
    """Merge ``extra`` into an existing manifest.json (atomic rewrite) —
    how the compile observatory's program table lands at run end: the
    manifest is written once at STARTUP, but per-program compile
    ms/fingerprint/cache/memory only exist after the programs compiled.
    Missing/corrupt manifests get a fresh one holding just ``extra``;
    returns the path, or None when the write fails (best-effort — a
    full disk at shutdown must not mask the run's real outcome)."""
    path = os.path.join(directory, MANIFEST)
    man: dict = {}
    try:
        with open(path) as f:
            man = json.load(f)
    except (OSError, ValueError):
        pass
    man.update(extra)
    try:
        os.makedirs(directory, exist_ok=True)
        _write_json_atomic(path, man)
    except OSError:
        return None
    return path


class TelemetryRecorder:
    """Host-side ring buffer of telemetry records, flushed as JSONL off
    the critical path (single background writer, append-mode file).

    ``process_index``/``process_count`` default to the pod identity (the
    FDT_POD_INDEX/FDT_POD_COUNT simulation seam, else the jax runtime —
    same resolution as resilience/coordinator.py), and exist as explicit
    arguments so tier-1 tests can run two recorders in one process as a
    simulated two-host pod sharing a telemetry directory."""

    def __init__(self, directory: str,
                 process_index: Optional[int] = None,
                 process_count: Optional[int] = None,
                 capacity: int = 256,
                 step_every: int = 1,
                 recent: int = 256,
                 log: Callable[[str], None] = print):
        if process_index is None or process_count is None:
            # lazy import: resilience.coordinator imports telemetry.spans
            # at module level, so importing it from THIS module's top
            # would be circular
            from faster_distributed_training_tpu.resilience.coordinator \
                import pod_identity
            pi, pc, _sim = pod_identity()
            process_index = pi if process_index is None else process_index
            process_count = pc if process_count is None else process_count
        self.pi = int(process_index)
        self.pc = int(process_count)
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self.path = os.path.join(self.directory,
                                 f"host_{self.pi:05d}.jsonl")
        self.capacity = max(int(capacity), 1)
        # --telemetry_every N: keep every Nth step record (the r12 note's
        # mitigation for per-dispatch clock pressure under async
        # dispatch).  Sampling drops whole records, never rewrites them,
        # so surviving records carry their true step numbers; compile-
        # marked first dispatches are always kept (there is exactly one
        # per program and aggregation keys on them), and span/epoch/
        # goodput events are never sampled.
        self.step_every = max(int(step_every or 1), 1)
        self._steps_seen = 0
        self._log = log
        self._lock = threading.Lock()
        self._buf: list = []
        # the flight-recorder RING: the last `recent` records, retained
        # ACROSS flushes (a crash's most interesting records are the
        # flushed-or-not tail) — telemetry/flight.py dumps it durably
        # from the failure seams.  One deque append per record on the
        # hot path; bounded by construction.
        self._recent: collections.deque = collections.deque(
            maxlen=max(int(recent), 1))
        self._pool: Optional[ThreadPoolExecutor] = None
        self._pending = 0
        self.dropped_records = 0
        self._closed = False
        self.record_event("run_start", t=round(time.time(), 3),
                          process_index=self.pi, process_count=self.pc,
                          schema=SCHEMA_VERSION)

    # -- recording (hot path) ---------------------------------------------

    def record_step(self, step: int, epoch: int, n: int, k: int,
                    wall_ms: float, dispatch_ms: float, examples: int,
                    data_ms: float = 0.0, block_ms: float = 0.0,
                    compile_: bool = False, h2d_ms: float = 0.0,
                    sync_ms: float = 0.0,
                    fence: Optional[Tuple[int, float]] = None,
                    counters: Optional[Dict[str, float]] = None) -> None:
        """``fence`` = (train steps, host ms) of the fenced window this
        dispatch closed; such a record is kept whatever the sampling
        cadence (there is one per --log_every window and the fenced
        step time is folded from them).  ``counters`` = the model's own
        counters read at that read-back (means over the steps since the
        last one), each written as a field of its own name."""
        self._steps_seen += 1
        if (self.step_every > 1 and not compile_ and fence is None
                and self._steps_seen % self.step_every):
            return
        rec = {"kind": "step", "step": int(step), "epoch": int(epoch),
               "n": int(n), "k": int(k), "wall_ms": round(wall_ms, 3),
               "dispatch_ms": round(dispatch_ms, 3),
               "data_ms": round(data_ms, 3), "block_ms": round(block_ms, 3),
               "examples": int(examples),
               "ex_s": round(examples / max(wall_ms / 1e3, 1e-9), 1),
               "h2d_ms": round(h2d_ms, 3)}
        if compile_:
            rec["compile"] = True
        if sync_ms:
            rec["sync_ms"] = round(sync_ms, 3)
        if fence is not None:
            rec["fence_steps"] = int(fence[0])
            rec["fence_ms"] = round(fence[1], 3)
        for name, value in (counters or {}).items():
            rec[name] = round(float(value), 4)
        self._append(rec)

    def next_step_kept(self) -> bool:
        """Whether the NEXT record_step call will be kept by the
        --telemetry_every cadence (compile-marked records are kept
        regardless).  The Trainer reads this BEFORE a dispatch so
        sampled-out dispatches skip their telemetry-only clock reads
        entirely — the actual point of the mitigation (dropping an
        already-timed record would keep 100% of the monotonic
        pressure); record_step remains the single counter owner."""
        return (self.step_every <= 1
                or (self._steps_seen + 1) % self.step_every == 0)

    def record_span(self, name: str, dur_ms: float,
                    step: Optional[int] = None,
                    t0_s: Optional[float] = None,
                    parent: Optional[str] = None) -> None:
        rec = {"kind": "span", "name": str(name),
               "dur_ms": round(dur_ms, 3)}
        if step is not None:
            rec["step"] = int(step)
        if t0_s is not None:
            rec["t0_s"] = round(t0_s, 6)
        if parent is not None:
            rec["parent"] = str(parent)
        self._append(rec)

    def record_event(self, kind: str, **fields) -> None:
        self._append({"kind": str(kind), **fields})

    def goodput_event_sink(self, counter: str, total: int) -> None:
        """Adapter for ``GoodputTracker.set_event_sink`` — restart/
        preemption/peer-failure counters land in the stream as they
        happen, so one file tells the run's whole story."""
        self.record_event("goodput_event", counter=str(counter),
                          total=int(total))

    def _append(self, rec: dict) -> None:
        with self._lock:
            if self._closed:
                return
            self._buf.append(rec)
            self._recent.append(rec)
            if len(self._buf) >= self.capacity:
                self._flush_locked()

    def recent_records(self) -> list:
        """Snapshot of the in-memory ring (newest last) — the crash
        flight recorder's payload (telemetry/flight.py)."""
        with self._lock:
            return list(self._recent)

    # -- flushing (background) --------------------------------------------

    def _flush_locked(self, wait: bool = False):
        if not self._buf:
            return None
        batch, self._buf = self._buf, []
        if self._pending >= _MAX_PENDING_BATCHES and not wait:
            # the writer is wedged (filesystem stall): drop, don't queue
            self.dropped_records += len(batch)
            return None
        if self._pool is None:
            self._pool = ThreadPoolExecutor(max_workers=1,
                                            thread_name_prefix="fdt-telem")
        self._pending += 1
        return self._pool.submit(self._write_batch, batch)

    def _write_batch(self, batch: list) -> None:
        try:
            with open(self.path, "a") as f:
                for rec in batch:
                    f.write(json.dumps(rec, default=str))
                    f.write("\n")
        except OSError as e:
            self.dropped_records += len(batch)
            self._log(f"[telemetry] could not append {len(batch)} records "
                      f"to {self.path}: {e!r}")
        finally:
            # under the SAME lock the step thread increments with: a
            # bare `-= 1` is a read-modify-write that can interleave
            # with the locked `+= 1`, and a lost decrement would drift
            # the backlog counter up until every batch is dropped
            with self._lock:
                self._pending -= 1

    def flush(self, wait: bool = False) -> None:
        """Hand the current buffer to the writer; ``wait=True`` blocks
        until it (and it alone) is on disk — epoch boundaries flush-wait
        before publishing their aggregation marker so process 0 reads a
        complete epoch."""
        with self._lock:
            fut = self._flush_locked(wait=wait)
        if wait and fut is not None:
            fut.result()

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            if self.dropped_records:
                self._buf.append({"kind": "flush_stats",
                                  "dropped_records": self.dropped_records})
            fut = self._flush_locked(wait=True)
            self._closed = True
        if fut is not None:
            try:
                fut.result()
            except Exception:
                pass
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None
