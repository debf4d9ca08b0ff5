"""Run-scoped telemetry subsystem (r12).

Every training run emits a structured, machine-readable record of
itself:

  * ``recorder``  — :class:`TelemetryRecorder`: low-overhead host-side
    ring buffer of per-dispatch records (step, wall ms, examples/s,
    data-wait ms, checkpoint-blocking ms, K, epoch) flushed as JSONL to
    ``<telemetry_dir>/host_<pi>.jsonl`` by a background writer (the r7
    off-critical-path idiom), plus the run manifest
    (:func:`write_manifest`: config, mesh, jax/jaxlib versions, device
    kind) written once at startup;
  * ``spans``     — ``with spans.span("restore"):`` records host wall
    time AND labels the region in any in-flight ``jax.profiler`` trace
    under the same name; instrumented seams: H2D upload / epoch
    re-shard (data/device_resident.py), checkpoint snapshot/commit
    (resilience/manager.py), restore/rendezvous
    (resilience/{manager,coordinator}.py), eval, each program's first
    dispatch with its lowering / compile / observation inside it, and
    set-up (platform, data, model, state) — spans that close before the
    recorder exists are held for it;
  * ``aggregate`` — process 0 folds the per-host JSONL into run-level
    p50/p95/p99 step times at epoch end (marker-file transport, the r10
    idiom) and flags stragglers in a ``[telemetry]`` log line;
  * windowed profiler capture rides beside it:
    ``--profile_steps A:B`` (utils/profiling.StepWindowProfiler) starts/
    stops ``jax.profiler`` around a step range mid-run.

Kill switch: ``--no_telemetry`` disables the whole subsystem —
:func:`build_telemetry` returns None and the Trainer's hot loop has zero
new work.  The enabled cost is meant to stay under 1% of median step
time; not measured on the chip.
"""

from __future__ import annotations

import os
import time
from typing import Callable, Optional

from faster_distributed_training_tpu.telemetry import flight  # noqa: F401
from faster_distributed_training_tpu.telemetry import programs  # noqa: F401
from faster_distributed_training_tpu.telemetry import spans  # noqa: F401
from faster_distributed_training_tpu.telemetry.aggregate import (  # noqa: F401,E501
    RunFold, aggregate_run, pod_epoch_aggregate, publish_epoch_marker,
    read_host_records, span_breakdown, step_time_ms)
from faster_distributed_training_tpu.telemetry.programs import (  # noqa: F401,E501
    ObservedJit, ProgramObservatory, sharding_fingerprint, sharding_table,
    state_bytes_table)
from faster_distributed_training_tpu.telemetry.recorder import (  # noqa: F401,E501
    MANIFEST, SCHEMA_VERSION, TELEMETRY_SCHEMA, TelemetryRecorder,
    update_manifest, write_manifest)


def resolve_telemetry_dir(cfg) -> str:
    """The run's telemetry directory: ``--telemetry_dir`` when set, else
    ``<checkpoint_dir>/telemetry`` — beside the checkpoints so pods
    already sharing a checkpoint fs share the telemetry surface too
    (the aggregation transport depends on it)."""
    explicit = getattr(cfg, "telemetry_dir", "") or ""
    if explicit:
        return explicit
    return os.path.join(getattr(cfg, "checkpoint_dir", "."), "telemetry")


class RunTelemetry:
    """The bundle the Trainer/cli consume: the recorder plus the pod
    aggregation policy.  Thin by design — the hot path talks straight to
    ``self.recorder``; this object owns the epoch-boundary fold and the
    lifecycle."""

    def __init__(self, recorder: TelemetryRecorder,
                 straggler_ratio: float = 2.0,
                 aggregate_wait_s: float = 2.0,
                 log: Callable[[str], None] = print):
        self.recorder = recorder
        self.directory = recorder.directory
        self.pi, self.pc = recorder.pi, recorder.pc
        self.straggler_ratio = float(straggler_ratio)
        self.aggregate_wait_s = float(aggregate_wait_s)
        self._log = log
        self._closed = False
        # the compile observatory (telemetry/programs.py): the Trainer
        # routes its jit compiles through it so every program records
        # compile ms / HLO fingerprint / cache verdict / memory bytes.
        # FDT_PROGRAM_OBS=0 removes it (plain jit dispatch, no program
        # events) while the rest of telemetry stays on.
        self.observatory = (ProgramObservatory(recorder=recorder, log=log)
                            if programs.observatory_enabled() else None)
        # incremental per-epoch fold state (process 0 only): each epoch
        # parses only the JSONL tails appended since the last fold
        self._fold = RunFold(self.directory) if self.pi == 0 else None
        # epoch markers older than this run's telemetry are a previous
        # attempt's residue in a reused directory and must not satisfy
        # the aggregation barrier (time-scoping, the r10 idiom)
        self._created_t = time.time()

    def end_epoch(self, epoch: int) -> Optional[dict]:
        """Epoch boundary: flush this host's records to disk, publish
        the epoch marker, and (process 0) fold all hosts into the
        ``[telemetry]`` pod line + straggler flags."""
        self.recorder.flush(wait=True)
        publish_epoch_marker(self.directory, epoch, self.pi)
        return pod_epoch_aggregate(
            self.directory, epoch, self.pi, self.pc,
            straggler_ratio=self.straggler_ratio, log=self._log,
            wait_s=self.aggregate_wait_s if self.pc > 1 else 0.0,
            fold=self._fold, newer_than=self._created_t)

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self.observatory is not None and self.pi == 0:
            # merge the program table into manifest.json (written at
            # STARTUP, before anything compiled): per program, compile
            # ms / fingerprint / cache verdict / memory breakdown — the
            # run's compile story survives the process.  Before
            # recorder.close() so a manifest-write crash can't orphan
            # the stream tail.
            try:
                from faster_distributed_training_tpu.telemetry.recorder \
                    import update_manifest
                update_manifest(self.directory,
                                {"compile": self.observatory.summary()})
            except Exception:
                pass
        self.recorder.close()
        if self.pi == 0:
            # refresh the committed run-level summary one last time (the
            # last epoch's fold may predate the final records); quiet —
            # the per-epoch lines already told the story
            try:
                from faster_distributed_training_tpu.telemetry.aggregate \
                    import SUMMARY
                from faster_distributed_training_tpu.telemetry.recorder \
                    import _write_json_atomic
                summary = aggregate_run(
                    self.directory, straggler_ratio=self.straggler_ratio)
                if summary.get("hosts"):
                    _write_json_atomic(
                        os.path.join(self.directory, SUMMARY), summary)
            except OSError:
                pass


def build_telemetry(cfg, log: Callable[[str], None] = print
                    ) -> Optional[RunTelemetry]:
    """RunTelemetry for a TrainConfig, or None when disabled
    (``--no_telemetry`` — the kill switch emergency rollbacks rely
    on).  Ends the set-up stretch ``cli.setup_platform`` opened: the
    spans it held go to the new recorder first, in order."""
    if not getattr(cfg, "telemetry", True):
        spans.hand_over(None)
        return None
    recorder = TelemetryRecorder(
        resolve_telemetry_dir(cfg),
        step_every=int(getattr(cfg, "telemetry_every", 1) or 1), log=log)
    dropped = spans.hand_over(recorder)
    if dropped:
        log(f"[telemetry] {dropped} set-up span(s) beyond the first "
            f"{spans.HELD_MAX} were dropped")
    return RunTelemetry(
        recorder,
        straggler_ratio=float(getattr(cfg, "straggler_ratio", 2.0) or 2.0),
        # --aggregate_grace_s: how long process 0 waits for the peers'
        # epoch markers before folding without them (the hard-coded 2 s
        # raced slow CI hosts; skipped hosts are now also recorded in
        # pod_summary.json, aggregate.pod_epoch_aggregate)
        aggregate_wait_s=float(
            getattr(cfg, "aggregate_grace_s", 2.0) or 0.0),
        log=log)
