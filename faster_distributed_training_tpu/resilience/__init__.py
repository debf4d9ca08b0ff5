"""Resilience subsystem: async + preemption-aware checkpointing, fault
injection, supervised restarts, and goodput accounting.

The reference has no fault-tolerance story at all (SURVEY.md §5: rank-0
``{net, acc, epoch}`` saves gated on best accuracy; recovery is a manual
re-launch) — on preemptible TPU pods every interruption costs whole
epochs.  This package closes that gap in five orthogonal pieces, each
layered on machinery the repo already has:

  * ``manager``     — :class:`AsyncCheckpointManager`: step/wall-clock
    cadence saves layered on ``train/checkpoint.py``, keep-last-K
    retention, atomic commit markers, off-critical-path writes;
  * ``preemption``  — :class:`PreemptionHandler`: SIGTERM/SIGINT →
    cross-host-agreed emergency save (the agreement bit makes the
    collective save deadlock-proof);
  * ``supervisor``  — :class:`Supervisor`: bounded-retry exponential-
    backoff restarts from the newest *valid* checkpoint, refusing to
    loop on deterministic crashes;
  * ``faults``      — :class:`FaultPlan`: deterministic env-driven fault
    injection (die/SIGTERM at step N, data-iterator raise, checkpoint
    corruption) that the CPU test suite drives;
  * ``goodput``     — :class:`GoodputTracker`: productive time vs.
    checkpoint/restore/restart badput (and restart MTTR), surfaced per
    epoch through ``train/metrics.py``;
  * ``coordinator`` — :class:`PodCoordinator` (r10): pod-coordinated
    restarts (shared-fs generation rendezvous so every host restarts
    into the same generation) + the cluster health watchdog (per-host
    heartbeats, peer-staleness detection, local step-hang escalation);
  * ``sentinel``    — :class:`Sentinel`: the SILENT-failure ladder —
    in-graph non-finite bad-step guard (train/steps.py), host-side
    loss-spike detection with durable batch quarantine +
    rollback-and-skip replay, and the data-integrity (CRC) verdict
    sink (``--sentinel guard|full``).

``Resilience`` bundles the pieces for the Trainer; ``build_resilience``
constructs the bundle from a TrainConfig (cli.run_training's path).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Callable, Optional


class Preempted(Exception):
    """Raised by the train loop after a cross-host-agreed preemption and
    a successful emergency save.  Carries the post-save train state so
    the caller can exit cleanly — this is a clean shutdown, NOT a
    failure: the supervisor re-raises it instead of retrying (the
    platform, not this process, owns the restart after a preemption)."""

    def __init__(self, message: str, state=None, step: Optional[int] = None):
        super().__init__(message)
        self.state = state
        self.step = step


# storage FIRST: it is dependency-free and both coordinator/manager and
# train/checkpoint.py import it — binding it on the package object
# before the manager->checkpoint->storage import cycle re-enters this
# partially-initialized module is what keeps that cycle resolvable
from faster_distributed_training_tpu.resilience import storage  # noqa: E402,F401,E501
from faster_distributed_training_tpu.resilience.storage import (  # noqa: E402,F401,E501
    FakeObjectStoreBackend, PosixBackend, StorageBackend, build_backend)
from faster_distributed_training_tpu.resilience.goodput import (  # noqa: E402,F401,E501
    GoodputTracker)
from faster_distributed_training_tpu.resilience.sentinel import (  # noqa: E402,F401,E501
    LossSpike, QuarantineLedger, Sentinel, SpikeDetector, host_finite)
from faster_distributed_training_tpu.resilience.coordinator import (  # noqa: E402,F401,E501
    PeerFailure, PodCoordinator, SeatTaken, StepTimeout, pod_identity,
    slice_identity, spare_identity)
from faster_distributed_training_tpu.resilience.executable_cache import (  # noqa: E402,F401,E501
    ExecutableCache, build_executable_cache)
from faster_distributed_training_tpu.resilience.manager import (  # noqa: E402,F401,E501
    AsyncCheckpointManager, RestoreDivergence)
from faster_distributed_training_tpu.resilience.preemption import (  # noqa: E402,F401,E501
    PreemptionHandler)
from faster_distributed_training_tpu.resilience.supervisor import (  # noqa: E402,F401,E501
    Supervisor)
from faster_distributed_training_tpu.resilience.faults import (  # noqa: E402,F401,E501
    FaultPlan, InjectedFault, corrupt_newest_checkpoint)


@dataclasses.dataclass
class Resilience:
    """The bundle the Trainer consumes (train/loop.py).  Any piece may be
    None; ``goodput`` always exists so accounting never needs guards.
    ``pod_index``/``pod_count``/``pod_simulated`` carry the pod identity
    the bundle was built for (the env seam or the real runtime) so the
    loop can gate per-pod-process behavior (e.g. only simulated-pod
    host 0 writes the shared epoch checkpoint — each simulated process
    computes the identical full state, and concurrent orbax writers on
    one path would race; a REAL pod's orbax save is collective and must
    be entered by every host)."""

    manager: Optional[AsyncCheckpointManager] = None
    preemption: Optional[PreemptionHandler] = None
    faults: Optional[FaultPlan] = None
    goodput: GoodputTracker = dataclasses.field(default_factory=GoodputTracker)
    coordinator: Optional[PodCoordinator] = None
    pod_index: int = 0
    pod_count: int = 1
    pod_simulated: bool = False
    slice_index: int = 0
    slice_count: int = 1
    backend: Optional[StorageBackend] = None
    spare_index: Optional[int] = None
    sentinel: Optional[Sentinel] = None

    def adopt_seat(self, seat: int) -> None:
        """r17 warm spares: after the coordinator claimed a failed pod
        seat (``PodCoordinator._adopt_seat``), re-key the rest of the
        bundle — the manager's shard ownership / commit-barrier role
        and the pod identity the train loop gates per-host behavior on
        (e.g. host-0-only epoch saves on fs-simulated pods)."""
        self.pod_index = int(seat)
        self.slice_index = (self.coordinator.si
                            if self.coordinator is not None else 0)
        if self.manager is not None:
            self.manager.adopt_identity(
                seat, shard_owner=(_sim_shard_owner(seat)
                                   if self.pod_simulated else None))

    def close(self) -> None:
        if self.manager is not None:
            self.manager.close()
        if self.preemption is not None:
            self.preemption.uninstall()
        if self.coordinator is not None:
            self.coordinator.close()


def _sim_shard_owner(pi: int):
    """The fs-SIMULATED pod's shard-ownership policy (one place, used
    at build time and again when a warm spare adopts a seat): host 0
    writes the full replica-0 cover, every other host writes an empty
    shard set whose DONE marker the commit barrier still requires."""
    if pi == 0:
        return lambda sh: sh.replica_id == 0
    return lambda sh: False


def build_resilience(cfg, log: Callable[[str], None] = print
                     ) -> Optional[Resilience]:
    """Resilience bundle for a TrainConfig, or None when every knob is
    off (the default — the Trainer's hot loop then has zero new work).

    Enabled by any of: --checkpoint_every / --checkpoint_every_secs
    (step-cadence manager + preemption handler), --supervise, an
    armed FDT_FAULT_* plan (fault injection needs the hooks even when
    checkpointing is off), or --sentinel guard|full (the anomaly
    sentinel's counters/ledger live on the bundle).

    Pod coordination (r10): with --supervise on a pod (real multi-host,
    or the FDT_POD_INDEX/FDT_POD_COUNT simulation seam) — or whenever
    --step_timeout_s arms the local hang watchdog — the bundle grows a
    :class:`PodCoordinator` under ``<checkpoint_dir>/_pod`` and the
    supervisor/loop drive the coordinated-restart protocol through it.
    In the fs-SIMULATED pod the manager also takes the simulated
    identity (host 0 owns the replica-0 shards, peers own none — every
    simulated process computes the identical full state) and the
    coordinator's marker-file allgather replaces the jax collective in
    the restore step-agreement.

    Storage + slices (r14): ``--storage_backend`` selects the durable
    medium every marker/sharded-checkpoint write rides
    (``resilience/storage.py`` — posix / fake_object_store / gs://);
    ``FDT_SLICE_INDEX``/``FDT_SLICE_COUNT`` partition the pod into
    slices and ``--readmit_timeout_s`` arms slice-granular elastic
    re-admission on the coordinator (surviving slices hold while a
    failed slice restarts and rejoins; whole-pod restart remains the
    fallback)."""
    pi, pc, simulated = pod_identity()
    spare = spare_identity()
    if spare is not None:
        # a warm spare is NOT one of the pod's pc members: park it under
        # a synthetic out-of-pod index (markers, shard files, telemetry
        # can never collide with a member's) until it claims a seat and
        # Resilience.adopt_seat re-keys the bundle
        pi = pc + spare
    si, sc, _slice_sim = slice_identity(process_index=pi, process_count=pc)
    faults = FaultPlan.from_env(process_index=pi)
    cadence = bool(cfg.checkpoint_every or cfg.checkpoint_every_secs)
    step_timeout = float(getattr(cfg, "step_timeout_s", 0.0) or 0.0)
    sentinel_mode = str(getattr(cfg, "sentinel", "none") or "none")
    if spare is not None and not cfg.supervise:
        log("[resilience] WARNING: FDT_SLICE_SPARE is set but --supervise "
            "is not — the warm-spare park lives on the pod coordinator, "
            "which only the supervised path builds; this process will "
            "train as an ordinary (out-of-pod!) run instead of parking")
    if step_timeout > 0 and not cfg.supervise:
        # BEFORE the enablement gate: --step_timeout_s as the ONLY
        # resilience flag must still warn, not silently no-op
        log("[resilience] WARNING: --step_timeout_s has no effect without "
            "--supervise — the hang watchdog lives on the pod coordinator, "
            "which only the supervised path builds; a wedged dispatch "
            "will block forever")
    if not (cadence or cfg.supervise or faults is not None
            or sentinel_mode != "none"):
        return None
    # the storage backend every resilience-critical durable write rides
    # (r14): markers, sharded checkpoint phases, retention.  posix =
    # today's shared-fs semantics, byte-compatible; fake_object_store /
    # gs:// = no-rename object semantics (multi-slice pods without a
    # shared filesystem)
    backend = storage.build_backend(
        getattr(cfg, "storage_backend", "posix"), cfg.checkpoint_dir,
        log=log)
    goodput = GoodputTracker()
    peer_timeout = float(getattr(cfg, "peer_timeout_s", 60.0))
    readmit_timeout = float(getattr(cfg, "readmit_timeout_s", 60.0))
    coordinator = None
    if cfg.supervise and (pc > 1 or step_timeout > 0 or spare is not None):
        coordinator = PodCoordinator(
            os.path.join(cfg.checkpoint_dir, "_pod"),
            process_index=pi, process_count=pc,
            sync_every=cfg.preempt_sync_every,
            peer_timeout_s=peer_timeout,
            step_timeout_s=step_timeout,
            slice_index=si, slice_count=sc,
            readmit_timeout_s=readmit_timeout,
            backend=backend, spare_index=spare,
            goodput=goodput, log=log)
    # commit-barrier timeout tied to the peer-detection timescale when
    # both are armed (r14 follow-on, now the default everywhere a
    # coordinator exists — not just simulated pods): the manager's old
    # 600 s default outlives both peer detection AND the re-admission
    # hold window, so a commit barrier stuck on a dead host burned the
    # whole hold into a pod_fallback_restart before anything timed out.
    # O(peer_timeout) keeps the ordering detection < barrier give-up.
    commit_timeout = float(getattr(cfg, "commit_timeout_s", 0.0) or 0.0)
    if commit_timeout <= 0:
        commit_timeout = (max(2.0 * peer_timeout, 10.0)
                          if coordinator is not None and pc > 1 else 600.0)
    elif coordinator is not None and pc > 1:
        if commit_timeout < peer_timeout:
            log(f"[resilience] WARNING: --commit_timeout_s "
                f"{commit_timeout:.0f} is below --peer_timeout_s "
                f"{peer_timeout:.0f} — the commit barrier gives up on a "
                f"slow-but-live peer before the watchdog could even call "
                f"it dead (inverted ordering: expect spurious counted "
                f"save_failures)")
        if readmit_timeout > 0 and sc > 1 \
                and commit_timeout > readmit_timeout:
            log(f"[resilience] WARNING: --commit_timeout_s "
                f"{commit_timeout:.0f} exceeds --readmit_timeout_s "
                f"{readmit_timeout:.0f} — a survivor draining a stuck "
                f"commit barrier can outlive the re-admission hold "
                f"window and degrade every slice recovery into a "
                f"pod_fallback_restart")
    manager = None
    if cadence:
        sim_kw = {"commit_timeout_s": commit_timeout}
        if simulated and pc > 1:
            # simulated pod: complementary shard owners (the r9 test
            # seam — host 0 writes the full replica-0 cover, peers write
            # empty shard sets whose DONE markers the commit barrier
            # still requires) + the fs-based restore step agreement
            sim_kw.update(
                process_index=pi, process_count=pc,
                shard_owner=_sim_shard_owner(pi))
        if coordinator is not None and (simulated or sc > 1) and pc > 1:
            # marker-transport restore agreement: fs-simulated pods (jax
            # single-process per host), and REAL multi-slice pods — a
            # jax collective across a pod with a dead/rejoining slice
            # is exactly the thing that cannot be relied on (the
            # slice-scoped barrier only exists on the marker transport)
            sim_kw["step_gather_fn"] = coordinator.gather_restored_step
        manager = AsyncCheckpointManager(
            cfg.checkpoint_dir,
            # mirror the epoch-checkpoint naming (loop.py ckpt_name) so
            # two workloads sharing a checkpoint_dir never restore each
            # other's step checkpoints
            prefix=(cfg.model if cfg.model in ("transformer", "decoder")
                    else "resnet"),
            every_steps=cfg.checkpoint_every,
            every_secs=cfg.checkpoint_every_secs,
            keep=cfg.checkpoint_keep,
            async_save=cfg.checkpoint_async,
            backend=backend,
            goodput=goodput, log=log, **sim_kw)
    if coordinator is not None and manager is not None:
        # survivors drain their in-flight background save before
        # publishing a re-admission HOLD (freezes the commit frontier
        # the rejoining slice walks — coordinator._await_readmission)
        coordinator.drain_fn = manager.wait
    preemption = PreemptionHandler(sync_every=cfg.preempt_sync_every,
                                   log=log).install()
    sentinel = None
    if sentinel_mode != "none":
        if sentinel_mode == "full" and not (cfg.supervise and cadence):
            # BEFORE the Sentinel builds, same precedent as the
            # step_timeout warning above: the spike path still
            # quarantines durably, but with no supervisor + checkpoint
            # cadence there is nothing to roll back through in-process
            log("[resilience] WARNING: --sentinel full without --supervise "
                "+ --checkpoint_every: a detected loss spike quarantines "
                "its batches durably but the run then ABORTS instead of "
                "rolling back in-process (the next start replays with the "
                "quarantine applied); add --supervise and a checkpoint "
                "cadence for automatic rollback-and-skip")
        sentinel = Sentinel(sentinel_mode, backend=backend, goodput=goodput,
                            window=int(getattr(cfg, "spike_window", 32)),
                            threshold=float(
                                getattr(cfg, "spike_threshold", 8.0)),
                            log=log, root=cfg.checkpoint_dir)
    return Resilience(manager=manager, preemption=preemption,
                      faults=faults, goodput=goodput,
                      coordinator=coordinator, pod_index=pi, pod_count=pc,
                      pod_simulated=simulated, slice_index=si,
                      slice_count=sc, backend=backend, spare_index=spare,
                      sentinel=sentinel)
