"""Async, cadence-driven checkpoint manager.

Layered on ``train/checkpoint.py``'s save/restore (the ISSUE's
prescription — orbax arrays + meta.json + atomic COMMIT marker), adding
the four things a preemptible-pod run needs that the epoch-level
checkpoints don't give:

  * WHEN to save — step cadence (``every_steps``) and/or wall-clock
    cadence (``every_secs``), whichever fires first;
  * OFF the critical path — the save splits into a blocking snapshot
    (``jax.device_get`` of the state, unavoidable: the very next train
    step donates those buffers) and the orbax serialization + disk
    write, which run on a single background worker.  Only the snapshot
    time touches step latency (target <1% of median step time; not
    measured on the chip);
  * keep-last-K retention — committed checkpoints beyond ``keep`` are
    pruned after each successful commit, and uncommitted residue
    (half-written directories from a previous crash) is swept;
  * newest-VALID restore — :meth:`restore_latest` walks committed
    checkpoints newest-first and falls back past any that fail to
    restore (corrupt/truncated data with an intact marker), so one bad
    write can never wedge recovery.

Multi-host: ``device_get`` can only fetch addressable shards — so the
multi-host async path doesn't try to: each process snapshots ONLY its
addressable shards (``checkpoint.host_shard_snapshot``, replica-0-owned
for a globally disjoint exact cover) and a background writer per
process streams them to a per-host shard file; process 0 writes the
``COMMIT`` marker only after a cross-host completion barrier (every
host's ``DONE`` marker on the shared checkpoint filesystem) — the
two-phase commit that keeps a partially-written pod save invisible to
restore.  Pods therefore get off-critical-path saves exactly like
single hosts (the r7 sync-collective fallback is gone; ``sync=True``
emergency saves keep the collective orbax path, whose entry is already
cross-host-agreed by the preemption bit).  Restore reassembles from the
per-host shard files and still reads pre-existing single-file orbax
checkpoints.  Only the STEP cadence is honored multi-host — a pure
function of the step counter, identical on every host, so every host
enters the same save; the wall-clock cadence reads per-host clocks that
can disagree near a threshold (hosts would write shard sets nobody
commits) and is disabled multi-host (warned).
"""

from __future__ import annotations

import os
import re
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, List, Optional, Tuple

import jax
import numpy as np

from faster_distributed_training_tpu.resilience import storage as storage_mod
from faster_distributed_training_tpu.telemetry import spans
from faster_distributed_training_tpu.train import checkpoint as ckpt

_STEP_DIR = re.compile(r"^(?P<prefix>.+)_step_(?P<step>\d{9})$")


class RestoreDivergence(RuntimeError):
    """Pod hosts restored DIFFERENT checkpoint steps (one host's
    fallback walk diverged from its peers') — resuming would train on
    divergent state; see AsyncCheckpointManager._verify_restore_agreement."""


def _local_delete_tree(path: str) -> None:
    """Historic default retention deleter (local/NFS recursive tree
    delete), kept for callers that installed it as a ``delete_fn`` hook.
    Retention now routes through the storage backend's BATCHED
    ``delete_prefix`` (r14 — the rmtree-per-dir idiom did not map to
    GCS; list-prefix + batched object deletes is the portable shape),
    and on POSIX that is exactly this rmtree."""
    storage_mod.posix_backend().delete_prefix(path)


class AsyncCheckpointManager:
    """Owns `<directory>/<prefix>_step_<N>` checkpoints.

    Not thread-safe for concurrent maybe_save callers (the train loop is
    single-threaded); the background worker only touches the host
    snapshot handed to it.

    ``process_index``/``process_count`` default to the real runtime and
    exist as the simulation seam the tier-1 tests use (two managers in
    one process, complementary ``shard_owner`` functions, one shared
    directory = a simulated two-host pod save).  ``force_sharded``
    routes even a single-process manager down the per-host shard-
    streaming path (tests/test_pod_scale.py)."""

    def __init__(self, directory: str, prefix: str = "ckpt",
                 every_steps: int = 0, every_secs: float = 0.0,
                 keep: int = 3, async_save: bool = True,
                 goodput=None, log: Callable[[str], None] = print,
                 delete_fn: Optional[Callable[[str], None]] = None,
                 force_sharded: bool = False,
                 process_index: Optional[int] = None,
                 process_count: Optional[int] = None,
                 shard_owner: Optional[Callable] = None,
                 commit_timeout_s: float = 600.0,
                 step_gather_fn: Optional[Callable] = None,
                 backend: Optional[storage_mod.StorageBackend] = None):
        self.directory = os.path.abspath(directory)
        self.prefix = prefix
        self.every_steps = int(every_steps)
        self.every_secs = float(every_secs)
        self._pc = (jax.process_count() if process_count is None
                    else int(process_count))
        self._pi = (jax.process_index() if process_index is None
                    else int(process_index))
        # the storage backend every durable write/list/delete routes
        # through (r14): posix by default — byte-compatible with every
        # pre-r14 checkpoint dir.  A non-posix backend has no rename
        # primitive, so the orbax single-file path (which stages +
        # renames internally) is unusable: force the sharded two-phase
        # path, whose writes are all whole-object puts.
        self.backend = backend if backend is not None \
            else storage_mod.posix_backend()
        # per-host shard-streaming saves whenever >1 process (the r7
        # sync-collective fallback is gone), or forced for tests,
        # or whenever the backend is not plain POSIX (see above)
        self._sharded = (bool(force_sharded) or self._pc > 1
                         or self.backend.kind != "posix")
        self._shard_owner = shard_owner
        self._commit_timeout_s = float(commit_timeout_s)
        # restore step-agreement transport override: fs-SIMULATED pods
        # (jax single-process per host) pass the pod coordinator's
        # marker-file allgather here; real pods keep the jax collective
        self._step_gather_fn = step_gather_fn
        self._delete = delete_fn or self.backend.delete_prefix
        if self.every_secs and self._pc > 1:
            # the wall-clock term reads each host's OWN monotonic clock,
            # so near a threshold hosts disagree: with the sharded path
            # a lone host writes a shard set nobody ever commits (and
            # the sync emergency path would deadlock its collective).
            # Only the step term is a pure function every host agrees
            # on.
            self.every_secs = 0.0
            if self._pi == 0:
                log("[ckpt] --checkpoint_every_secs is per-host-clock-"
                    "nondeterministic: hosts near a threshold would "
                    "disagree and write shard sets that never commit; "
                    "disabled — use the step cadence (--checkpoint_every)")
        self.keep = max(int(keep), 1)
        self.async_save = bool(async_save)
        self._goodput = goodput
        self._base_log = log
        self._log = log if self._pi == 0 else (lambda *_: None)
        self._last_save_t = time.monotonic()
        self._last_save_step: Optional[int] = None
        self._pool: Optional[ThreadPoolExecutor] = None
        self._inflight: Optional[Future] = None
        self._inflight_path: Optional[str] = None
        self._skip_logged = False
        self.backend.ensure_dir(self.directory)

    # -- cadence ----------------------------------------------------------

    def should_save(self, step: int) -> bool:
        """Multi-host, only the STEP term is live (a pure function of
        the observed step sequence, identical on every host — what keeps
        the collective save deadlock-free); the per-host wall-clock term
        is disabled at construction there.  Single-process runs use both.

        The step term fires when `step` has CROSSED an every_steps
        boundary since the last save — exact multiples for the classic
        per-step loop (identical behavior), and the first dispatch
        boundary at-or-past each multiple under a K-step fused dispatch,
        whose ticks only land at steps K, 2K, … (cli rounds
        checkpoint_every up to a multiple of K so the two coincide; the
        crossing form keeps cadence robust for epoch-tail dispatches of
        size < K, which shift every later boundary off the multiples)."""
        if step <= 0 or step == self._last_save_step:
            return False
        if self.every_steps:
            anchor = self._last_save_step or 0
            if anchor > step:
                # the step counter moved BACKWARD (auto-recover rolled
                # the state back to an epoch snapshot taken outside this
                # manager): a stale forward anchor would silence the
                # cadence for the whole replay window — reset so the
                # replay is checkpointable immediately
                anchor = 0
            if step // self.every_steps > anchor // self.every_steps:
                return True
        if self.every_secs:
            return time.monotonic() - self._last_save_t >= self.every_secs
        return False

    # -- saving -----------------------------------------------------------

    def maybe_save(self, state, step: int, epoch: int = 0,
                   step_in_epoch: int = 0, best_acc: float = 0.0) -> bool:
        if not self.should_save(step):
            return False
        return self.save(state, step, epoch=epoch,
                         step_in_epoch=step_in_epoch, best_acc=best_acc)

    def save(self, state, step: int, epoch: int = 0, step_in_epoch: int = 0,
             best_acc: float = 0.0, sync: bool = False,
             segment: str = "checkpoint_blocking_s") -> bool:
        """Checkpoint `state` at `step`.  Async (default): snapshot on
        the caller's thread, serialize + commit in the background; one
        save in flight at a time — a cadence tick that lands while the
        previous write is still running is SKIPPED (counted, never
        queued: a slow filesystem must not grow an unbounded backlog of
        full-state snapshots in host memory).  sync=True (emergency
        save path) waits for any in-flight write first and blocks until
        committed."""
        meta = {"step": int(step), "epoch": int(epoch),
                "step_in_epoch": int(step_in_epoch),
                "best_acc": float(best_acc)}
        # computed from the LIVE device state (the async snapshot below
        # is host numpy, where every leaf reads as tier "host")
        layout = ckpt.opt_state_layout(state)
        if layout:
            meta["opt_state_layout"] = layout
        name = self._name(step)
        if not (self.async_save or sync):
            sync = True      # async disabled: blocking collective path
        if sync and self.backend.kind != "posix":
            # the sync path is the single-file orbax save, which stages
            # + renames internally — impossible on an object store.  A
            # sharded save followed by a full drain gives the same
            # blocking "committed on return" contract on the backend.
            ok = self._save_sharded(state, step, meta, name, segment)
            self._drain_inflight()
            return ok
        if sync:
            self._drain_inflight()
            t0 = time.monotonic()
            with spans.span("ckpt_sync_save", step=step):
                ckpt.save_checkpoint(self.directory, name, state,
                                     epoch=epoch, best_acc=best_acc,
                                     extra_meta=meta)
            self._prune()
            self._record_save(step, time.monotonic() - t0, segment)
            if self._goodput:
                self._goodput.count("saves")   # committed — the sync
                # path only returns after the marker is on disk
            return True
        if self._sharded:
            return self._save_sharded(state, step, meta, name, segment)
        if self._inflight is not None and not self._inflight.done():
            if self._goodput:
                self._goodput.count("skipped_saves")
            # consume this cadence tick: without the anchor update the
            # crossing-based should_save would re-fire EVERY subsequent
            # step while the write runs, counting one skip per step
            # instead of one per missed tick
            self._last_save_step = step
            if not self._skip_logged:    # once per in-flight save, not per tick
                self._skip_logged = True
                self._log(f"[ckpt] step {step}: previous async save still "
                          f"in flight; skipping cadence ticks until it "
                          f"commits")
            return False
        self._finalize_inflight()
        t0 = time.monotonic()
        # the blocking part: the next train step will donate these
        # buffers, so the snapshot must complete before it dispatches
        with spans.span("ckpt_snapshot", step=step):
            snapshot = jax.device_get(ckpt._state_pytree(state))
        blocking = time.monotonic() - t0
        path = os.path.join(self.directory, name)
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="fdt-ckpt")
        self._inflight_path = path
        self._skip_logged = False
        self._inflight = self._pool.submit(
            self._write_pytree_bg, path, snapshot, meta, step)
        self._record_save(step, blocking, segment)
        return True

    @staticmethod
    def _write_pytree_bg(path: str, snapshot, meta: dict,
                         step: int) -> None:
        """Background worker body of the single-host async save —
        span-wrapped so the serialize+commit cost shows up in telemetry
        (recorded from the writer thread; the recorder is lock-safe)."""
        with spans.span("ckpt_commit", step=step):
            ckpt.save_pytree_checkpoint(path, snapshot, meta)

    def _save_sharded(self, state, step: int, meta: dict, name: str,
                      segment: str) -> bool:
        """The multi-host async path: per-host addressable-shard snapshot
        (the only blocking piece) + a background shard write per process,
        two-phase commit through ``checkpoint.write_host_shards`` /
        ``commit_sharded_checkpoint``.

        Unlike the single-host async path this DRAINS a still-running
        previous write instead of skipping the tick: the skip decision
        depends on per-host write timing (NOT a pure function of the
        step), so one host could skip a tick its peers take and the
        commit barrier would starve waiting for its shard.  Draining
        keeps every host's tick set identical; in steady state the
        previous write is long finished and the drain is free."""
        t0 = time.monotonic()   # before the drain: a slow-writer stall
        # is critical-path time and must land in the blocking segment
        if self._inflight is not None and not self._inflight.done():
            self._log(f"[ckpt] step {step}: waiting for the previous "
                      f"sharded save to finish (slow writer) — the tick "
                      f"is taken on every host to keep the pod's commit "
                      f"barrier aligned")
        self._drain_inflight()
        # blocking part: the drain above + fetching THIS process's owned
        # shards to host — the next train step donates those buffers
        with spans.span("ckpt_snapshot", step=step):
            blocks = ckpt.host_shard_snapshot(state, self._shard_owner)
        blocking = time.monotonic() - t0
        path = os.path.join(self.directory, name)
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="fdt-ckpt")
        self._inflight_path = path
        self._skip_logged = False
        self._inflight = self._pool.submit(
            self._write_shards_and_commit, path, blocks, meta)
        self._record_save(step, blocking, segment)
        return True

    def _write_shards_and_commit(self, path: str, blocks: list,
                                 meta: dict) -> None:
        """Background worker body: phase-1 shard write (every host),
        phase-2 barrier + COMMIT (process 0 only)."""
        with spans.span("ckpt_commit", step=meta.get("step")):
            ckpt.write_host_shards(path, self._pi, blocks,
                                   backend=self.backend)
            if self._pi == 0:
                ckpt.commit_sharded_checkpoint(
                    path, meta, n_hosts=self._pc,
                    timeout_s=self._commit_timeout_s,
                    backend=self.backend)

    def _record_save(self, step: int, blocking_s: float,
                     segment: str = "checkpoint_blocking_s") -> None:
        """Cadence anchors + blocking time (into `segment` — cadence
        saves bill checkpoint_blocking_s, the preemption path passes
        emergency_save_s so the seconds land in exactly ONE badput
        bucket), recorded at INITIATION (a failed write must not trigger
        an immediate save-retry storm); the 'saves' counter is only
        incremented once a save actually COMMITS — sync: on return,
        async: at _finalize_inflight."""
        self._last_save_t = time.monotonic()
        self._last_save_step = step
        if self._goodput:
            self._goodput.add(segment, blocking_s)

    def _name(self, step: int) -> str:
        return f"{self.prefix}_step_{step:09d}"

    def align_cadence(self, step: int) -> None:
        """Re-anchor the step cadence at `step` (idempotent, forward
        only).  Called after a completed slice re-admission
        (coordinator.consume_cadence_align): hold and catch-up phases
        suppressed different ticks on different hosts, and the pod's
        commit barrier needs every host's NEXT tick to be the same pure
        function of the shared step sequence again."""
        self._last_save_step = max(self._last_save_step or 0, int(step))

    def _finalize_inflight(self) -> None:
        """Reap a COMPLETED background save: surface its error (warn +
        count, never crash training over a failed save) and prune."""
        fut, self._inflight = self._inflight, None
        self._inflight_path = None
        if fut is None:
            return
        try:
            fut.result()
        except Exception as e:
            if self._goodput:
                self._goodput.count("save_failures")
            self._log(f"[ckpt] background save failed: {e!r} — training "
                      f"continues; the previous checkpoint remains newest")
            return
        if self._goodput:
            if self._sharded and self._pi != 0:
                # this host only knows its phase-1 shard write landed;
                # whether process 0's barrier COMMITTED the step is not
                # observable here — count the honest thing and leave
                # 'saves' (= committed checkpoints) to process 0
                self._goodput.count("shard_writes")
            else:
                self._goodput.count("saves")   # committed for real
        self._prune()

    def _drain_inflight(self) -> None:
        if self._inflight is not None:
            try:
                self._inflight.result()
            except Exception:
                pass
            self._finalize_inflight()

    def adopt_identity(self, process_index: int,
                       shard_owner: Optional[Callable] = None) -> None:
        """Re-key this manager to an adopted pod seat (r17 warm spares):
        a spare parks under a synthetic out-of-pod index (it must never
        commit, prune, or sweep while the real pod runs) and, after
        claiming a failed member's seat, takes over that member's shard
        ownership, commit-barrier role, and log gating."""
        self._pi = int(process_index)
        if shard_owner is not None:
            self._shard_owner = shard_owner
        self._log = self._base_log if self._pi == 0 else (lambda *_: None)

    def wait(self) -> None:
        """Block until no save is in flight (tests / epoch boundaries)."""
        self._drain_inflight()

    def close(self) -> None:
        self._drain_inflight()
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None

    # -- discovery / restore ----------------------------------------------

    def _entries(self) -> List[Tuple[int, str]]:
        """[(step, dirname)] of this prefix's step checkpoints, any
        state — discovered through the backend's one-level entry
        listing (an object store has no directories: the "entry" is the
        first key component under the manager's namespace; POSIX reads
        one directory, never walking the tree)."""
        out = []
        for name in self.backend.list_entries(self.directory):
            m = _STEP_DIR.match(name)
            if m and m.group("prefix") == self.prefix:
                out.append((int(m.group("step")), name))
        return sorted(out)

    def committed_steps(self) -> List[int]:
        return [s for s, n in self._entries()
                if ckpt.is_committed(os.path.join(self.directory, n),
                                     backend=self.backend)]

    def latest_valid(self) -> Optional[Tuple[int, str]]:
        """Newest COMMITTED (step, name); commit says "fully written",
        restore_latest additionally survives corrupted-but-committed."""
        for step, name in reversed(self._entries()):
            if ckpt.is_committed(os.path.join(self.directory, name),
                                 backend=self.backend):
                return step, name
        return None

    def restore_latest(self, state) -> Optional[Tuple[Any, dict]]:
        """Span-wrapped entry (telemetry "restore" — failed walks still
        record their cost; that time IS the MTTR restore component):
        see :meth:`_restore_latest_impl` for the semantics."""
        with spans.span("restore"):
            return self._restore_latest_impl(state)

    def peek_latest(self, state) -> Optional[Tuple[Any, dict]]:
        """Barrier-free READ-ONLY restore of the newest committed
        checkpoint — the warm-spare refresh path (r17).  A parked spare
        is OUTSIDE the pod's restore protocol: it must neither join the
        members' rendezvous/agreement barriers (it would wedge them)
        nor sweep uncommitted residue (restore_latest's deletion point
        is only race-free because the peers are blocked in the
        agreement collective — a spare has no such guarantee).  Walks
        newest-first past corrupt-but-committed entries exactly like
        restore_latest; returns (state, meta) or None.  Does NOT touch
        cadence anchors or goodput (a refresh is not recovery)."""
        for step, name in reversed(self._entries()):
            path = os.path.join(self.directory, name)
            if not ckpt.is_committed(path, backend=self.backend):
                continue
            try:
                if ckpt.is_sharded_checkpoint(path, backend=self.backend):
                    restored, _e, _b = ckpt.restore_sharded_checkpoint(
                        self.directory, name, state, backend=self.backend)
                else:
                    restored, _e, _b = ckpt.restore_checkpoint(
                        self.directory, name, state)
                meta = ckpt.read_checkpoint_meta(self.directory, name,
                                                 backend=self.backend)
                return restored, meta
            except Exception as e:
                self._base_log(f"[ckpt] peek: checkpoint {name} is "
                               f"committed but failed to restore ({e!r}); "
                               f"trying the previous one")
        return None

    def _restore_latest_impl(self, state) -> Optional[Tuple[Any, dict]]:
        """(restored_state, meta) from the newest checkpoint that BOTH
        carries a commit marker and actually restores — a committed-but-
        corrupt newest (bit rot, torn block device) falls back to the
        previous valid one with a warning.  None when nothing restores.
        Sharded (per-host shard-file) and single-file orbax checkpoints
        interoperate: each entry restores through whichever format it
        was written in, so a pod run resumes from a pre-sharding
        checkpoint (and vice versa) transparently."""
        self._drain_inflight()
        # Pre-walk rendezvous (r10): no host may WALK until every host
        # has drained its in-flight background write.  Without it, a
        # host that restarts quickly after a pod failure walks the
        # directory before a slower peer's two-phase COMMIT lands,
        # restores an older step (or nothing), and the agreement below
        # kills the attempt with RestoreDivergence — burning a whole
        # restart generation on a transient that draining fixes.  One
        # extra allgather per restore; restores are rare.
        self._rendezvous()
        result, restored_step, t0 = None, -1, time.monotonic()
        for step, name in reversed(self._entries()):
            path = os.path.join(self.directory, name)
            if not ckpt.is_committed(path, backend=self.backend):
                continue
            try:
                if ckpt.is_sharded_checkpoint(path, backend=self.backend):
                    restored, _epoch, _best = ckpt.restore_sharded_checkpoint(
                        self.directory, name, state, backend=self.backend)
                else:
                    restored, _epoch, _best = ckpt.restore_checkpoint(
                        self.directory, name, state)
                meta = ckpt.read_checkpoint_meta(self.directory, name,
                                                 backend=self.backend)
                saved_layout = meta.get("opt_state_layout")
                live_layout = ckpt.opt_state_layout(restored)
                if saved_layout and live_layout \
                        and saved_layout != live_layout:
                    self._log(f"[ckpt] restore {name}: opt-state layout "
                              f"changed {saved_layout} -> {live_layout} "
                              f"(ZeRO<->replicated interchange; values "
                              f"re-placed by the restore template)")
                result, restored_step = (restored, meta), step
                break
            except Exception as e:
                self._log(f"[ckpt] checkpoint {name} is committed but "
                          f"failed to restore ({e!r}); falling back to "
                          f"the previous one")
        # Sweep ALL uncommitted residue now, BEFORE the agreement
        # collective: a crashed sharded save leaves a dir with every
        # host's DONE marker but no COMMIT, and if it survived to the
        # re-reached save step the commit barrier would see the stale
        # markers and COMMIT a mix of two attempts' shard files.
        # Restore is the one point where deletion is race-free — the
        # peers are blocked in _gather_restored_steps below until
        # process 0 (the only deleter) joins, so no host can be
        # writing.  Uncommitted dirs are never restorable, so this
        # deletes only disk (and the stale-marker trap).
        if self._pi == 0:
            for _s, n in self._entries():
                p = os.path.join(self.directory, n)
                if not ckpt.is_committed(p, backend=self.backend):
                    self._delete(p)
        # cross-host agreement AFTER the walk, joined by EVERY host
        # regardless of its outcome (None restores gather -1): a host
        # whose walk fell back — or exhausted every entry — must still
        # meet its peers in the collective, or they would block forever
        # waiting for it instead of raising
        gathered = (self._step_gather_fn(restored_step, phase="agree")
                    if self._step_gather_fn is not None
                    else self._gather_restored_steps(restored_step))
        self._verify_restore_agreement(gathered)
        if result is None:
            return None
        if self._goodput:
            self._goodput.count("restores")
            self._goodput.add("restore_s", time.monotonic() - t0)
        self._last_save_step = restored_step
        return result

    def _rendezvous(self) -> None:
        """The pre-walk barrier of restore_latest: joined by every host
        AFTER draining its in-flight write, so the newest checkpoint's
        COMMIT (or its absence) is identical in every host's subsequent
        walk.  The gathered values are ignored — only the rendezvous
        matters."""
        if self._step_gather_fn is not None:
            self._step_gather_fn(0, phase="enter")
        else:
            self._gather_restored_steps(0)

    @staticmethod
    def _gather_restored_steps(step: int) -> np.ndarray:
        """Every REAL host's restored step (−1 = nothing restored),
        stacked — the collective piece, split from the pure decision
        below so the tier-1 simulated-pod tests can exercise the
        decision without multi-process collectives."""
        if jax.process_count() == 1:
            return np.asarray([step], np.int32)
        from faster_distributed_training_tpu.parallel.collectives import (
            all_gather_across_processes)
        return all_gather_across_processes(np.asarray(step, np.int32))

    @staticmethod
    def _verify_restore_agreement(steps: np.ndarray) -> None:
        """Multi-host: the restore walk runs independently per host, so a
        host whose shard-file read failed (torn page, transient IO) would
        silently fall back to an OLDER checkpoint while its peers resume
        the newest — divergent state with no error.  Fail LOUDLY on
        disagreement (every host sees the same gathered vector, so all
        raise together); the r7 collective restore failed loudly too,
        this keeps that property."""
        if int(steps.min()) != int(steps.max()):
            raise RestoreDivergence(
                f"hosts restored different checkpoint steps "
                f"{sorted(set(int(s) for s in steps))} (−1 = none) — a "
                f"per-host shard-read failure made one host fall back "
                f"while its peers took the newest; refusing to resume "
                f"divergent (clear or repair the newest checkpoint dir "
                f"and rerun)")

    # -- retention --------------------------------------------------------

    def _prune(self) -> None:
        """Keep the newest `keep` COMMITTED checkpoints; also sweep
        uncommitted residue older than the newest committed one (a
        half-written dir from a crash — never restorable, only disk).
        Process 0 only; other hosts see the shared result.  Deletion is
        the backend's BATCHED ``delete_prefix`` (r14 — rmtree on POSIX,
        list+batched object deletes on GCS/fake; the ``delete_fn`` hook
        still overrides for custom retention policies)."""
        if self._pi != 0:
            return
        entries = self._entries()
        committed = [(s, n) for s, n in entries if ckpt.is_committed(
            os.path.join(self.directory, n), backend=self.backend)]
        doomed = [n for _s, n in committed[:-self.keep]]
        if committed:
            newest_committed = committed[-1][0]
            doomed += [n for s, n in entries
                       if s < newest_committed
                       and not ckpt.is_committed(
                           os.path.join(self.directory, n),
                           backend=self.backend)
                       and os.path.join(self.directory, n)
                       != self._inflight_path]
        for n in doomed:
            self._delete(os.path.join(self.directory, n))
