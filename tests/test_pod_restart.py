"""Pod-coordinated restart + cluster health watchdog tests (r10,
resilience/coordinator.py) — all CPU, ONE pytest process, tier-1.

The simulation seam is the r9 one, extended: two PodCoordinators /
AsyncCheckpointManagers / Supervisors with complementary
``process_index`` against ONE shared directory ARE a simulated two-host
pod — each "host" runs in its own thread (jax stays single-process, so
every host computes the identical full state), coordination happens
purely through the shared-fs marker files, and the manager's restore
step-agreement rides the coordinator's marker-file allgather
(``step_gather_fn``) instead of a real jax collective.  The ISSUE
acceptance tests at the bottom drive REAL train steps through real
supervisors end-to-end: kill one host → both converge on the next
generation, restore the SAME step, and finish bitwise-equal to the
uninterrupted reference; injected hang → the watchdog (the only thing
able to act while the main thread is blocked) escalates and the pod
restarts without deadlock."""

import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from faster_distributed_training_tpu.config import TrainConfig
from faster_distributed_training_tpu.models import Transformer
from faster_distributed_training_tpu.optim import build_optimizer
from faster_distributed_training_tpu.resilience import (
    AsyncCheckpointManager, FakeObjectStoreBackend, FaultPlan,
    GoodputTracker, PeerFailure, PodCoordinator, StepTimeout, Supervisor,
    build_resilience, pod_identity, slice_identity)
from faster_distributed_training_tpu.resilience import coordinator as coord_mod
from faster_distributed_training_tpu.resilience import faults as faults_mod
from faster_distributed_training_tpu.train import (checkpoint as ckpt,
                                                   create_train_state,
                                                   make_train_step)


def _tiny_state(seed=0):
    """Small but real TrainState (transformer d16) + one batch — the
    test_resilience.py fixture, duplicated so this file imports nothing
    from another test module."""
    cfg = TrainConfig(model="transformer", dataset="agnews", num_classes=4,
                      batch_size=4, seq_len=8, optimizer="sgd",
                      precision="fp32", epochs=1, donate=False)
    model = Transformer(n_class=4, vocab=32, n_layers=1, h=2, d_model=16,
                        d_ff=32, d_hidden=16, maxlen=8)
    tx, _ = build_optimizer(cfg, steps_per_epoch=2)
    state = create_train_state(model, tx, jnp.zeros((4, 8), jnp.int32),
                               jax.random.PRNGKey(seed),
                               init_kwargs={"train": True})
    batch = {"tokens": np.random.default_rng(0).integers(
                 0, 32, size=(4, 8)).astype(np.int32),
             "label": np.arange(4, dtype=np.int32) % 4}
    return cfg, state, batch


def _assert_tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestPodIdentity:
    def test_env_seam_overrides_runtime(self):
        assert pod_identity({"FDT_POD_COUNT": "2",
                             "FDT_POD_INDEX": "1"}) == (1, 2, True)
        assert pod_identity({"FDT_POD_COUNT": "4"}) == (0, 4, True)

    def test_without_env_reads_jax_runtime(self):
        pi, pc, sim = pod_identity({})
        assert (pi, pc) == (jax.process_index(), jax.process_count())
        assert not sim


class TestGenerationProtocol:
    def _pair(self, d, **kw):
        kw.setdefault("sync_every", 1)
        kw.setdefault("peer_timeout_s", 0.0)   # staleness off: these
        # tests pin the FAIL-marker protocol alone
        c0 = PodCoordinator(str(d), process_index=0, process_count=2,
                            log=lambda *_: None, **kw)
        c1 = PodCoordinator(str(d), process_index=1, process_count=2,
                            log=lambda *_: None, **kw)
        return c0, c1

    def test_failure_converges_both_hosts_on_next_generation(self, tmp_path):
        c0, c1 = self._pair(tmp_path)
        try:
            assert c0.begin_attempt() == 0
            assert c1.begin_attempt() == 0
            c0.check(1)                      # clean generation: no raise
            c1.record_failure(RuntimeError("boom"), step=6)
            with pytest.raises(PeerFailure, match=r"host\(s\) \[1\]"):
                c0.check(2)
            # BOTH re-enter at 1 + the newest failed generation — however
            # each got there (own crash vs observed peer failure)
            assert c1.begin_attempt() == 1
            assert c0.begin_attempt() == 1
            c0.check(1)                      # new generation is clean
        finally:
            c0.close(), c1.close()

    def test_fail_marker_payload_and_kinds(self, tmp_path):
        c0, c1 = self._pair(tmp_path)
        try:
            c1.begin_attempt()
            c1.record_failure(StepTimeout("wedged"), step=7)
            marker = os.path.join(str(tmp_path), "gen_000000", "FAIL_00001")
            with open(marker) as f:
                got = json.load(f)
            assert got["kind"] == "hang" and got["step"] == 7
            assert "wedged" in got["reason"]
            c1.record_failure(PeerFailure("peer died"))
            with open(marker) as f:
                assert json.load(f)["kind"] == "peer"
        finally:
            c0.close(), c1.close()

    def test_fresh_process_joins_incident_generation(self, tmp_path):
        c0, c1 = self._pair(tmp_path)
        try:
            c1.begin_attempt()
            c1.record_failure(RuntimeError("x"), step=3)
        finally:
            c0.close(), c1.close()
        # a re-LAUNCHED process (nothing in memory) joins at the
        # incident's next generation instead of rewinding to 0
        fresh = PodCoordinator(str(tmp_path), process_index=0,
                               process_count=2, peer_timeout_s=0.0,
                               log=lambda *_: None)
        try:
            assert fresh.begin_attempt() == 1
        finally:
            fresh.close()

    def test_check_cadence_gating(self, tmp_path):
        c0, c1 = self._pair(tmp_path, sync_every=4)
        try:
            c0.begin_attempt(), c1.begin_attempt()
            c0.check(1)                       # first poll of the attempt
            c1.record_failure(RuntimeError("late"), step=1)
            c0.check(2)                       # same sync window: no poll
            c0.check(3)
            with pytest.raises(PeerFailure):
                c0.check(4)                   # crossed the boundary
        finally:
            c0.close(), c1.close()

    def test_generation_pruning_keeps_recent(self, tmp_path):
        c0 = PodCoordinator(str(tmp_path), process_index=0, process_count=1,
                            peer_timeout_s=0.0, log=lambda *_: None)
        try:
            for g in range(6):
                d = os.path.join(str(tmp_path), f"gen_{g:06d}")
                os.makedirs(d)
                coord_mod._write_json_atomic(
                    os.path.join(d, "FAIL_00000"), {"kind": "crash"})
            assert c0.begin_attempt() == 6
            kept = sorted(n for n in os.listdir(str(tmp_path))
                          if n.startswith("gen_"))
            assert kept == ["gen_000004", "gen_000005", "gen_000006"]
        finally:
            c0.close()


class TestHealthWatchdog:
    def test_missing_peer_heartbeat_goes_stale(self, tmp_path):
        g = GoodputTracker().start()
        c0 = PodCoordinator(str(tmp_path), process_index=0, process_count=2,
                            sync_every=1, peer_timeout_s=0.15, goodput=g,
                            log=lambda *_: None)
        try:
            c0.begin_attempt()
            c0.check(1)             # within the attempt-start grace
            time.sleep(0.25)
            with pytest.raises(PeerFailure, match="heartbeat-stale"):
                c0.check(2)
            assert g.summary()["peer_failures"] == 1
        finally:
            c0.close()

    def test_exited_peer_not_stale_and_stale_detect_latency(self, tmp_path):
        """r10 review fixes: (1) heartbeat-staleness detect_s is the full
        silence age — necessarily >= peer_timeout_s, a silent death
        cannot be observed faster than the threshold (the previous
        max(age - timeout, 0) under-reported MTTR by ~timeout for
        exactly the SIGKILL/machine-loss class the watchdog exists
        for); (2) an EXITED peer's quiet heartbeat is success, not
        death — stragglers keep running instead of restart-looping."""
        g = GoodputTracker().start()
        c0 = PodCoordinator(str(tmp_path), process_index=0, process_count=2,
                            sync_every=1, peer_timeout_s=5.0, goodput=g,
                            log=lambda *_: None)
        c1 = PodCoordinator(str(tmp_path), process_index=1, process_count=2,
                            sync_every=1, peer_timeout_s=5.0,
                            log=lambda *_: None)
        try:
            c1.begin_attempt()          # one heartbeat, then silence
            c1.close()
            c0.begin_attempt()
            c0.check(1)                 # fresh heartbeat: healthy
            # silence is SIMULATED by backdating the heartbeat mtime
            # (no sleeps — load-robust), 10 s > the 5 s timeout
            hb1 = os.path.join(c0._require_gen(), "HB_00001")
            past = time.time() - 10.0
            os.utime(hb1, (past, past))
            with pytest.raises(PeerFailure, match="heartbeat-stale"):
                c0.check(2)
            assert g.summary()["detect_s"] >= 5.0     # full silence age
            # peer 1 actually FINISHED: its EXIT marker retro-explains
            # the silence and host 0 keeps running
            c1.record_completion(step=8)
            c0.check(3)                 # no raise
        finally:
            c0.close(), c1.close()

    def test_live_peer_heartbeat_keeps_pod_healthy(self, tmp_path):
        c0 = PodCoordinator(str(tmp_path), process_index=0, process_count=2,
                            sync_every=1, peer_timeout_s=0.4,
                            hb_interval_s=0.05, log=lambda *_: None)
        c1 = PodCoordinator(str(tmp_path), process_index=1, process_count=2,
                            sync_every=1, peer_timeout_s=0.4,
                            hb_interval_s=0.05, log=lambda *_: None)
        try:
            c0.begin_attempt(), c1.begin_attempt()
            for i in range(1, 4):
                time.sleep(0.15)    # > several hb intervals, < timeout
                c0.check(i)         # peer 1's thread keeps HB fresh
        finally:
            c0.close(), c1.close()
        # AFTER close (heartbeats stopped) staleness accrues again
        time.sleep(0.5)
        c2 = PodCoordinator(str(tmp_path), process_index=0, process_count=2,
                            sync_every=1, peer_timeout_s=0.4,
                            log=lambda *_: None)
        try:
            c2._attempt_wall_t = time.time() - 10.0   # no fresh-start grace
            with pytest.raises(PeerFailure, match="heartbeat-stale"):
                c2.check(1)
        finally:
            c2.close()

    def test_step_watchdog_escalates_writes_fail_then_aborts(self, tmp_path):
        aborted = threading.Event()
        g = GoodputTracker().start()
        c0 = PodCoordinator(str(tmp_path), process_index=0, process_count=1,
                            step_timeout_s=0.15, hb_interval_s=0.03,
                            peer_timeout_s=0.0, goodput=g,
                            abort_fn=lambda reason: aborted.set(),
                            log=lambda *_: None)
        try:
            c0.begin_attempt()
            with c0.watch_steps():
                c0.check(1)
                # the "main thread" stops making progress; only the
                # watchdog thread can act
                assert aborted.wait(5.0), "watchdog never escalated"
            fails = c0._failures(c0._gen_dir)
            assert fails[0]["kind"] == "hang"       # durably published
            assert g.summary()["step_timeouts"] == 1
            # the intercepted abort surfaces as a RESTARTABLE fault on
            # the very next poll (cadence bypassed after escalation)
            with pytest.raises(StepTimeout, match="watchdog"):
                c0.check(2)
        finally:
            c0.close()

    def test_watchdog_only_armed_inside_watch_steps(self, tmp_path):
        aborted = threading.Event()
        c0 = PodCoordinator(str(tmp_path), process_index=0, process_count=1,
                            step_timeout_s=0.1, hb_interval_s=0.02,
                            peer_timeout_s=0.0,
                            abort_fn=lambda reason: aborted.set(),
                            log=lambda *_: None)
        try:
            c0.begin_attempt()
            time.sleep(0.3)      # eval/restore phase: no step progress,
            assert not aborted.is_set()   # no escalation
        finally:
            c0.close()

    def test_pause_watch_suspends_escalation_during_blocking_saves(
            self, tmp_path):
        """r10 review fix: blocking checkpoint work on the step thread
        (a cadence save draining a prior write's commit barrier, the
        preemption emergency save) is legitimate stalling — inside
        pause_watch the watchdog must NOT SIGKILL the healthy host,
        and it re-arms with a fresh step clock on exit."""
        aborted = threading.Event()
        c0 = PodCoordinator(str(tmp_path), process_index=0, process_count=1,
                            step_timeout_s=0.5, hb_interval_s=0.02,
                            peer_timeout_s=0.0,
                            abort_fn=lambda reason: aborted.set(),
                            log=lambda *_: None)
        try:
            c0.begin_attempt()
            with c0.watch_steps():
                with c0.pause_watch():
                    time.sleep(1.5)       # "saving": way past the timeout
                assert not aborted.is_set()
                # re-armed: a REAL stall after resume still escalates
                assert aborted.wait(timeout=10.0)
        finally:
            c0.close()


class TestRestoreStepGather:
    """The fs allgather that replaces the jax restore-agreement
    collective on fs-simulated pods (manager ``step_gather_fn``)."""

    def _pair(self, d, **kw):
        kw.setdefault("peer_timeout_s", 0.0)
        return (PodCoordinator(str(d), process_index=0, process_count=2,
                               log=lambda *_: None, **kw),
                PodCoordinator(str(d), process_index=1, process_count=2,
                               log=lambda *_: None, **kw))

    def test_rendezvous_returns_every_hosts_step(self, tmp_path):
        c0, c1 = self._pair(tmp_path)
        out = {}
        try:
            c0.begin_attempt(), c1.begin_attempt()
            t = threading.Thread(
                target=lambda: out.update(r1=c1.gather_restored_step(-1)))
            t.start()
            out["r0"] = c0.gather_restored_step(4)
            t.join(timeout=30)
            np.testing.assert_array_equal(out["r0"], [4, -1])
            np.testing.assert_array_equal(out["r1"], [4, -1])
        finally:
            c0.close(), c1.close()

    def test_barrier_timeout_raises_instead_of_deadlocking(self, tmp_path):
        c0, _c1 = self._pair(tmp_path, gather_timeout_s=0.2)
        try:
            c0.begin_attempt()
            with pytest.raises(PeerFailure, match="timed out"):
                c0.gather_restored_step(4)
        finally:
            c0.close(), _c1.close()

    def test_peer_failure_during_barrier_raises(self, tmp_path):
        c0, c1 = self._pair(tmp_path)
        try:
            c0.begin_attempt(), c1.begin_attempt()
            c1.record_failure(RuntimeError("died mid-restore"))
            with pytest.raises(PeerFailure, match="restore-agreement"):
                c0.gather_restored_step(4)
        finally:
            c0.close(), c1.close()

    def test_stale_exit_from_previous_run_ignored(self, tmp_path):
        """r10 review fix: EXIT markers are time-scoped to THIS run — a
        previous completed run's markers in a reused checkpoint_dir
        must neither fail fresh restore barriers ("pod already
        finished") nor disable peer-staleness detection, and a
        relaunching host clears its own."""
        c1a = PodCoordinator(str(tmp_path), process_index=1,
                             process_count=2, log=lambda *_: None)
        try:
            c1a.begin_attempt()
            c1a.record_completion(step=16)     # run 1 finished
        finally:
            c1a.close()
        time.sleep(0.05)
        # run 2 relaunches host 0 in the same directory
        c0 = PodCoordinator(str(tmp_path), process_index=0, process_count=2,
                            sync_every=1, peer_timeout_s=5.0,
                            gather_timeout_s=0.3, log=lambda *_: None)
        try:
            c0.begin_attempt()
            with pytest.raises(PeerFailure, match="timed out"):
                c0.gather_restored_step(4)     # waits — no stale fail-fast
            # ...and staleness detection still works against the peer
            hb1 = os.path.join(c0._require_gen(), "HB_00001")
            past = time.time() - 10.0
            os.utime(hb1, (past, past))
            with pytest.raises(PeerFailure, match="heartbeat-stale"):
                c0.check(1)
        finally:
            c0.close()
        # host 1's relaunch clears its own stale completion marker
        c1b = PodCoordinator(str(tmp_path), process_index=1,
                             process_count=2, log=lambda *_: None)
        try:
            c1b.begin_attempt()
            assert not os.path.exists(
                os.path.join(str(tmp_path), "EXIT_00001"))
        finally:
            c1b.close()

    def test_completed_peer_fails_barrier_fast_not_timeout(self, tmp_path):
        """r10 review fix: a peer that already COMPLETED the run (EXIT
        marker) can never join the barrier — a host restarting after
        its peer finished must learn that in milliseconds, not wait
        out gather_timeout_s per supervisor attempt."""
        c0, c1 = self._pair(tmp_path, gather_timeout_s=30.0)
        try:
            c0.begin_attempt(), c1.begin_attempt()
            c1.record_completion(step=16)
            t0 = time.monotonic()
            with pytest.raises(PeerFailure, match="already completed"):
                c0.gather_restored_step(4)
            assert time.monotonic() - t0 < 5.0    # fast, not the timeout
        finally:
            c0.close(), c1.close()


class TestBuildResilienceWiring:
    """config -> bundle: the env pod seam grows a coordinator, the
    manager rides the coordinator's step gather, and the plain
    single-host default stays coordinator-free."""

    def _cfg(self, tmp, **kw):
        return TrainConfig(model="transformer", dataset="synthetic",
                           checkpoint_dir=str(tmp), checkpoint_every=2,
                           donate=False, **kw)

    def test_simulated_pod_gets_coordinator_and_gather(self, tmp_path,
                                                       monkeypatch):
        monkeypatch.setenv(coord_mod.ENV_POD_INDEX, "1")
        monkeypatch.setenv(coord_mod.ENV_POD_COUNT, "2")
        res = build_resilience(self._cfg(tmp_path, supervise=True),
                               log=lambda *_: None)
        try:
            assert res.pod_simulated and (res.pod_index,
                                          res.pod_count) == (1, 2)
            assert res.coordinator is not None
            assert res.coordinator.directory == os.path.join(
                str(tmp_path), "_pod")
            assert res.manager is not None
            assert res.manager._step_gather_fn == \
                res.coordinator.gather_restored_step
            assert res.manager._sharded and res.manager._pi == 1
            # non-zero simulated host owns no shards (host 0 writes the
            # full replica-0 cover of the identical state)
            assert not res.manager._shard_owner(object())
        finally:
            res.close()

    def test_single_host_default_has_no_coordinator(self, tmp_path):
        res = build_resilience(self._cfg(tmp_path, supervise=True),
                               log=lambda *_: None)
        try:
            assert res.coordinator is None and res.pod_count == 1
        finally:
            res.close()

    def test_step_timeout_arms_watchdog_even_single_host(self, tmp_path):
        res = build_resilience(
            self._cfg(tmp_path, supervise=True, step_timeout_s=120.0),
            log=lambda *_: None)
        try:
            assert res.coordinator is not None
            assert res.coordinator.step_timeout_s == 120.0
        finally:
            res.close()

    def test_commit_timeout_tied_to_peer_timeout_when_armed(
            self, tmp_path, monkeypatch):
        """r17 satellite (the r14 follow-on): whenever a pod coordinator
        is armed, the manager's commit-barrier timeout defaults to
        O(peer_timeout_s) instead of the historic 600s — a barrier that
        outlives peer detection turns every re-admission hold into a
        pod_fallback_restart."""
        monkeypatch.setenv(coord_mod.ENV_POD_INDEX, "0")
        monkeypatch.setenv(coord_mod.ENV_POD_COUNT, "2")
        res = build_resilience(
            self._cfg(tmp_path, supervise=True, peer_timeout_s=20.0),
            log=lambda *_: None)
        try:
            assert res.manager._commit_timeout_s == 40.0   # max(2x, 10)
        finally:
            res.close()
        # a tiny peer timeout still gets the 10s floor
        res = build_resilience(
            self._cfg(tmp_path, supervise=True, peer_timeout_s=1.0),
            log=lambda *_: None)
        try:
            assert res.manager._commit_timeout_s == 10.0
        finally:
            res.close()

    def test_commit_timeout_unarmed_keeps_600_and_user_value_warns(
            self, tmp_path, monkeypatch):
        # no coordinator (single host, no supervise): historic default
        res = build_resilience(self._cfg(tmp_path), log=lambda *_: None)
        try:
            assert res.manager._commit_timeout_s == 600.0
        finally:
            res.close()
        # a user value that INVERTS the detection ordering warns
        monkeypatch.setenv(coord_mod.ENV_POD_INDEX, "0")
        monkeypatch.setenv(coord_mod.ENV_POD_COUNT, "2")
        logs = []
        res = build_resilience(
            self._cfg(tmp_path, supervise=True, peer_timeout_s=60.0,
                      commit_timeout_s=5.0),
            log=logs.append)
        try:
            assert res.manager._commit_timeout_s == 5.0   # honored...
            assert any("commit_timeout_s" in m and "WARNING" in m
                       for m in logs)                     # ...but warned
        finally:
            res.close()
        # ...and one that outlives the re-admission hold window warns too
        monkeypatch.setenv(coord_mod.ENV_SLICE_COUNT, "2")
        logs.clear()
        res = build_resilience(
            self._cfg(tmp_path, supervise=True, peer_timeout_s=10.0,
                      readmit_timeout_s=30.0, commit_timeout_s=120.0),
            log=logs.append)
        try:
            assert any("readmit_timeout_s" in m and "WARNING" in m
                       for m in logs)
        finally:
            res.close()

    def test_spare_env_builds_out_of_pod_identity(self, tmp_path,
                                                  monkeypatch):
        """r17 warm spares: FDT_SLICE_SPARE parks the bundle under a
        synthetic out-of-pod index (pc + spare id) — its markers, shard
        files and commit-barrier role can never collide with a
        member's — and the coordinator carries the spare identity."""
        monkeypatch.setenv(coord_mod.ENV_POD_COUNT, "2")
        monkeypatch.setenv(coord_mod.ENV_SLICE_COUNT, "2")
        monkeypatch.setenv(coord_mod.ENV_SLICE_SPARE, "0")
        res = build_resilience(self._cfg(tmp_path, supervise=True),
                               log=lambda *_: None)
        try:
            assert res.spare_index == 0
            assert res.pod_index == 2           # pc + spare id
            assert res.coordinator is not None
            assert res.coordinator.spare_index == 0
            assert res.coordinator.pi == 2
            assert res.manager._pi == 2         # never commits/prunes
        finally:
            res.close()

    def test_step_timeout_without_supervise_warns(self, tmp_path):
        """r10 review fix: the hang watchdog lives on the coordinator,
        which only the supervised path builds — --step_timeout_s
        without --supervise must WARN rather than silently no-op, even
        when it is the only resilience flag (bundle not built at
        all)."""
        logs = []
        cfg = TrainConfig(model="transformer", dataset="synthetic",
                          checkpoint_dir=str(tmp_path), donate=False,
                          step_timeout_s=60.0)
        assert build_resilience(cfg, log=logs.append) is None
        assert any("step_timeout_s" in m and "WARNING" in m for m in logs)
        # with cadence on, the bundle builds but still warns + no watchdog
        logs.clear()
        res = build_resilience(self._cfg(tmp_path, step_timeout_s=60.0),
                               log=logs.append)
        try:
            assert res.coordinator is None
            assert any("WARNING" in m for m in logs)
        finally:
            res.close()


class TestBatchOrderReagreement:
    """The restart protocol ASSUMES nothing about data position: the
    batch order is a pure function of (seed, epoch), so hosts that
    restart re-derive the identical stream and a mid-epoch resume is a
    skip into the same permutation.  The ISSUE says assert this, not
    assume it — a stateful/shuffled-in-place loader would silently
    diverge the pod after a coordinated restart."""

    def test_order_is_pure_in_seed_epoch_across_restarts(self):
        from faster_distributed_training_tpu.data.loader import (
            pod_epoch_order, shard_for_host)
        for epoch in (0, 1, 5):
            a = shard_for_host(257, epoch, seed=3)
            b = shard_for_host(257, epoch, seed=3)   # "restarted" host
            np.testing.assert_array_equal(a, b)
            pa = pod_epoch_order(64, epoch, seed=3, process_count=2,
                                 local_batch_size=4)
            pb = pod_epoch_order(64, epoch, seed=3, process_count=2,
                                 local_batch_size=4)
            np.testing.assert_array_equal(pa, pb)
        # different epochs genuinely reshuffle (the purity is in (seed,
        # epoch), not a frozen order)
        assert not np.array_equal(shard_for_host(257, 0, seed=3),
                                  shard_for_host(257, 1, seed=3))

    def test_mid_epoch_resume_position_reagrees(self):
        """Skipping start_step batches of a freshly rebuilt loader
        replays exactly the remainder of the original stream — the
        property the coordinated restart's mid-epoch resume rides."""
        from faster_distributed_training_tpu.data import (BatchLoader,
                                                          synthetic_agnews)
        ds = synthetic_agnews(n=64, max_len=16)
        mk = lambda: BatchLoader(ds, batch_size=8, epoch=1, seed=5,  # noqa: E731,E501
                                 max_len=16, process_index=0,
                                 process_count=1)
        full = [b["tokens"] for b in mk()]
        resumed = [b["tokens"] for b in mk()][3:]     # skip-replay
        assert len(full) == 8
        for a, b in zip(full[3:], resumed):
            np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# ISSUE acceptance: simulated 2-host pod, end-to-end through REAL train
# steps, managers, supervisors and the shared-fs coordination protocol.
# ---------------------------------------------------------------------------

_TOTAL = 12      # global steps per host
_EVERY = 4       # checkpoint cadence


class TestSliceIdentity:
    """r14 multi-slice seam: FDT_SLICE_INDEX/FDT_SLICE_COUNT beside
    pod_identity, contiguous-block membership, per-slice fault
    scoping (FDT_FAULT_SLICE)."""

    def test_env_seam(self):
        assert slice_identity({}) == (0, 1, False)
        assert slice_identity({"FDT_SLICE_COUNT": "1"}) == (0, 1, False)
        env = {"FDT_SLICE_COUNT": "2", "FDT_POD_COUNT": "4",
               "FDT_POD_INDEX": "3"}
        assert slice_identity(env) == (1, 2, True)
        env["FDT_SLICE_INDEX"] = "0"          # explicit override wins
        assert slice_identity(env) == (0, 2, True)

    def test_contiguous_blocks(self, tmp_path):
        c = PodCoordinator(str(tmp_path), process_index=0, process_count=8,
                           slice_index=0, slice_count=4,
                           log=lambda *_: None)
        assert [c.slice_of(p) for p in range(8)] == [0, 0, 1, 1, 2, 2, 3, 3]
        assert c._slice_members(2) == [4, 5]
        c.close()

    def test_slice_qualified_marker_names(self, tmp_path):
        c = PodCoordinator(str(tmp_path), process_index=2, process_count=4,
                           slice_index=1, slice_count=2,
                           log=lambda *_: None)
        assert c._marker_name("FAIL", 2) == "FAIL_s001_00002"
        assert c._marker_name("HB", 0) == "HB_s000_00000"
        m = coord_mod._FAIL.match(c._marker_name("FAIL", 2))
        assert m and int(m.group("pi")) == 2 and int(m.group("si")) == 1
        c.close()

    def test_fault_slice_scoping(self):
        env = {"FDT_FAULT_DIE_AT_STEP": "6", "FDT_FAULT_SLICE": "1",
               "FDT_SLICE_COUNT": "2", "FDT_POD_COUNT": "4"}
        # slice 1 = processes {2, 3}: they get the plan, slice 0 doesn't
        assert FaultPlan.from_env(env, process_index=0) is None
        assert FaultPlan.from_env(env, process_index=1) is None
        assert FaultPlan.from_env(env, process_index=2).die_at == 6
        assert FaultPlan.from_env(env, process_index=3).die_at == 6
        # composes with FDT_FAULT_HOST: both must match
        env["FDT_FAULT_HOST"] = "2"
        assert FaultPlan.from_env(env, process_index=3) is None
        assert FaultPlan.from_env(env, process_index=2).die_at == 6
        assert faults_mod.ENV_SLICE == "FDT_FAULT_SLICE"


def _slice_pair(d, readmit=10.0, backend=None, **kw):
    """Minimal 2-slice pod: one host per slice, shared directory."""
    kw.setdefault("sync_every", 1)
    kw.setdefault("peer_timeout_s", 30.0)
    out = []
    for pi in (0, 1):
        out.append(PodCoordinator(
            os.path.join(d, "_pod"), process_index=pi, process_count=2,
            slice_index=pi, slice_count=2, readmit_timeout_s=readmit,
            backend=backend, goodput=GoodputTracker(),
            log=lambda *_: None, **kw))
    return out


class TestReadmissionProtocol:
    """Unit-level drive of the r14 hold/rejoin handshake: two
    coordinators, one host per slice, no train loop."""

    def test_survivor_holds_until_rejoiner_ready_then_releases(
            self, tmp_path):
        c0, c1 = _slice_pair(str(tmp_path))
        c0.begin_attempt(), c1.begin_attempt()
        c1.record_failure(RuntimeError("boom"), step=6)
        c1.close()
        outcome = {}

        def survivor():
            try:
                c0.check(6)          # foreign-slice FAIL -> parks
                outcome["released"] = True
            except BaseException as e:   # pragma: no cover - surfaced
                outcome["error"] = e

        t = threading.Thread(target=survivor, daemon=True)
        t.start()
        hold = os.path.join(c0._gen_path(0), "HOLD_s000_00000")
        deadline = time.monotonic() + 5.0
        while not os.path.exists(hold) and time.monotonic() < deadline:
            time.sleep(0.01)
        assert os.path.exists(hold), "survivor never published its HOLD"
        assert json.load(open(hold))["step"] == 6
        # the restarted slice-1 process: fresh coordinator, same dir —
        # begin_attempt must REJOIN generation 0, not advance to 1
        c1b = PodCoordinator(
            os.path.join(str(tmp_path), "_pod"), process_index=1,
            process_count=2, sync_every=1, peer_timeout_s=30.0,
            slice_index=1, slice_count=2, readmit_timeout_s=10.0,
            goodput=GoodputTracker(), log=lambda *_: None)
        g = c1b.begin_attempt()
        assert g == 0 and c1b.rejoining
        c1b.rejoin_sync(6)           # restored step == target: completes
        t.join(timeout=10.0)
        assert outcome.get("released") is True, outcome
        # both advanced to generation 1 IN PLACE, cadence realigns at 6
        assert c0._gen == 1 and c1b._gen == 1
        assert not c1b.rejoining
        assert c0.consume_cadence_align() == 6
        assert c1b.consume_cadence_align() == 6
        assert c0.consume_cadence_align() is None      # one-shot
        s0 = c0._goodput.summary()
        s1 = c1b._goodput.summary()
        assert s0["slice_readmissions"] == 1
        assert s0["readmission_hold_s"] > 0
        assert s0["restarts"] == 0
        assert s1["slice_readmissions"] == 1
        assert s0["pod_fallback_restarts"] == 0
        c0.close(), c1b.close()

    def test_hold_timeout_falls_back_to_whole_pod(self, tmp_path):
        c0, c1 = _slice_pair(str(tmp_path), readmit=0.3)
        c0.begin_attempt(), c1.begin_attempt()
        c1.record_failure(RuntimeError("boom"), step=6)
        c1.close()
        with pytest.raises(PeerFailure, match="falling back"):
            c0.check(6)
        s0 = c0._goodput.summary()
        assert s0["pod_fallback_restarts"] == 1
        assert s0["peer_failures"] == 1
        assert s0["readmission_hold_s"] > 0.2     # the hold was real
        c0.close()

    def test_readmit_disabled_raises_immediately_like_r10(self, tmp_path):
        c0, c1 = _slice_pair(str(tmp_path), readmit=0.0)
        c0.begin_attempt(), c1.begin_attempt()
        c1.record_failure(RuntimeError("boom"), step=6)
        c1.close()
        t0 = time.monotonic()
        with pytest.raises(PeerFailure):
            c0.check(6)
        assert time.monotonic() - t0 < 1.0        # no hold happened
        assert not os.path.exists(
            os.path.join(c0._gen_path(0), "HOLD_s000_00000"))
        assert c0._goodput.summary()["pod_fallback_restarts"] == 0
        c0.close()

    def test_multi_slice_incident_goes_whole_pod(self, tmp_path):
        """Failures spanning TWO foreign slices: no hold — the r10
        whole-pod PeerFailure (re-admission only handles one slice)."""
        cs = []
        for pi in range(3):
            cs.append(PodCoordinator(
                os.path.join(str(tmp_path), "_pod"), process_index=pi,
                process_count=3, sync_every=1, slice_index=pi,
                slice_count=3, readmit_timeout_s=10.0,
                goodput=GoodputTracker(), log=lambda *_: None))
        for c in cs:
            c.begin_attempt()
        cs[1].record_failure(RuntimeError("b1"), step=6)
        cs[2].record_failure(RuntimeError("b2"), step=6)
        t0 = time.monotonic()
        with pytest.raises(PeerFailure):
            cs[0].check(6)
        assert time.monotonic() - t0 < 1.0
        for c in cs:
            c.close()

    def test_rejoin_retry_aborts_to_whole_pod(self, tmp_path):
        """Own rejoin residue in the incident generation (a previous
        rejoin attempt died mid-handshake): begin_attempt publishes
        RJ_ABORT and takes the whole-pod path — retry ambiguity always
        degrades to the proven r10 protocol."""
        c0, c1 = _slice_pair(str(tmp_path))
        c0.begin_attempt(), c1.begin_attempt()
        c1.record_failure(RuntimeError("boom"), step=6)
        # residue of a first rejoin attempt by host 1
        coord_mod._write_json_atomic(
            os.path.join(c1._gen_path(0), "RJRENTER_s001_00001"),
            {"step": 4})
        c1.close()
        c1b = PodCoordinator(
            os.path.join(str(tmp_path), "_pod"), process_index=1,
            process_count=2, sync_every=1, slice_index=1, slice_count=2,
            readmit_timeout_s=10.0, goodput=GoodputTracker(),
            log=lambda *_: None)
        g = c1b.begin_attempt()
        assert g == 1 and not c1b.rejoining       # whole-pod path
        assert os.path.exists(os.path.join(c1b._gen_path(0), "RJ_ABORT"))
        c1b.close()

    def test_stale_foreign_slice_gets_proxied_fail(self, tmp_path):
        """A silently-SIGKILLed foreign slice (no FAIL marker): the
        survivor writes a proxied FAIL on its behalf — the durable
        incident record the relaunched slice keys its rejoin on — then
        holds (here: times out into the fallback)."""
        c0, c1 = _slice_pair(str(tmp_path), readmit=0.3,
                             peer_timeout_s=0.2)
        c0.begin_attempt(), c1.begin_attempt()
        c1.close()                     # slice 1 goes silent
        time.sleep(0.4)                # heartbeat goes stale
        with pytest.raises(PeerFailure, match="falling back"):
            c0.check(6)
        fail = os.path.join(c0._gen_path(0), "FAIL_s001_00001")
        got = json.load(open(fail))
        assert got["kind"] == "stale" and got["proxied_by"] == 0
        # ...and a fresh slice-1 relaunch keys its rejoin on it
        c1b = PodCoordinator(
            os.path.join(str(tmp_path), "_pod"), process_index=1,
            process_count=2, sync_every=1, slice_index=1, slice_count=2,
            readmit_timeout_s=10.0, goodput=GoodputTracker(),
            log=lambda *_: None)
        c1b.begin_attempt()
        assert c1b.rejoining
        c0.close(), c1b.close()


class TestWarmSpareProtocol:
    """Unit drive of the r17 SPARE/CLAIM marker exchange (no train
    loop): a parked spare claims a failed seat only once the survivors
    are provably holding, arbitration is first-writer-wins, a
    relaunched original finds the claim and stands down, and a
    completed pod sends the spare home."""

    def _spare(self, d, idx=0, pi=None):
        c = PodCoordinator(
            os.path.join(d, "_pod"), process_index=0, process_count=2,
            sync_every=1, peer_timeout_s=30.0, slice_count=2,
            readmit_timeout_s=10.0, spare_index=idx,
            goodput=GoodputTracker(), log=lambda *_: None)
        if pi is not None:
            c.pi = pi
        return c

    def test_claim_waits_for_holds_then_swaps(self, tmp_path):
        c0, c1 = _slice_pair(str(tmp_path))
        c0.begin_attempt(), c1.begin_attempt()
        c1.record_failure(RuntimeError("boom"), step=6)
        c1.close()
        sp = self._spare(str(tmp_path))
        assert sp.pi == 2                  # synthetic out-of-pod index
        # survivors not parked yet: no claim (racing the whole-pod path)
        assert sp._spare_try_claim() is None
        outcome = {}

        def survivor():
            try:
                c0.check(6)                # foreign-slice FAIL -> parks
                outcome["released"] = True
            except BaseException as e:     # pragma: no cover - surfaced
                outcome["error"] = e

        t = threading.Thread(target=survivor, daemon=True)
        t.start()
        hold = os.path.join(c0._gen_path(0), "HOLD_s000_00000")
        deadline = time.monotonic() + 5.0
        while not os.path.exists(hold) and time.monotonic() < deadline:
            time.sleep(0.01)
        assert os.path.exists(hold)
        claim = sp._spare_try_claim()
        assert claim == {"seat": 1, "slice": 1, "generation": 0}
        assert sp.pi == 1 and sp.si == 1 and sp.rejoining
        # first writer won: a second spare finds every seat claimed
        sp2 = self._spare(str(tmp_path), idx=1, pi=3)
        assert sp2._spare_try_claim() is None
        # the spare completes the swap (restored step == target here)
        sp.rejoin_sync(6)
        t.join(timeout=10.0)
        assert outcome.get("released") is True, outcome
        s = sp._goodput.summary()
        assert s["warm_spare_claims"] == 1
        assert s["warm_spare_swaps"] == 1
        assert s["warm_spare_swap_s"] > 0
        assert c0._goodput.summary()["slice_readmissions"] == 1
        sp.close(), sp2.close(), c0.close()

    def test_relaunched_original_raises_seat_taken(self, tmp_path):
        """The original host coming back after a spare claimed its seat
        must stand down — two processes under one pod identity would
        corrupt every barrier — and SeatTaken is not restartable (the
        supervisor pass-through is pinned in test_resilience)."""
        from faster_distributed_training_tpu.resilience import SeatTaken
        c0, c1 = _slice_pair(str(tmp_path))
        c0.begin_attempt(), c1.begin_attempt()
        c1.record_failure(RuntimeError("boom"), step=6)
        c1.close()
        coord_mod._write_json_atomic(
            os.path.join(c0._gen_path(0), "CLAIM_s001_00001"),
            {"spare": 0})
        c1b = PodCoordinator(
            os.path.join(str(tmp_path), "_pod"), process_index=1,
            process_count=2, sync_every=1, slice_index=1, slice_count=2,
            readmit_timeout_s=10.0, goodput=GoodputTracker(),
            log=lambda *_: None)
        with pytest.raises(SeatTaken, match="warm spare"):
            c1b.begin_attempt()
        c1b.close(), c0.close()

    def test_spare_stands_down_when_pod_completes(self, tmp_path):
        c0, c1 = _slice_pair(str(tmp_path))
        c0.begin_attempt(), c1.begin_attempt()
        sp = self._spare(str(tmp_path))      # created BEFORE the EXITs
        time.sleep(0.02)   # EXIT times are ms-rounded; step past the
        #                    spare's creation stamp deterministically
        c0.record_completion(step=16)
        c1.record_completion(step=16)
        refreshes = []
        got = sp.spare_wait(refresh_fn=lambda: refreshes.append(1),
                            poll_s=0.01)
        assert got is None                   # stood down, nothing claimed
        assert refreshes                     # the park loop did refresh
        sp.close(), c0.close(), c1.close()

    def test_original_rejoin_claims_seat_atomically(self, tmp_path):
        """Review fix (TOCTOU): the relaunched ORIGINAL arbitrates its
        seat through the same first-writer-wins CLAIM create_if_absent
        a spare uses — a check-then-proceed would race a spare's claim
        in the begin_attempt-to-first-rejoin-marker gap and put two
        processes under one pod identity.  Winning blocks every spare;
        a rejoin RETRY (our own earlier claim) still proceeds."""
        c0, c1 = _slice_pair(str(tmp_path))
        c0.begin_attempt(), c1.begin_attempt()
        c1.record_failure(RuntimeError("boom"), step=6)
        c1.close()
        # survivors hold (so a spare WOULD otherwise claim)
        coord_mod._write_json_atomic(
            os.path.join(c0._gen_path(0), "HOLD_s000_00000"), {"step": 6})
        c1b = PodCoordinator(
            os.path.join(str(tmp_path), "_pod"), process_index=1,
            process_count=2, sync_every=1, slice_index=1, slice_count=2,
            readmit_timeout_s=10.0, goodput=GoodputTracker(),
            log=lambda *_: None)
        g = c1b.begin_attempt()
        assert g == 0 and c1b.rejoining       # the original won its seat
        claim = json.load(open(os.path.join(
            c1b._gen_path(0), "CLAIM_s001_00001")))
        assert claim["spare"] is None and claim["pi"] == 1
        sp = self._spare(str(tmp_path))
        assert sp._spare_try_claim() is None  # spare lost arbitration
        # a retry by the SAME original (fresh process, same seat) finds
        # its own claim and keeps the seat; the RJRENTER-residue rule
        # then decides retry-vs-abort exactly as before
        c1c = PodCoordinator(
            os.path.join(str(tmp_path), "_pod"), process_index=1,
            process_count=2, sync_every=1, slice_index=1, slice_count=2,
            readmit_timeout_s=10.0, goodput=GoodputTracker(),
            log=lambda *_: None)
        assert c1c.begin_attempt() == 0 and c1c.rejoining
        sp.close(), c0.close(), c1b.close(), c1c.close()

    def test_malformed_spare_id_fails_fast(self):
        """Review fix: two spares whose malformed ids both silently
        mapped to 0 would collide on the synthetic pod index — a typo'd
        launcher config must raise, not alias."""
        with pytest.raises(ValueError, match="FDT_SLICE_SPARE"):
            coord_mod.spare_identity(env={"FDT_SLICE_SPARE": "yes"})
        assert coord_mod.spare_identity(env={}) is None
        assert coord_mod.spare_identity(env={"FDT_SLICE_SPARE": "2"}) == 2

    def test_spare_ignores_incident_already_rejoining(self, tmp_path):
        """The real slice beat the spare to its own seat (RJRENTER in
        the generation): the spare stands aside instead of racing it."""
        c0, c1 = _slice_pair(str(tmp_path))
        c0.begin_attempt(), c1.begin_attempt()
        c1.record_failure(RuntimeError("boom"), step=6)
        coord_mod._write_json_atomic(
            os.path.join(c0._gen_path(0), "HOLD_s000_00000"), {"step": 6})
        coord_mod._write_json_atomic(
            os.path.join(c0._gen_path(0), "RJRENTER_s001_00001"),
            {"step": 4})
        sp = self._spare(str(tmp_path))
        assert sp._spare_try_claim() is None
        sp.close(), c0.close(), c1.close()


def _run_spare(d, step_fn, state0, gp, total=_TOTAL):
    """The spare side of the warm-spare e2e: park (programs already
    warm — step_fn is the shared compiled program), claim, restore
    through the slice-scoped barrier, catch up, release, finish the
    run in the dead member's place."""
    coord = PodCoordinator(
        os.path.join(d, "_pod"), process_index=0, process_count=2,
        sync_every=1, peer_timeout_s=30.0, slice_count=2,
        readmit_timeout_s=30.0, spare_index=0, goodput=gp,
        log=lambda *_: None)
    claim = coord.spare_wait(poll_s=0.02)
    if claim is None:
        coord.close()
        return None
    mgr = AsyncCheckpointManager(
        d, every_steps=_EVERY, process_index=coord.pi, process_count=2,
        shard_owner=(lambda sh: False), commit_timeout_s=15.0,
        step_gather_fn=coord.gather_restored_step, goodput=gp,
        log=lambda *_: None)
    coord.drain_fn = mgr.wait
    try:
        st, start = state0, 0
        got = mgr.restore_latest(st)
        if got is not None:
            st, meta = got
            start = int(meta["step"])
        coord.rejoin_sync(start)
        with coord.watch_steps():
            for i in range(start + 1, total + 1):
                st, _m = step_fn(st)
                coord.check(i)
                align = coord.consume_cadence_align()
                if align is not None:
                    mgr.align_cadence(align)
                if not coord.saves_suspended:
                    mgr.maybe_save(st, i)
        mgr.wait()
        coord.record_completion(step=total)
        return st
    finally:
        mgr.close()
        coord.close()


class TestWarmSpareEndToEnd:
    """ISSUE acceptance (r17): kill slice 1 for good -> the spare
    claims its seat -> the survivor's HOLD is shorter than the
    cold-rejoin twin's (which pays a fresh program build, the process-
    relaunch reality) -> final states bitwise-equal to the
    uninterrupted reference."""

    @pytest.fixture(scope="class")
    def program(self):
        cfg, state, batch = _tiny_state()
        step = jax.jit(make_train_step(cfg))
        reference = state
        for _ in range(_TOTAL):
            reference, _m = step(reference, batch)
        return cfg, state, batch, (lambda st: step(st, batch)), reference

    def test_spare_swap_bitwise_and_faster_than_cold_rejoin(
            self, program, tmp_path):
        cfg, state, batch, step_fn, reference = program

        # -- scenario A: warm spare; the victim has NO restart budget
        # (dead for good — the platform never relaunches it)
        d = str(tmp_path / "spare")
        barrier = threading.Barrier(2)
        kw = dict(pc=2, readmit_timeout_s=30.0, step_delay=0.02,
                  slice_count=2)
        h0 = _SimHost(0, d, barrier, slice_index=0, **kw)
        h1 = _SimHost(1, d, barrier, faults=FaultPlan(die_at=6),
                      slice_index=1, max_restarts=0, **kw)
        gp_spare = GoodputTracker().start()
        results, errors = {}, {}

        def run_host(h):
            try:
                results[h.pi] = h.run(step_fn, state)
            except BaseException as e:
                errors[h.pi] = e

        def run_sp():
            try:
                results["spare"] = _run_spare(d, step_fn, state, gp_spare)
            except BaseException as e:     # pragma: no cover - surfaced
                errors["spare"] = e

        threads = [threading.Thread(target=run_host, args=(h,),
                                    daemon=True) for h in (h0, h1)]
        threads.append(threading.Thread(target=run_sp, daemon=True))
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not any(t.is_alive() for t in threads), "spare pod hung"
        # the victim died for good, by design; nothing else may fail
        assert isinstance(errors.pop(1, None), faults_mod.InjectedFault)
        assert not errors, f"unexpected failures: {errors!r}"
        # survivor: held once, never restarted, never rolled back
        s0 = h0.goodput.summary()
        assert s0["restarts"] == 0 and s0["restores"] == 0
        assert s0["slice_readmissions"] == 1
        spare_hold = s0["readmission_hold_s"]
        assert spare_hold > 0
        # spare: claimed + swapped, finished bitwise-correct
        ssp = gp_spare.summary()
        assert ssp["warm_spare_claims"] == 1
        assert ssp["warm_spare_swaps"] == 1
        _assert_tree_equal(ckpt._state_pytree(results["spare"]),
                           ckpt._state_pytree(reference))
        _assert_tree_equal(ckpt._state_pytree(results[0]),
                           ckpt._state_pytree(reference))

        # -- scenario B: cold-rejoin twin — no spare; the killed slice
        # restarts and rejoins through a FRESHLY BUILT program (a new
        # jax.jit recompiles: the relaunch reality a restarted slice
        # pays), so the survivor's hold covers that compile
        d2 = str(tmp_path / "cold")
        barrier2 = threading.Barrier(2)

        def fresh_program():
            fresh = jax.jit(make_train_step(cfg))
            return lambda st: fresh(st, batch)

        c0 = _SimHost(0, d2, barrier2, slice_index=0, **kw)
        c1 = _SimHost(1, d2, barrier2, faults=FaultPlan(die_at=6),
                      slice_index=1, fresh_program_fn=fresh_program, **kw)
        results2 = _run_pod([c0, c1], step_fn, state)
        s0c = c0.goodput.summary()
        assert s0c["slice_readmissions"] == 1
        cold_hold = s0c["readmission_hold_s"]
        for pi in (0, 1):
            _assert_tree_equal(ckpt._state_pytree(results2[pi]),
                               ckpt._state_pytree(reference))
        # the tentpole claim, measured: the warm spare's swap keeps the
        # survivors parked for LESS time than a cold rejoin that must
        # rebuild its programs
        assert spare_hold < cold_hold, \
            f"spare hold {spare_hold:.3f}s !< cold hold {cold_hold:.3f}s"


class TestSimulatedSlicePodEndToEnd:
    """ISSUE acceptance (r14): simulated 2-slice pod, 4 hosts, slice 1
    killed whole mid-run — the surviving slice parks (never exits its
    dispatch loop, never restarts, never rolls back), the killed slice
    restarts, rejoins the SAME generation and catches up, and every
    host finishes bitwise-equal to the uninterrupted reference.  Run on
    the shared POSIX directory AND on the fake object store (shared
    MemoryMedium across the host threads) with the rename primitives
    trapped on the checkpoint namespace."""

    @pytest.fixture(scope="class")
    def program(self):
        cfg, state, batch = _tiny_state()
        step = jax.jit(make_train_step(cfg))
        reference = state
        for _ in range(_TOTAL):
            reference, _m = step(reference, batch)
        return state, (lambda st: step(st, batch)), reference

    @pytest.mark.parametrize("store", ["posix", "fake_object_store"])
    def test_slice_kill_survivors_hold_rejoin_bitwise(
            self, program, tmp_path, store, monkeypatch):
        state, step_fn, reference = program
        d = str(tmp_path)
        be = None
        if store == "fake_object_store":
            be = FakeObjectStoreBackend()
            # zero-rename proof: any rename primitive touching the
            # checkpoint namespace while the object store serves it is
            # a routing bug
            real = os.replace

            def guarded(src, dst, *a, **k):
                if str(dst).startswith(d):
                    raise AssertionError(
                        f"os.replace on object-store path {dst}")
                return real(src, dst, *a, **k)
            monkeypatch.setattr(os, "replace", guarded)
        barrier = threading.Barrier(4)
        kw = dict(pc=4, backend=be, slice_count=2, readmit_timeout_s=30.0,
                  step_delay=0.02)
        hosts = [
            _SimHost(0, d, barrier, slice_index=0, **kw),
            _SimHost(1, d, barrier, slice_index=0, **kw),
            _SimHost(2, d, barrier, faults=FaultPlan(die_at=6),
                     slice_index=1, **kw),
            _SimHost(3, d, barrier, faults=FaultPlan(die_at=6),
                     slice_index=1, **kw),
        ]
        results = _run_pod(hosts, step_fn, state)
        for pi in range(4):
            _assert_tree_equal(ckpt._state_pytree(results[pi]),
                               ckpt._state_pytree(reference))
        s = [h.goodput.summary() for h in hosts]
        for i in (0, 1):     # the surviving slice: held, nothing else
            assert s[i]["restarts"] == 0 and s[i]["restores"] == 0, s[i]
            assert s[i]["slice_readmissions"] == 1
            assert s[i]["readmission_hold_s"] > 0
            assert hosts[i].generations == [0]
        for i in (2, 3):     # the killed slice: restarted + re-admitted
            assert s[i]["restarts"] == 1
            assert s[i]["slice_readmissions"] == 1
            # the second attempt REJOINED generation 0, no advance
            assert hosts[i].generations == [0, 0]
            assert hosts[i].restored_steps[1] >= 0
        assert all(x["pod_fallback_restarts"] == 0 for x in s), s


class _SimHost:
    """One simulated pod host running in its own thread: its own
    coordinator + sharded manager (complementary owners) + supervisor +
    fault plan against the SHARED directory (or shared object-store
    backend, r14).  ``barrier`` keeps the hosts in loose lockstep so
    the failure injection interleaves deterministically enough to
    assert on; it is aborted (not just broken) the moment any attempt
    dies, so the survivors never wait out the full barrier timeout.
    ``step_delay`` paces the free-running phase after an abort (slice
    tests: a survivor must observe the FAIL marker before it can finish
    the run).  The attempt body mirrors Trainer._resilience_hooks'
    hazard order INCLUDING the r14 hooks: rejoin_sync after restore,
    cadence re-align after check, saves gated on saves_suspended."""

    def __init__(self, pi, d, barrier, faults=None, total=_TOTAL,
                 pc=2, backend=None, step_delay=0.0, max_restarts=3,
                 fresh_program_fn=None, **coord_kw):
        self.pi, self.total, self.barrier = pi, total, barrier
        self.step_delay = step_delay
        # r17 cold-rejoin twin: when set, every RESTART attempt steps
        # through fresh_program_fn() instead of the shared warm step_fn
        # — a fresh jax.jit recompiles, modeling the process relaunch a
        # real restarted slice pays (the warm-spare e2e measures the
        # survivor hold against exactly this)
        self.fresh_program_fn = fresh_program_fn
        self.goodput = GoodputTracker()
        coord_kw.setdefault("sync_every", 1)
        coord_kw.setdefault("peer_timeout_s", 30.0)
        self.coord = PodCoordinator(
            os.path.join(d, "_pod"), process_index=pi, process_count=pc,
            backend=backend,
            goodput=self.goodput, log=lambda *_: None, **coord_kw)
        self.mgr = AsyncCheckpointManager(
            d, every_steps=_EVERY, process_index=pi, process_count=pc,
            shard_owner=((lambda sh: sh.replica_id == 0) if pi == 0
                         else (lambda sh: False)),
            commit_timeout_s=15.0, backend=backend,
            step_gather_fn=self.coord.gather_restored_step,
            goodput=self.goodput, log=lambda *_: None)
        self.coord.drain_fn = self.mgr.wait
        self.faults = faults
        self.sup = Supervisor(max_restarts=max_restarts, backoff_base=0.01,
                              goodput=self.goodput, log=lambda *_: None,
                              coordinator=self.coord)
        self.progress = 0
        self.generations = []        # generation entered per attempt
        self.restored_steps = []     # restore_latest outcome per attempt

    def _lockstep(self):
        try:
            self.barrier.wait(timeout=30.0)
        except threading.BrokenBarrierError:
            if self.step_delay:
                time.sleep(self.step_delay)   # pace the free run

    def run(self, step_fn, state0):
        def attempt(_i):
            try:
                fn = step_fn
                if self.fresh_program_fn is not None and _i > 0:
                    fn = self.fresh_program_fn()
                self.generations.append(self.coord._gen)
                st, start = state0, 0
                got = self.mgr.restore_latest(st)
                if got is not None:
                    st, meta = got
                    start = int(meta["step"])
                self.restored_steps.append(start if got is not None else -1)
                self.progress = start
                if self.coord.rejoining:
                    # r14: agree the catch-up target with the parked
                    # survivors (completes here when start == target)
                    self.coord.rejoin_sync(start)
                # mirror Trainer._resilience_hooks' hazard order: faults
                # (the crash), then the coordinator poll, then the save
                with self.coord.watch_steps():
                    for i in range(start + 1, self.total + 1):
                        self._lockstep()
                        st, _m = fn(st)
                        self.progress = i
                        if self.faults is not None:
                            self.faults.on_step(i)
                        self.coord.check(i)
                        align = self.coord.consume_cadence_align()
                        if align is not None:
                            self.mgr.align_cadence(align)
                        if not self.coord.saves_suspended:
                            self.mgr.maybe_save(st, i)
                self.mgr.wait()
                return st
            except BaseException:
                self.barrier.abort()
                raise
        try:
            return self.sup.run(attempt, lambda: self.progress)
        finally:
            self.mgr.close()
            self.coord.close()


def _run_pod(hosts, step_fn, state0):
    results, errors = {}, {}

    def body(h):
        try:
            results[h.pi] = h.run(step_fn, state0)
        except BaseException as e:          # pragma: no cover - surfaced
            errors[h.pi] = e

    threads = [threading.Thread(target=body, args=(h,), daemon=True)
               for h in hosts]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    assert not any(t.is_alive() for t in threads), \
        "pod deadlocked: a host thread never finished"
    assert not errors, f"host(s) died unrecovered: {errors!r}"
    return results


class TestSimulatedPodEndToEnd:
    @pytest.fixture(scope="class")
    def program(self):
        cfg, state, batch = _tiny_state()
        step = jax.jit(make_train_step(cfg))
        reference = state
        for _ in range(_TOTAL):
            reference, _m = step(reference, batch)
        return state, (lambda st: step(st, batch)), reference

    @pytest.mark.slow  # r24 budget diet: 15 s — the FAIL-marker /
    # generation-agreement protocol keeps tier-1 coverage via
    # TestSimulatedSlicePodEndToEnd::test_slice_kill_survivors_hold_rejoin_bitwise
    # (same markers + restore-step agreement on the richer slice path)
    # and kill-at-N bitwise resume stays pinned by test_mesh2d,
    # test_pipeline, and test_sentinel's kill-mid-replay twin
    def test_killed_host_pod_restarts_same_generation_bitwise(
            self, program, tmp_path):
        """Kill host 1 at step 6: host 0 observes the FAIL marker, both
        supervisors re-enter generation 1, restore_latest agrees step 4
        on both, and both finish bitwise-equal to uninterrupted."""
        state, step_fn, reference = program
        barrier = threading.Barrier(2)
        h0 = _SimHost(0, str(tmp_path), barrier)
        h1 = _SimHost(1, str(tmp_path), barrier, faults=FaultPlan(die_at=6))
        results = _run_pod([h0, h1], step_fn, state)
        # same generation sequence on both hosts
        assert h0.generations == [0, 1]
        assert h1.generations == [0, 1]
        # restore step-agreement: both restored the SAME step (the last
        # committed cadence save before the kill)
        assert h0.restored_steps == [-1, _EVERY]
        assert h1.restored_steps == [-1, _EVERY]
        # resumed runs are bitwise-equal to the uninterrupted reference
        for pi in (0, 1):
            _assert_tree_equal(ckpt._state_pytree(results[pi]),
                               ckpt._state_pytree(reference))
        # MTTR accounting: the survivor observed a peer failure and its
        # recovery latency decomposes into detect + backoff + restore
        s0, s1 = h0.goodput.summary(), h1.goodput.summary()
        assert s0["peer_failures"] == 1 and s0["restarts"] == 1
        assert s0["restart_mttr_s"] > 0 and s0["restore_s"] > 0
        assert s1["restarts"] == 1 and s1["restart_mttr_s"] > 0

    def test_hung_host_watchdog_escalates_pod_recovers(self, program,
                                                      tmp_path):
        """FDT_FAULT_HANG_AT_STEP semantics: host 1's main thread blocks
        forever at step 6 — nothing raises, nothing exits.  Its watchdog
        escalates within step_timeout_s (FAIL marker first, then the
        abort, which the test intercepts to release the hang in place of
        SIGKILL), host 0 observes the marker, and the pod restarts
        without deadlock."""
        state, step_fn, reference = program
        barrier = threading.Barrier(2)
        plan = FaultPlan(hang_at=6)
        h0 = _SimHost(0, str(tmp_path), barrier)
        h1 = _SimHost(1, str(tmp_path), barrier, faults=plan,
                      step_timeout_s=0.4, hb_interval_s=0.05,
                      abort_fn=lambda reason: plan.hang_release.set())
        t0 = time.monotonic()
        results = _run_pod([h0, h1], step_fn, state)
        elapsed = time.monotonic() - t0
        assert h0.generations == [0, 1] and h1.generations == [0, 1]
        assert h0.restored_steps == [-1, _EVERY]
        assert h1.restored_steps == [-1, _EVERY]
        for pi in (0, 1):
            _assert_tree_equal(ckpt._state_pytree(results[pi]),
                               ckpt._state_pytree(reference))
        s0, s1 = h0.goodput.summary(), h1.goodput.summary()
        assert s1["step_timeouts"] == 1      # the watchdog fired
        assert s0["peer_failures"] == 1      # ...and the peer saw it
        assert s0["restart_mttr_s"] > 0 and s1["restart_mttr_s"] > 0
        # detection was watchdog-fast, not peer-timeout-slow: the whole
        # recovered run is far inside the 30s staleness window
        assert elapsed < 30.0


def _load_smoke_module(monkeypatch):
    """The smoke script, plus env so its subprocess children inherit
    conftest's numeric config (x64, partitionable threefry: set here
    in-process via jax.config, invisible to subprocesses) — or the
    byte-equality checks would compare across float semantics."""
    import importlib.util

    monkeypatch.setenv("JAX_ENABLE_X64", str(int(jax.config.jax_enable_x64)))
    monkeypatch.setenv("JAX_THREEFRY_PARTITIONABLE",
                       str(int(jax.config.jax_threefry_partitionable)))
    spec = importlib.util.spec_from_file_location(
        "pod_restart_smoke",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "pod_restart_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_SMOKE_REF = {}


def _smoke_reference_digest(mod):
    """The uninterrupted in-process reference, computed ONCE per pytest
    process and shared by every smoke wrapper (same math regardless of
    the pod scenario/backend under test — recomputing it per wrapper
    would triple the tier-1 cost for zero coverage)."""
    if "digest" not in _SMOKE_REF:
        import tempfile

        from faster_distributed_training_tpu.cli import run_training
        ref = run_training(mod.reference_cfg(tempfile.mkdtemp()),
                           log=lambda *_: None)
        assert int(ref["state"].step) == mod.TOTAL_STEPS
        _SMOKE_REF["digest"] = mod.state_digest(ref["state"])
    return _SMOKE_REF["digest"]


@pytest.mark.slow  # r21 budget diet: 35 s (includes the in-process
# reference training the other smoke variants share) — process-level
# kill/respawn keeps a tier-1 representative in the decode smoke
# wrapper (tests/test_decode.py::test_decode_smoke_in_process: real
# SIGKILL of a spawned worker + respawn/readmit), and bitwise
# kill-at-N resume stays tier-1 in test_mesh2d/test_resilience
def test_pod_restart_smoke(monkeypatch):
    """scripts/pod_restart_smoke.py end-to-end: a REAL two-process
    simulated pod (coordination genuinely cross-process through the
    shared fs), host 1 killed via FDT_FAULT_HOST+FDT_FAULT_DIE_AT_STEP,
    coordinated restart + final-state equality asserted by the script
    itself.  The uninterrupted reference digest is computed IN-process
    (warm jax) so the smoke only spawns the two pod children."""
    mod = _load_smoke_module(monkeypatch)
    assert mod.main(ref_digest=_smoke_reference_digest(mod)) == 0


@pytest.mark.slow  # r20 budget diet: 29 s — the SAME smoke as
# test_pod_restart_smoke (which stays tier-1) on the fake-object-store
# backend; the backend's rename-free semantics are unit-tested in
# test_resilience.py
def test_pod_restart_smoke_fake_object_store(monkeypatch):
    """r14 satellite: the SAME two-process kill/recover scenario with
    every resilience-critical durable write on the rename-free
    fake-object-store backend (framed generation files under
    <dir>/_objects, cross-PROCESS) — digest equality must hold with no
    rename primitive, and the script asserts no marker/step-checkpoint
    state leaked onto the plain filesystem."""
    mod = _load_smoke_module(monkeypatch)
    assert mod.main(ref_digest=_smoke_reference_digest(mod),
                    backend="fake_object_store") == 0


@pytest.mark.slow  # r21 budget diet: 32 s — the plain
# test_pod_restart_smoke stays tier-1 for the restart flow; the r17
# cache_source=deserialized contract keeps tier-1 coverage via the
# manifest compile-table tests and the decode program-pin test (which
# round-trips the executable cache), and the MTTR A/B stays with the
# manual script run (not measured on the chip)
def test_pod_restart_smoke_cache(monkeypatch):
    """r17 acceptance: scripts/pod_restart_smoke.py --cache — crash +
    process relaunch with the executable cache armed: the relaunched
    process records cache_source=deserialized for EVERY steady-state
    program, zero retraces, bitwise-equal final state.  Budget mode
    (cache_cold_twin=False): the digest compares against the
    UNINTERRUPTED reference, which the resilience e2e suite already
    pins bitwise-equal to a cold restart (kill-at-N resume, r7), and
    the cold-acquisition A/B stays with the manual script run, which
    keeps the full cold twin (~25 s of extra compile this wrapper
    spares tier-1)."""
    mod = _load_smoke_module(monkeypatch)
    assert mod.main(ref_digest=_smoke_reference_digest(mod),
                    cache=True, cache_cold_twin=False) == 0


@pytest.mark.slow
def test_pod_restart_smoke_two_slices(monkeypatch):
    """r14 acceptance at PROCESS level (the threaded twin runs tier-1;
    this one is `-m slow`): 2-slice pod, 4 processes, slice 1 killed
    whole via FDT_FAULT_SLICE — survivors hold (zero restarts / zero
    restores), the slice rejoins, all digests equal the reference."""
    mod = _load_smoke_module(monkeypatch)
    assert mod.main(ref_digest=_smoke_reference_digest(mod),
                    slices=2) == 0
