"""Resilience subsystem tests (resilience/): async checkpoint manager,
fault injection, supervisor restarts, preemption, goodput accounting —
all CPU, single-process, tier-1 (no `slow` marker, no multi-process
requirement).

The end-to-end tests drive the REAL cli.run_training path with faults
injected through the FDT_FAULT_* env knobs, exactly as the preemption
smoke script (scripts/preemption_smoke.py) does across processes.
donate=False throughout: these tests run several train programs in one
pytest process, and multiple DONATING programs per process is a known
backend hazard."""

import json
import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from faster_distributed_training_tpu.config import TrainConfig
from faster_distributed_training_tpu.models import Transformer
from faster_distributed_training_tpu.optim import build_optimizer
from faster_distributed_training_tpu.resilience import (
    AsyncCheckpointManager, FaultPlan, GoodputTracker, InjectedFault,
    Preempted, PreemptionHandler, Supervisor, build_resilience,
    corrupt_newest_checkpoint)
from faster_distributed_training_tpu.resilience import faults as faults_mod
from faster_distributed_training_tpu.train import (checkpoint as ckpt,
                                                   create_train_state,
                                                   make_train_step)


def _tiny_state(seed=0):
    """A small but real TrainState (transformer d16) — big enough to
    exercise orbax, small enough to save in tens of milliseconds."""
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


class TestCheckpointAtomicity:
    """Satellites 1+2: atomic meta.json + commit-marker-based
    has_checkpoint (a half-written directory is not a checkpoint)."""

    def test_save_writes_commit_marker_and_meta(self, tmp_path):
        _cfg, state, _batch = _tiny_state()
        path = ckpt.save_checkpoint(str(tmp_path), "c", state,
                                    epoch=2, best_acc=0.5,
                                    extra_meta={"step": 7})
        assert os.path.exists(os.path.join(path, ckpt._COMMIT))
        meta = ckpt.read_checkpoint_meta(str(tmp_path), "c")
        assert meta == {"epoch": 2, "best_acc": 0.5, "step": 7}
        # no torn .tmp residue from the atomic writes
        assert not [f for f in os.listdir(path) if f.endswith(".tmp")]
        assert ckpt.has_checkpoint(str(tmp_path), "c")

    def test_half_written_directory_is_not_a_checkpoint(self, tmp_path):
        # the pre-r7 bare-isdir bug: a preemption mid-save leaves a
        # directory that --resume then crashed on
        os.makedirs(tmp_path / "torn")
        (tmp_path / "torn" / "some_partial_file").write_bytes(b"xx")
        assert not ckpt.has_checkpoint(str(tmp_path), "torn")
        assert not ckpt.has_checkpoint(str(tmp_path), "never_existed")

    def test_pre_r7_orbax_checkpoint_still_recognized(self):
        # the committed round-2 fixture has orbax's _CHECKPOINT_METADATA
        # but predates our COMMIT marker — it must keep restoring
        fixtures = os.path.join(os.path.dirname(__file__), "fixtures")
        assert ckpt.has_checkpoint(fixtures, "legacy_transformer")

    def test_atomic_json_survives_existing_file(self, tmp_path):
        p = str(tmp_path / "m.json")
        ckpt._write_json_atomic(p, {"a": 1})
        ckpt._write_json_atomic(p, {"a": 2})
        with open(p) as f:
            assert json.load(f) == {"a": 2}


class TestAsyncCheckpointManager:
    def _run_and_save(self, mgr, steps, sync_wait=True):
        cfg, state, batch = _tiny_state()
        step = jax.jit(make_train_step(cfg))
        snaps = {}
        for i in range(1, steps + 1):
            state, _m = step(state, batch)
            if mgr.maybe_save(state, i, epoch=0, step_in_epoch=i):
                snaps[i] = jax.device_get(ckpt._state_pytree(state))
            if sync_wait:
                mgr.wait()   # deterministic cadence for the assertions
        return state, snaps

    def test_cadence_retention_and_bitwise_roundtrip(self, tmp_path):
        g = GoodputTracker().start()
        mgr = AsyncCheckpointManager(str(tmp_path), every_steps=2, keep=2,
                                     goodput=g, log=lambda *_: None)
        state, snaps = self._run_and_save(mgr, 7)
        # cadence respected: saves exactly at the multiples of 2...
        assert sorted(snaps) == [2, 4, 6]
        # ...retention keeps the newest K committed
        assert mgr.committed_steps() == [4, 6]
        got = mgr.restore_latest(state)
        assert got is not None
        restored, meta = got
        assert meta["step"] == 6 and meta["step_in_epoch"] == 6
        # the async snapshot round-trips BITWISE, optimizer state included
        _assert_tree_equal(ckpt._state_pytree(restored), snaps[6])
        s = g.summary()
        assert s["saves"] == 3 and s["restores"] == 1
        assert s["checkpoint_blocking_s"] > 0
        mgr.close()

    def test_wallclock_cadence(self, tmp_path):
        mgr = AsyncCheckpointManager(str(tmp_path), every_secs=0.05,
                                     log=lambda *_: None)
        assert not mgr.should_save(1)
        time.sleep(0.06)
        assert mgr.should_save(2)

    def test_inflight_save_skips_not_queues(self, tmp_path):
        g = GoodputTracker().start()
        mgr = AsyncCheckpointManager(str(tmp_path), every_steps=1,
                                     goodput=g, log=lambda *_: None)
        _state, snaps = self._run_and_save(mgr, 4, sync_wait=False)
        mgr.wait()
        # at least one tick landed while a save was writing; it was
        # counted as skipped, never queued (bounded memory)
        s = g.summary()
        assert s["saves"] == len(snaps)
        assert s["saves"] + s["skipped_saves"] == 4
        mgr.close()

    def test_corrupt_newest_falls_back_to_previous_valid(self, tmp_path):
        mgr = AsyncCheckpointManager(str(tmp_path), every_steps=2, keep=3,
                                     log=lambda *_: None)
        state, snaps = self._run_and_save(mgr, 4)
        assert mgr.committed_steps() == [2, 4]
        corrupted = corrupt_newest_checkpoint(str(tmp_path))
        assert corrupted.endswith("_step_000000004")
        got = mgr.restore_latest(state)
        assert got is not None
        restored, meta = got
        assert meta["step"] == 2   # fell back past the corrupt newest
        _assert_tree_equal(ckpt._state_pytree(restored), snaps[2])
        mgr.close()

    def test_unmarked_checkpoint_invisible(self, tmp_path):
        mgr = AsyncCheckpointManager(str(tmp_path), every_steps=2, keep=3,
                                     log=lambda *_: None)
        state, _snaps = self._run_and_save(mgr, 4)
        corrupt_newest_checkpoint(str(tmp_path), mode="unmark")
        assert mgr.committed_steps() == [2]
        assert mgr.latest_valid()[0] == 2
        mgr.close()

    def test_restore_latest_none_when_empty(self, tmp_path):
        _cfg, state, _batch = _tiny_state()
        mgr = AsyncCheckpointManager(str(tmp_path), every_steps=2,
                                     log=lambda *_: None)
        assert mgr.restore_latest(state) is None
        assert mgr.latest_valid() is None


class TestFaultPlan:
    def test_from_env(self):
        assert FaultPlan.from_env({}) is None
        plan = FaultPlan.from_env({faults_mod.ENV_DIE: "5"})
        assert plan.die_at == 5 and plan.sigterm_at is None
        with pytest.raises(ValueError, match="FDT_FAULT_DIE_AT_STEP"):
            FaultPlan.from_env({faults_mod.ENV_DIE: "soon"})

    def test_die_fires_once(self):
        plan = FaultPlan(die_at=3)
        plan.on_step(1)
        plan.on_step(2)
        with pytest.raises(InjectedFault, match="step 3"):
            plan.on_step(3)
        plan.on_step(3)   # after a supervisor restart the replay succeeds
        plan.on_step(4)

    def test_data_iterator_fault_propagates_through_prefetch(self):
        from faster_distributed_training_tpu.data import PrefetchIterator
        plan = FaultPlan(data_at=2)
        it = PrefetchIterator(plan.wrap_data(iter(range(5))), depth=2)
        got = []
        with pytest.raises(InjectedFault, match="batch 2"):
            for x in it:
                got.append(x)
        assert got == [0, 1]

    def test_host_scoping(self):
        """r10: FDT_FAULT_HOST scopes any armed fault to one pod
        process — the other hosts of a (simulated or real) pod run
        fault-free."""
        env = {faults_mod.ENV_DIE: "5", faults_mod.ENV_HOST: "1"}
        assert FaultPlan.from_env(env, process_index=0) is None
        plan = FaultPlan.from_env(env, process_index=1)
        assert plan is not None and plan.die_at == 5
        # unresolved index falls back to the pod-identity env seam
        assert FaultPlan.from_env(
            dict(env, FDT_POD_INDEX="1", FDT_POD_COUNT="2")).die_at == 5
        assert FaultPlan.from_env(
            dict(env, FDT_POD_INDEX="0", FDT_POD_COUNT="2")) is None

    def test_hang_blocks_until_released_then_fires_once(self):
        """r10: FDT_FAULT_HANG_AT_STEP really BLOCKS the calling thread
        (indistinguishable from a wedged dispatch — only the watchdog
        thread can act); the release event is the test harness's stand-
        in for the watchdog's SIGKILL, and the fault fires once so the
        post-restart replay passes."""
        import threading

        plan = FaultPlan.from_env({faults_mod.ENV_HANG: "3"})
        assert plan.hang_at == 3
        plan.on_step(2)                      # not yet
        t = threading.Timer(0.15, plan.hang_release.set)
        t.start()
        t0 = time.monotonic()
        plan.on_step(3)                      # blocks until released
        assert time.monotonic() - t0 >= 0.1
        t.join()
        t0 = time.monotonic()
        plan.on_step(3)                      # fired once: replay is free
        assert time.monotonic() - t0 < 0.1


class TestSupervisor:
    def _supervisor(self, **kw):
        sleeps = []
        kw.setdefault("backoff_base", 0.25)
        sup = Supervisor(sleep=sleeps.append, log=lambda *_: None, **kw)
        return sup, sleeps

    def test_recovers_then_returns(self):
        sup, sleeps = self._supervisor(max_restarts=3)
        calls = []

        def attempt(i):
            calls.append(i)
            if i < 2:
                raise RuntimeError(f"boom {i}")
            return "done"

        progress = iter([3, 7])   # failures at different steps: transient
        assert sup.run(attempt, lambda: next(progress)) == "done"
        assert calls == [0, 1, 2]
        # r17: the FIRST restart is immediate (no sleep at all — the
        # measured 1.07s MTTR was ~1.0s of base backoff paid on one
        # transient fault); the exponential ramp starts at the second
        assert sleeps == [0.25]

    def test_first_restart_immediate_backoff_from_second(self):
        """r17 satellite pin: one transient failure recovers with ZERO
        backoff (restart_mttr_backoff_s ≈ 0), repeated failures ramp
        base·2^k from the second restart, still capped."""
        sup, sleeps = self._supervisor(max_restarts=4, backoff_cap=0.6)
        steps = iter([1, 2, 3, 4, 5])
        with pytest.raises(RuntimeError):
            sup.run(lambda i: (_ for _ in ()).throw(RuntimeError("x")),
                    lambda: next(steps))
        # restarts 1..4 -> delays 0 (immediate), 0.25, 0.5, 0.6 (capped)
        assert sleeps == [0.25, 0.5, 0.6]

    def test_deterministic_crash_reraises_with_budget_left(self):
        sup, sleeps = self._supervisor(max_restarts=10)
        with pytest.raises(RuntimeError, match="boom"):
            sup.run(lambda i: (_ for _ in ()).throw(RuntimeError("boom")),
                    lambda: 5)   # same step every time
        assert sleeps == []   # one (immediate) retry, then the re-raise

    def test_same_step_different_exception_types_keep_retrying(self):
        """r10 satellite fix: two DIFFERENT transient faults landing at
        one step — a storage flake, then a peer failure at the same
        checkpoint-cadence step — are not evidence of determinism and
        must keep retrying while budget remains."""
        sup, sleeps = self._supervisor(max_restarts=5)
        excs = iter([OSError("storage flake"), RuntimeError("peer died")])

        def attempt(i):
            e = next(excs, None)
            if e is not None:
                raise e
            return "done"

        assert sup.run(attempt, lambda: 5) == "done"   # same step each time
        assert len(sleeps) == 1      # both retried (first immediate)

    def test_peer_failure_never_deterministic(self):
        """r10 review fix: a PeerFailure's step is the poll-quantized
        OBSERVATION point, not the fault point — repeated PeerFailure
        at one step must keep retrying (a flapping peer exhausts the
        whole budget, never the two-strikes short-circuit), and it
        neither records nor clears the (step, type) pair an own-crash
        determinism check runs on."""
        from faster_distributed_training_tpu.resilience import PeerFailure
        sup, sleeps = self._supervisor(max_restarts=3)
        with pytest.raises(PeerFailure):    # budget-exhausted, not
            sup.run(lambda i: (_ for _ in ()).throw(   # deterministic
                PeerFailure("host 1 flapping")), lambda: 5)
        assert len(sleeps) == 2     # every restart burned (first immediate)
        # ...and an own-crash recurring at one step with a peer incident
        # in between is STILL deterministic (PeerFailure is transparent)
        sup, sleeps = self._supervisor(max_restarts=10)
        excs = iter([RuntimeError("bad batch"), PeerFailure("peer"),
                     RuntimeError("bad batch")])
        with pytest.raises(RuntimeError, match="bad batch"):
            sup.run(lambda i: (_ for _ in ()).throw(next(excs)), lambda: 5)
        assert len(sleeps) == 1   # two retries (first immediate), re-raise

    def test_success_records_completion_on_coordinator(self):
        """r10 review fix: a finishing host durably marks itself DONE so
        a peer restarting after this host exits fails its restore
        barrier fast instead of waiting out the gather timeout."""
        events = []

        class _Coord:
            def begin_attempt(self):
                events.append("begin")

            def record_failure(self, e, step=None):
                events.append("fail")

            def record_completion(self, step=None):
                events.append("done")

        sup = Supervisor(max_restarts=2, backoff_base=0.0,
                         sleep=lambda _s: None, log=lambda *_: None,
                         coordinator=_Coord())
        flaky = iter([RuntimeError("once")])
        assert sup.run(lambda i: ("ok" if next(flaky, None) is None
                                  else (_ for _ in ()).throw(
                                      RuntimeError("once"))),
                       lambda: 1) == "ok"
        assert events == ["begin", "fail", "begin", "done"]

    def test_progress_none_twice_same_type_is_deterministic(self):
        """r10 satellite fix: two failures with progress() None (neither
        attempt completed a step) compare like any repeated step — the
        run cannot even start, and replaying is futile."""
        sup, sleeps = self._supervisor(max_restarts=10)
        with pytest.raises(RuntimeError, match="init"):
            sup.run(lambda i: (_ for _ in ()).throw(RuntimeError("init")),
                    lambda: None)
        assert sleeps == []   # one (immediate) retry, then the re-raise

    def test_bounded_restarts(self):
        sup, sleeps = self._supervisor(max_restarts=2, backoff_cap=0.3)
        steps = iter([1, 2, 3, 4])
        with pytest.raises(RuntimeError):
            sup.run(lambda i: (_ for _ in ()).throw(RuntimeError("x")),
                    lambda: next(steps))
        # restart 1 immediate, restart 2 at base; budget exhausted
        assert sleeps == [0.25]

    def test_preempted_passes_through(self):
        sup, sleeps = self._supervisor(max_restarts=5)
        with pytest.raises(Preempted):
            sup.run(lambda i: (_ for _ in ()).throw(Preempted("p")),
                    lambda: 1)
        assert sleeps == []   # never treated as a failure

    def test_seat_taken_passes_through(self):
        """r17 warm spares: SeatTaken is protocol, not failure — a
        spare durably claimed this host's seat and retrying can never
        win it back, so the supervisor re-raises immediately instead of
        burning the restart budget against a first-writer-wins
        marker."""
        from faster_distributed_training_tpu.resilience import SeatTaken
        sup, sleeps = self._supervisor(max_restarts=5)
        with pytest.raises(SeatTaken):
            sup.run(lambda i: (_ for _ in ()).throw(
                SeatTaken("spare 0 holds seat 1")), lambda: 1)
        assert sleeps == []   # zero retries


class TestPreemptionHandler:
    def test_sigterm_sets_flag_and_should_stop(self):
        with PreemptionHandler(log=lambda *_: None) as h:
            assert not h.seen() and not h.should_stop(1)
            os.kill(os.getpid(), signal.SIGTERM)
            deadline = time.monotonic() + 5.0
            while not h.seen() and time.monotonic() < deadline:
                time.sleep(0.01)
            assert h.seen() and h.should_stop(2)
        # uninstalled: our handler no longer owns SIGTERM
        assert signal.getsignal(signal.SIGTERM) != h._on_signal


class TestGoodput:
    def test_segments_counters_and_summary(self):
        t = [0.0]
        g = GoodputTracker(clock=lambda: t[0]).start()
        t[0] = 10.0
        g.add("checkpoint_blocking_s", 1.0)
        g.add("restore_s", 1.0)
        g.count("saves")
        g.count("steps", 8)
        s = g.summary()
        assert s["wall_s"] == 10.0 and s["badput_s"] == 2.0
        assert s["productive_s"] == 8.0 and s["goodput_pct"] == 80.0
        assert s["productive_step_ms"] == 1000.0
        with pytest.raises(KeyError):
            g.add("not_a_segment", 1.0)
        with pytest.raises(KeyError):
            g.count("not_a_counter")

    def test_mttr_excludes_pre_restart_resume_restore(self):
        """r10 review fix: the restore a resumed run STARTS from is
        startup, not recovery — only restore time after the first
        restart feeds the restart_mttr_s headline."""
        g = GoodputTracker().start()
        g.add("restore_s", 5.0)          # --resume startup restore
        g.count("restarts")              # then one crash
        g.add("restart_backoff_s", 1.0)
        g.add("restore_s", 0.5)          # the recovery restore
        s = g.summary()
        assert s["restart_mttr_s"] == 1.5          # NOT (5.0+0.5+1.0)/1
        assert s["restore_s"] == 5.5               # total still accounted

    def test_mttr_splits_into_compile_and_restore(self):
        """r17 tentpole: restart_mttr_s = detect + backoff + recovery
        restore + recovery COMPILE (program re-acquisition, the
        compile-dominated real-hardware half restore_s alone can't
        see), with the two halves published as components — and, like
        restore, compile time paid BEFORE the first restart is startup,
        not recovery."""
        g = GoodputTracker().start()
        g.add_compile(3.0)               # the run's first-start compiles
        g.add("restore_s", 5.0)          # --resume startup restore
        g.count("restarts")              # then one crash
        g.add("restore_s", 0.5)          # recovery restore
        g.add_compile(2.0)               # recovery recompile
        s = g.summary()
        assert s["compile_s"] == 5.0                    # total accounted
        assert s["restart_mttr_restore_s"] == 0.5
        assert s["restart_mttr_compile_s"] == 2.0
        assert s["restart_mttr_s"] == 2.5               # 0.5 + 2.0

    def test_warm_spare_swap_published_but_not_badput(self):
        """Review fix: the swap window CONTAINS the restore segment and
        productive catch-up steps — it is published in the summary but
        never summed into badput (double-billing would understate the
        spare's goodput_pct)."""
        clock = iter([0.0, 10.0]).__next__      # start, summary
        g = GoodputTracker(clock=clock)
        g.start()
        g.add("restore_s", 2.0)                 # inside the swap window
        g.add_warm_spare_swap(5.0)              # the whole swap
        g.count("warm_spare_claims")
        g.count("warm_spare_swaps")
        s = g.summary()
        assert s["warm_spare_swap_s"] == 5.0
        assert s["warm_spare_claims"] == 1 and s["warm_spare_swaps"] == 1
        assert s["badput_s"] == 2.0             # restore only, not 7.0
        assert s["productive_s"] == 8.0

    def test_metrics_surface(self):
        from faster_distributed_training_tpu.train.metrics import (
            attach_goodput, format_goodput)
        g = GoodputTracker().start()
        g.count("saves")
        out = attach_goodput({"loss": 1.0}, g)
        assert out["loss"] == 1.0 and "goodput_pct" in out
        assert out["goodput_saves"] == 1
        assert attach_goodput({"x": 1}, None) == {"x": 1}
        assert "goodput" in format_goodput(g)


def _e2e_cfg(tmp, **kw):
    """Tiny REAL run_training config: synthetic AG News, 8 steps/epoch x
    2 epochs = 16 global steps, 8-virtual-device dp mesh."""
    return TrainConfig(model="transformer", dataset="synthetic",
                       num_classes=4, batch_size=8, seq_len=16, n_layers=1,
                       d_model=16, d_ff=32, n_heads=2, epochs=2,
                       subset_stride=64, optimizer="sgd", precision="fp32",
                       plot=False, workers=2, log_every=0, donate=False,
                       checkpoint_dir=str(tmp), **kw)


class TestEndToEndRecovery:
    """The r7 acceptance: a synthetic run killed at step N resumes under
    the supervisor and reaches 2N with params/opt-state/RNG BITWISE equal
    to an uninterrupted run (CPU, deterministic hash dropout)."""

    @pytest.fixture(scope="class")
    def reference_state(self, tmp_path_factory):
        from faster_distributed_training_tpu.cli import run_training
        tmp = tmp_path_factory.mktemp("ref")
        return run_training(_e2e_cfg(tmp), log=lambda *_: None)["state"]

    def test_killed_run_resumes_bitwise_equal(self, reference_state,
                                              tmp_path, monkeypatch):
        from faster_distributed_training_tpu.cli import run_training
        monkeypatch.setenv(faults_mod.ENV_DIE, "6")
        got = run_training(
            _e2e_cfg(tmp_path, checkpoint_every=2, supervise=True),
            log=lambda *_: None)
        assert int(got["state"].step) == int(reference_state.step) == 16
        _assert_tree_equal(got["state"].params, reference_state.params)
        _assert_tree_equal(got["state"].opt_state, reference_state.opt_state)
        np.testing.assert_array_equal(np.asarray(got["state"].rng),
                                      np.asarray(reference_state.rng))
        # the crash really happened and was really recovered — and the
        # goodput surface reports it (satellite: metrics wiring)
        assert got["goodput_restarts"] == 1
        assert got["goodput_restores"] == 1
        assert got["goodput_restore_s"] > 0
        assert not got["preempted"]

    def test_sigterm_emergency_save_then_resume(self, reference_state,
                                                tmp_path, monkeypatch):
        from faster_distributed_training_tpu.cli import run_training
        # run 1: SIGTERM at step 5 — cadence far beyond the run, so the
        # only step checkpoint can be the cross-host-agreed emergency save
        monkeypatch.setenv(faults_mod.ENV_SIGTERM, "5")
        first = run_training(_e2e_cfg(tmp_path, checkpoint_every=1000),
                             log=lambda *_: None)
        monkeypatch.delenv(faults_mod.ENV_SIGTERM)
        assert first["preempted"]
        assert first["goodput_preemptions"] == 1
        assert int(first["state"].step) == 5
        mgr = AsyncCheckpointManager(str(tmp_path), prefix="transformer",
                                     log=lambda *_: None)
        assert mgr.committed_steps() == [5]
        # run 2 (the re-launch after preemption): resumes from the
        # emergency checkpoint and finishes bitwise-equal to uninterrupted
        second = run_training(_e2e_cfg(tmp_path, checkpoint_every=1000),
                              log=lambda *_: None)
        assert not second["preempted"]
        assert second["goodput_restores"] == 1
        assert int(second["state"].step) == 16
        _assert_tree_equal(second["state"].params, reference_state.params)
        np.testing.assert_array_equal(np.asarray(second["state"].rng),
                                      np.asarray(reference_state.rng))

    def test_deterministic_crash_not_retried_forever(self, tmp_path,
                                                     monkeypatch):
        from faster_distributed_training_tpu.cli import run_training
        monkeypatch.setenv(faults_mod.ENV_DIE, "4")
        # keep the fault armed on every attempt: the step-4 crash then
        # reproduces after restore and must re-raise after exactly one
        # retry, restarts budget notwithstanding
        monkeypatch.setattr(FaultPlan, "on_step",
                            lambda self, step: (_ for _ in ()).throw(
                                InjectedFault("always dies at step 4"))
                            if step == 4 else None)
        with pytest.raises(InjectedFault):
            run_training(_e2e_cfg(tmp_path, checkpoint_every=2,
                                  supervise=True, max_restarts=50),
                         log=lambda *_: None)

    def test_resilience_disabled_is_default(self):
        cfg = _e2e_cfg("/tmp/unused")
        assert build_resilience(cfg, log=lambda *_: None) is None
