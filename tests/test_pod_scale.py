"""Pod-scale hot path tests (r9): per-host sharded device residency +
shard-streaming async checkpoints, plus the ride-along satellites
(packed metric collective, donation version gate, retention delete
hook).

Everything here is tier-1: CPU, ONE process, using the pure-function /
simulated-``process_index`` seams — ``pod_epoch_order`` and
``ShardedDeviceResidentData`` take explicit (process_index,
process_count), and two ``AsyncCheckpointManager`` instances with
complementary ``shard_owner`` functions against one shared directory
ARE a simulated two-host pod save (the test-budget satellite: no real
multi-process runs in tier-1)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from faster_distributed_training_tpu.config import TrainConfig
from faster_distributed_training_tpu.data import (BatchLoader,
                                                  DeviceResidentData,
                                                  ShardedDeviceResidentData,
                                                  pod_epoch_order,
                                                  synthetic_agnews,
                                                  synthetic_cifar)
from faster_distributed_training_tpu.resilience import (
    AsyncCheckpointManager)
from faster_distributed_training_tpu.train import checkpoint as ckpt


def _assert_tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestPodEpochOrder:
    """The tentpole's pure-function contract: the sliced-permutation
    logic the sharded re-shard derives must reproduce BatchLoader's
    batch stream for every simulated (process_index, process_count)."""

    @pytest.mark.parametrize("pc,lbs", [(1, 16), (2, 8), (4, 4)])
    def test_matches_batchloader_plan(self, pc, lbs):
        n, seed = 70, 42
        for epoch in (0, 3):
            order = pod_epoch_order(n, epoch, seed, process_count=pc,
                                    local_batch_size=lbs)
            steps = (n // pc) // lbs
            assert order.size == steps * pc * lbs
            plans = [BatchLoader((np.zeros((n, 1)), np.arange(n)), lbs,
                                 epoch=epoch, seed=seed, process_index=pi,
                                 process_count=pc).plan()
                     for pi in range(pc)]
            for b in range(steps):
                got = order[b * pc * lbs:(b + 1) * pc * lbs]
                want = np.concatenate([plans[pi][b][0] for pi in range(pc)])
                np.testing.assert_array_equal(got, want)

    def test_single_process_degenerates_to_r8_order(self):
        # pc=1 == the replicated DeviceResidentData's epoch_order — the
        # two resident layouts share one batch-order algebra
        x, y = synthetic_cifar(70, seed=3)
        res = DeviceResidentData((x, y), 16, seed=9)
        np.testing.assert_array_equal(
            pod_epoch_order(70, 4, 9, process_count=1, local_batch_size=16),
            np.asarray(res.epoch_order(4)))


class TestShardedResidency:
    """ISSUE acceptance: the sharded-residency batch stream is bitwise
    the host BatchLoader order for simulated 2- and 4-process layouts,
    on a real multi-device CPU mesh; storage is row-SHARDED (each device
    holds only its slice), not replicated."""

    def _mesh(self):
        from faster_distributed_training_tpu.parallel import make_mesh
        return make_mesh(("dp",), (8,))

    @pytest.mark.parametrize("pc", [2, 4])
    def test_batch_stream_bitwise_matches_host_loaders(self, pc):
        x, y = synthetic_cifar(70, seed=3)
        bs, seed = 16, 42
        res = ShardedDeviceResidentData((x, y), bs, seed=seed,
                                        mesh=self._mesh(),
                                        process_count=pc)
        lbs = bs // pc
        assert res.steps_per_epoch == (70 // pc) // lbs
        for epoch in (0, 2):
            view = res.epoch_arrays(epoch)
            assert view["image"].shape[:2] == (res.steps_per_epoch, bs)
            imgs = np.asarray(view["image"])
            labs = np.asarray(view["label"])
            loaders = [BatchLoader((x, y), lbs, epoch=epoch, seed=seed,
                                   process_index=pi, process_count=pc)
                       for pi in range(pc)]
            plans = [ld.plan() for ld in loaders]
            for b in range(res.steps_per_epoch):
                want = [loaders[pi].materialize(plans[pi][b])
                        for pi in range(pc)]
                np.testing.assert_array_equal(
                    imgs[b], np.concatenate([w["image"] for w in want]))
                np.testing.assert_array_equal(
                    labs[b], np.concatenate([w["label"] for w in want]))

    def test_storage_is_row_sharded_not_replicated(self):
        x, y = synthetic_cifar(64, seed=3)
        res = ShardedDeviceResidentData((x, y), 16, mesh=self._mesh(),
                                        process_count=2)
        for arr in res.arrays.values():
            rows = {s.data.shape[0] for s in arr.addressable_shards}
            # every device holds exactly its 1/8 row slice of the split
            assert rows == {res._n_pad // 8}, rows

    def test_text_stream_matches_mod_padding(self):
        ds = synthetic_agnews(40, max_len=60, seed=7)
        bs, seed, pc = 8, 9, 2
        res = ShardedDeviceResidentData(ds, bs, seed=seed, max_len=64,
                                        mesh=self._mesh(), process_count=pc)
        L = res.seq_len
        view = res.epoch_arrays(1)
        toks = np.asarray(view["tokens"])
        loaders = [BatchLoader(ds, bs // pc, epoch=1, seed=seed, max_len=64,
                               process_index=pi, process_count=pc)
                   for pi in range(pc)]
        plans = [ld.plan() for ld in loaders]
        for b in range(res.steps_per_epoch):
            hb = [loaders[pi].materialize(plans[pi][b]) for pi in range(pc)]
            hl = max(h["tokens"].shape[1] for h in hb)
            assert hl <= L
            got = toks[b]
            off = 0
            for h in hb:
                w = h["tokens"]
                np.testing.assert_array_equal(
                    got[off:off + w.shape[0], :w.shape[1]], w)
                assert not got[off:off + w.shape[0], w.shape[1]:].any()
                off += w.shape[0]

    @pytest.mark.slow
    def test_fused_dispatch_bitwise_sharded_vs_replicated(self):
        """The batch-major dynamic_index gather advances the SAME state
        the replicated path's in-graph permutation gather does, bitwise
        — the mini 2-stage ResNet direct-step family (the r8 pattern:
        uint8 in-graph batch source, in-step augmentation keyed by
        state.step, mixup, BN stat threading), two K=2 dispatches.

        `-m slow` (r9 test-budget satellite): the two fused-program
        compiles cost ~40 s of the 870 s tier-1 budget.  The tier-1
        pins that remain are the batch-STREAM bitwise tests above (the
        view the dispatch indexes is byte-compared against the host
        loaders on the mesh — the dispatch itself adds only a
        dynamic_index) and the run_training e2e twin below."""
        from faster_distributed_training_tpu.cli import (
            enable_compilation_cache)
        from faster_distributed_training_tpu.models.resnet import (
            BasicBlock, ResNet)
        from faster_distributed_training_tpu.optim import build_optimizer
        from faster_distributed_training_tpu.train import (
            create_train_state, make_fused_train_step)

        # the two fused programs dominate this test's cost; the
        # persistent cache (the same one every run_training e2e test
        # uses) makes re-runs compile-free
        enable_compilation_cache()
        cfg = TrainConfig(model="resnet18", num_classes=10, batch_size=8,
                          optimizer="sgd", precision="fp32", alpha=0.2,
                          seed=11, donate=False)
        x, y = synthetic_cifar(40, seed=5)
        model = ResNet(block=BasicBlock, stage_sizes=(1, 1))
        tx, _ = build_optimizer(cfg, steps_per_epoch=4)
        mesh = self._mesh()
        rep = DeviceResidentData((x, y), 8, seed=cfg.seed, mesh=mesh)
        shd = ShardedDeviceResidentData((x, y), 8, seed=cfg.seed,
                                        mesh=mesh, process_count=1)
        state0 = create_train_state(model, tx,
                                    jnp.zeros((8, 32, 32, 3), jnp.float32),
                                    jax.random.PRNGKey(cfg.seed),
                                    init_kwargs={"train": True})
        with mesh:
            f_rep = jax.jit(make_fused_train_step(cfg, 2, resident=rep,
                                                  mesh=mesh))
            f_shd = jax.jit(make_fused_train_step(cfg, 2, resident=shd,
                                                  mesh=mesh))
            s_rep, s_shd = state0, state0
            rep_order = rep.epoch_order(0)
            shd_data = shd.epoch_arrays(0)
            shd_order = shd.epoch_order(0)
            for start in (0, 2):
                s_rep, _ = f_rep(s_rep, rep.arrays, rep_order,
                                 jnp.asarray(start, jnp.int32))
                s_shd, _ = f_shd(s_shd, shd_data, shd_order,
                                 jnp.asarray(start, jnp.int32))
        assert int(s_rep.step) == int(s_shd.step) == 4
        _assert_tree_equal(s_rep.params, s_shd.params)
        _assert_tree_equal(s_rep.batch_stats, s_shd.batch_stats)
        _assert_tree_equal(s_rep.opt_state, s_shd.opt_state)
        np.testing.assert_array_equal(np.asarray(s_rep.rng),
                                      np.asarray(s_shd.rng))

    @pytest.mark.slow
    def test_run_training_sharded_layout_bitwise_e2e(self, tmp_path):
        """Full run_training twin of the direct pin above (out of the
        tier-1 budget per the r9 test-budget satellite): a sharded-
        layout resident run is bitwise the replicated resident run."""
        from faster_distributed_training_tpu.cli import run_training
        base = dict(model="transformer", dataset="synthetic",
                    num_classes=4, batch_size=8, seq_len=16, n_layers=1,
                    d_model=16, d_ff=32, n_heads=2, epochs=2,
                    subset_stride=64, optimizer="sgd", precision="fp32",
                    plot=False, workers=2, log_every=0, donate=False,
                    data_path="resident")
        ref = run_training(TrainConfig(checkpoint_dir=str(tmp_path / "a"),
                                       **base),
                           log=lambda *_: None)["state"]
        got = run_training(TrainConfig(checkpoint_dir=str(tmp_path / "b"),
                                       resident_layout="sharded",
                                       steps_per_dispatch=2, **base),
                           log=lambda *_: None)["state"]
        assert int(got.step) == int(ref.step) == 16
        _assert_tree_equal(got.params, ref.params)
        _assert_tree_equal(got.opt_state, ref.opt_state)
        np.testing.assert_array_equal(np.asarray(got.rng),
                                      np.asarray(ref.rng))

    def test_build_device_resident_layouts(self):
        x, y = synthetic_cifar(64, seed=3)
        cfg = TrainConfig(batch_size=16, data_path="resident")
        mesh = self._mesh()
        auto = __import__(
            "faster_distributed_training_tpu.data.device_resident",
            fromlist=["build_device_resident"])
        rep = auto.build_device_resident(cfg, (x, y), mesh=mesh)
        assert isinstance(rep, DeviceResidentData)   # single-host auto
        shd = auto.build_device_resident(
            cfg.replace(resident_layout="sharded"), (x, y), mesh=mesh)
        assert isinstance(shd, ShardedDeviceResidentData)
        assert auto.build_device_resident(
            cfg.replace(data_path="host"), (x, y), mesh=mesh) is None


class TestShardedCheckpoint:
    """ISSUE acceptance: per-host shard snapshot + background write with
    two-phase COMMIT; a kill between phase 1 and the commit leaves a dir
    ``has_checkpoint`` rejects and restore falls back past; restore of a
    pre-PR single-file (orbax) checkpoint still works."""

    @pytest.fixture()
    def tiny(self):
        from faster_distributed_training_tpu.models import Transformer
        from faster_distributed_training_tpu.optim import build_optimizer
        from faster_distributed_training_tpu.train import (
            create_train_state)
        cfg = TrainConfig(model="transformer", num_classes=4, batch_size=4,
                          seq_len=8, optimizer="sgd", precision="fp32",
                          donate=False)
        model = Transformer(n_class=4, vocab=32, n_layers=1, h=2,
                            d_model=16, d_ff=32, d_hidden=16, maxlen=8)
        tx, _ = build_optimizer(cfg, steps_per_epoch=2)
        return create_train_state(model, tx, jnp.zeros((4, 8), jnp.int32),
                                  jax.random.PRNGKey(3),
                                  init_kwargs={"train": True})

    def _managers(self, d, **kw):
        """Two simulated pod hosts sharing one checkpoint dir: pi=0 owns
        the replica-0 shards (on this single-device state: everything),
        pi=1 owns nothing — its phase-1 contribution is an empty shard
        file whose DONE marker the commit barrier still requires."""
        m0 = AsyncCheckpointManager(d, process_index=0, process_count=2,
                                    shard_owner=lambda sh:
                                    sh.replica_id == 0,
                                    log=lambda *_: None,
                                    commit_timeout_s=20.0, **kw)
        m1 = AsyncCheckpointManager(d, process_index=1, process_count=2,
                                    shard_owner=lambda sh: False,
                                    log=lambda *_: None,
                                    commit_timeout_s=20.0, **kw)
        return m0, m1

    def test_two_phase_commit_and_bitwise_restore(self, tmp_path, tiny):
        m0, m1 = self._managers(str(tmp_path), every_steps=1)
        # host 1 finishes phase 1 first: no COMMIT until host 0's
        # barrier sees every DONE marker
        assert m1.save(tiny, 4, epoch=1, step_in_epoch=4)
        m1.wait()
        path = os.path.join(str(tmp_path), m1._name(4))
        assert ckpt.is_sharded_checkpoint(path)
        assert not ckpt.is_committed(path)
        assert m0.save(tiny, 4, epoch=1, step_in_epoch=4)
        m0.wait()
        assert ckpt.is_committed(path)
        got = m0.restore_latest(tiny)
        assert got is not None
        restored, meta = got
        assert meta["step"] == 4 and meta["epoch"] == 1
        _assert_tree_equal(ckpt._state_pytree(restored),
                           ckpt._state_pytree(tiny))
        m0.close(), m1.close()

    def test_split_blocks_reassemble_bitwise(self, tmp_path, tiny):
        """Real multi-block reassembly: every leaf's rows split across
        two hosts' shard files, restored into the template exactly."""
        path = os.path.join(str(tmp_path), "ck_step_000000008")
        b0, b1 = [], []
        for key, _idx, arr in ckpt.host_shard_snapshot(tiny):
            if arr.ndim == 0 or arr.shape[0] < 2:
                b0.append((key, None, arr))
            else:
                h = arr.shape[0] // 2
                rest = tuple(slice(0, s) for s in arr.shape[1:])
                b0.append((key, (slice(0, h),) + rest, arr[:h]))
                b1.append((key, (slice(h, arr.shape[0]),) + rest, arr[h:]))
        ckpt.write_host_shards(path, 0, b0)
        ckpt.write_host_shards(path, 1, b1)
        ckpt.commit_sharded_checkpoint(
            path, {"step": 8, "epoch": 2, "best_acc": 0.5}, n_hosts=2,
            timeout_s=5.0)
        restored, epoch, best = ckpt.restore_sharded_checkpoint(
            str(tmp_path), "ck_step_000000008", tiny)
        assert epoch == 2 and best == 0.5
        _assert_tree_equal(ckpt._state_pytree(restored),
                           ckpt._state_pytree(tiny))

    def test_commit_barrier_times_out_without_peers(self, tmp_path, tiny):
        path = os.path.join(str(tmp_path), "c")
        ckpt.write_host_shards(path, 0, ckpt.host_shard_snapshot(tiny))
        with pytest.raises(TimeoutError, match="DONE markers missing"):
            ckpt.commit_sharded_checkpoint(path, {"step": 1}, n_hosts=2,
                                           timeout_s=0.2)
        assert not ckpt.is_committed(path)

    def test_dead_host_barrier_timeout_swept_and_falls_back(self, tmp_path,
                                                            tiny):
        """r10 satellite: the MANAGER-path ordering under a dead host —
        host 1 dies before its phase-1 DONE, host 0's background commit
        barrier times out (a counted save FAILURE, training continues),
        the dir stays uncommitted and invisible, and the next restore
        sweeps the residue and falls back to the older committed
        checkpoint."""
        from faster_distributed_training_tpu.resilience import (
            GoodputTracker)
        g = GoodputTracker().start()
        m0 = AsyncCheckpointManager(str(tmp_path), process_index=0,
                                    process_count=2,
                                    shard_owner=lambda sh:
                                    sh.replica_id == 0,
                                    every_steps=2, goodput=g,
                                    log=lambda *_: None,
                                    commit_timeout_s=0.3)
        m0.save(tiny, 2, epoch=0, step_in_epoch=2, sync=True)
        # step 4: host 1 is DEAD — no shard file, no DONE, ever
        assert m0.save(tiny, 4, epoch=1, step_in_epoch=4)
        m0.wait()       # drains the barrier TimeoutError
        s = g.summary()
        assert s["save_failures"] == 1     # surfaced, not raised
        torn = os.path.join(str(tmp_path), m0._name(4))
        assert os.path.isdir(torn)
        assert not ckpt.has_checkpoint(str(tmp_path), m0._name(4))
        got = m0.restore_latest(tiny)
        assert got is not None and got[1]["step"] == 2   # fell back
        assert not os.path.exists(torn)    # residue swept at restore
        _assert_tree_equal(ckpt._state_pytree(got[0]),
                           ckpt._state_pytree(tiny))
        m0.close()

    def test_kill_between_phase1_and_commit_falls_back(self, tmp_path,
                                                       tiny):
        m0, m1 = self._managers(str(tmp_path), every_steps=2)
        # a COMMITTED earlier checkpoint to fall back to (the sync
        # collective orbax path — also the pre-PR single-file format,
        # pinning the interop acceptance)
        m0.save(tiny, 2, epoch=0, step_in_epoch=2, sync=True)
        # phase 1 of step 4 on host 1 only = the kill window between
        # shard write and COMMIT
        m1.save(tiny, 4, epoch=0, step_in_epoch=4)
        m1.wait()
        torn = os.path.join(str(tmp_path), m1._name(4))
        assert os.path.isdir(torn)
        assert not ckpt.has_checkpoint(str(tmp_path), m1._name(4))
        got = m0.restore_latest(tiny)
        assert got is not None
        _restored, meta = got
        assert meta["step"] == 2      # fell back past the torn step 4
        m0.close(), m1.close()

    def test_crashed_attempt_residue_swept_at_restore(self, tmp_path,
                                                      tiny):
        """A crash AFTER every host's phase 1 but BEFORE the COMMIT
        leaves a dir with a full set of stale DONE markers.  If it
        survived to the re-reached save step, process 0's commit
        barrier would see them and COMMIT while peers are still
        mid-write — mixing two attempts' shard files.  restore_latest
        (the one point where no host can be writing) sweeps ALL
        uncommitted residue, so the re-save starts clean."""
        m0, m1 = self._managers(str(tmp_path), every_steps=2)
        m0.save(tiny, 2, epoch=0, step_in_epoch=2, sync=True)
        # crashed attempt at step 4: BOTH hosts' DONE markers on disk,
        # no COMMIT (killed in the barrier window)
        stale = os.path.join(str(tmp_path), m0._name(4))
        ckpt.write_host_shards(stale, 0, ckpt.host_shard_snapshot(tiny))
        ckpt.write_host_shards(stale, 1, [])
        assert not ckpt.is_committed(stale)
        got = m0.restore_latest(tiny)
        assert got is not None and got[1]["step"] == 2
        assert not os.path.exists(stale)   # residue gone, trap disarmed
        # the re-reached save at the same step commits cleanly
        assert m1.save(tiny, 4, epoch=1, step_in_epoch=4)
        m1.wait()
        assert m0.save(tiny, 4, epoch=1, step_in_epoch=4)
        m0.wait()
        assert ckpt.is_committed(stale)
        got = m0.restore_latest(tiny)
        assert got is not None and got[1]["step"] == 4
        _assert_tree_equal(ckpt._state_pytree(got[0]),
                           ckpt._state_pytree(tiny))
        m0.close(), m1.close()

    def test_mixed_format_dirs_interoperate(self, tmp_path, tiny):
        """A dir holding a pre-PR single-file checkpoint AND a newer
        sharded one: restore takes the sharded newest; corrupting it
        falls back to the single-file one."""
        m0 = AsyncCheckpointManager(str(tmp_path), every_steps=1,
                                    force_sharded=True,
                                    log=lambda *_: None,
                                    commit_timeout_s=10.0)
        m0.save(tiny, 2, epoch=0, step_in_epoch=2, sync=True)   # orbax
        m0.save(tiny, 4, epoch=1, step_in_epoch=4)              # sharded
        m0.wait()
        assert ckpt.is_sharded_checkpoint(
            os.path.join(str(tmp_path), m0._name(4)))
        got = m0.restore_latest(tiny)
        assert got is not None and got[1]["step"] == 4
        # corrupt the sharded newest: delete its shard payloads
        import glob
        for f in glob.glob(os.path.join(str(tmp_path), m0._name(4),
                                        "shards", "host_*.npz")):
            os.remove(f)
        got = m0.restore_latest(tiny)
        assert got is not None and got[1]["step"] == 2
        m0.close()

    def test_block_filtered_restore_reads_only_needed_shards(
            self, tmp_path, tiny):
        """r10 satellite (ROADMAP r9 follow-on): restore reads ONLY the
        manifest entries overlapping this host's needed regions and
        fills a per-host partial buffer — per-host bytes read < full
        state size.  Simulated 2-host split: every rank>=1 leaf's rows
        are halved across two shard files; "host 0" needs only the
        first halves."""
        name = "ck_step_000000016"
        path = os.path.join(str(tmp_path), name)
        b0, b1 = [], []
        for key, _idx, arr in ckpt.host_shard_snapshot(tiny):
            if arr.ndim == 0 or arr.shape[0] < 2:
                b0.append((key, None, arr))
            else:
                h = arr.shape[0] // 2
                rest = tuple(slice(0, s) for s in arr.shape[1:])
                b0.append((key, (slice(0, h),) + rest, arr[:h]))
                b1.append((key, (slice(h, arr.shape[0]),) + rest, arr[h:]))
        ckpt.write_host_shards(path, 0, b0)
        ckpt.write_host_shards(path, 1, b1)
        ckpt.commit_sharded_checkpoint(path, {"step": 16, "epoch": 3,
                                              "best_acc": 0.25},
                                       n_hosts=2, timeout_s=5.0)
        full_bytes = sum(arr.nbytes
                         for _k, _i, arr in ckpt.host_shard_snapshot(tiny))

        def first_half_rows(_key, tv):
            shape = np.shape(tv)
            if len(shape) == 0 or shape[0] < 2:
                return None                      # whole (tiny scalars)
            return [(slice(0, shape[0] // 2),)
                    + tuple(slice(0, s) for s in shape[1:])]

        stats = {}
        restored, epoch, best = ckpt.restore_sharded_checkpoint(
            str(tmp_path), name, tiny, needed_fn=first_half_rows,
            stats=stats)
        assert epoch == 3 and best == 0.25
        # the filtering is real: the second-half blocks were never read
        assert stats["blocks_skipped"] > 0
        assert 0 < stats["bytes_read"] < full_bytes
        # ...and every needed region restored bitwise
        want = jax.tree_util.tree_flatten_with_path(
            ckpt._state_pytree(tiny))[0]
        got = {jax.tree_util.keystr(p): v for p, v in
               jax.tree_util.tree_flatten_with_path(
                   ckpt._state_pytree(restored))[0]}
        for p, tv in want:
            key = jax.tree_util.keystr(p)
            tv = np.asarray(tv)
            if tv.ndim == 0 or tv.shape[0] < 2:
                np.testing.assert_array_equal(np.asarray(got[key]), tv)
            else:
                h = tv.shape[0] // 2
                np.testing.assert_array_equal(
                    np.asarray(got[key])[:h], tv[:h])
        # the default (no needed_fn, single process) still reads all
        stats = {}
        ckpt.restore_sharded_checkpoint(str(tmp_path), name, tiny,
                                        stats=stats)
        assert stats["blocks_skipped"] == 0
        assert stats["bytes_read"] == full_bytes
        assert ckpt.template_needed_regions(np.zeros((4, 4))) is None

    def test_restore_agreement_decision(self):
        """The cross-host restore-divergence check as a pure function of
        the gathered steps vector: agreement (incl. all-None = −1)
        passes, any disagreement — one host fell back or exhausted its
        walk — raises for every host (they all see the same vector)."""
        from faster_distributed_training_tpu.resilience import (
            RestoreDivergence)
        ok = AsyncCheckpointManager._verify_restore_agreement
        ok(np.asarray([40, 40, 40], np.int32))
        ok(np.asarray([-1, -1], np.int32))        # nobody restored
        for bad in ([40, 30, 40], [40, -1]):      # fallback / exhausted
            with pytest.raises(RestoreDivergence, match="different"):
                ok(np.asarray(bad, np.int32))

    def test_force_sharded_single_process_roundtrip(self, tmp_path, tiny):
        # one process down the per-host shard-streaming path
        m = AsyncCheckpointManager(str(tmp_path), every_steps=1,
                                   force_sharded=True,
                                   log=lambda *_: None)
        assert m.save(tiny, 3)
        m.wait()
        got = m.restore_latest(tiny)
        assert got is not None and got[1]["step"] == 3
        _assert_tree_equal(ckpt._state_pytree(got[0]),
                           ckpt._state_pytree(tiny))
        m.close()


class TestRetentionDeleteHook:
    """Satellite: keep-last-K pruning goes through the delete hook (the
    GCS seam) with bit-identical local behavior — torn dirs still get
    swept."""

    def test_prune_routes_through_hook_and_sweeps_torn_dirs(
            self, tmp_path):
        from faster_distributed_training_tpu.resilience.manager import (
            _local_delete_tree)
        deleted = []

        def hook(path):
            deleted.append(os.path.basename(path))
            _local_delete_tree(path)

        m = AsyncCheckpointManager(str(tmp_path), every_steps=1, keep=1,
                                   delete_fn=hook, log=lambda *_: None)
        for step in (2, 4):
            d = os.path.join(str(tmp_path), m._name(step))
            os.makedirs(d)
            ckpt._write_json_atomic(os.path.join(d, "meta.json"),
                                    {"step": step})
            ckpt._write_json_atomic(os.path.join(d, "COMMIT"), {})
        torn = os.path.join(str(tmp_path), m._name(3))
        os.makedirs(torn)                     # uncommitted crash residue
        m._prune()
        assert m._name(2) in deleted          # keep=1: newest survives
        assert m._name(3) in deleted          # torn dir swept
        assert not os.path.exists(torn)
        assert os.path.isdir(os.path.join(str(tmp_path), m._name(4)))


class TestPackedMetricCollective:
    """Satellite: all_reduce_metrics packs the dict into ONE collective;
    the pack/unpack algebra is pure and the single-process no-op is
    unchanged."""

    def test_single_process_noop_copy(self):
        from faster_distributed_training_tpu.parallel.collectives import (
            all_reduce_metrics)
        m = {"loss": 1.5, "correct": 10.0}
        out = all_reduce_metrics(m)
        assert out == m and out is not m
        assert all_reduce_metrics({}) == {}

    def test_pack_unpack_roundtrip(self):
        from faster_distributed_training_tpu.parallel.collectives import (
            _pack_values, _unpack_values)
        # 1_000_000_007 > 2^24: float32 packing would round it — the
        # packed vector must be float64 (exact to 2^53, covering
        # byte/sample counters)
        m = {"a": 1.5, "b": np.arange(3, dtype=np.float32),
             "c": 1_000_000_007}
        sizes, packed = _pack_values(m)
        assert sizes == [1, 3, 1] and packed.size == 5
        assert packed.dtype == np.float64
        out = _unpack_values(list(m), sizes, packed * 2)
        assert out["a"] == 3.0 and out["c"] == 2_000_000_014.0
        np.testing.assert_array_equal(out["b"],
                                      np.asarray([0.0, 2.0, 4.0]))

    def test_gather_single_process_adds_leading_axis(self):
        from faster_distributed_training_tpu.parallel.collectives import (
            all_gather_across_processes)
        got = all_gather_across_processes(np.asarray(7, np.int32))
        assert got.shape == (1,) and int(got[0]) == 7
