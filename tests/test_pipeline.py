"""Pipeline-parallelism tests (r22 tentpole: the pp axis).

The ISSUE acceptance pins, all tier-1 on the 8-virtual-device CPU mesh
(conftest) with clean `requires_devices` degradation elsewhere:

  * schedule/partition/microbatch resolution as data: contiguous
    balanced 1F1B stages, v=2 interleaving, (S-1)/(M+S-1) bubble, the
    rotation schedule's (stage, microbatch) tick table, and the
    divisor-only auto microbatch policy;
  * `_ici_device_mesh` hybrid DCN factoring for 3-axis (dp, tp, pp)
    meshes: pp (sorting outermost at speed -1) is the PREFERRED DCN
    axis, dp absorbs the process count when pp is absent, tp/sp stay
    ICI-only, and an unservable request falls back to None (the plain
    reshape) instead of crashing;
  * pp=2 ≡ pp=1 train-step parity in the documented cross-program
    allclose class (batch-dim tiling + microbatch reduction order —
    the r8 scan-rounding precedent; XLA:CPU compiles the fp32
    LN/softmax islands with different fusion per program, ~1 ULP/step);
  * pp=1 byte-identity: the pipeline plumbing adds NOTHING to the
    trace when disabled (lowered HLO text equality — the r19 program
    pin is the downstream safety net);
  * kill-at-N on a (dp, pp) mesh resumes BITWISE through the r14
    elastic-recovery path (within one program family everything stays
    bitwise);
  * the pipeline rule table lands in manifest.json beside the r15
    compile table (enabled runs carry the full stage/placement record,
    pp=1 runs record {"enabled": false});
  * --lm_causal: causal masking at TRAINING time for --task lm (auto-
    routed dense — flash takes key-padding masks only), position-t
    logits independent of future tokens, and the causal-train → decode
    round trip: incremental (prefix-truncated) logits match the full
    forward, so autoregressive serving replays exactly what training
    optimized.  The heavy DecodeEngine twin is `-m slow`.
"""

import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from faster_distributed_training_tpu.config import TrainConfig, parse_mesh
from faster_distributed_training_tpu.parallel import make_mesh
from faster_distributed_training_tpu.parallel.mesh import (_ici_device_mesh,
                                                           canonical_axes,
                                                           pp_size)
from faster_distributed_training_tpu.parallel.pipeline import (
    PipelineSpec, build_pipeline_spec, bubble_fraction, partition_stages,
    pipeline_rules, resolve_microbatches, schedule_ticks, stage_idle_ticks,
    virtual_chunks)
from faster_distributed_training_tpu.resilience import faults as faults_mod

_SILENT = lambda *_: None                                 # noqa: E731


def _tiny_tf_cfg(tmp, **kw):
    """The resilience-suite tiny transformer, two layers so a pp=2 mesh
    has something to stage (partition_stages refuses S > L)."""
    base = dict(model="transformer", dataset="synthetic", num_classes=4,
                batch_size=8, seq_len=16, n_layers=2, d_model=16, d_ff=32,
                n_heads=2, epochs=1, subset_stride=64, optimizer="sgd",
                precision="fp32", plot=False, workers=0, log_every=0,
                donate=False, checkpoint_dir=str(tmp))
    base.update(kw)
    return TrainConfig(**base)


def _tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _tree_allclose(a, b, rtol, atol=0.0):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


class TestScheduleUnits:
    """The rule table's pure-python pieces — no devices, no tracing."""

    def test_partition_contiguous_balanced(self):
        assert partition_stages(6, 2) == ((0, 1, 2), (3, 4, 5))
        # earlier stages take the extra layer on uneven splits
        assert partition_stages(7, 3) == ((0, 1, 2), (3, 4), (5, 6))
        assert partition_stages(4, 1) == ((0, 1, 2, 3),)
        with pytest.raises(ValueError, match="cannot split"):
            partition_stages(2, 3)
        with pytest.raises(ValueError, match="unknown pipeline schedule"):
            partition_stages(4, 2, "gpipe")

    def test_partition_interleaved_v2_and_fallback(self):
        # L=8, S=2: chunks of 2 dealt round-robin — each stage touches
        # two non-adjacent depth regions (the Megatron v-interleave)
        assert partition_stages(8, 2, "interleaved") == \
            ((0, 1, 4, 5), (2, 3, 6, 7))
        # every layer appears exactly once, whatever the shape
        for L, S in ((8, 2), (7, 3), (9, 4)):
            got = partition_stages(L, S, "interleaved")
            assert sorted(i for st in got for i in st) == list(range(L))
        # interleaving requires L % 2S == 0 (equal chunks, slot j on
        # stage j % S); anything else is the contiguous fallback —
        # including L < 2S and the ragged L=7,S=3 / L=9,S=4 shapes
        for L, S in ((3, 2), (6, 2), (7, 3), (9, 4)):
            assert partition_stages(L, S, "interleaved") == \
                partition_stages(L, S, "1f1b")

    def test_virtual_chunks_depth_order(self):
        """The high-severity r22 review fix: the tick loop executes
        depth-ordered virtual chunks, never a stage's concatenated
        round-robin layer list — a microbatch must see layer 0..L-1 in
        order under EVERY schedule."""
        # interleaved L=8,S=2: stages own (0,1,4,5)/(2,3,6,7) but the
        # execution order is the four depth chunks, slot j on stage j%S
        spec = PipelineSpec(
            n_layers=8, n_stages=2, n_microbatches=4,
            stage_layers=partition_stages(8, 2, "interleaved"),
            schedule="interleaved")
        chunks = virtual_chunks(spec)
        assert chunks == ((0, 1), (2, 3), (4, 5), (6, 7))
        assert [i for ch in chunks for i in ch] == list(range(8))
        # V = 2S virtual slots lengthen fill/drain: T = M + V - 1 and
        # the HONEST bubble (V-1)/(M+V-1), not the 1f1b (S-1)/(M+S-1)
        assert spec.n_virtual == 4
        assert spec.n_ticks == 7
        assert spec.bubble_pct == pytest.approx(100.0 * 3 / 7)
        # per-stage idle is per-slot idle x V/S slots
        assert stage_idle_ticks(spec) == (6, 6)
        # 1f1b: chunks ARE the stages, everything degenerates to S
        spec1 = PipelineSpec(n_layers=8, n_stages=2, n_microbatches=4,
                             stage_layers=partition_stages(8, 2))
        assert virtual_chunks(spec1) == spec1.stage_layers
        assert spec1.n_virtual == 2 and spec1.n_ticks == 5

    def test_bubble_fraction(self):
        assert bubble_fraction(1, 8) == 0.0
        assert bubble_fraction(2, 4) == pytest.approx(1 / 5)
        assert bubble_fraction(4, 8) == pytest.approx(3 / 11)
        # doubling M toward 2S halves the bubble's share of the ticks
        assert bubble_fraction(4, 4) > bubble_fraction(4, 8)

    def test_schedule_ticks_rotation(self):
        ticks = schedule_ticks(2, 3)
        assert len(ticks) == 4                      # T = M + S - 1
        assert ticks[0] == ((0, 0),)                # fill: stage 1 idle
        assert ticks[1] == ((0, 1), (1, 0))
        assert ticks[2] == ((0, 2), (1, 1))
        assert ticks[3] == ((1, 2),)                # drain: stage 0 idle
        # every (stage, microbatch) pair runs exactly once
        pairs = [p for t in ticks for p in t]
        assert sorted(pairs) == [(s, m) for s in range(2) for m in range(3)]

    def test_stage_idle_ticks(self):
        spec = PipelineSpec(n_layers=4, n_stages=2, n_microbatches=4,
                            stage_layers=partition_stages(4, 2))
        assert spec.n_ticks == 5
        assert spec.bubble_pct == pytest.approx(20.0)
        assert stage_idle_ticks(spec) == (1, 1)     # S-1 per stage

    def test_resolve_microbatches(self):
        # explicit request must divide the global batch
        assert resolve_microbatches(16, 2, requested=8) == 8
        with pytest.raises(ValueError, match="does not divide"):
            resolve_microbatches(16, 2, requested=3)
        # negative counts must not sneak past divisibility (8 % -2 == 0
        # in python) into an obscure downstream reshape failure
        with pytest.raises(ValueError, match="must be in"):
            resolve_microbatches(8, 2, requested=-2)
        with pytest.raises(ValueError, match="must be in"):
            resolve_microbatches(8, 2, requested=16)
        # auto: largest divisor in [S, 2S] (2S halves the bubble vs S)
        assert resolve_microbatches(16, 2) == 4
        assert resolve_microbatches(16, 4) == 8
        assert resolve_microbatches(12, 2) == 4     # 4 | 12, skips 3
        # no divisor in [S, 2S]: largest divisor <= S, floor 1
        assert resolve_microbatches(7, 2) == 1

    def test_build_spec_gates(self, requires_devices):
        requires_devices(4)
        mesh = make_mesh(("dp", "pp"), (2, 2), jax.devices()[:4])
        assert pp_size(mesh) == 2
        cfg = _tiny_tf_cfg("/tmp", batch_size=8)
        spec = build_pipeline_spec(cfg, mesh)
        assert spec.n_stages == 2 and spec.n_microbatches == 4
        assert spec.stage_layers == ((0,), (1,))
        # pp=1 mesh -> None (the byte-identity contract's gate)
        assert build_pipeline_spec(cfg, make_mesh(("dp",), (2,),
                                                  jax.devices()[:2])) is None
        with pytest.raises(ValueError, match="no staged form"):
            build_pipeline_spec(cfg.replace(model="resnet18"), mesh)
        # quant + pp composes since r23 (the PipelineTickCtx per-step
        # amax cadence; scale-state parity pinned in
        # tests/test_pp_residency.py) — only the remat combination
        # still refuses: the cadence's cross-tick history stash cannot
        # cross nn.remat's per-tick checkpoint traces
        spec_q = build_pipeline_spec(cfg.replace(quant="int8"), mesh)
        assert spec_q is not None and spec_q.n_stages == 2
        with pytest.raises(ValueError, match="remat"):
            build_pipeline_spec(cfg.replace(quant="int8", remat=True),
                                mesh)
        # non-parity dropout combos still warn: xla (threefry masks
        # fold per invocation) and hash under AUTO attention (the
        # resolved kernel is unknown, treated conservatively) ...
        with pytest.warns(UserWarning, match="dropout"):
            build_pipeline_spec(cfg.replace(dropout_impl="xla"), mesh)
        with pytest.warns(UserWarning, match="dropout"):
            build_pipeline_spec(cfg.replace(dropout_impl="hash"), mesh)
        # ... but the r23 parity combo (hash engine + dense attention +
        # flax FFN, no remat) and dropout_impl=none stay silent
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            build_pipeline_spec(cfg.replace(dropout_impl="hash",
                                            attention="dense"), mesh)
            build_pipeline_spec(cfg.replace(dropout_impl="none"), mesh)

    def test_rule_table_shapes(self):
        assert pipeline_rules(None) == {"enabled": False, "n_stages": 1}
        spec = PipelineSpec(n_layers=4, n_stages=2, n_microbatches=4,
                            stage_layers=partition_stages(4, 2))
        rules = pipeline_rules(spec)
        assert rules["enabled"] and rules["n_stages"] == 2
        assert rules["stages"][0]["layers"] == ["layer_0", "layer_1"]
        assert rules["stages"][0]["extra"] == ["embeddings"]
        assert rules["stages"][1]["extra"] == ["ln_final", "head"]
        assert rules["bubble_pct"] == pytest.approx(20.0)
        assert "pp" in rules["activation_placement"]
        json.dumps(rules)                           # manifest-serializable

    def test_mesh_axis_aliases(self):
        assert canonical_axes(("dp", "pipe")) == ("dp", "pp")
        assert canonical_axes(("data", "stage")) == ("dp", "pp")
        assert parse_mesh("dp=2,tp=2,pp=2") == (("dp", "tp", "pp"),
                                                (2, 2, 2))


class TestIciDeviceMeshDcn:
    """Satellite 2: the hybrid DCN factoring for 3-axis meshes.  The
    CPU container is single-process, so the multi-process branch is
    exercised directly — process_count monkeypatched, the hybrid
    constructor stubbed to capture its (ici, dcn) factoring (the real
    one validates physical TPU topology this host doesn't have)."""

    def _capture(self, monkeypatch, pc=2):
        import jax.experimental.mesh_utils as mu
        calls = {}

        def stub(ici, dcn):
            calls["args"] = (tuple(ici), tuple(dcn))
            shape = tuple(i * d for i, d in zip(ici, dcn))
            return np.arange(int(np.prod(shape))).reshape(shape)

        monkeypatch.setattr(jax, "process_count", lambda: pc)
        monkeypatch.setattr(mu, "create_hybrid_device_mesh", stub)
        return calls

    def test_pp_is_preferred_dcn_axis(self, monkeypatch):
        calls = self._capture(monkeypatch)
        got = _ici_device_mesh((2, 2, 2), ("dp", "tp", "pp"))
        # permuted slowest-first = (pp, dp, tp); pp absorbs the 2
        # processes (one stage per slice), dp/tp stay inside a slice
        assert calls["args"] == ((1, 2, 2), (2, 1, 1))
        assert got.shape == (2, 2, 2)               # caller's axis order

    def test_dp_dcn_when_pp_absent(self, monkeypatch):
        calls = self._capture(monkeypatch)
        got = _ici_device_mesh((4, 2), ("dp", "tp"))
        assert calls["args"] == ((2, 2), (2, 1))
        assert got.shape == (4, 2)

    def test_tp_never_spans_dcn(self, monkeypatch):
        # a tp-only mesh cannot absorb the process count -> an error
        # with the cause in it (no silent plain-reshape fallback),
        # never a tp DCN factoring
        calls = self._capture(monkeypatch)
        with pytest.raises(RuntimeError, match="never span DCN"):
            _ici_device_mesh((4,), ("tp",))
        assert "args" not in calls
        # pp present but indivisible, dp too small: same error
        with pytest.raises(RuntimeError, match="divisible"):
            _ici_device_mesh((3, 2), ("pp", "tp"))

    def test_topology_failure_raises_with_cause(self, monkeypatch):
        import jax.experimental.mesh_utils as mu
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        monkeypatch.setattr(mu, "create_hybrid_device_mesh",
                            lambda *a, **k: (_ for _ in ()).throw(
                                RuntimeError("no topology")))
        with pytest.raises(RuntimeError, match="no topology"):
            _ici_device_mesh((2, 2, 2), ("dp", "tp", "pp"))

    def test_single_process_three_axes(self, requires_devices):
        requires_devices(8)
        got = _ici_device_mesh((2, 2, 2), ("dp", "tp", "pp"))
        assert got is not None and got.shape == (2, 2, 2)


class TestPipelineParity:
    """pp=2 ≡ pp=1 on the same weights/batch: the staged encoder
    computes the SAME values as sequential microbatching, so the only
    daylight is batch-dim tiling + the microbatch reduction order —
    the documented cross-program allclose class (r8 precedent)."""

    @pytest.fixture(scope="class")
    def parity(self, requires_devices):
        requires_devices(4)
        import optax

        from faster_distributed_training_tpu.cli import build_model
        from faster_distributed_training_tpu.train.state import (
            create_train_state)
        from faster_distributed_training_tpu.train.steps import (
            make_train_step)
        cfg = TrainConfig(model="transformer", dataset="synthetic",
                          task="lm", batch_size=8, seq_len=16, n_layers=2,
                          d_model=32, d_ff=64, n_heads=4,
                          dropout_impl="none", optimizer="sgd",
                          precision="fp32", donate=False, num_classes=4)
        mesh = make_mesh(("dp", "pp"), (2, 2), jax.devices()[:4])
        spec = build_pipeline_spec(cfg, mesh)
        model = build_model(cfg, vocab_size=100, mesh=None)
        sample = jnp.zeros((8, 16), jnp.int32)
        state = create_train_state(model, optax.sgd(0.1), sample,
                                   jax.random.PRNGKey(0),
                                   init_kwargs={"train": True})
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                              (8, 16), 0, 100)}
        return cfg, mesh, spec, state, batch

    def test_pp2_step_matches_unstaged(self, parity):
        from faster_distributed_training_tpu.train.steps import (
            make_train_step)
        cfg, mesh, spec, state, batch = parity
        assert spec.n_stages == 2 and spec.n_microbatches == 4
        with mesh:
            s_ref, m_ref = jax.jit(make_train_step(cfg))(state, batch)
            s_pp, m_pp = jax.jit(make_train_step(cfg, pipeline=spec))(
                state, batch)
        np.testing.assert_allclose(float(m_pp["loss"]),
                                   float(m_ref["loss"]), rtol=1e-4)
        # post-step params: one optimizer step apart only by the fp32
        # fusion-island class (~1 ULP measured; 1e-4 is the r8 bound)
        _tree_allclose(s_ref.params, s_pp.params, rtol=1e-4, atol=1e-6)

    def test_interleaved_pp2_step_matches_unstaged(self, requires_devices):
        """The r22 review's high-severity pin: interleaved assignment
        must still execute layers in DEPTH order (the tick loop runs
        virtual_chunks, not a stage's concatenated round-robin list),
        so pp=2 interleaved sits in the same allclose class vs pp=1 as
        1f1b does.  L=4, S=2 → four single-layer chunks, stages own
        (0,2)/(1,3), execution order 0,1,2,3."""
        requires_devices(4)
        import optax

        from faster_distributed_training_tpu.cli import build_model
        from faster_distributed_training_tpu.train.state import (
            create_train_state)
        from faster_distributed_training_tpu.train.steps import (
            make_train_step)
        cfg = TrainConfig(model="transformer", dataset="synthetic",
                          task="lm", batch_size=8, seq_len=16, n_layers=4,
                          d_model=32, d_ff=64, n_heads=4,
                          dropout_impl="none", optimizer="sgd",
                          precision="fp32", donate=False, num_classes=4,
                          pp_schedule="interleaved")
        mesh = make_mesh(("dp", "pp"), (2, 2), jax.devices()[:4])
        spec = build_pipeline_spec(cfg, mesh)
        assert spec.schedule == "interleaved"
        assert spec.stage_layers == ((0, 2), (1, 3))
        assert virtual_chunks(spec) == ((0,), (1,), (2,), (3,))
        assert spec.n_virtual == 4 and spec.n_microbatches == 4
        model = build_model(cfg, vocab_size=100, mesh=None)
        sample = jnp.zeros((8, 16), jnp.int32)
        state = create_train_state(model, optax.sgd(0.1), sample,
                                   jax.random.PRNGKey(0),
                                   init_kwargs={"train": True})
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                              (8, 16), 0, 100)}
        with mesh:
            s_ref, m_ref = jax.jit(make_train_step(cfg))(state, batch)
            s_pp, m_pp = jax.jit(make_train_step(cfg, pipeline=spec))(
                state, batch)
        np.testing.assert_allclose(float(m_pp["loss"]),
                                   float(m_ref["loss"]), rtol=1e-4)
        _tree_allclose(s_ref.params, s_pp.params, rtol=1e-4, atol=1e-6)

    def test_pp1_trace_is_byte_identical(self, parity):
        """The pipeline plumbing must add NOTHING when disabled: the
        lowered HLO of a pipeline=None step is textually identical to
        the plain step (python-level gating, no traced residue).  The
        r19 program-set pin is the downstream safety net."""
        from faster_distributed_training_tpu.train.steps import (
            make_train_step)
        cfg, _mesh, _spec, state, batch = parity
        plain = jax.jit(make_train_step(cfg)).lower(state, batch)
        gated = jax.jit(make_train_step(cfg, pipeline=None)).lower(
            state, batch)
        assert plain.as_text() == gated.as_text()


class TestTrainPpMesh:
    """End-to-end run_training on a (dp, pp) mesh: the rule table in
    manifest.json, the pp telemetry kinds, and kill-at-N bitwise
    resume through the r14 elastic-recovery path."""

    def _run(self, tmp, **kw):
        from faster_distributed_training_tpu.cli import run_training
        return run_training(_tiny_tf_cfg(tmp, **kw), log=_SILENT)

    @pytest.fixture(scope="class")
    def run_pp2(self, tmp_path_factory, requires_devices):
        requires_devices(4)
        return self._run(tmp_path_factory.mktemp("pp2"),
                         mesh_axes=("dp", "pp"), mesh_shape=(2, 2))

    def test_manifest_rule_table_and_telemetry(self, run_pp2):
        td = run_pp2["telemetry_dir"]
        man = json.load(open(os.path.join(td, "manifest.json")))
        rules = man["pipeline"]
        assert rules["enabled"] and rules["n_stages"] == 2
        assert rules["n_microbatches"] == 4 and rules["n_ticks"] == 5
        assert rules["bubble_pct"] == pytest.approx(20.0)
        assert [s["layers"] for s in rules["stages"]] == \
            [["layer_0"], ["layer_1"]]
        assert "pp" in rules["activation_placement"]
        assert "collective-permute" in rules["boundary_collective"]
        # r22 telemetry kinds land append-only in the event stream
        kinds = set()
        with open(os.path.join(td, "host_00000.jsonl")) as fh:
            for line in fh:
                kinds.add(json.loads(line).get("kind"))
        assert {"pp_bubble", "pp_stage"} <= kinds

    @pytest.mark.slow  # r22 budget diet: 9 s (a full pp=1 training run
    # just for one manifest row) — tier-1 keeps the pp=1 contract via
    # the lowered-HLO byte-identity pin (TestPipelineParity) and the
    # pipeline_rules(None) == disabled unit (TestScheduleUnits)
    def test_pp1_manifest_records_disabled(self, tmp_path):
        out = self._run(tmp_path, mesh_axes=("dp",), mesh_shape=(2,))
        man = json.load(open(os.path.join(out["telemetry_dir"],
                                          "manifest.json")))
        assert man["pipeline"] == {"enabled": False, "n_stages": 1}

    def test_kill_at_n_resumes_bitwise_pp(self, tmp_path, monkeypatch,
                                          run_pp2, requires_devices):
        requires_devices(4)
        import faster_distributed_training_tpu.train.checkpoint as ckpt
        from faster_distributed_training_tpu.cli import run_training
        ref = run_pp2
        monkeypatch.setenv(faults_mod.ENV_DIE, "4")
        got = run_training(
            _tiny_tf_cfg(tmp_path / "killed", checkpoint_every=2,
                         supervise=True, mesh_axes=("dp", "pp"),
                         mesh_shape=(2, 2)),
            log=_SILENT)
        assert int(got["state"].step) == int(ref["state"].step) == 8
        assert got["goodput_restarts"] == 1
        _tree_equal(ckpt._state_pytree(ref["state"]),
                    ckpt._state_pytree(got["state"]))


class TestLmCausal:
    """Satellite 1: --lm_causal applies the causal mask at TRAINING
    time for --task lm, routed dense (flash takes key-padding masks
    only — ops/flash_attention.py), with a warned fallback for
    explicitly requested incompatible impls."""

    def _cfg(self, **kw):
        base = dict(model="transformer", task="lm", lm_causal=True,
                    batch_size=4, seq_len=8, n_layers=2, d_model=32,
                    d_ff=64, n_heads=4, dropout_impl="none",
                    num_classes=4)
        base.update(kw)
        return TrainConfig(**base)

    def test_auto_route_is_dense(self):
        from faster_distributed_training_tpu.cli import resolve_attention
        assert resolve_attention(self._cfg(), None) == "dense"
        # without the flag the lm task keeps its normal routing
        flagless = resolve_attention(self._cfg(lm_causal=False), None)
        assert flagless in ("dense", "flash")

    def test_explicit_flash_warns_and_falls_back(self):
        from faster_distributed_training_tpu.cli import build_model
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            model = build_model(self._cfg(attention="flash"),
                                vocab_size=50, mesh=None)
        assert model.attention_impl == "dense"
        assert any("lm_causal" in str(x.message) for x in w)

    def test_causal_mask_blocks_future_tokens(self):
        from faster_distributed_training_tpu.cli import build_model
        rng = jax.random.PRNGKey(0)
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, 50)
        toks2 = toks.at[:, 5].set((toks[:, 5] + 7) % 50)
        model = build_model(self._cfg(), vocab_size=50, mesh=None)
        assert model.causal
        v = model.init({"params": rng, "dropout": rng, "mixup": rng},
                       toks, train=False)
        l1 = model.apply(v, toks, train=False)
        l2 = model.apply(v, toks2, train=False)
        # position-t logits independent of tokens > t ...
        np.testing.assert_array_equal(np.asarray(l1[:, :5]),
                                      np.asarray(l2[:, :5]))
        assert float(jnp.max(jnp.abs(l1[:, 5:] - l2[:, 5:]))) > 0
        # ... and the bidirectional twin does leak (the mask is load-
        # bearing, not the test)
        m_bi = build_model(self._cfg(lm_causal=False), vocab_size=50,
                           mesh=None)
        v_bi = m_bi.init({"params": rng, "dropout": rng, "mixup": rng},
                         toks, train=False)
        b1 = m_bi.apply(v_bi, toks, train=False)
        b2 = m_bi.apply(v_bi, toks2, train=False)
        assert float(jnp.max(jnp.abs(b1[:, :5] - b2[:, :5]))) > 0


class TestCausalDecodeRoundTrip:
    """Satellite 1's pin: train tiny with --lm_causal, then verify the
    serving contract holds BY TRAINING — (a) decode's imposed causal
    mask is a bitwise no-op on a causal-trained model (training and
    serving see the same masking), and (b) prefix-truncated logits
    match the full forward at every kept position (the property that
    makes incremental/paged decode valid)."""

    @pytest.fixture(scope="class")
    def causal_ckpt(self, tmp_path_factory):
        from faster_distributed_training_tpu.cli import run_training
        from faster_distributed_training_tpu.data.stream import (
            synthetic_corpus, write_lm_corpus)
        d = str(tmp_path_factory.mktemp("causal_lm"))
        cfg = TrainConfig(model="transformer", dataset="stream",
                          task="lm", lm_causal=True, data_path="stream",
                          stream_dir=os.path.join(d, "stream"),
                          batch_size=8, seq_len=16, n_layers=1,
                          d_model=16, d_ff=32, n_heads=2, epochs=1,
                          steps_per_dispatch=2, stream_window=4,
                          optimizer="sgd", precision="fp32", plot=False,
                          workers=0, log_every=0, donate=False,
                          checkpoint_dir=os.path.join(d, "ckpt"),
                          seq_buckets=(8, 16), decode_batch_size=2,
                          decode_page=4, decode_max_new_tokens=8,
                          device="cpu")
        texts = synthetic_corpus(40, seed=3, words_per_doc=(25, 50))
        write_lm_corpus(cfg.stream_dir, texts, seq_len=16,
                        rows_per_shard=16, val_fraction=0.15)
        run_training(cfg, log=_SILENT)
        return cfg

    @pytest.fixture(scope="class")
    def served(self, causal_ckpt):
        from faster_distributed_training_tpu.serve import (
            load_serving_state)
        model, sstate, meta = load_serving_state(causal_ckpt, log=_SILENT)
        return model, sstate, meta

    def test_serving_mask_is_noop_on_causal_model(self, served):
        from faster_distributed_training_tpu.models.decode import (
            causal_mask)
        model, sstate, _meta = served
        assert model.causal
        toks = np.arange(1, 9, dtype=np.int32)[None, :]
        var = {"params": sstate.params["model"],
               "batch_stats": sstate.batch_stats}
        bare = model.apply(var, toks, train=False)
        masked = model.apply(var, toks, mask=causal_mask(8), train=False)
        # cm * cm == cm: training-time and serving-time masking agree
        np.testing.assert_array_equal(np.asarray(bare),
                                      np.asarray(masked))

    def test_prefix_logits_match_full_forward(self, served):
        model, sstate, _meta = served
        var = {"params": sstate.params["model"],
               "batch_stats": sstate.batch_stats}
        toks = np.arange(2, 18, dtype=np.int32)[None, :]   # L=16
        full = np.asarray(model.apply(var, toks, train=False))
        for t in (4, 8):
            pre = np.asarray(model.apply(var, toks[:, :t], train=False))
            # same math on a shorter program: fp32 fusion-island class
            np.testing.assert_allclose(pre, full[:, :t], rtol=1e-5,
                                       atol=1e-6)

    @pytest.mark.slow
    def test_engine_greedy_decode_matches_cacheless_slow(self, served):
        """Heavy twin: the REAL paged-KV DecodeEngine greedy stream on
        the causal-trained checkpoint is token-for-token the cacheless
        argmax loop (the r21 headline, re-pinned on a checkpoint whose
        TRAINING already saw the serving mask)."""
        from faster_distributed_training_tpu.serve.decode import (
            DecodeEngine, DecodeScheduler)
        from faster_distributed_training_tpu.serve import RequestQueue
        model, sstate, _meta = served
        eng = DecodeEngine(model, sstate, (8, 16), batch_size=2, page=4,
                           name="causal", log=_SILENT)
        eng.warmup()
        prompt = list(range(3, 9))
        q = RequestQueue(eng.buckets, max_len=16)
        sched = DecodeScheduler(q, eng, max_new_tokens=4,
                                name="causal", log=_SILENT)
        sched.start()
        try:
            got = list(map(int, q.submit(prompt, max_new_tokens=4)
                           .wait(timeout=120.0)))
        finally:
            q.close()
            sched.close()
        var = {"params": sstate.params["model"],
               "batch_stats": sstate.batch_stats}
        toks = list(prompt)
        want = []
        for _ in range(4):
            out = model.apply(var, np.asarray(toks, np.int32)[None, :],
                              train=False)
            nxt = int(np.argmax(np.asarray(out)[0, len(toks) - 1]))
            want.append(nxt)
            toks.append(nxt)
        assert got == want
