"""Program-level observability (ISSUE 11): the compile observatory
(per-program compile ms / HLO fingerprint / cache verdict / memory
bytes + the retrace detector), HBM attribution (state byte table,
sharding-drift guard), the crash flight recorder, the append-only
telemetry schema lint, and the e2e program-set pin — a 2-epoch CPU
run_training compiles EXACTLY the expected program set at K in {1, 4},
so an accidental retrace (non-weak-type scalar / shape leak) fails
tier-1."""

import glob
import importlib.util
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from faster_distributed_training_tpu.config import TrainConfig
from faster_distributed_training_tpu.telemetry import (
    TelemetryRecorder, flight, programs, spans)
from faster_distributed_training_tpu.telemetry.programs import (
    ObservedJit, ProgramObservatory, leaf_bytes_per_chip,
    sharding_fingerprint, sharding_table, state_bytes_table)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -------------------------------------------------------------------------
class TestObservedJit:
    def test_single_program_observed_once_and_results_match(self):
        obs = ProgramObservatory(log=lambda *_: None)
        calls = []
        jitted = jax.jit(lambda a, b: a * 2 + b)
        wrapped = obs.wrap("prog", jitted, sig_argnums=(1,))
        a = jnp.arange(4, dtype=jnp.float32)
        b = jnp.ones(4, dtype=jnp.float32)
        for _ in range(3):
            calls.append(np.asarray(wrapped(a, b)))
        ref = np.asarray(jitted(a, b))
        for got in calls:
            np.testing.assert_array_equal(got, ref)
        summ = obs.summary()
        assert [p["name"] for p in summ["programs"]] == ["prog"]
        assert summ["programs"][0]["lowerings"] == 1
        v = summ["programs"][0]["variants"][0]
        assert v["compile_ms"] >= 0 and v["lower_ms"] >= 0
        assert v["cache"] in ("hit", "miss", "below_threshold", "off",
                              "unknown")
        assert v["cache_method"] in ("dir_stat", "timing_threshold",
                                     "jax_event", "none")
        # sha256 prefix of lowered.as_text() (16 hex chars) unless the
        # env kill switch stripped it
        assert len(v["fingerprint"]) in (0, 16)
        # memory_analysis lands as byte fields on the CPU backend too
        assert "argument_bytes" in v and v["argument_bytes"] > 0
        assert summ["retraces"] == []
        # total rounds to 0.1 ms, per-variant to 0.01 — allow the gap
        assert summ["total_compile_ms"] >= v["compile_ms"] - 0.1

    def test_shape_variants_are_counted_not_retraced(self):
        """Text bucket widths: a second SHAPE for the same name is a
        legitimate variant — no warning, no retrace event."""
        import warnings as w

        obs = ProgramObservatory(log=lambda *_: None)
        wrapped = obs.wrap("prog", jax.jit(lambda a, b: a + b.sum()),
                           sig_argnums=(1,))
        a = jnp.ones(2, jnp.float32)
        with w.catch_warnings():
            w.simplefilter("error")
            wrapped(a, jnp.ones(4, jnp.float32))
            wrapped(a, jnp.ones(8, jnp.float32))
        summ = obs.summary()
        assert summ["programs"][0]["lowerings"] == 2
        assert summ["retraces"] == []

    def test_dtype_leak_warns_and_records_retrace(self):
        """Same shapes, different dtype — the classic scalar/dtype leak
        — must emit a loud warning AND a retrace event."""
        obs = ProgramObservatory(log=lambda *_: None)
        wrapped = obs.wrap("prog", jax.jit(lambda a, b: a + b.sum()),
                           sig_argnums=(1,))
        a = jnp.ones(2, jnp.float32)
        wrapped(a, jnp.ones(4, jnp.float32))
        with pytest.warns(UserWarning, match="re-traced"):
            wrapped(a, jnp.ones(4, jnp.int32))
        summ = obs.summary()
        assert summ["programs"][0]["lowerings"] == 2
        assert [r["reason"] for r in summ["retraces"]] \
            == ["dtype-or-weak-type-leak"]

    def test_non_signature_arg_change_reobserves_as_retrace(self):
        """A state-arg aval change violates the signature-stable
        contract: the AOT call rejects it pre-execution, the wrapper
        re-observes, and the duplicate lowering is flagged."""
        obs = ProgramObservatory(log=lambda *_: None)
        wrapped = obs.wrap("prog", jax.jit(lambda a, b: a.sum() + b),
                           sig_argnums=(1,))
        b = jnp.ones(4, jnp.float32)
        r1 = wrapped(jnp.ones(3, jnp.float32), b)
        with pytest.warns(UserWarning, match="re-traced"):
            r2 = wrapped(jnp.ones(5, jnp.float32), b)
        np.testing.assert_allclose(np.asarray(r1), 3.0 + 1.0)
        np.testing.assert_allclose(np.asarray(r2), 5.0 + 1.0)
        assert [r["reason"] for r in obs.summary()["retraces"]] \
            == ["duplicate-avals"]

    def test_observe_failure_degrades_to_plain_jit(self):
        obs = ProgramObservatory(log=lambda *_: None)
        jitted = jax.jit(lambda a: a * 3)

        class _Broken:
            def lower(self, *a, **k):
                raise RuntimeError("no AOT here")

            def __call__(self, *a):
                return jitted(*a)

        wrapped = ObservedJit("prog", _Broken(), obs, sig_argnums=())
        out = wrapped(jnp.ones(3, jnp.float32))
        np.testing.assert_array_equal(np.asarray(out), 3.0)
        assert wrapped._fallback
        assert obs.summary()["programs"] == []

    def test_program_events_land_in_recorder_stream(self, tmp_path):
        rec = TelemetryRecorder(str(tmp_path), process_index=0,
                                process_count=1, log=lambda *_: None)
        obs = ProgramObservatory(recorder=rec, log=lambda *_: None)
        wrapped = obs.wrap("prog", jax.jit(lambda a: a + 1))
        wrapped(jnp.ones(2, jnp.float32))
        rec.close()
        recs = _read_jsonl(os.path.join(str(tmp_path),
                                        "host_00000.jsonl"))
        ev = [r for r in recs if r["kind"] == "program"]
        assert len(ev) == 1 and ev[0]["name"] == "prog"
        assert ev[0]["lowerings"] == 1 and "compile_ms" in ev[0]

    def test_acquisition_spans_nest_under_first_dispatch(self, tmp_path):
        """A signature's first call is one first_dispatch_compile span
        with the caller's step, holding program_lower, program_compile
        and program_observe (before and after the compile), in that
        order; the children's sum is within the parent, and lower_ms /
        compile_ms are the durations of their spans.  Later calls write
        no span."""
        rec = TelemetryRecorder(str(tmp_path), process_index=0,
                                process_count=1, log=lambda *_: None)
        prev = spans.set_recorder(rec)
        try:
            obs = ProgramObservatory(recorder=rec, log=lambda *_: None)
            wrapped = obs.wrap("prog", jax.jit(lambda a: a * 3),
                               step=lambda: 41)
            for _ in range(3):
                wrapped(jnp.ones(4, jnp.float32))
        finally:
            spans.set_recorder(prev)
        rec.close()
        recs = _read_jsonl(os.path.join(str(tmp_path), "host_00000.jsonl"))
        got = [r for r in recs if r["kind"] == "span"]
        assert [r["name"] for r in got] == [
            "program_lower", "program_observe", "program_compile",
            "program_observe", "first_dispatch_compile"]
        parent, kids = got[-1], got[:-1]
        assert parent["step"] == 41 and "parent" not in parent
        assert all(r["parent"] == "first_dispatch_compile" for r in kids)
        starts = [r["t0_s"] for r in kids]
        assert starts == sorted(starts) and starts[0] >= parent["t0_s"]
        assert sum(r["dur_ms"] for r in kids) <= parent["dur_ms"] + 1e-3
        prog = [r for r in recs if r["kind"] == "program"][0]
        assert prog["lower_ms"] <= kids[0]["dur_ms"] + 0.01
        assert prog["compile_ms"] <= kids[2]["dur_ms"] + 0.01

    def test_goodput_counts_the_whole_acquisition(self, monkeypatch):
        """goodput's compile seconds are the whole acquisition: the
        lowering, the compile and the observatory's own work."""
        import time as time_mod
        billed = []
        obs = ProgramObservatory(log=lambda *_: None)
        obs.goodput = types.SimpleNamespace(add_compile=billed.append)
        slow = ProgramObservatory.__dict__["_lowered_text"]

        def slow_text(self, lowered):
            time_mod.sleep(0.2)
            return slow(self, lowered)
        monkeypatch.setattr(ProgramObservatory, "_lowered_text", slow_text)
        obs.wrap("prog", jax.jit(lambda a: a - 1))(jnp.ones(2))
        v = obs.programs["prog"][0]
        assert len(billed) == 1
        assert billed[0] >= (v["lower_ms"] + v["compile_ms"]) / 1e3 + 0.2

    def test_kill_switch_removes_observatory(self, tmp_path, monkeypatch):
        from faster_distributed_training_tpu.telemetry import (
            build_telemetry)
        monkeypatch.setenv(programs.ENV_KILL, "0")
        cfg = TrainConfig(checkpoint_dir=str(tmp_path))
        tel = build_telemetry(cfg, log=lambda *_: None)
        assert tel.observatory is None
        tel.close()

    def test_trainer_routes_programs_through_observatory(self, tmp_path):
        from faster_distributed_training_tpu.telemetry import (
            build_telemetry)
        from faster_distributed_training_tpu.train.loop import Trainer
        cfg = TrainConfig(model="transformer", dataset="synthetic",
                          num_classes=4, batch_size=8, seq_len=16,
                          n_layers=1, d_model=16, d_ff=32, n_heads=2,
                          checkpoint_dir=str(tmp_path))
        tel = build_telemetry(cfg, log=lambda *_: None)
        try:
            tr = Trainer(cfg, telemetry=tel, log=lambda *_: None)
            assert isinstance(tr.train_step, ObservedJit)
            assert isinstance(tr.eval_step, ObservedJit)
            assert isinstance(tr._fused_step(4), ObservedJit)
            # without telemetry: plain jit dispatch, byte-identical r14
            tr2 = Trainer(cfg, log=lambda *_: None)
            assert not isinstance(tr2.train_step, ObservedJit)
        finally:
            tel.close()


# -------------------------------------------------------------------------
class TestStateBytes:
    def _state(self):
        return types.SimpleNamespace(
            params={"w": jnp.ones((16, 8), jnp.float32),
                    "b": jnp.ones((8,), jnp.float32)},
            opt_state=({"mu": jnp.ones((16, 8), jnp.float32)},),
            batch_stats={"mean": jnp.ones((8,), jnp.float32)})

    def test_group_split_and_totals(self):
        t = state_bytes_table(self._state())
        assert t["scope"] == "state"
        assert t["params_bytes_per_chip"] == (16 * 8 + 8) * 4
        assert t["opt_state_bytes_per_chip"] == 16 * 8 * 4
        assert t["batch_stats_bytes_per_chip"] == 8 * 4
        assert t["total_bytes_per_chip"] == sum(
            t[f"{g}_bytes_per_chip"]
            for g in ("params", "opt_state", "batch_stats"))
        assert t["params_leaves"] == 2
        top = t["top_leaves"]
        assert top[0]["bytes_per_chip"] == 16 * 8 * 4
        assert top[0]["path"].startswith(("params", "opt_state"))
        # every emitted key is in the committed field vocabulary the
        # schema lint resolves the **splat through
        assert set(t) <= set(programs.STATE_MEMORY_FIELDS)

    def test_sharded_leaf_counts_per_chip_bytes(self):
        if jax.device_count() < 8:
            pytest.skip("needs the 8-device CPU harness")
        from jax.sharding import NamedSharding, PartitionSpec
        mesh = jax.sharding.Mesh(np.array(jax.devices()), ("dp",))
        arr = jax.device_put(
            np.ones((8, 4), np.float32),
            NamedSharding(mesh, PartitionSpec("dp")))
        assert leaf_bytes_per_chip(arr) == arr.nbytes // 8
        rep = jax.device_put(np.ones((8, 4), np.float32),
                             NamedSharding(mesh, PartitionSpec()))
        assert leaf_bytes_per_chip(rep) == rep.nbytes

    def test_sharding_fingerprint_stable_and_sensitive(self):
        if jax.device_count() < 8:
            pytest.skip("needs the 8-device CPU harness")
        from jax.sharding import NamedSharding, PartitionSpec
        mesh = jax.sharding.Mesh(np.array(jax.devices()), ("dp",))
        sharded = NamedSharding(mesh, PartitionSpec("dp"))
        rep = NamedSharding(mesh, PartitionSpec())
        s1 = {"w": jax.device_put(np.ones((8, 4), np.float32), sharded)}
        s2 = {"w": jax.device_put(np.ones((8, 4), np.float32), sharded)}
        assert sharding_fingerprint(s1) == sharding_fingerprint(s2)
        s3 = {"w": jax.device_put(np.ones((8, 4), np.float32), rep)}
        assert sharding_fingerprint(s1) != sharding_fingerprint(s3)
        # the debug table names the leaf
        t1, t3 = sharding_table(s1), sharding_table(s3)
        assert set(t1) == set(t3) and t1["['w']"] != t3["['w']"]

    def test_host_leaves_read_host(self):
        s = {"w": np.ones((4,), np.float32)}
        assert sharding_table(s) == {"['w']": "host"}
        assert leaf_bytes_per_chip(s["w"]) == 16


# -------------------------------------------------------------------------
class TestFlightRecorder:
    def test_dump_payload_and_dedupe(self, tmp_path):
        rec = TelemetryRecorder(str(tmp_path), process_index=3,
                                process_count=4, log=lambda *_: None)
        prev_rec = spans.set_recorder(rec)
        prev_cfg = flight.configure(str(tmp_path), log=lambda *_: None)
        try:
            rec.record_step(7, 0, 7, 1, 10.0, 9.0, 8)
            exc = RuntimeError("boom")
            path = flight.emergency_dump("test_reason", exc=exc, step=7)
            assert path is not None and os.path.exists(path)
            assert os.path.basename(path).startswith("flight_00003_")
            payload = json.load(open(path))
            assert payload["reason"] == "test_reason"
            assert payload["step"] == 7
            assert payload["process_index"] == 3
            assert payload["exception"]["type"] == "RuntimeError"
            assert "boom" in payload["exception"]["message"]
            assert "traceback" in payload["exception"]
            # the in-memory ring survives flushes: run_start + the step
            kinds = [r["kind"] for r in payload["recent_records"]]
            assert "run_start" in kinds and "step" in kinds
            # same exception object: one incident, one dump
            assert flight.emergency_dump("again", exc=exc) is None
            # a DIFFERENT exception is a new incident (the dedupe marks
            # the exception OBJECT, not its id — a gc'd exception's
            # reused address must never suppress a later crash's dump)
            exc2 = RuntimeError("boom2")
            path2 = flight.emergency_dump("other", exc=exc2)
            assert path2 is not None and path2 != path
            # the stream itself mentions both dumps
            rec.close()
            recs = _read_jsonl(os.path.join(str(tmp_path),
                                            "host_00003.jsonl"))
            fl = [r for r in recs if r["kind"] == "flight"]
            assert [r["path"] for r in fl] == [path, path2]
        finally:
            flight.restore(prev_cfg)
            spans.set_recorder(prev_rec)

    def test_unconfigured_is_noop(self):
        prev = flight.configure(None)
        try:
            assert not flight.configured()
            assert flight.emergency_dump("x",
                                         exc=RuntimeError("y")) is None
        finally:
            flight.restore(prev)

    def test_open_span_captured_in_payload(self, tmp_path):
        rec = TelemetryRecorder(str(tmp_path), process_index=0,
                                process_count=1, log=lambda *_: None)
        prev_rec = spans.set_recorder(rec)
        try:
            with spans.span("restore", step=12):
                payload = flight.build_payload("r")
            names = [s["name"] for s in payload["active_spans"]]
            assert names == ["restore"]
            assert payload["active_spans"][0]["step"] == 12
            assert payload["active_spans"][0]["elapsed_ms"] >= 0
            # closed again after the block
            assert spans.active_spans() == []
        finally:
            spans.set_recorder(prev_rec)
            rec.close()

    def test_read_flights_skips_torn_files(self, tmp_path):
        good = tmp_path / "flight_00000_1.json"
        good.write_text(json.dumps({"reason": "r"}))
        (tmp_path / "flight_00000_2.json").write_text("{torn")
        got = flight.read_flights(str(tmp_path))
        assert [os.path.basename(p) for p, _ in got] \
            == ["flight_00000_1.json"]


# -------------------------------------------------------------------------
class TestSchemaLint:
    def test_repo_is_clean(self):
        lint = _load_script("check_telemetry_schema")
        assert lint.check() == []

    def test_unregistered_kind_and_field_flagged(self, tmp_path):
        lint = _load_script("check_telemetry_schema")
        bad = tmp_path / "bad.py"
        bad.write_text(
            "def f(rec):\n"
            "    rec.record_event('step', bogus_field=1)\n"
            "    rec.record_event('madeup_kind', x=2)\n")
        problems = lint.check(paths=lint.default_paths() + [str(bad)])
        assert any("bogus_field" in p for p in problems)
        assert any("madeup_kind" in p for p in problems)

    def test_unresolvable_splat_on_closed_kind_flagged(self, tmp_path):
        lint = _load_script("check_telemetry_schema")
        bad = tmp_path / "bad.py"
        bad.write_text(
            "def f(rec, mystery):\n"
            "    rec.record_event('step', **mystery())\n")
        problems = lint.check(paths=lint.default_paths() + [str(bad)])
        assert any("unresolvable" in p for p in problems)

    def test_resolvable_local_dict_passes(self, tmp_path):
        lint = _load_script("check_telemetry_schema")
        ok = tmp_path / "ok.py"
        ok.write_text(
            "def f(rec, v):\n"
            "    ev = {'epoch': 1, 'steps': 2}\n"
            "    ev['loss'] = v\n"
            "    rec.record_event('epoch', **ev)\n")
        assert lint.check(paths=lint.default_paths() + [str(ok)]) == []

    def test_registered_kind_never_emitted_flagged(self, tmp_path,
                                                   monkeypatch):
        lint = _load_script("check_telemetry_schema")
        from faster_distributed_training_tpu.telemetry import recorder
        schema = dict(recorder.TELEMETRY_SCHEMA)
        schema["ghost_kind"] = frozenset({"x"})
        monkeypatch.setattr(recorder, "TELEMETRY_SCHEMA", schema)
        problems = lint.check()
        assert any("ghost_kind" in p for p in problems)


# -------------------------------------------------------------------------
def _tiny_cfg(tmp_path, **kw):
    return TrainConfig(model="transformer", dataset="synthetic",
                       num_classes=4, batch_size=8, seq_len=16, n_layers=1,
                       d_model=16, d_ff=32, n_heads=2, epochs=2,
                       subset_stride=64, optimizer="sgd", precision="fp32",
                       plot=False, workers=0, log_every=0, donate=False,
                       checkpoint_dir=str(tmp_path), **kw)


def _run_and_programs(cfg):
    from faster_distributed_training_tpu.cli import run_training
    out = run_training(cfg, log=lambda *_: None)
    td = out["telemetry_dir"]
    recs = _read_jsonl(os.path.join(td, "host_00000.jsonl"))
    return out, td, recs


class TestProgramSetPin:
    """The retrace-count pin (ISSUE 11 satellite): a 2-epoch CPU run
    compiles EXACTLY the expected program set — train per (path, K),
    eval, and (sharded residency) the epoch re-shard.  An accidental
    extra lowering — a non-weak-type scalar, a shape leak, a dropped
    jit cache — fails here before it taxes a real run's MTTR."""

    def _pin(self, recs, expected):
        progs = [r for r in recs if r["kind"] == "program"]
        assert sorted(p["name"] for p in progs) == sorted(expected), progs
        assert [r for r in recs if r["kind"] == "retrace"] == []
        for p in progs:
            assert p["lowerings"] == 1
            assert p["compile_ms"] >= 0
            assert p["cache"] in ("hit", "miss", "below_threshold",
                                  "off", "unknown")
            assert "argument_bytes" in p
        return progs

    def test_k1_host_program_set(self, tmp_path):
        out, td, recs = _run_and_programs(_tiny_cfg(tmp_path))
        self._pin(recs, ["train:host:k1", "eval"])
        # the state byte table landed (scope "state", once)
        mems = [r for r in recs if r["kind"] == "memory"]
        assert [m["scope"] for m in mems] == ["state"]
        assert mems[0]["opt_state_bytes_per_chip"] > 0
        assert mems[0]["params_bytes_per_chip"] > 0
        # ...and the compile table merged into the manifest at close
        man = json.load(open(os.path.join(td, "manifest.json")))
        assert sorted(p["name"] for p in man["compile"]["programs"]) \
            == ["eval", "train:host:k1"]
        for p in man["compile"]["programs"]:
            v = p["variants"][0]
            assert {"compile_ms", "fingerprint", "cache",
                    "argument_bytes"} <= set(v)
        assert man["compile"]["retraces"] == []

    def test_k4_host_program_set(self, tmp_path):
        # 8 steps/epoch divides K=4: one fused program, no tail variant
        out, td, recs = _run_and_programs(
            _tiny_cfg(tmp_path, steps_per_dispatch=4))
        self._pin(recs, ["train:host:k4", "eval"])

    def test_k4_sharded_resident_includes_reshard(self, tmp_path):
        if jax.device_count() < 8:
            pytest.skip("needs the 8-device CPU harness")
        out, td, recs = _run_and_programs(
            _tiny_cfg(tmp_path, steps_per_dispatch=4,
                      data_path="resident", resident_layout="sharded"))
        self._pin(recs, ["train:resident:k4", "eval", "epoch_reshard"])


class TestFlightEndToEnd:
    def test_injected_crash_leaves_renderable_flight_dump(
            self, tmp_path, monkeypatch):
        """The ISSUE 11 acceptance pin: FDT_FAULT_DIE_AT_STEP under
        --supervise leaves a flight dump naming the injected fault,
        and ``telemetry_report.py --flight`` renders it."""
        monkeypatch.setenv("FDT_FAULT_DIE_AT_STEP", "6")
        out, td, recs = _run_and_programs(
            _tiny_cfg(tmp_path, checkpoint_every=4, supervise=True,
                      max_restarts=2))
        files = glob.glob(os.path.join(td, "flight_*.json"))
        assert len(files) == 1, files
        payload = json.load(open(files[0]))
        assert payload["reason"] == "supervisor_failure"
        assert payload["exception"]["type"] == "InjectedFault"
        assert payload["step"] == 6
        assert payload["recent_records"]
        assert [p["name"] for p in payload["programs"]["programs"]]
        # the stream carries the flight event; the run then recovered
        assert [r["path"] for r in recs if r["kind"] == "flight"] \
            == files
        assert int(out["state"].step) == 16
        report = _load_script("telemetry_report")
        rep = report.run(td, with_flight=True)
        assert rep["flights"][0]["exception"]["type"] == "InjectedFault"
        text = report.render(rep)
        assert "FLIGHT" in text and "InjectedFault" in text
        assert "compiled programs" in text
        assert "train-state HBM per chip" in text


class TestAggregateGrace:
    def test_missing_hosts_recorded_in_summary(self, tmp_path):
        from faster_distributed_training_tpu.telemetry import (
            pod_epoch_aggregate, publish_epoch_marker)
        d = str(tmp_path)
        publish_epoch_marker(d, 0, 0)
        summary = pod_epoch_aggregate(d, 0, pi=0, pc=2, wait_s=0.05,
                                      log=lambda *_: None)
        assert summary["hosts_reported"] == [0]
        assert summary["hosts_missing"] == [1]
        assert summary["grace_s"] == 0.05
        committed = json.load(open(os.path.join(d, "pod_summary.json")))
        assert committed["hosts_missing"] == [1]

    def test_grace_flag_reaches_run_telemetry(self, tmp_path):
        from faster_distributed_training_tpu.telemetry import (
            build_telemetry)
        cfg = TrainConfig(checkpoint_dir=str(tmp_path),
                          aggregate_grace_s=7.5)
        tel = build_telemetry(cfg, log=lambda *_: None)
        try:
            assert tel.aggregate_wait_s == 7.5
        finally:
            tel.close()
