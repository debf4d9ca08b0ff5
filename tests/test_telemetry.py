"""Run-scoped telemetry subsystem (r12): recorder/JSONL schema, span
API, pod aggregation + straggler detection, the windowed profiler, the
live-throughput fix, and the report script against the recorded
fixture.

Pod scope uses the established simulation seams (two recorders with
explicit process_index sharing one directory = a simulated two-host
pod — the r9/r10 pattern), never real multi-process runs."""

import glob
import importlib.util
import json
import os
import time

import numpy as np
import pytest

from faster_distributed_training_tpu.config import TrainConfig
from faster_distributed_training_tpu.telemetry import (
    TelemetryRecorder, aggregate_run, build_telemetry, pod_epoch_aggregate,
    publish_epoch_marker, read_host_records, span_breakdown, spans,
    write_manifest)
from faster_distributed_training_tpu.train.metrics import percentiles
from faster_distributed_training_tpu.utils.profiling import (
    StepWindowProfiler, parse_profile_steps)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(ROOT, "tests", "fixtures", "telemetry")


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


class TestRecorder:
    def test_jsonl_stream_and_manifest(self, tmp_path):
        d = str(tmp_path)
        rec = TelemetryRecorder(d, process_index=0, process_count=1,
                                log=lambda *_: None)
        rec.record_step(1, 0, 1, 1, 12.0, 10.0, 64, data_ms=1.5,
                        block_ms=0.5, compile_=True)
        rec.record_step(2, 0, 2, 1, 10.0, 9.5, 64)
        rec.record_span("eval", 123.4, step=2)
        rec.record_event("epoch", epoch=0, steps=2, loss=1.25)
        rec.close()
        recs = _read_jsonl(os.path.join(d, "host_00000.jsonl"))
        kinds = [r["kind"] for r in recs]
        assert kinds == ["run_start", "step", "step", "span", "epoch"]
        s1, s2 = recs[1], recs[2]
        assert s1["compile"] is True and "compile" not in s2
        assert s1["wall_ms"] == 12.0 and s1["data_ms"] == 1.5
        assert s2["ex_s"] == round(64 / (10.0 / 1e3), 1)
        assert recs[3]["name"] == "eval" and recs[3]["step"] == 2
        # manifest is self-describing: versions + device + config + mesh
        write_manifest(d, cfg=TrainConfig(), extra={"workload": "t"})
        man = json.load(open(os.path.join(d, "manifest.json")))
        for key in ("schema", "jax_version", "jaxlib_version", "backend",
                    "device_kind", "config", "workload"):
            assert key in man, key
        assert man["config"]["batch_size"] == TrainConfig().batch_size

    def test_capacity_triggers_background_flush(self, tmp_path):
        rec = TelemetryRecorder(str(tmp_path), process_index=0,
                                process_count=1, capacity=8,
                                log=lambda *_: None)
        for i in range(20):
            rec.record_step(i + 1, 0, i + 1, 1, 1.0, 1.0, 4)
        deadline = time.monotonic() + 10
        path = os.path.join(str(tmp_path), "host_00000.jsonl")
        while time.monotonic() < deadline:
            if os.path.exists(path) and len(_read_jsonl(path)) >= 16:
                break
            time.sleep(0.02)
        # >= two capacity batches hit disk WITHOUT any explicit flush
        assert len(_read_jsonl(path)) >= 16
        rec.close()
        assert len([r for r in _read_jsonl(path)
                    if r["kind"] == "step"]) == 20
        assert rec.dropped_records == 0

    def test_kill_switch_and_flag(self, tmp_path):
        cfg = TrainConfig(checkpoint_dir=str(tmp_path))
        assert build_telemetry(cfg.replace(telemetry=False)) is None
        tel = build_telemetry(cfg, log=lambda *_: None)
        assert tel is not None
        assert tel.directory == os.path.abspath(
            os.path.join(str(tmp_path), "telemetry"))
        tel.close()


class TestSpans:
    def test_span_records_to_active_recorder(self, tmp_path):
        rec = TelemetryRecorder(str(tmp_path), process_index=0,
                                process_count=1, log=lambda *_: None)
        prev = spans.set_recorder(rec)
        try:
            with spans.span("restore", step=7):
                time.sleep(0.01)
            with pytest.raises(RuntimeError):
                with spans.span("rendezvous"):
                    raise RuntimeError("mid-span failure")
        finally:
            spans.set_recorder(prev)
        rec.close()
        recs = [r for r in _read_jsonl(rec.path) if r["kind"] == "span"]
        names = [r["name"] for r in recs]
        assert names == ["restore", "rendezvous"]
        assert recs[0]["dur_ms"] >= 10.0 and recs[0]["step"] == 7
        # the failed span still recorded its cost (that time IS the
        # MTTR restore component)
        assert recs[1]["dur_ms"] >= 0.0

    def test_span_without_recorder_is_noop(self):
        assert spans.get_recorder() is None
        with spans.span("eval"):
            pass  # no recorder installed: must not raise or record

    def test_spans_carry_start_and_parent_across_two_levels(self,
                                                             tmp_path):
        """t0_s is the monotonic clock at entry; parent is the innermost
        span open on the SAME thread (a span on another thread while
        one is open here is top-level there)."""
        import threading
        rec = TelemetryRecorder(str(tmp_path), process_index=0,
                                process_count=1, log=lambda *_: None)
        prev = spans.set_recorder(rec)
        try:
            before = time.monotonic()
            with spans.span("outer"):
                with spans.span("middle", step=3):
                    with spans.span("inner"):
                        with spans.span("innermost"):
                            pass
                        t = threading.Thread(target=self._one_span)
                        t.start()
                        t.join()
            with spans.span("after"):
                pass
        finally:
            spans.set_recorder(prev)
        rec.close()
        got = {r["name"]: r for r in _read_jsonl(rec.path)
               if r["kind"] == "span"}
        assert got["inner"]["parent"] == "middle"
        assert got["innermost"]["parent"] == "inner"
        assert got["middle"]["parent"] == "outer"
        assert got["middle"]["step"] == 3
        assert "parent" not in got["outer"]
        assert "parent" not in got["after"]
        assert "parent" not in got["thread_top"]
        assert (before <= got["outer"]["t0_s"] <= got["middle"]["t0_s"]
                <= got["inner"]["t0_s"] <= got["thread_top"]["t0_s"])
        for child, parent in (("inner", "middle"), ("middle", "outer")):
            c, p = got[child], got[parent]
            assert (c["t0_s"] + c["dur_ms"] / 1e3
                    <= p["t0_s"] + p["dur_ms"] / 1e3 + 1e-5)
        assert got["after"]["t0_s"] >= (got["outer"]["t0_s"]
                                        + got["outer"]["dur_ms"] / 1e3
                                        - 1e-5)

    @staticmethod
    def _one_span():
        with spans.span("thread_top"):
            pass


class TestSetUpStretch:
    """Spans that close before a recorder exists (cli.setup_platform
    opens the stretch) are held for the one build_telemetry makes."""

    def test_held_spans_reach_the_built_recorder_in_order(self, tmp_path):
        assert spans.get_recorder() is None
        spans.open_setup()
        with spans.span("setup_platform"):
            pass
        with spans.span("setup_model"):
            with spans.span("inner"):
                pass
        tel = build_telemetry(TrainConfig(checkpoint_dir=str(tmp_path)),
                              log=lambda *_: None)
        # the stretch ended with the hand-over: nothing more is held
        with spans.span("after_the_stretch"):
            pass
        assert spans.hand_over(None) == 0
        tel.close()
        got = [r for r in _read_jsonl(tel.recorder.path)
               if r["kind"] == "span"]
        assert [r["name"] for r in got] == ["setup_platform", "inner",
                                            "setup_model"]
        assert got[1]["parent"] == "setup_model"
        assert got[0]["t0_s"] < got[2]["t0_s"] <= got[1]["t0_s"]

    def test_nothing_is_held_outside_a_stretch(self, tmp_path):
        with spans.span("library_use"):
            pass
        rec = TelemetryRecorder(str(tmp_path), process_index=0,
                                process_count=1, log=lambda *_: None)
        assert spans.hand_over(rec) == 0
        rec.close()
        assert not [r for r in _read_jsonl(rec.path) if r["kind"] == "span"]

    def test_the_held_list_is_bounded(self, tmp_path):
        spans.open_setup()
        for i in range(spans.HELD_MAX + 6):
            with spans.span(f"s{i}"):
                pass
        rec = TelemetryRecorder(str(tmp_path), process_index=0,
                                process_count=1, log=lambda *_: None)
        assert spans.hand_over(rec) == 6
        rec.close()
        got = [r["name"] for r in _read_jsonl(rec.path)
               if r["kind"] == "span"]
        assert got == [f"s{i}" for i in range(spans.HELD_MAX)]

    def test_a_later_recorder_sees_none_of_an_earlier_runs_spans(
            self, tmp_path):
        """An earlier run that opened a stretch and never built its
        telemetry; a later one installs its own recorder: only its own
        spans reach it, and a telemetry-off build ends the stretch."""
        spans.open_setup()
        with spans.span("earlier"):
            pass
        rec = TelemetryRecorder(str(tmp_path), process_index=0,
                                process_count=1, log=lambda *_: None)
        prev = spans.set_recorder(rec)
        try:
            with spans.span("mine"):
                pass
        finally:
            spans.set_recorder(prev)
        rec.close()
        assert [r["name"] for r in _read_jsonl(rec.path)
                if r["kind"] == "span"] == ["mine"]
        assert build_telemetry(TrainConfig(telemetry=False)) is None
        assert spans.hand_over(None) == 0


class TestPercentiles:
    def test_nearest_rank(self):
        vals = list(range(1, 101))
        assert percentiles(vals) == {50: 50.0, 95: 95.0, 99: 99.0}
        assert percentiles([7.0], qs=(50, 99)) == {50: 7.0, 99: 7.0}
        assert percentiles([]) == {}


class TestPodAggregation:
    def _simulated_pod(self, d, slow_host=1, factor=3.0, steps=20):
        """Two recorders sharing one directory = a simulated 2-host
        pod (the r9/r10 seam); host `slow_host` dispatches `factor`x
        slower.  Records carry injected times — the aggregation math is
        the unit under test, not the clock."""
        for pi in (0, 1):
            rec = TelemetryRecorder(d, process_index=pi, process_count=2,
                                    log=lambda *_: None)
            base = 10.0 * (factor if pi == slow_host else 1.0)
            rec.record_step(1, 0, 1, 1, 500.0, 500.0, 64, compile_=True)
            for i in range(2, steps + 2):
                rec.record_step(i, 0, i, 1, base + 1.0, base, 64)
            rec.flush(wait=True)
            publish_epoch_marker(d, 0, pi)
            rec.close()

    def test_straggler_flagged_and_compile_excluded(self, tmp_path):
        d = str(tmp_path)
        self._simulated_pod(d)
        summary = aggregate_run(d, straggler_ratio=2.0)
        assert summary["host_count"] == 2
        # compile records never pollute the percentiles: host 0's p99
        # would be 500 if they did
        assert summary["hosts"]["0"]["step_ms_p99"] == 10.0
        assert summary["hosts"]["1"]["step_ms_p95"] == 30.0
        # 2-host pods use the LOW median so the slow half is flaggable
        assert summary["pod_median_host_p95_ms"] == 10.0
        assert [s["host"] for s in summary["stragglers"]] == [1]
        assert summary["stragglers"][0]["ratio"] == 3.0

    def test_epoch_fold_logs_and_writes_summary(self, tmp_path):
        d = str(tmp_path)
        self._simulated_pod(d)
        lines = []
        out = pod_epoch_aggregate(d, 0, pi=0, pc=2, straggler_ratio=2.0,
                                  log=lines.append, wait_s=0.0)
        assert out["epoch"] == 0 and out["hosts_reported"] == [0, 1]
        text = "\n".join(lines)
        assert "[telemetry] epoch 0: pod step p50=" in text
        assert "straggler: host 1" in text
        disk = json.load(open(os.path.join(d, "pod_summary.json")))
        assert disk["stragglers"][0]["host"] == 1
        # non-zero hosts never aggregate (their job was flush + marker)
        assert pod_epoch_aggregate(d, 0, pi=1, pc=2) is None

    def test_fold_proceeds_without_missing_host(self, tmp_path):
        d = str(tmp_path)
        rec = TelemetryRecorder(d, process_index=0, process_count=2,
                                log=lambda *_: None)
        rec.record_step(1, 0, 1, 1, 10.0, 10.0, 64)
        rec.flush(wait=True)
        publish_epoch_marker(d, 0, 0)
        rec.close()
        lines = []
        out = pod_epoch_aggregate(d, 0, pi=0, pc=2, log=lines.append,
                                  wait_s=0.1)
        # a host that never flushed is reported, not waited on forever
        assert out["hosts_reported"] == [0]
        assert any("had not flushed" in ln for ln in lines)

    def test_no_straggler_on_uniform_pod(self, tmp_path):
        d = str(tmp_path)
        self._simulated_pod(d, factor=1.1)
        assert aggregate_run(d, straggler_ratio=2.0)["stragglers"] == []

    def test_runfold_incremental_matches_stateless(self, tmp_path):
        """RunFold (per-epoch tail parsing) and aggregate_run (whole
        directory) share one step-time definition and must produce the
        same summary — incrementality can't change the math."""
        from faster_distributed_training_tpu.telemetry import RunFold

        d = str(tmp_path)
        rec = TelemetryRecorder(d, process_index=0, process_count=1,
                                log=lambda *_: None)
        fold = RunFold(d)
        for i in range(1, 11):
            rec.record_step(i, 0, i, 2, 20.0 + i, 20.0 + i, 64)
        rec.flush(wait=True)
        first = fold.summary()           # consumes the first tail
        for i in range(11, 21):
            rec.record_step(i, 1, i, 2, 40.0 + i, 40.0 + i, 64)
        rec.flush(wait=True)
        second = fold.summary()          # parses ONLY the new tail
        rec.close()
        assert first["pod"]["steps"] == 20      # 10 records x k=2
        assert second == aggregate_run(d)
        assert second["pod"]["steps"] == 40

    def test_runfold_resets_on_truncated_file(self, tmp_path):
        """A host file that SHRANK (a relaunch replaced it) resets that
        host's fold instead of seeking past the end forever."""
        from faster_distributed_training_tpu.telemetry import RunFold

        d = str(tmp_path)
        rec = TelemetryRecorder(d, process_index=0, process_count=1,
                                log=lambda *_: None)
        for i in range(1, 6):
            rec.record_step(i, 0, i, 1, 10.0, 10.0, 8)
        rec.flush(wait=True)
        fold = RunFold(d)
        assert fold.summary()["pod"]["steps"] == 5
        rec.close()
        os.remove(rec.path)
        rec2 = TelemetryRecorder(d, process_index=0, process_count=1,
                                 log=lambda *_: None)
        rec2.record_step(1, 0, 1, 1, 30.0, 30.0, 8)
        rec2.flush(wait=True)
        rec2.close()
        s = fold.summary()
        assert s["pod"]["steps"] == 1
        assert s["hosts"]["0"]["step_ms_p50"] == 30.0

    def test_stale_markers_from_previous_run_ignored(self, tmp_path):
        """Time-scoping (the r10 EXIT-marker idiom): an epoch marker
        older than this run's telemetry is a reused directory's residue
        and must not satisfy the aggregation barrier."""
        d = str(tmp_path)
        self._simulated_pod(d)            # both hosts' epoch-0 markers
        lines = []
        out = pod_epoch_aggregate(d, 0, pi=0, pc=2, log=lines.append,
                                  wait_s=0.1,
                                  newer_than=time.time() + 60.0)
        assert out["hosts_reported"] == []
        assert any("had not flushed" in ln for ln in lines)
        # markers newer than the scope are honored
        out = pod_epoch_aggregate(d, 0, pi=0, pc=2, wait_s=0.1,
                                  log=lambda *_: None,
                                  newer_than=time.time() - 60.0)
        assert out["hosts_reported"] == [0, 1]


class TestStepWindowProfiler:
    def _fake(self):
        calls = []
        return (calls, lambda d: calls.append(("start", d)),
                lambda: calls.append(("stop",)))

    def test_window_covers_requested_steps_k1(self):
        calls, start, stop = self._fake()
        p = StepWindowProfiler("/tmp/t", 3, 5, start_fn=start,
                               stop_fn=stop, log=lambda *_: None)
        for s in range(8):           # dispatches run step s+1
            p.before_dispatch(s, 1)
            p.after_dispatch(s + 1)
        assert calls == [("start", "/tmp/t"), ("stop",)]
        # started before step 3 ran, stopped once step 5 completed
        assert p.started_at == 2 and p.stopped_at == 5

    def test_window_quantizes_to_dispatch_boundaries(self):
        calls, start, stop = self._fake()
        p = StepWindowProfiler("/tmp/t", 3, 5, start_fn=start,
                               stop_fn=stop, log=lambda *_: None)
        fenced = []
        for s in range(0, 8, 2):     # K=2 dispatches
            p.before_dispatch(s, 2)
            p.after_dispatch(s + 2, fence=lambda: fenced.append(True))
        # the dispatch covering step 3 is steps 3-4 (starts at 2);
        # the stop lands after the dispatch that completes step 5 (6)
        assert p.started_at == 2 and p.stopped_at == 6
        assert fenced == [True]      # fence ran exactly at the stop
        assert calls == [("start", "/tmp/t"), ("stop",)]

    def test_resume_past_window_never_starts(self):
        calls, start, stop = self._fake()
        p = StepWindowProfiler("/tmp/t", 3, 5, start_fn=start,
                               stop_fn=stop, log=lambda *_: None)
        p.before_dispatch(10, 1)     # resumed past B
        p.after_dispatch(11)
        p.close()
        assert calls == [] and p.done

    def test_run_ending_early_still_captures(self):
        calls, start, stop = self._fake()
        p = StepWindowProfiler("/tmp/t", 2, 100, start_fn=start,
                               stop_fn=stop, log=lambda *_: None)
        p.before_dispatch(1, 1)
        p.after_dispatch(2)
        p.close()                    # run ended before step 100
        assert calls == [("start", "/tmp/t"), ("stop",)]

    def test_parse_profile_steps(self):
        assert parse_profile_steps("") is None
        assert parse_profile_steps("3:5") == (3, 5)
        assert parse_profile_steps("7:7") == (7, 7)
        for bad in ("5", "0:3", "5:3", "a:b", "3:"):
            with pytest.raises(ValueError):
                parse_profile_steps(bad)


def _tiny_cfg(tmp_path, epochs=2, **kw):
    return TrainConfig(model="transformer", dataset="synthetic",
                       num_classes=4, batch_size=8, seq_len=16, n_layers=1,
                       d_model=16, d_ff=32, n_heads=2, epochs=epochs,
                       subset_stride=64, optimizer="sgd", precision="fp32",
                       plot=False, workers=0, log_every=0, donate=False,
                       checkpoint_dir=str(tmp_path), **kw)


class TestEndToEnd:
    def test_run_emits_valid_stream_matching_summary(self, tmp_path):
        """The r12 acceptance pin: a CPU run with telemetry enabled
        emits a valid manifest + per-dispatch JSONL whose step count and
        loss match the epoch summary, with the checkpoint/eval/compile
        seams visible as spans."""
        from faster_distributed_training_tpu.cli import run_training

        cfg = _tiny_cfg(tmp_path, checkpoint_every=4)
        out = run_training(cfg, log=lambda *_: None)
        td = out["telemetry_dir"]
        man = json.load(open(os.path.join(td, "manifest.json")))
        assert man["workload"] == "transformer"
        assert man["config"]["batch_size"] == 8
        assert man["steps_per_epoch"] == 8
        recs = _read_jsonl(os.path.join(td, "host_00000.jsonl"))
        epochs = [r for r in recs if r["kind"] == "epoch"]
        assert [e["epoch"] for e in epochs] == [0, 1]
        for e in epochs:
            step_recs = [r for r in recs if r["kind"] == "step"
                         and r["epoch"] == e["epoch"]]
            # step count matches the epoch summary exactly
            assert sum(r["k"] for r in step_recs) == e["trained_steps"] == 8
            # the epoch event's loss IS the epoch summary's loss
            assert e["loss"] == out["history"]["train_loss"][e["epoch"]]
            assert e["eval_accuracy"] == out["history"]["test_acc"][
                e["epoch"]]
        names = {r["name"] for r in recs if r["kind"] == "span"}
        # instrumented seams: compile, eval, checkpoint snapshot+commit
        # (checkpoint_every=4 fired mid-epoch on the async path)
        assert {"first_dispatch_compile", "eval", "ckpt_snapshot",
                "ckpt_commit"} <= names, names
        # set-up, held until the recorder existed: the first spans of the
        # stream, top-level, in the order they ran, none overlapping
        setup = [r for r in recs if r["kind"] == "span"][:4]
        assert [r["name"] for r in setup] == [
            "setup_platform", "setup_model", "setup_data", "setup_state"]
        assert all("parent" not in r for r in setup)
        for a, b in zip(setup, setup[1:]):
            assert a["t0_s"] + a["dur_ms"] / 1e3 <= b["t0_s"] + 1e-5
        # the step program's acquisition inside its first dispatch
        first = [r for r in recs if r["kind"] == "span"
                 and r["name"] == "first_dispatch_compile"
                 and r.get("step") == 1]
        assert len(first) == 1 and "parent" not in first[0]
        kids = [r for r in recs if r["kind"] == "span"
                and r.get("parent") == "first_dispatch_compile"
                and first[0]["t0_s"] <= r["t0_s"]
                <= first[0]["t0_s"] + first[0]["dur_ms"] / 1e3]
        assert sorted({r["name"] for r in kids}) == [
            "program_compile", "program_lower", "program_observe"]
        # goodput rides the same stream (one snapshot per epoch)
        goodputs = [r for r in recs if r["kind"] == "goodput"]
        assert len(goodputs) == 2 and goodputs[-1]["saves"] >= 1
        # compile marked exactly once for the single (host, 1) program
        assert sum(1 for r in recs
                   if r["kind"] == "step" and r.get("compile")) == 1

    @pytest.mark.slow  # r20 budget diet: 38 s — operator tooling, not
    # a correctness contract; the window boundary arithmetic stays
    # tier-1 via the profile-window unit tests above
    def test_profile_steps_window_produces_trace(self, tmp_path):
        """--profile_steps A:B produces a trace directory covering only
        the requested window (start/stop observed via the log; the real
        jax.profiler runs and leaves trace files behind)."""
        from faster_distributed_training_tpu.cli import run_training

        lines = []
        cfg = _tiny_cfg(tmp_path, epochs=1, profile_steps="3:5")
        out = run_training(cfg, log=lines.append)
        trace_dir = os.path.join(out["telemetry_dir"], "trace_steps_3_5")
        assert os.path.isdir(trace_dir)
        assert glob.glob(os.path.join(trace_dir, "**", "*"),
                         recursive=True), "trace directory is empty"
        text = "\n".join(lines)
        assert "trace started before step 3" in text
        assert "trace stopped after step 5" in text

    def test_no_telemetry_runs_clean(self, tmp_path):
        from faster_distributed_training_tpu.cli import run_training

        out = run_training(_tiny_cfg(tmp_path, epochs=1, telemetry=False),
                           log=lambda *_: None)
        assert "telemetry_dir" not in out
        assert not os.path.exists(os.path.join(str(tmp_path), "telemetry"))


class TestLiveThroughputFix:
    def test_log_dispatch_subtracts_blocked_time(self):
        """The r12 satellite pin: the live ex/s line reports STEP
        throughput — checkpoint-blocking/hook seconds measured since the
        last line are subtracted from the wall window (a save landing
        mid-window used to read as a throughput dip)."""
        from faster_distributed_training_tpu.train.loop import Trainer

        lines = []
        cfg = TrainConfig(model="transformer", batch_size=100,
                          log_every=10, donate=False)
        tr = Trainer(cfg, log=lines.append)
        metrics = {"loss": np.float32(1.0)}
        t_now = time.monotonic()
        # a 2 s window, 1 s of which was a blocking checkpoint
        tr._blocked_since_log = 1.0
        tr._log_dispatch(0, 10, 1, metrics, (t_now - 2.0, 0))
        assert len(lines) == 1, lines
        exs = float(lines[0].split(" ex/s")[0].split()[-1])
        # 10 steps x 100 ex over (2.0 - 1.0) s ~= 1000 ex/s; the raw
        # wall number (the old bug) would be ~500
        assert 900 <= exs <= 1100, lines[0]
        assert "(+1.00s blocked)" in lines[0]
        assert tr._blocked_since_log == 0.0   # window accounting reset
        # K=1 lines carry no fused suffix (unchanged r8 format)
        assert "fused" not in lines[0]

    def test_log_dispatch_without_blocking_unchanged(self):
        from faster_distributed_training_tpu.train.loop import Trainer

        lines = []
        cfg = TrainConfig(model="transformer", batch_size=64,
                          log_every=4, donate=False)
        tr = Trainer(cfg, log=lines.append)
        metrics = {"loss": np.float32(2.0)}
        tr._log_dispatch(1, 8, 4, metrics, (time.monotonic() - 1.0, 4))
        assert len(lines) == 1
        assert "blocked" not in lines[0]
        assert "(K=4 fused)" in lines[0]
        # no emission when the dispatch didn't cross a boundary:
        # `last` is returned untouched
        last = (time.monotonic(), 8)
        assert tr._log_dispatch(1, 10, 2, metrics, last) == last
        assert len(lines) == 1


class TestReportScript:
    def _mod(self):
        spec = importlib.util.spec_from_file_location(
            "telemetry_report",
            os.path.join(ROOT, "scripts", "telemetry_report.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_report_against_recorded_fixture(self):
        """Tier-1 smoke against the committed fixture: percentiles,
        straggler table, span breakdown, throughput curve — pinned
        values, so a schema change that breaks consumers fails here."""
        mod = self._mod()
        rep = mod.run(FIXTURE)
        s = rep["summary"]
        assert s["hosts"]["0"]["step_ms_p50"] == 102.0
        assert s["hosts"]["1"]["step_ms_p95"] == 304.0
        assert s["pod"]["steps"] == 46          # compile records excluded
        assert [x["host"] for x in s["stragglers"]] == [1]
        assert rep["manifest"]["workload"] == "resnet"
        assert {"eval", "ckpt_snapshot", "ckpt_commit"} <= set(rep["spans"])
        assert [e["epoch"] for e in rep["throughput_curve"]] == [0, 1]
        assert rep["throughput_curve"][1]["eval_accuracy"] == 0.65
        assert rep["goodput"]["goodput_pct"] == 96.0
        text = mod.render(rep)
        assert "straggler" in text and "host 1" in text
        assert "span breakdown" in text

    def test_report_cli_main(self, capsys):
        mod = self._mod()
        rep = mod.main([FIXTURE, "--straggler_ratio", "2.0"])
        assert rep["summary"]["stragglers"]
        assert "stragglers" in capsys.readouterr().out

    def test_fixture_helpers_roundtrip(self):
        hosts = read_host_records(FIXTURE)
        assert set(hosts) == {0, 1}
        bd = span_breakdown(hosts[0] + hosts[1])
        assert bd["eval"]["count"] == 4
        assert bd["ckpt_commit"]["total_ms"] == 360.0

    def test_nested_spans_sit_under_their_parent_and_count_once(self):
        """A child sits under the parent span that held its start, at any
        depth; the top level's totals hold no time twice, and the report
        shows the children indented under their parent."""
        def sp(name, t0, dur_ms, parent=None):
            r = {"kind": "span", "name": name, "t0_s": t0, "dur_ms": dur_ms}
            if parent:
                r["parent"] = parent
            return r
        recs = [sp("setup_platform", 0.0, 500.0),
                sp("program_lower", 1.0, 300.0, "first_dispatch_compile"),
                sp("program_compile", 1.3, 600.0, "first_dispatch_compile"),
                sp("first_dispatch_compile", 1.0, 1000.0),
                sp("program_lower", 5.0, 30.0, "first_dispatch_compile"),
                sp("first_dispatch_compile", 5.0, 50.0, "eval"),
                sp("eval", 4.99, 80.0),
                {"kind": "span", "name": "ckpt_commit", "dur_ms": 7.0}]
        bd = span_breakdown(recs)
        assert set(bd) == {"setup_platform", "first_dispatch_compile",
                           "eval", "ckpt_commit"}
        fdc = bd["first_dispatch_compile"]
        assert fdc["count"] == 1 and fdc["total_ms"] == 1000.0
        assert fdc["children"]["program_lower"]["total_ms"] == 300.0
        assert fdc["children"]["program_compile"]["total_ms"] == 600.0
        inner = bd["eval"]["children"]["first_dispatch_compile"]
        assert inner["total_ms"] == 50.0
        assert inner["children"]["program_lower"]["total_ms"] == 30.0
        assert sum(s["total_ms"] for s in bd.values()) == 1587.0
        text = self._mod().render({"directory": "/tmp/x", "spans": bd})
        rows = [ln for ln in text.splitlines() if "total=" in ln]
        assert [r.split()[0] for r in rows] == [
            "first_dispatch_compile", "program_compile", "program_lower",
            "setup_platform", "eval", "first_dispatch_compile",
            "program_lower", "ckpt_commit", "top-level"]
        assert rows[1].startswith("    program_compile")
        assert rows[6].startswith("      program_lower")
        assert "1587.0ms" in rows[-1]

    def test_render_orders_hosts_numerically(self):
        """Host rows sort by host INDEX, not by the stringified key —
        host 10 must render after host 2 on big pods."""
        mod = self._mod()
        summary = {"hosts": {str(pi): {"step_ms_p50": 1.0,
                                       "step_ms_p95": 1.0,
                                       "step_ms_p99": 1.0, "steps": 4}
                             for pi in (0, 2, 10)},
                   "host_count": 3, "straggler_ratio": 2.0,
                   "stragglers": [],
                   "pod": {"step_ms_p50": 1.0, "step_ms_p95": 1.0,
                           "step_ms_p99": 1.0, "steps": 12}}
        text = mod.render({"directory": "/tmp/x", "summary": summary})
        rows = [ln for ln in text.splitlines() if "host " in ln]
        assert [r.split()[1] for r in rows] == ["0", "2", "10"]


class TestStepSampling:
    """--telemetry_every N (r13 satellite): the r12 note names
    per-dispatch time.monotonic pressure under async dispatch as the
    first suspect if telemetry_overhead_pct ever fails on live TPU —
    sampling every Nth dispatch is the landed mitigation.  Sampling
    drops whole records (surviving ones keep their TRUE step numbers);
    compile-marked first dispatches are always kept."""

    def test_every_n_keeps_true_step_numbers(self, tmp_path):
        rec = TelemetryRecorder(str(tmp_path), process_index=0,
                                process_count=1, step_every=3,
                                log=lambda *_: None)
        rec.record_step(1, 0, 1, 1, 1.0, 1.0, 4, compile_=True)
        for i in range(2, 13):
            rec.record_step(i, 0, i, 1, 1.0, 1.0, 4)
        rec.record_event("epoch", epoch=0)   # events are never sampled
        rec.close()
        recs = _read_jsonl(os.path.join(str(tmp_path),
                                        "host_00000.jsonl"))
        steps = [r for r in recs if r["kind"] == "step"]
        assert steps[0]["step"] == 1 and steps[0].get("compile")
        # every 3rd dispatch thereafter, true global steps preserved
        assert [r["step"] for r in steps[1:]] == [3, 6, 9, 12]
        assert any(r["kind"] == "epoch" for r in recs)

    def test_compile_records_survive_sampling(self, tmp_path):
        rec = TelemetryRecorder(str(tmp_path), process_index=0,
                                process_count=1, step_every=100,
                                log=lambda *_: None)
        for i in range(1, 6):
            rec.record_step(i, 0, i, 1, 1.0, 1.0, 4, compile_=(i == 2))
        rec.close()
        steps = [r for r in _read_jsonl(os.path.join(
            str(tmp_path), "host_00000.jsonl")) if r["kind"] == "step"]
        # only the compile-marked dispatch survives a 1-in-100 rate
        assert [r["step"] for r in steps] == [2]
        assert steps[0]["compile"] is True

    def test_build_telemetry_wires_the_flag(self, tmp_path):
        cfg = TrainConfig(checkpoint_dir=str(tmp_path),
                          telemetry_every=4)
        tel = build_telemetry(cfg, log=lambda *_: None)
        assert tel.recorder.step_every == 4
        tel.close()

    def test_default_records_every_dispatch(self, tmp_path):
        rec = TelemetryRecorder(str(tmp_path), process_index=0,
                                process_count=1, log=lambda *_: None)
        for i in range(1, 6):
            rec.record_step(i, 0, i, 1, 1.0, 1.0, 4)
        rec.close()
        steps = [r for r in _read_jsonl(os.path.join(
            str(tmp_path), "host_00000.jsonl")) if r["kind"] == "step"]
        assert [r["step"] for r in steps] == [1, 2, 3, 4, 5]

    def test_next_step_kept_predicts_record_decisions(self, tmp_path):
        """The Trainer consults next_step_kept BEFORE a dispatch to
        skip the telemetry-only clock reads (review pass: sampling at
        the recorder layer alone would keep 100% of the monotonic
        pressure) — the prediction must agree exactly with what
        record_step then keeps."""
        rec = TelemetryRecorder(str(tmp_path), process_index=0,
                                process_count=1, step_every=3,
                                log=lambda *_: None)
        preds = []
        for i in range(1, 10):
            preds.append(rec.next_step_kept())
            rec.record_step(i, 0, i, 1, 1.0, 1.0, 4)
        rec.close()
        steps = [r["step"] for r in _read_jsonl(os.path.join(
            str(tmp_path), "host_00000.jsonl")) if r["kind"] == "step"]
        assert steps == [i for i, p in zip(range(1, 10), preds) if p]
