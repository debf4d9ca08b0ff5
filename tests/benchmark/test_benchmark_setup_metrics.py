"""The four per-layer metrics that split ``setup_s`` (``setup_lower_s``,
``setup_compile_s``, ``setup_observe_s``, ``setup_host_s``): on synthetic
span records in the recorder's file; on what a program from before the
spans had a start writes (nothing to read: None, never a raise); their
BENCHMARK.json entries under the harness's contract; and a tiny traced
run, whose line carries all four and whose set-up spans nest as the
program writes them."""

import importlib
import json
import os
import sys

import pytest

from conftest import load, tiny_cell

BENCH = load("BENCHMARK.json")
NEW = ("setup_lower_s", "setup_compile_s", "setup_observe_s",
       "setup_host_s")


def span(name, dur_ms, t0_s, parent=None, **fields):
    rec = {"kind": "span", "name": name, "dur_ms": dur_ms, "t0_s": t0_s}
    if parent is not None:
        rec["parent"] = parent
    return dict(rec, **fields)


# a set-up as the program writes it: its own spans, then the step
# program's acquisition inside its first dispatch (children first: a span
# is written when it closes), and a second program's
SETUP = [span("setup_platform", 900.0, 10.0),
         span("setup_data", 5.0, 11.0),
         span("setup_model", 300.0, 11.1),
         span("setup_state", 2000.0, 11.5),
         span("restore", 100.0, 13.6),
         span("program_lower", 20000.0, 14.0, "first_dispatch_compile"),
         span("program_observe", 700.0, 34.0, "first_dispatch_compile"),
         span("program_compile", 9000.0, 34.7, "first_dispatch_compile"),
         span("program_observe", 400.0, 43.7, "first_dispatch_compile"),
         span("first_dispatch_compile", 30500.0, 14.0, step=1),
         span("program_lower", 100.0, 45.0, "first_dispatch_compile"),
         span("program_observe", 3.0, 45.1, "first_dispatch_compile"),
         span("program_compile", 50.0, 45.11, "first_dispatch_compile"),
         span("program_observe", 2.0, 45.16, "first_dispatch_compile"),
         span("first_dispatch_compile", 160.0, 45.0, step=4)]
# what a program from before writes: no start, no parent, no set-up spans
PARENT = [{"kind": "span", "name": "first_dispatch_compile",
           "dur_ms": 31000.0, "step": 1}]

EXPECTED = {
    "setup_lower_s": 20.1,
    "setup_compile_s": 9.05,
    "setup_observe_s": 1.105,
    "setup_host_s": 3.305,
}


def step(n):
    return {"kind": "step", "step": n, "epoch": 0, "n": n, "k": 1,
            "wall_ms": 4.0, "dispatch_ms": 3.0, "data_ms": 1.0,
            "block_ms": 0.0, "examples": 8, "ex_s": 2000.0}


# the recorder's file of each case, up to and past the window's first step
# record (13): a compile in the window is not set-up
WINDOW = [span("program_lower", 5000.0, 99.0, "first_dispatch_compile"),
          span("setup_model", 5000.0, 99.0), step(14)]
STEPS = [step(s) for s in range(1, 14)]
FILES = {"new": SETUP + STEPS + WINDOW, "parent": PARENT + STEPS + WINDOW,
         "empty": STEPS + WINDOW, "missing": None, "no_cell": SETUP + STEPS}


@pytest.mark.parametrize("metric", NEW)
@pytest.mark.parametrize("case", sorted(FILES))
def test_reader_on_the_recorders_file(metric, case, tmp_path, monkeypatch):
    """The records come from the recorder's file of the command line's
    cell, up to the window's first step record; a parent's file, one with
    no span, no file and no cell on the command line give None."""
    from benchmark.metrics import _setup_spans

    monkeypatch.setattr(_setup_spans, "ROOT", str(tmp_path))
    monkeypatch.setattr(sys, "argv", ["run.py", "--seed", "1"] + (
        [] if case == "no_cell" else ["--workload", "a.cell"]))
    if FILES[case] is not None:
        d = tmp_path / "benchmark_out" / "a.cell" / "telemetry"
        d.mkdir(parents=True)
        (d / "host_00000.jsonl").write_text(
            "".join(json.dumps(r) + "\n" for r in FILES[case]))
    got = importlib.import_module(f"benchmark.metrics.{metric}").read(
        {"records": [step(13), step(14)]})
    if case == "new":
        assert got == pytest.approx(EXPECTED[metric], rel=1e-12)
    else:
        assert got is None


@pytest.mark.parametrize("metric", NEW)
def test_entry_is_appended_and_keeps_to_the_contract(metric):
    """Appended after every entry the benchmark had, with no ``workloads``
    list (every cell reports ``setup_s``), one layer's name for all four."""
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-len(NEW):] == list(NEW)
    m = BENCH["per_layer"][names.index(metric)]
    assert m == {"name": metric, "unit": "s", "better": "lower",
                 "source": "program_span", "layer": "platform/cache",
                 "moves": "setup_s"}


def test_a_tiny_traced_run_carries_them(tree, monkeypatch, capsys):
    """``run.main --trace 1`` at the tiny size on the CPU (the device
    trace's reduction stubbed: a CPU trace has no device plane): the line
    carries the four metrics, and in the recorder's file the set-up spans
    nest as specified: every ``program_*`` span inside a
    ``first_dispatch_compile``, no two top-level set-up spans overlapping,
    the children's sum within their parent."""
    from benchmark import run, trace_reduce

    resolved = tiny_cell(tree, monkeypatch)
    resolved[3]["trace_seconds"] = 0.5
    cell = resolved[1]["name"]
    argv = ["--workload", cell, "--seed", "2100000917", "--seconds", "1.5",
            "--trace", "1"]
    monkeypatch.setattr(sys, "argv", ["benchmark/run.py"] + argv)
    monkeypatch.setattr(run, "resolve", lambda workload, **kw: resolved)
    monkeypatch.setattr(run, "check_devices", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    monkeypatch.setattr(trace_reduce, "reduce_dir", lambda *a, **kw: {
        "busy_s": 0.0, "window_s": 0.5, "device_ms_per_step": 0.0,
        "steps_traced": 0, "device_ops": [], "idle_gaps": [], "kernels": {},
        "scopes": {}})
    t0 = run.T0
    assert run.main(argv) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(got), sorted(got)
    assert all(line["metrics"][k]["unit"] == "s" for k in NEW)
    assert all(got[k] > 0.0 for k in NEW), got

    path = os.path.join(run.ROOT, "benchmark_out", cell, "telemetry",
                        "host_00000.jsonl")
    recs = [json.loads(ln) for ln in open(path)]
    spans = [r for r in recs if r["kind"] == "span"]
    top = [r for r in spans if "parent" not in r]
    assert {"setup_platform", "setup_data", "setup_model",
            "first_dispatch_compile"} <= {r["name"] for r in top}
    assert all(r["t0_s"] >= t0 for r in spans)
    for r in spans:
        if r["name"].startswith("program_"):
            assert r["parent"] == "first_dispatch_compile"
    top.sort(key=lambda r: r["t0_s"])
    for a, b in zip(top, top[1:]):
        assert a["t0_s"] + a["dur_ms"] / 1e3 <= b["t0_s"] + 1e-5, (a, b)
    for p in (r for r in top if r["name"] == "first_dispatch_compile"):
        end = p["t0_s"] + p["dur_ms"] / 1e3
        kids = [r for r in spans if r.get("parent") == p["name"]
                and p["t0_s"] - 1e-5 <= r["t0_s"] <= end + 1e-5]
        assert {r["name"] for r in kids} == {
            "program_lower", "program_compile", "program_observe"}
        assert sum(r["dur_ms"] for r in kids) <= p["dur_ms"] + 1e-3
