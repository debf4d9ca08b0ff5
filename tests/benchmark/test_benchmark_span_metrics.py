"""The three per-layer metrics read from the program's step records
(``h2d_ms``, ``host_sync_ms``, ``step_fenced_ms``): on synthetic records, on
records of a program from before the fields (nothing to read: None, never a
raise), and their BENCHMARK.json entries under the harness's contract."""

import importlib
import json

import pytest

from conftest import load, tiny_cell

BENCH = load("BENCHMARK.json")
NEW = {"h2d_ms": "data", "host_sync_ms": "loop", "step_fenced_ms": "loop"}


def rec(step, **fields):
    base = {"kind": "step", "step": step, "epoch": 0, "n": step, "k": 1,
            "wall_ms": 4.0, "dispatch_ms": 3.0, "data_ms": 1.0,
            "block_ms": 0.0, "examples": 8, "ex_s": 2000.0}
    return dict(base, **fields)


# what a window of this program writes: a compile record (left out), plain
# steps, and two read-backs that each close a fenced window
NEW_RECORDS = ([rec(1, h2d_ms=9.0, compile=True)]
               + [rec(s, h2d_ms=0.5) for s in range(2, 50)]
               + [rec(50, h2d_ms=1.5, sync_ms=300.0, fence_steps=49,
                      fence_ms=49 * 160.0)]
               + [rec(s, h2d_ms=0.5) for s in range(51, 100)]
               + [rec(100, h2d_ms=0.5, sync_ms=294.0, fence_steps=50,
                      fence_ms=50 * 162.0)])
# what the parent's program writes: none of the fields
OLD_RECORDS = [rec(1, compile=True)] + [rec(s) for s in range(2, 101)]
# the fields, but no read-back inside the window (--log_every 0)
NO_FENCE = [rec(s, h2d_ms=0.25) for s in range(1, 21)]

EXPECTED = {
    ("h2d_ms", "new"): (97 * 0.5 + 1.5 + 0.5) / 99,
    ("h2d_ms", "old"): None,
    ("h2d_ms", "no_fence"): 0.25,
    ("h2d_ms", "empty"): None,
    # over whole read-back periods: from after the first read-back (record
    # 50) to the last (record 100), 50 records that hold one wait
    ("host_sync_ms", "new"): 294.0 / 50,
    ("host_sync_ms", "three"): (294.0 + 310.0) / 100,
    ("host_sync_ms", "one"): None,          # fewer than two read-backs
    ("host_sync_ms", "old"): None,
    ("host_sync_ms", "no_fence"): None,
    ("host_sync_ms", "empty"): None,
    ("step_fenced_ms", "new"): (49 * 160.0 + 50 * 162.0) / 99,
    ("step_fenced_ms", "old"): None,
    ("step_fenced_ms", "no_fence"): None,
    ("step_fenced_ms", "empty"): None,
}
# a third read-back, and a tail of 7 records after it: where the stretch
# ends no longer moves the number (the old mean over all records read
# 2 waits in 99 records as 6.0 and 3 waits in 157 as 5.8)
THREE = (NEW_RECORDS + [rec(s, h2d_ms=0.5) for s in range(101, 150)]
         + [rec(150, h2d_ms=0.5, sync_ms=310.0, fence_steps=50,
                fence_ms=50 * 161.0)]
         + [rec(s, h2d_ms=0.5) for s in range(151, 158)])
RECORDS = {"new": NEW_RECORDS, "old": OLD_RECORDS, "no_fence": NO_FENCE,
           "empty": [], "three": THREE, "one": NEW_RECORDS[:70]}


@pytest.mark.parametrize("metric,records", sorted(EXPECTED))
def test_reader_on_step_records(metric, records):
    reader = importlib.import_module(f"benchmark.metrics.{metric}")
    got = reader.read({"records": RECORDS[records]})
    want = EXPECTED[metric, records]
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("metric", sorted(NEW))
def test_entry_is_appended_and_keeps_to_the_contract(metric):
    """The harness's own checks (test_benchmark_harness.py) run over every
    entry of BENCHMARK.json, these included; here what is particular to
    them: appended after the seven the benchmark had, no ``workloads`` list
    (every training cell's records carry the fields), the layer's name as
    BENCHMARK.json already spells it."""
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(metric) >= 7
    m = BENCH["per_layer"][names.index(metric)]
    assert m == {"name": metric, "unit": "ms", "better": "lower",
                 "source": "program_span", "layer": NEW[metric],
                 "moves": "examples_per_s_per_chip"}
    assert NEW[metric] in {e["layer"] for e in BENCH["per_layer"][:7]}


def test_result_line_of_a_tiny_traced_run_carries_them(tree, monkeypatch,
                                                       capsys):
    """``run.main --trace 1`` at the tiny size on the CPU (the reduction of
    the device trace stubbed: a CPU trace has no device plane): the readers
    find the fields in the records of the program as it is (the tiny cell
    reads back every 2 steps), and the numbers hang together: a fenced
    step is about as long as the host's share of it or longer."""
    from benchmark import run, trace_reduce

    resolved = tiny_cell(tree, monkeypatch)
    resolved[3]["trace_seconds"] = 0.5
    monkeypatch.setattr(run, "resolve", lambda workload, **kw: resolved)
    monkeypatch.setattr(run, "check_devices", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    monkeypatch.setattr(trace_reduce, "reduce_dir", lambda *a, **kw: {
        "busy_s": 0.0, "window_s": 0.5, "device_ms_per_step": 0.0,
        "steps_traced": 0, "device_ops": [], "idle_gaps": [], "kernels": {},
        "scopes": {}})
    rc = run.main(["--workload", "tiny.cell", "--seed", "2100000123",
                   "--seconds", "1.5", "--trace", "1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(got), sorted(got)
    assert all(line["metrics"][k]["unit"] == "ms" for k in NEW)
    assert 0.0 <= got["h2d_ms"] <= got["data_wait_ms"]
    assert got["host_sync_ms"] > 0.0
    # period by period a fenced step is no shorter than the host's share of
    # it; the two readers differ by the stretch's first fenced window (the
    # read-backs are counted from after the first), so on a busy host they
    # agree only roughly
    assert got["step_fenced_ms"] >= 0.5 * got["host_sync_ms"]
