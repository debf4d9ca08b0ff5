"""benchmark/trace_reduce.py on a synthetic event list (every rule by hand)
and on the small recorded trace under benchmark/fixtures/ (cut from the
first chip trace of PR 23)."""

import gzip
import json
import os

import pytest

from conftest import ROOT

from benchmark import trace_reduce as tr

MS = 1e6        # nanoseconds


def planes():
    """Two whole steps of 'jit_step' on one chip: each 10 ms long with two
    operations (4 ms 'conv', 3 ms 'flash_fwd' overlapping 'conv' by 1 ms),
    a 5 ms gap between the steps while the host sits in bench/next, and a
    stray short program before them."""
    ops, modules = [], []
    for start in (100 * MS, 115 * MS):
        modules.append(("jit_step(123)", start, start + 10 * MS))
        ops.append(("conv.1", start, start + 4 * MS))
        ops.append(("flash_fwd", start + 3 * MS, start + 6 * MS))
        ops.append(("tail", start + 9 * MS, start + 10 * MS))
    modules.append(("jit_copy", 90 * MS, 91 * MS))
    ops.append(("copy", 90 * MS, 91 * MS))
    host = {"main": [("bench/next", 109 * MS, 116 * MS),
                     ("bench/step_call", 99 * MS, 100 * MS),
                     ("PjitFunction", 91 * MS, 99.5 * MS)]}
    return {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules},
            "/host:CPU": host}


def test_union_of_intervals():
    assert tr.union([(0, 2), (1, 3), (5, 6), (6, 6), (5.5, 7)]) == \
        [(0, 3), (5, 7)]
    assert tr.total(tr.clip([(0, 3), (5, 7)], 2, 6)) == 2


def test_idle_share_is_one_minus_the_union_over_the_window():
    out = tr.reduce_planes(planes(), 1, {})
    # window: first op start (90) to last op end (125) = 35 ms; busy:
    # 1 + 2 x (6 + 1) = 15 ms
    assert out["window_s"] == pytest.approx(0.035)
    assert out["busy_s"] == pytest.approx(0.015)


def test_device_time_per_step_counts_whole_steps_of_the_step_program():
    out = tr.reduce_planes(planes(), 1, {})
    assert out["step_program"] == "jit_step(123)"
    assert out["steps_traced"] == 2
    assert out["device_ms_per_step"] == pytest.approx(7.0)


def test_kernel_events_are_matched_by_name_and_counted_a_step():
    out = tr.reduce_planes(planes(), 1, {"flash_attn": ["flash_"],
                                         "absent": ["nothing_like_it"]})
    assert out["kernels"]["flash_attn"]["events_per_step"] == 1
    assert out["kernels"]["flash_attn"]["seconds_per_step"] == \
        pytest.approx(0.003)
    assert "absent" not in out["kernels"]     # nothing to read: left out


def test_idle_gaps_go_to_what_the_host_was_doing():
    out = tr.reduce_planes(planes(), 1, {})
    gaps = dict(out["idle_gaps"])
    # 110..115 under bench/next (5 ms); 91..100 mostly under the runtime's
    # PjitFunction (9 ms); the 3 ms holes inside the steps have no span
    assert gaps["bench/next"] == pytest.approx(0.005)
    assert gaps["PjitFunction"] == pytest.approx(0.009)
    assert gaps["unattributed"] == pytest.approx(0.006)
    assert out["device_ops"][0] == ["conv.1", pytest.approx(0.008)]


def test_a_trace_without_device_operations_is_refused():
    empty = {"/device:TPU:0": {"XLA Ops": [], "XLA Modules": []},
             "/host:CPU": {}}
    with pytest.raises(ValueError, match="no operation ran"):
        tr.reduce_planes(empty, 1, {})
    with pytest.raises(ValueError, match="device plane"):
        tr.reduce_planes({"/host:CPU": {}}, 1, {})


def test_recorded_chip_trace_reduces_to_the_numbers_read_by_hand():
    path = os.path.join(ROOT, "benchmark", "fixtures",
                        "resnet50_cifar_bs1024_trace.json.gz")
    with gzip.open(path, "rt") as f:
        fixture = json.load(f)
    planes_ = {p: {line: [tuple(e) for e in evs]
                   for line, evs in lines.items()}
               for p, lines in fixture["planes"].items()}
    out = tr.reduce_planes(planes_, 1, {})
    want = fixture["read_by_hand"]
    assert out["steps_traced"] == want["steps_traced"]
    assert out["device_ms_per_step"] == pytest.approx(
        want["device_ms_per_step"], rel=1e-6)
    assert out["busy_s"] / out["window_s"] == pytest.approx(
        want["busy_share"], rel=1e-6)
    assert out["device_ops"][0][0] == want["top_op"]
