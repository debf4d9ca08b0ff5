"""Reduction of a JAX profiler trace (``*.xplane.pb``) to the numbers the
per-layer metrics and the result line read.  Owned by the benchmark: every
PR computes the same number the same way.

Read with ``jax.profiler.ProfileData`` only.  What a trace of this program
on a TPU v5e holds (read by hand, PERF.md section 5): one plane a chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per executed HLO
operation (name as the compiled module prints it, start and duration in
nanoseconds) and whose line ``XLA Modules`` has one event per execution of
a compiled program; and the plane ``/host:CPU`` with one line per host
thread, on which ``jax.profiler.TraceAnnotation`` spans (the program's
``fdt/*``, the benchmark's ``bench/*``) and the runtime's own spans lie on
the same clock.

All functions below the loader work on plain lists of
``(name, start_ns, end_ns)`` so that they can be tested on synthetic events.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start_ns, end_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# host spans that say what the host was doing, most telling first
HOST_PREFIXES = ("bench/", "fdt/")


# -- loading -------------------------------------------------------------------

def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """{plane name: {line name: [events]}} of the planes the reduction
    reads: the chips' and the host's."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in data.planes:
        if not (DEVICE_PLANE.match(plane.name) or plane.name == HOST_PLANE):
            continue
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                events.append((ev.name, float(ev.start_ns),
                               float(ev.start_ns + ev.duration_ns)))
    return out


def describe(path: str, top: int = 12) -> str:
    """A page for reading a trace by hand: every plane and line, with its
    span, its number of events and its most frequent names."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    rows = []
    for plane in data.planes:
        rows.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            if not evs:
                continue
            t0 = min(e.start_ns for e in evs)
            t1 = max(e.start_ns + e.duration_ns for e in evs)
            total: Dict[str, float] = {}
            for e in evs:
                total[e.name] = total.get(e.name, 0.0) + e.duration_ns
            rows.append(f"  line {line.name!r}: {len(evs)} events, "
                        f"{(t1 - t0) / 1e6:.1f} ms span from {t0 / 1e6:.1f}")
            for name, ns in sorted(total.items(), key=lambda kv: -kv[1])[:top]:
                n = sum(1 for e in evs if e.name == name)
                rows.append(f"    {ns / 1e6:10.3f} ms  x{n:<6d} {name[:110]}")
    return "\n".join(rows)


# -- arithmetic on event lists ---------------------------------------------------

def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def busy_seconds(ops: Sequence[Event], window: Tuple[float, float]) -> float:
    """Seconds of the window in which an operation ran."""
    return total(clip(union((s, e) for _, s, e in ops), *window)) / 1e9


def step_module(modules: Sequence[Event]) -> str:
    """The compiled program that took most device time: the train step."""
    spent: Dict[str, float] = {}
    for name, s, e in modules:
        spent[name] = spent.get(name, 0.0) + (e - s)
    if not spent:
        raise ValueError("no compiled program ran on the device in the trace")
    return max(spent, key=spent.get)


def whole_runs(modules: Sequence[Event], window: Tuple[float, float]
               ) -> List[Tuple[float, float]]:
    """The whole executions of the step program inside the window."""
    name = step_module(modules)
    lo, hi = window
    return [(s, e) for n, s, e in modules if n == name and s >= lo and e <= hi]


def device_ms_per_step(ops: Sequence[Event], modules: Sequence[Event],
                       window: Tuple[float, float]) -> Tuple[float, int]:
    """(device-busy milliseconds a step, steps counted): the operations'
    busy time inside each whole execution of the step program in the
    window, over their number."""
    runs = whole_runs(modules, window)
    if not runs:
        raise ValueError(f"no whole execution of {step_module(modules)!r} "
                         f"in the window")
    busy = union((s, e) for _, s, e in ops)
    spent = sum(total(clip(busy, s, e)) for s, e in runs)
    return spent / len(runs) / 1e6, len(runs)


def kernel_per_step(ops: Sequence[Event], modules: Sequence[Event],
                    window: Tuple[float, float], patterns: Sequence[str]
                    ) -> Tuple[float, float]:
    """(device seconds a step, events a step) of the operations whose name
    matches any of the patterns, over the whole executions of the step
    program in the window."""
    runs = whole_runs(modules, window)
    regs = [re.compile(p) for p in patterns]
    hits = [(s, e) for n, s, e in ops if any(r.search(n) for r in regs)]
    inside = [(s, e) for s, e in hits
              if any(a <= s and e <= b for a, b in runs)]
    if not runs or not inside:
        return 0.0, 0.0
    return (sum(e - s for s, e in inside) / len(runs) / 1e9,
            len(inside) / len(runs))


def top_ops(ops: Sequence[Event], n: int = 10) -> List[List]:
    """The operations that took most device time, under the names the trace
    prints (the head of the HLO line).  A loop's event spans its body's
    events, so the two are both listed."""
    spent: Dict[str, float] = {}
    for name, s, e in ops:
        spent[name] = spent.get(name, 0.0) + (e - s)
    return [[name[:160], ns / 1e9]
            for name, ns in sorted(spent.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(ops: Sequence[Event], host: Sequence[Event],
              window: Tuple[float, float], n: int = 10,
              shortest_ns: float = 20e3, most: int = 200) -> List[List]:
    """Idle time of the device by what the host was doing: every gap
    between operations (longer than ``shortest_ns``) goes to the host span
    that covers most of it — the benchmark's and the program's annotations
    before the runtime's own — or to ``unattributed``; only the ``most``
    longest gaps are looked up, the rest are summed under one name.  Returns the ``n``
    names with most idle seconds, [[name, seconds], ...]."""
    lo, hi = window
    busy = clip(union((s, e) for _, s, e in ops), lo, hi)
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = sorted(((edges[i], edges[i + 1])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] - edges[i] > shortest_ns),
                  key=lambda g: g[0] - g[1])
    host = [ev for ev in host if ev[2] > lo and ev[1] < hi
            and ev[2] - ev[1] >= shortest_ns]
    told = [ev for ev in host if ev[0].startswith(HOST_PREFIXES)]
    spent: Dict[str, float] = {}
    if len(gaps) > most:
        spent[f"{len(gaps) - most} shorter gaps"] = sum(
            b - a for a, b in gaps[most:])
    for a, b in gaps[:most]:
        best, cover = "unattributed", 0.0
        for pool in (told, host):
            for name, s, e in pool:
                c = min(b, e) - max(a, s)
                if c > cover:
                    best, cover = name, c
            if cover >= 0.5 * (b - a):
                break
        spent[best] = spent.get(best, 0.0) + (b - a)
    return [[name, ns / 1e9]
            for name, ns in sorted(spent.items(), key=lambda kv: -kv[1])[:n]]


# -- the whole reduction -----------------------------------------------------------

def reduce_planes(planes: Dict[str, Dict[str, List[Event]]], chips: int,
                  kernels: Dict[str, Sequence[str]]) -> dict:
    devices = sorted(p for p in planes if DEVICE_PLANE.match(p))
    if len(devices) < chips:
        raise ValueError(f"the trace holds {len(devices)} device plane(s), "
                         f"the cell runs on {chips}")
    devices = [d for d in devices if planes[d].get(OPS_LINE)][:chips]
    if not devices:
        raise ValueError("no operation ran on a device in the trace")
    host = [ev for line in planes.get(HOST_PLANE, {}).values() for ev in line]
    # the traced window: from the first to the last thing any chip did
    lo = min(s for d in devices for _, s, _ in planes[d][OPS_LINE])
    hi = max(e for d in devices for _, _, e in planes[d][OPS_LINE])
    window = (lo, hi)
    busy = [busy_seconds(planes[d][OPS_LINE], window) for d in devices]
    steps = [device_ms_per_step(planes[d][OPS_LINE],
                                planes[d].get(MODULES_LINE, []), window)
             for d in devices]
    first = planes[devices[0]]
    out = {"busy_s": sum(busy) / len(busy), "window_s": (hi - lo) / 1e9,
           "device_ms_per_step": sum(s[0] for s in steps) / len(steps),
           "steps_traced": steps[0][1],
           "step_program": step_module(first.get(MODULES_LINE, [])),
           "device_ops": top_ops(first[OPS_LINE]),
           "idle_gaps": idle_gaps(first[OPS_LINE], host, window),
           "kernels": {}}
    for kernel, patterns in kernels.items():
        per = [kernel_per_step(planes[d][OPS_LINE],
                               planes[d].get(MODULES_LINE, []), window,
                               patterns) for d in devices]
        if per[0][1]:
            out["kernels"][kernel] = {
                "seconds_per_step": sum(p[0] for p in per) / len(per),
                "events_per_step": per[0][1]}
    return out


def reduce_dir(trace_dir: str, chips: int, kernels: Dict[str, Sequence[str]],
               log=print) -> dict:
    path = find_xplane(trace_dir)
    log(f"[bench] trace: {path} ({os.path.getsize(path) / 1e6:.1f} MB)")
    out = reduce_planes(load(path), chips, kernels)
    log(f"[bench] trace: window {out['window_s']:.3f} s, busy "
        f"{out['busy_s']:.3f} s, {out['steps_traced']} whole steps of "
        f"{out['step_program']!r} at {out['device_ms_per_step']:.3f} ms")
    return out
