"""An operator's reader for the profiler trace ``--profile_steps A:B``
captures (``<telemetry_dir>/trace_steps_A_B``): where the step's device
time goes, by the program's own ``fdt/*`` vocabulary (telemetry/spans.py).

Three tables, from one ``*.xplane.pb`` read with ``jax.profiler.ProfileData``
and nothing else:

  * device milliseconds per step by SCOPE: every instant of a whole
    execution of the step program goes to the innermost operation running
    then, and the operation to the scope its ``op_name`` carries
    (:func:`scope_of`; forward = ``jvp(fdt/model)``, backward = its
    ``transpose``, of which a scope entered inside it has a row of its own;
    an operation without a scope inherits the loop or
    conditional around it, and ``unscoped`` is the rest);
  * device milliseconds per step by Pallas KERNEL: the operations whose
    ``op_name`` or name carries an ``fdt_<kernel>`` name;
  * host seconds by ``fdt/*`` PHASE and span, on the threads that wrote
    them.

What a TPU v5e trace of this program holds (read by hand, PERF.md section
5): one plane a chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` has one
event per executed HLO operation — a loop's or a conditional's event
spans its body's — and whose line ``XLA Modules`` has one event per
execution of a compiled program; the plane ``/host:CPU`` with one line
per host thread.  The operation's ``op_name`` (the ``jax.named_scope``
path: it lives in the HLO's metadata, never in the HLO text the event is
named by) is the stat ``tf_op`` — not of the event but of the event's
METADATA record, which ``ProfileData`` does not hand out; :func:`op_names`
reads that one table from the file's protobuf wire format itself
(``XSpace.planes[].event_metadata[].stats``), nothing imported for it.

The scopes are metadata of the COMPILED program: an executable served by
a compile cache that a build without the scopes warmed carries none, and
everything reads ``unscoped`` — take the trace with a fresh
``JAX_COMPILATION_CACHE_DIR``.

The functions below the loader work on plain tuples so that they can be
tested on a small recorded fixture (tests/fixtures/trace_report/).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Op = Tuple[str, float, float, str]        # name, start_ns, end_ns, op_name
Span = Tuple[str, float, float]           # name, start_ns, end_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# the event-metadata stat that carries the operation's op_name on this
# runtime (jaxlib 0.9.0, TPU v5e)
OP_NAME_STAT = "tf_op"
UNSCOPED = "unscoped"
FORWARD = "jvp(fdt/model)"
BACKWARD = "transpose(jvp(fdt/model))"
_SCOPE = re.compile(r"fdt/[a-z0-9_]+")
_KERNEL = re.compile(r"fdt_[a-z0-9_]+")


# -- loading ---------------------------------------------------------------

def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return files[-1]


def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    """(value, next index) of the varint at ``buf[i]``."""
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return val, i


def _fields(buf: memoryview):
    """(field number, wire type, value) of one protobuf message: varints
    as ints, length-delimited fields as views, fixed-width ones skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield field, wire, val
        elif wire == 2:
            size, i = _varint(buf, i)
            yield field, wire, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire} in an xplane file")


def _map_entry(buf: memoryview):
    return next((v for f, w, v in _fields(buf) if f == 2 and w == 2), None)


def op_names(path: str) -> Dict[str, str]:
    """{event name: op_name} of the chips' planes: the ``tf_op`` stat of
    each event-metadata record, read from the wire format (field numbers of
    tsl/profiler/protobuf/xplane.proto: XSpace.planes = 1; XPlane.name = 2,
    event_metadata = 4, stat_metadata = 5; XEventMetadata.name = 2,
    stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1,
    str_value = 5, ref_value = 7)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, str] = {}
    for field, wire, plane in _fields(space):
        if field != 1 or wire != 2:
            continue
        name, events, stat_names = "", [], {}
        for f, w, v in _fields(plane):
            if f == 2 and w == 2:
                name = bytes(v).decode()
            elif f == 4 and w == 2:
                events.append(_map_entry(v))
            elif f == 5 and w == 2:
                key = next((x for g, t, x in _fields(v) if g == 1), 0)
                meta = _map_entry(v)
                stat_names[key] = next(
                    (bytes(x).decode() for g, t, x in _fields(meta)
                     if g == 2 and t == 2), "") if meta is not None else ""
        if not DEVICE_PLANE.match(name):
            continue
        wanted = {k for k, n in stat_names.items() if n == OP_NAME_STAT}
        for meta in events:
            if meta is None:
                continue
            ev_name, op_name = "", ""
            for f, w, v in _fields(meta):
                if f == 2 and w == 2:
                    ev_name = bytes(v).decode()
                elif f == 5 and w == 2:
                    stat = {g: x for g, t, x in _fields(v)}
                    if stat.get(1) in wanted:
                        if 5 in stat:
                            op_name = bytes(stat[5]).decode()
                        elif 7 in stat:
                            op_name = stat_names.get(stat[7], "")
            if op_name:
                out[ev_name] = op_name
    return out


def load(path: str) -> dict:
    """``{"ops": [Op], "modules": [Span], "host": [Span]}`` of the first
    chip that ran anything and of the host's threads."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    names = op_names(path)
    out: dict = {"ops": [], "modules": [], "host": []}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name) and not out["ops"]:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        out["ops"].append(
                            (ev.name, float(ev.start_ns),
                             float(ev.start_ns + ev.duration_ns),
                             names.get(ev.name, "")))
                elif line.name == MODULES_LINE:
                    out["modules"] = [
                        (ev.name, float(ev.start_ns),
                         float(ev.start_ns + ev.duration_ns))
                        for ev in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out["host"].extend(
                    (ev.name, float(ev.start_ns),
                     float(ev.start_ns + ev.duration_ns))
                    for ev in line.events if ev.name.startswith("fdt/"))
    return out


# -- classification --------------------------------------------------------

def scope_of(op_name: str) -> Optional[str]:
    """The scope an ``op_name`` belongs to, most specific first; None when
    it carries none.  A transform (``jvp``, ``transpose``, ``vmap``,
    ``cond``) wraps one path element, so scopes match as substrings."""
    if "fisher_update" in op_name:
        return "fdt/optimizer/ngd/fisher_update"
    if "fdt/optimizer/ngd" in op_name:
        return "fdt/optimizer/ngd"
    for outer in (BACKWARD, FORWARD):      # BACKWARD first: it holds FORWARD
        if outer in op_name:
            # a scope entered inside the model's backward or forward
            # (ops/conv_bn.py's fdt/conv1x1_bn_bwd, fdt/conv1x1_bn_stats)
            # is a part of it, shown apart
            inner = _SCOPE.search(op_name.split(outer, 1)[1])
            return f"{outer}/{inner.group(0)}" if inner else outer
    m = _SCOPE.search(op_name)
    return m.group(0) if m else None


def kernel_of(name: str, op_name: str = "") -> Optional[str]:
    """The ``fdt_*`` Pallas kernel an operation is, or None.  The kernel's
    ``name=`` becomes one element of the op_name
    (``jit(step)/jvp(fdt_flash_fwd_lse)/pallas_call``) and, mangled, the
    custom call's own name (``%jvp_fdt_flash_fwd_lse_.1``: read on a
    v5e compile of the flash kernels, PERF.md section 7)."""
    m = _KERNEL.search(op_name) or _KERNEL.search(name)
    return m.group(0).rstrip("_") if m else None


# -- arithmetic ------------------------------------------------------------

def step_runs(modules: Sequence[Span]
              ) -> Tuple[str, List[Tuple[float, float]]]:
    """(the compiled program that took most device time — the train step —
    and its executions in the trace, in order)."""
    spent: Dict[str, float] = {}
    for name, s, e in modules:
        spent[name] = spent.get(name, 0.0) + (e - s)
    if not spent:
        raise ValueError("no compiled program ran on the device in the trace")
    step = max(spent, key=spent.get)
    return step, sorted((s, e) for n, s, e in modules if n == step)


def attribute(events: Iterable[Tuple[float, float, Optional[str]]]
              ) -> Dict[str, float]:
    """Nanoseconds by class: every instant goes to the innermost event
    covering it (events nest: a loop's spans its body's); an event whose
    class is None takes its parent's, or ``unscoped`` at the top."""
    out: Dict[str, float] = {}
    stack: List[Tuple[float, str]] = []      # (end, class), innermost last
    cursor = 0.0

    def spend(upto: float) -> None:
        nonlocal cursor
        if stack and upto > cursor:
            cls = stack[-1][1]
            out[cls] = out.get(cls, 0.0) + (upto - cursor)
        cursor = max(cursor, upto)

    for s, e, cls in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            spend(stack[-1][0])
            stack.pop()
        spend(s)
        stack.append((e, cls or (stack[-1][1] if stack else UNSCOPED)))
    while stack:
        spend(stack[-1][0])
        stack.pop()
    return out


def _inside(ops: Sequence[Op], runs: Sequence[Tuple[float, float]]
            ) -> List[Op]:
    """The operations that ran inside one of the (sorted, disjoint) runs."""
    starts = [s for s, _ in runs]
    out = []
    for op in ops:
        i = bisect.bisect_right(starts, op[1]) - 1
        if i >= 0 and op[2] <= runs[i][1]:
            out.append(op)
    return out


def by_scope(ops: Sequence[Op], runs: Sequence[Tuple[float, float]]
             ) -> List[Dict[str, float]]:
    """Device milliseconds by scope, one dict per whole execution of the
    step program; a dict's values sum to that step's device-busy time."""
    starts = [s for s, _ in runs]
    per_run: List[list] = [[] for _ in runs]
    for _, s, e, op_name in _inside(ops, runs):
        per_run[bisect.bisect_right(starts, s) - 1].append(
            (s, e, scope_of(op_name)))
    return [{k: v / 1e6 for k, v in attribute(evs).items()}
            for evs in per_run]


def by_kernel(ops: Sequence[Op], runs: Sequence[Tuple[float, float]]
              ) -> Dict[str, dict]:
    """{kernel: {"ms_per_step", "calls_per_step"}} of the ``fdt_*`` kernels."""
    out: Dict[str, dict] = {}
    for name, s, e, op_name in _inside(ops, runs):
        kernel = kernel_of(name, op_name)
        if kernel is None:
            continue
        k = out.setdefault(kernel, {"ms_per_step": 0.0, "calls_per_step": 0})
        k["ms_per_step"] += (e - s) / len(runs) / 1e6
        k["calls_per_step"] += 1.0 / len(runs)
    return out


def host_phases(host: Sequence[Span]) -> Dict[str, dict]:
    """{fdt/<name>: {"seconds", "count", "mean_ms"}} of the host's spans."""
    out: Dict[str, dict] = {}
    for name, s, e in host:
        p = out.setdefault(name, {"seconds": 0.0, "count": 0})
        p["seconds"] += (e - s) / 1e9
        p["count"] += 1
    for p in out.values():
        p["mean_ms"] = 1e3 * p["seconds"] / p["count"]
    return out


# -- the whole report ------------------------------------------------------

def summarize(planes: dict) -> dict:
    step, runs = step_runs(planes["modules"])
    # an execution counts where operations were recorded from its start to
    # its end; the first and the last may still be cut by the trace's edges
    # (a cut one starts with the first recorded operation, as a whole one
    # does), so they are left out where three or more are there
    lo = min((s for _, s, _, _ in planes["ops"]), default=0.0)
    hi = max((e for _, _, e, _ in planes["ops"]), default=0.0)
    runs = [(s, e) for s, e in runs if s >= lo and e <= hi]
    if len(runs) >= 3:
        runs = runs[1:-1]
    if not runs:
        raise ValueError(f"no whole execution of {step!r} in the trace")
    ops = planes["ops"]
    per_step = by_scope(ops, runs)
    scopes: Dict[str, float] = {}
    for one in per_step:
        for k, v in one.items():
            scopes[k] = scopes.get(k, 0.0) + v / len(runs)
    lengths = sorted(e - s for s, e in runs)
    return {"step_program": step, "steps": len(runs),
            "step_ms_min": lengths[0] / 1e6,
            "step_ms_median": lengths[len(lengths) // 2] / 1e6,
            "step_ms_max": lengths[-1] / 1e6,
            "device_ms_per_step": sum(scopes.values()),
            # each step's length beside its Fisher refresh: the refresh
            # runs every update_period-th step, and is that step's excess
            "per_step": [
                {"ms": (e - s) / 1e6,
                 "fisher_update_ms": one.get(
                     "fdt/optimizer/ngd/fisher_update", 0.0)}
                for (s, e), one in zip(runs, per_step)],
            "named_ops_share": (sum(1 for op in ops if op[3])
                                / max(len(ops), 1)),
            "scopes": scopes, "kernels": by_kernel(ops, runs),
            "host": host_phases(planes["host"])}


def report(trace_dir: str) -> dict:
    path = find_xplane(trace_dir)
    return dict(summarize(load(path)), path=path,
                size_mb=os.path.getsize(path) / 1e6)


def render(rep: dict) -> str:
    total = rep["device_ms_per_step"]
    rows = [f"trace report: {rep.get('path', '?')} "
            f"({rep.get('size_mb', 0.0):.1f} MB)",
            f"  {rep['steps']} whole steps of {rep['step_program']!r}: "
            f"min {rep['step_ms_min']:.2f} / median "
            f"{rep['step_ms_median']:.2f} / max {rep['step_ms_max']:.2f} ms; "
            f"{100 * rep['named_ops_share']:.1f}% of the operations carry "
            f"an op_name",
            f"device ms per step by scope (sum {total:.3f}):"]
    for name, ms in sorted(rep["scopes"].items(), key=lambda kv: -kv[1]):
        rows.append(f"  {name:<36} {ms:>9.3f} ms  "
                    f"{100 * ms / max(total, 1e-12):>5.1f}%")
    rows.append("each step's ms / of which fisher_update: " + "  ".join(
        f"{p['ms']:.1f}/{p['fisher_update_ms']:.1f}"
        for p in rep["per_step"]))
    if rep["kernels"]:
        rows.append("device ms per step by Pallas kernel:")
        for name, k in sorted(rep["kernels"].items(),
                              key=lambda kv: -kv[1]["ms_per_step"]):
            rows.append(f"  {name:<36} {k['ms_per_step']:>9.3f} ms  "
                        f"x{k['calls_per_step']:.1f} a step")
    if rep["host"]:
        rows.append("host seconds by fdt/* phase:")
        for name, p in sorted(rep["host"].items(),
                              key=lambda kv: -kv[1]["seconds"]):
            rows.append(f"  {name:<36} {p['seconds']:>9.4f} s   "
                        f"x{p['count']:<5d} mean {p['mean_ms']:.3f} ms")
    return "\n".join(rows)
