"""Summarize a run's telemetry directory (r12 observability satellite).

Reads the run manifest + every ``host_<pi>.jsonl`` the run emitted
(telemetry/recorder.py) and prints the run's story in one screen:

  * manifest header (workload, mesh, device kind, jax/jaxlib versions);
  * per-host and pod step-time percentiles (p50/p95/p99 of per-step
    dispatch time, compile records excluded — the same definition as the
    in-run ``[telemetry]`` epoch line, telemetry/aggregate.py);
  * the straggler table (hosts whose p95 exceeds the configured ratio
    of the pod median host-p95);
  * the throughput curve (per-epoch examples/s + loss from the epoch
    events);
  * the span breakdown (count/total/mean per span name: checkpoint
    snapshot/commit, restore, rendezvous, eval, H2D upload, epoch
    re-shard, first-dispatch compile);
  * the final goodput/MTTR snapshot riding the same stream;
  * (r15) the compile observatory: per-program compile ms, persistent-
    cache verdict, HLO fingerprint and memory_analysis bytes, plus any
    RETRACE detections;
  * (r15) HBM attribution: the per-chip params/opt_state/batch_stats
    byte table, per-epoch device watermarks, sharding-drift detections;
  * (r15, ``--flight``) crash flight dumps: the failing host's reason/
    exception, the spans open at death, the in-memory record ring and
    the goodput snapshot (telemetry/flight.py);
  * (``--trace <dir>``) the profiler trace ``--profile_steps A:B``
    captured (``<telemetry_dir>/trace_steps_A_B``): device ms per step by
    ``fdt/*`` scope (forward, backward, augment, NGD, its Fisher
    refresh, ``unscoped``), by ``fdt_*`` Pallas kernel, and host seconds
    by ``fdt/*`` phase (telemetry/trace_report.py).  Alone, or after the
    directory's report.

Run:  python scripts/telemetry_report.py <telemetry_dir>
          [--straggler_ratio 2.0] [--json] [--flight] [--trace <dir>]
      python scripts/telemetry_report.py --trace <trace_dir>

Smoke-tested (tier-1, milliseconds) against the recorded fixture
``tests/fixtures/telemetry/`` by tests/test_telemetry.py.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(directory: str, straggler_ratio: float = 2.0,
        with_flight: bool = False) -> dict:
    """The report as a dict (main() renders it; tests assert on it)."""
    from faster_distributed_training_tpu.telemetry import (MANIFEST,
                                                           aggregate_run,
                                                           read_host_records,
                                                           span_breakdown)

    report: dict = {"directory": os.path.abspath(directory)}
    man_path = os.path.join(directory, MANIFEST)
    if os.path.exists(man_path):
        try:
            with open(man_path) as f:
                report["manifest"] = json.load(f)
        except (OSError, ValueError) as e:
            report["manifest_error"] = repr(e)
    report["summary"] = aggregate_run(directory,
                                      straggler_ratio=straggler_ratio)
    hosts = read_host_records(directory)
    # throughput curve + goodput from host 0's stream (metrics are
    # already pod-global: the jitted step psums them, so every host's
    # epoch events agree — train/metrics.py)
    lead = hosts.get(0) or (hosts[min(hosts)] if hosts else [])
    report["throughput_curve"] = [
        {k: r[k] for k in ("epoch", "steps", "trained_steps", "wall_s",
                           "ex_s", "loss", "accuracy", "eval_loss",
                           "eval_accuracy", "peak_mem_bytes") if k in r}
        for r in lead if r.get("kind") == "epoch"]
    goodputs = [r for r in lead if r.get("kind") == "goodput"]
    if goodputs:
        report["goodput"] = {k: v for k, v in goodputs[-1].items()
                             if k != "kind"}
    all_recs: list = []
    for recs in hosts.values():
        all_recs.extend(recs)
    report["spans"] = span_breakdown(all_recs)
    # compile observatory (r15): per-program compile ms / fingerprint /
    # cache verdict / memory bytes from host 0's program events (each
    # host compiles its own copy; the manifest carries the same table
    # under "compile" when the run closed cleanly), retraces pooled
    # across hosts — a retrace anywhere is worth a line
    progs = [r for r in lead if r.get("kind") == "program"]
    if progs:
        report["programs"] = progs
    retraces = [r for r in all_recs if r.get("kind") == "retrace"]
    if retraces:
        report["retraces"] = retraces
    # HBM attribution: the state byte table (scope "state" — the newest
    # one; a re-anchor after drift replaces it), per-epoch watermarks,
    # and any sharding-drift detections
    mem = [r for r in lead if r.get("kind") == "memory"]
    states = [r for r in mem if r.get("scope") == "state"]
    if states:
        report["state_memory"] = states[-1]
    marks = [r for r in mem if r.get("scope") == "epoch"]
    if marks:
        report["memory_watermarks"] = marks
    drifts = [r for r in all_recs if r.get("kind") == "memory"
              and r.get("scope") == "sharding_drift"]
    if drifts:
        report["sharding_drifts"] = drifts
    if with_flight:
        from faster_distributed_training_tpu.telemetry.flight import (
            read_flights)
        report["flights"] = [
            {"path": p, **payload} for p, payload in
            read_flights(directory)]
    dropped = sum(r.get("dropped_records", 0) for r in all_recs
                  if r.get("kind") == "flush_stats")
    if dropped:
        report["dropped_records"] = dropped
    return report


def _fmt_pct_row(tag: str, st: dict) -> str:
    return (f"  {tag:<8} p50={st.get('step_ms_p50', 0):>8.2f}ms "
            f"p95={st.get('step_ms_p95', 0):>8.2f}ms "
            f"p99={st.get('step_ms_p99', 0):>8.2f}ms "
            f"({st.get('steps', 0)} steps)")


def render(report: dict) -> str:
    lines = [f"telemetry report: {report['directory']}"]
    man = report.get("manifest")
    if man:
        mesh = man.get("mesh")
        lines.append(
            f"  run: {man.get('workload', '?')} on "
            f"{man.get('device_count', '?')}x "
            f"{man.get('device_kind', '?')} ({man.get('backend', '?')}), "
            f"mesh={mesh}, jax {man.get('jax_version', '?')} / jaxlib "
            f"{man.get('jaxlib_version', '?')}")
    s = report.get("summary", {})
    pod = s.get("pod")
    if pod:
        fenced = {st.get("step_time_source")
                  for st in s.get("hosts", {}).values()} == {"fenced"}
        lines.append("step-time percentiles (fence_ms / fence_steps per "
                     "--log_every window):" if fenced else
                     "step-time percentiles (fenced windows where "
                     "recorded, else dispatch_ms / K = the ENQUEUE; "
                     "compile excluded):")
        lines.append(_fmt_pct_row("pod", pod))
        # numeric sort: aggregate_run stringifies host keys, and a
        # lexicographic sort would list host 10 before host 2
        for pi, st in sorted(s.get("hosts", {}).items(),
                             key=lambda kv: int(kv[0])):
            lines.append(_fmt_pct_row(f"host {pi}", st))
    if s.get("stragglers"):
        lines.append(f"stragglers (p95 > "
                     f"{s.get('straggler_ratio', 2.0):.1f}x pod median "
                     f"host-p95 {s.get('pod_median_host_p95_ms', 0):.2f}"
                     f"ms):")
        for st in s["stragglers"]:
            lines.append(f"  host {st['host']}: "
                         f"p95={st['step_ms_p95']:.2f}ms "
                         f"({st['ratio']:.2f}x)")
    elif s.get("host_count", 0) > 1:
        lines.append("stragglers: none")
    curve = report.get("throughput_curve")
    if curve:
        lines.append("throughput curve:")
        for e in curve:
            bits = [f"  epoch {e.get('epoch')}:"]
            if "ex_s" in e:
                bits.append(f"{e['ex_s']:.0f} ex/s")
            if "loss" in e:
                bits.append(f"loss={e['loss']:.4f}")
            if "eval_accuracy" in e:
                bits.append(f"eval_acc={e['eval_accuracy']:.4f}")
            if "peak_mem_bytes" in e:
                bits.append(f"peak_mem={e['peak_mem_bytes'] / 1e6:.0f}MB")
            lines.append(" ".join(bits))
    sp = report.get("spans")
    if sp:
        lines.append("span breakdown (all hosts):")
        for name, st in sorted(sp.items(),
                               key=lambda kv: -kv[1]["total_ms"]):
            lines.append(f"  {name:<24} x{st['count']:<4} "
                         f"total={st['total_ms']:>10.1f}ms "
                         f"mean={st['mean_ms']:>8.1f}ms")
    progs = report.get("programs")
    if progs:
        lines.append("compiled programs (host 0; compile ms / source / "
                     "cache / HLO fingerprint / temp bytes):")
        for p in progs:
            lines.append(
                f"  {p.get('name', '?'):<24} "
                f"compile={p.get('compile_ms', 0):>8.1f}ms "
                # r17: which tier served the executable (deserialized =
                # the persistent executable cache; compile_ms is then
                # the deserialize time)
                f"src={p.get('cache_source', '?'):<14} "
                f"cache={p.get('cache', '?'):<15} "
                f"hlo={p.get('fingerprint', '')[:12]:<12} "
                f"temp={p.get('temp_bytes', 0) / 1e6:>8.1f}MB")
    for r in report.get("retraces", ()):
        lines.append(f"RETRACE: program {r.get('name')!r} lowered "
                     f"{r.get('lowerings')}x ({r.get('reason')}) — "
                     f"avals [{r.get('avals')}] vs [{r.get('prev_avals')}]")
    sm = report.get("state_memory")
    if sm:
        lines.append(
            f"train-state HBM per chip: "
            f"params={sm.get('params_bytes_per_chip', 0) / 1e6:.1f}MB "
            f"opt_state={sm.get('opt_state_bytes_per_chip', 0) / 1e6:.1f}MB"
            f" batch_stats="
            f"{sm.get('batch_stats_bytes_per_chip', 0) / 1e6:.1f}MB "
            f"(total {sm.get('total_bytes_per_chip', 0) / 1e6:.1f}MB)")
        for leaf in sm.get("top_leaves", ())[:3]:
            lines.append(f"  top leaf: {leaf.get('path')} "
                         f"{leaf.get('bytes_per_chip', 0) / 1e6:.1f}MB")
    for d in report.get("sharding_drifts", ()):
        lines.append(f"SHARDING DRIFT at epoch {d.get('epoch')}: "
                     f"{d.get('expected')} -> {d.get('got')}"
                     + (f" leaves {d.get('changed_leaves')}"
                        if d.get("changed_leaves") else ""))
    flights = report.get("flights")
    if flights is not None:
        if not flights:
            lines.append("flight dumps: none")
        for fl in flights:
            exc = fl.get("exception") or {}
            lines.append(
                f"FLIGHT {os.path.basename(fl.get('path', '?'))}: "
                f"{fl.get('reason', '?')}"
                + (f" at step {fl['step']}" if "step" in fl else "")
                + (f" — {exc.get('type')}: {exc.get('message')}"
                   if exc else ""))
            for s in fl.get("active_spans", ()):
                lines.append(f"  open span: {s.get('name')} "
                             f"({s.get('elapsed_ms', 0):.0f}ms, "
                             f"{s.get('thread')})")
            ring = fl.get("recent_records", ())
            steps = [r for r in ring if r.get("kind") == "step"]
            if steps:
                lines.append(f"  ring: {len(ring)} records, last step "
                             f"{steps[-1].get('step')}")
            g = fl.get("goodput")
            if g:
                lines.append(f"  goodput at crash: "
                             f"{g.get('goodput_pct', '?')}% over "
                             f"{g.get('wall_s', '?')}s")
    g = report.get("goodput")
    if g:
        lines.append(f"goodput: {g.get('goodput_pct', '?')}% over "
                     f"{g.get('wall_s', '?')}s"
                     + (f", mttr {g['restart_mttr_s']}s/restart"
                        if g.get("restart_mttr_s") else ""))
    if report.get("dropped_records"):
        lines.append(f"WARNING: {report['dropped_records']} records "
                     f"dropped (writer backlog — see recorder.py)")
    return "\n".join(lines)


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("directory", nargs="?",
                    help="a run's telemetry directory "
                         "(<checkpoint_dir>/telemetry)")
    ap.add_argument("--trace", metavar="DIR",
                    help="a profiler trace directory (--profile_steps "
                         "writes <telemetry_dir>/trace_steps_A_B): device "
                         "ms per step by fdt/* scope and fdt_* kernel, "
                         "host seconds by fdt/* phase")
    ap.add_argument("--straggler_ratio", type=float, default=2.0)
    ap.add_argument("--json", action="store_true",
                    help="emit the raw report dict as JSON")
    ap.add_argument("--flight", action="store_true",
                    help="include crash flight dumps (telemetry/"
                         "flight.py): reason, exception, open spans, "
                         "the in-memory record ring, goodput at crash")
    args = ap.parse_args(argv)
    if args.directory is None and args.trace is None:
        ap.error("give a telemetry directory, --trace <dir>, or both")
    report: dict = {}
    if args.directory is not None:
        report = run(args.directory, straggler_ratio=args.straggler_ratio,
                     with_flight=args.flight)
    if args.trace is not None:
        from faster_distributed_training_tpu.telemetry import trace_report
        report["trace"] = trace_report.report(args.trace)
    if args.json:
        print(json.dumps(report, indent=1, default=str))
    else:
        if args.directory is not None:
            print(render(report))
        if args.trace is not None:
            print(trace_report.render(report["trace"]))
    return report


if __name__ == "__main__":
    main()
