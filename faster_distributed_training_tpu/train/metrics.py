"""Metric accumulation.

The reference accumulates loss/correct/total on device and all-reduces
at epoch end (resnet50_test.py:550-558,616-619).  Here per-step metrics
are already global (jit over the sharded batch psums them), so the
accumulator only sums device scalars and converts once per epoch —
one host sync per epoch, not per batch."""

from __future__ import annotations

import math
from typing import Dict, Iterable, List

import jax
import numpy as np

Metrics = Dict[str, jax.Array]


def percentiles(values: Iterable[float],
                qs: Iterable[int] = (50, 95, 99)) -> Dict[int, float]:
    """Nearest-rank percentiles of host floats — {q: value}, {} when
    empty.  Shared by the telemetry aggregation (per-step p50/p95/p99,
    telemetry/aggregate.py) and scripts/telemetry_report.py so the two
    can never disagree on the definition.  Nearest-rank (not
    interpolated): a reported p99 is a step time that actually
    happened, which is what straggler forensics wants."""
    vals = sorted(float(v) for v in values)
    if not vals:
        return {}
    out = {}
    for q in qs:
        idx = max(0, min(len(vals) - 1,
                         math.ceil(q / 100.0 * len(vals)) - 1))
        out[int(q)] = round(vals[idx], 3)
    return out


def perplexity(loss: float, cap: float = 30.0) -> float:
    """exp of a per-token cross-entropy — the LM workload's headline
    metric (--task lm).  The exponent is capped so an early-training /
    diverged loss reports a large finite ppl instead of overflowing to
    inf (exp(30) ~ 1e13 — unambiguous, still orderable)."""
    return float(math.exp(min(float(loss), cap)))


class MetricAccumulator:
    def __init__(self):
        self._sums: Dict[str, List[jax.Array]] = {}

    def add(self, metrics: Metrics) -> None:
        for k, v in metrics.items():
            if k != "counters":    # the dispatch clock's (train/loop.py)
                self._sums.setdefault(k, []).append(v)

    def summary(self) -> Dict[str, float]:
        """One device->host sync for the whole epoch."""
        out = {}
        vals = {k: np.asarray(jax.device_get(v)) for k, v in self._sums.items()}
        n_steps = max(len(v) for v in vals.values()) if vals else 0
        for k, arr in vals.items():
            out[k + "_sum"] = float(arr.sum())
        if "loss_total" in vals and "total" in vals and vals["total"].sum():
            # exact sample-weighted loss — correct even when the final
            # (padded) eval batch holds fewer valid samples than the rest
            out["loss"] = float(vals["loss_total"].sum()
                                / vals["total"].sum())
        elif "loss" in vals and n_steps:
            out["loss"] = float(vals["loss"].mean())
        if "correct" in vals and "total" in vals:
            total = float(vals["total"].sum())
            out["accuracy"] = (float(vals["correct"].sum()) / total
                               if total else 0.0)
        return out

    def last(self) -> Metrics:
        return {k: v[-1] for k, v in self._sums.items()}

    def reset(self) -> None:
        self._sums.clear()


def attach_goodput(summary: Dict[str, float], tracker) -> Dict[str, float]:
    """Merge a GoodputTracker snapshot into an epoch/run summary dict
    under ``goodput_``-prefixed keys (resilience/goodput.py) — the
    resilience subsystem's metrics ride the same summary surface as
    loss/accuracy instead of a side channel.  No-op on tracker=None."""
    if tracker is None:
        return summary
    for k, v in tracker.summary().items():
        summary[f"goodput_{k}" if not k.startswith("goodput") else k] = v
    return summary


def format_goodput(tracker) -> str:
    """One log line: `96.2% goodput (ckpt 0.8s block, 2 saves, 1 restore)`
    — the Trainer's per-epoch [goodput] observability."""
    s = tracker.summary()
    bits = [f"{s['goodput_pct']:.1f}% goodput over {s['wall_s']:.1f}s"]
    if s.get("checkpoint_blocking_s"):
        bits.append(f"ckpt block {s['checkpoint_blocking_s']:.2f}s")
    if s.get("emergency_save_s"):
        bits.append(f"emergency save {s['emergency_save_s']:.2f}s")
    if s.get("restore_s"):
        bits.append(f"restore {s['restore_s']:.2f}s")
    if s.get("restart_backoff_s"):
        bits.append(f"backoff {s['restart_backoff_s']:.2f}s")
    if s.get("detect_s"):
        bits.append(f"detect {s['detect_s']:.2f}s")
    if s.get("restart_mttr_s"):
        # detect + backoff + restore per restart — the pod-coordinated
        # recovery headline (resilience/coordinator.py)
        bits.append(f"mttr {s['restart_mttr_s']:.2f}s/restart")
    if s.get("readmission_hold_s"):
        # r14 elastic recovery: survivor parked time while a failed
        # slice restarted and rejoined (the hold component of a
        # slice restart's MTTR)
        bits.append(f"readmit hold {s['readmission_hold_s']:.2f}s")
    counts = ", ".join(f"{int(s[k])} {k.rstrip('s') if s[k] == 1 else k}"
                       for k in ("saves", "skipped_saves", "restores",
                                 "restarts", "preemptions", "peer_failures",
                                 "step_timeouts", "restart_generations",
                                 "slice_readmissions",
                                 "pod_fallback_restarts",
                                 "skipped_steps", "rollbacks",
                                 "quarantined_batches",
                                 "quarantined_shards")
                       if s.get(k))
    if counts:
        bits.append(counts)
    return "; ".join(bits)
