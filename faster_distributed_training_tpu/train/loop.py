"""The training loop: epochs, eval, best-acc checkpointing, timing.

Re-design of train()/test() (resnet50_test.py:506-677,
transformer_test.py:205-347).  Differences by design:
  * one jitted step (steps.py) instead of per-batch Python;
  * loaders are *functions of the epoch* so every epoch reshuffles —
    fixing the missing DistributedSampler.set_epoch in the reference's
    ResNet DDP loop (SURVEY.md §5);
  * per-epoch wall time is fenced with block_until_ready (the
    reference's time.monotonic() pairs measured async CUDA dispatch);
  * checkpoints capture full state (train/checkpoint.py).
"""

from __future__ import annotations

import contextlib
import itertools
import time
from typing import Any, Callable, Dict, Iterable, List, Optional

import jax
import numpy as np

from faster_distributed_training_tpu.config import TrainConfig
from faster_distributed_training_tpu.data.loader import device_prefetch
# host_finite is THE repo-wide host-side finiteness definition (one
# non-finite vocabulary shared with the in-graph sentinel guard); it
# deliberately operates on ALREADY-FETCHED Python floats — using
# jax.numpy.isfinite here would accept a still-on-device scalar and add
# a blocking round-trip at the epoch boundary
from faster_distributed_training_tpu.resilience.sentinel import host_finite
from faster_distributed_training_tpu.telemetry import spans
from faster_distributed_training_tpu.train import checkpoint as ckpt
from faster_distributed_training_tpu.train.metrics import (MetricAccumulator,
                                                           format_goodput)
from faster_distributed_training_tpu.train.state import TrainState
from faster_distributed_training_tpu.train.steps import (
    make_eval_step, make_fused_train_step, make_train_step)
from faster_distributed_training_tpu.utils.profiling import (
    memory_watermarks, peak_memory_bytes)

LoaderFn = Callable[[int], Iterable[Dict[str, Any]]]


def _stack_host_batches(group: List[Dict[str, Any]]) -> Dict[str, Any]:
    """K host batches -> one dict with a leading K axis per leaf, ready
    for a single staged transfer into the fused dispatch.  Text batches
    bucketed to different widths within the group are right-padded to
    the group max (tokens/token_types/mask all pad with 0 = ignore)."""
    out = {}
    for key in group[0]:
        arrs = [np.asarray(b[key]) for b in group]
        if any(a.shape != arrs[0].shape for a in arrs):
            tgt = tuple(max(a.shape[d] for a in arrs)
                        for d in range(arrs[0].ndim))
            arrs = [np.pad(a, [(0, t - s) for s, t in zip(a.shape, tgt)])
                    for a in arrs]
        out[key] = np.stack(arrs)
    return out


class _DispatchClock:
    """The host side of ONE ``run_epoch`` call, shared by the four epoch
    loops: the iteration's clock reads, its ``fdt/*`` trace phases
    (telemetry/spans.py — siblings that tile the iteration, none nested
    in another, all tagged with the iteration's ``step``), the
    ``--log_every`` window and the step record.  An iteration is

        clock.start(...)             # top of the iteration
        ...the data phase...         # device_prefetch annotates itself;
                                     # the other loops use clock.phase()
        with clock.dispatch(kk):     # one block a segment
            state, metrics = step(...)
        ...acc / n / global_step bookkeeping...
        state = clock.finish(state, n, run, metrics, key, group)

    ``finish`` runs the resilience hooks, the ``--log_every`` read-back
    and THEN writes the record, so ``wall_ms`` is the whole iteration.
    The read-back drains the device, so each one closes a FENCED window:
    train steps and host seconds since the previous one (or since this
    call's first dispatch began, the device being drained between
    ``run_epoch`` calls).  The seconds the loop spent blocked in hooks
    are NOT taken out, unlike in ``_log_dispatch``'s ex/s line: the
    host runs tens of steps ahead, so the device works through its
    queue while the host blocks, and a window less its blocked time
    read under the device's own busy time on the chip (PERF.md, PR 24);
    the record's ``block_ms`` says how much there was.  A window that
    held a program's first (compiling) dispatch carries no fence."""

    def __init__(self, trainer: "Trainer", epoch: int, t0: float,
                 start_step: int):
        self.tr = trainer
        self.epoch = epoch
        self.last = (t0, start_step)      # _log_dispatch's (t, n)
        self.fence_t: Optional[float] = None
        self.fence_step = trainer.global_step
        self.fence_clean = True
        self.step = trainer.global_step + 1
        self.want = True
        self.t_rec = self.t_disp = self.t_done = self.t_hooks = t0
        trainer._blocked_since_log = 0.0
        trainer._sync_s = 0.0
        # the model's own counters (spans.COUNTERS), whatever their
        # names: each dispatch's device scalars, kept until the loop reads
        # the loss anyway (only where it ever does: no --log_every,
        # nothing kept)
        self.counters = [] if trainer.cfg.log_every else None

    def start(self, key: Optional[tuple] = None, due: bool = False) -> None:
        """Top of an iteration.  ``key`` (where the loop knows its
        program before the data phase) lets a dispatch whose record the
        --telemetry_every cadence drops skip its telemetry-only clock
        reads; ``due`` = it will cross a --log_every boundary, whose
        record is always kept."""
        tr = self.tr
        self.step = tr.global_step + 1
        self.want = key is None or due or tr._keep_dispatch_times(key)
        self.t_rec = time.monotonic() if self.want else 0.0
        self.t_disp = None

    def phase(self, name: str):
        return spans.phase(name, step=self.step)

    def _close_window(self, now: float) -> Optional[tuple]:
        """(train steps, host ms) of the fenced window that a drain at
        ``now`` closes — None where it held a compiling dispatch — and
        open the next one there."""
        tr = self.tr
        fence = None
        if self.fence_clean and self.fence_t is not None:
            fence = (tr.global_step - self.fence_step,
                     (now - self.fence_t) * 1e3)
        self.fence_t, self.fence_step = now, tr.global_step
        self.fence_clean = True
        return fence

    @contextlib.contextmanager
    def dispatch(self, kk: int):
        """Round one jitted step call.  A context manager and not a
        wrapper that makes the call: the call's Python stack stays as
        deep as the loop's own, and a program's first call traces under
        it (set-up time on the chip's host moved by 20 s with one frame
        more — PERF.md, PR 24)."""
        if self.t_disp is None:
            first = self.fence_t is None
            self.t_disp = (time.monotonic() if self.want or first
                           else 0.0)
            if first:
                self.fence_t = self.t_disp
        self.tr._prof_before(kk)
        with spans.phase("dispatch", step=self.step):
            yield
        self.t_done = time.monotonic()

    def finish(self, state: TrainState, n: int, run: int, metrics,
               key: tuple, group: tuple, h2d_s: float = 0.0) -> TrainState:
        """After the dispatch's bookkeeping (``n`` and ``global_step``
        already advanced; ``run`` = train steps this iteration
        dispatched): placement probe, profiler window, hooks, read-back,
        record."""
        tr = self.tr
        if tr._sharding_expect is None:
            tr._observe_state_placement(state)
        tr._prof_after(metrics)
        self.t_hooks = t_end = time.monotonic()
        if tr.resilience is not None:
            with spans.phase("hooks", step=self.step):
                state = tr._resilience_hooks(state, self.epoch, n,
                                             n_steps=run, metrics=metrics,
                                             group=group)
            t_end = time.monotonic()
        block_s = t_end - self.t_done
        tr._blocked_since_log += block_s
        if key not in tr._dispatched:
            self.fence_clean = False
        if self.counters is not None and "counters" in metrics:
            self.counters.append(metrics["counters"])
        fence = counters = None
        if tr._log_due(n, run):
            self.last = tr._log_dispatch(self.epoch, n, run, metrics,
                                         self.last)
            fence = self._close_window(self.last[0])
            if self.counters:
                # the device is drained: this read waits for nothing
                kept = jax.device_get(self.counters)
                counters = {k: sum(float(c[k]) for c in kept) / len(kept)
                            for k in kept[0]}
                self.counters = []
            t_end = time.monotonic()
        sync_s, tr._sync_s = tr._sync_s, 0.0
        want = self.want
        tr._record_dispatch(
            self.epoch, n, run, t_end - self.t_rec if want else 0.0,
            self.t_done - self.t_disp if want else 0.0,
            self.t_disp - self.t_rec if want else 0.0, block_s, key,
            h2d_s=h2d_s, sync_s=sync_s, fence=fence, counters=counters)
        return state

    def fence(self, metrics) -> None:
        """run_epoch's closing fence: dispatch is asynchronous, and
        without it the reference-parity epoch timing
        (resnet50_test.py:519,614) would measure the enqueue.  It drains
        the device like a read-back, so it closes the last fenced window
        too — as an ``epoch_fence`` record (the last step's record is
        already written): an epoch shorter than --log_every, the paper's
        ResNet at 48 steps, has no other."""
        if metrics is None:
            return
        tr = self.tr
        t0 = time.monotonic()
        with spans.phase("epoch_fence", step=tr.global_step):
            jax.block_until_ready(metrics["loss"])
        now = time.monotonic()
        fence = self._close_window(now)
        if tr.telemetry is not None and fence is not None and fence[0]:
            tr.telemetry.recorder.record_event(
                "epoch_fence", step=tr.global_step, epoch=self.epoch,
                fence_steps=fence[0], fence_ms=round(fence[1], 3),
                sync_ms=round((now - t0) * 1e3, 3))


class Trainer:
    """Owns the compiled steps and the epoch loop."""

    def __init__(self, cfg: TrainConfig, put_batch: Optional[Callable] = None,
                 put_eval_batch: Optional[Callable] = None,
                 log: Callable[[str], None] = print,
                 state_shardings=None, resilience=None,
                 put_stacked: Optional[Callable] = None, resident=None,
                 telemetry=None, profiler=None, stream=None,
                 pipeline=None):
        self.cfg = cfg
        # parallel.pipeline.PipelineSpec on a pp>1 mesh (None everywhere
        # else): threads into every train-step build so the forward runs
        # the staged 1F1B microbatch rotation; eval stays unstaged (the
        # params are identical, pp only reorders the encoder's work)
        self.pipeline = pipeline
        # telemetry.RunTelemetry bundle (or None = zero hot-path
        # overhead): per-dispatch JSONL records, span breakdown, epoch
        # pod aggregation + straggler flags — telemetry/__init__.py
        self.telemetry = telemetry
        # utils.profiling.StepWindowProfiler (or None): --profile_steps
        # A:B windowed jax.profiler capture, driven at dispatch
        # boundaries by the epoch loops below
        self.profiler = profiler
        # resilience.Resilience bundle (or None = zero hot-path overhead):
        # step-cadence async checkpoints, preemption handling, fault
        # injection, goodput accounting — resilience/__init__.py
        self.resilience = resilience
        self.put_batch = put_batch or (lambda b: b)
        # eval staging may differ (e.g. normalize-only augmentation);
        # defaults to the train staging function
        self.put_eval_batch = put_eval_batch or self.put_batch
        # staging for K-stacked host batches (leading K axis kept on-host;
        # the batch dim below it is the sharded one) — placement.
        # make_put_batch(..., stacked=True)
        self.put_stacked = put_stacked or (lambda b: b)
        # device-resident train split (data/device_resident.py) — when
        # set, run_epoch never touches a host loader: batches are
        # gathered inside the fused dispatch.  Eval stays on the host
        # path (once per epoch, off the hot loop).
        self.resident = resident
        # beyond-HBM streaming source (data/stream/window.py
        # DiskStreamSource, or None): the split lives on disk; each
        # epoch trains through a double-buffered device window refilled
        # by a background thread.  Steady-state stall accounting
        # (fraction of step time blocked on data — the
        # stream_stall_pct property) accumulates here across epochs, excluding
        # each program's compile-marked first dispatch.
        self.stream = stream
        self._stream_stall_s = 0.0
        self._stream_wall_s = 0.0
        # K train steps per device dispatch (the fused lax.scan program);
        # 1 keeps the classic one-jit-call-per-step loop bit-for-bit.
        self.k = max(int(getattr(cfg, "steps_per_dispatch", 1) or 1), 1)
        self.log = log if jax.process_index() == 0 else (lambda *_: None)
        donate = {"donate_argnums": 0} if getattr(cfg, "donate", True) else {}
        self._donate = donate
        self._state_shardings = state_shardings
        # state_shardings is only needed for --host_offload (the train step
        # fetch/stashes the state across memory kinds per batch,
        # steps._offload_transfers; evaluate() fetches once per epoch)
        self._offload_shardings = (state_shardings if cfg.host_offload
                                   else None)
        # compile observatory (telemetry/programs.py): every program this
        # Trainer builds goes through an observed explicit lower/compile
        # on its first call (compile ms, HLO fingerprint, cache verdict,
        # memory_analysis — all at compile boundaries, nothing
        # per-dispatch) and dispatches through the AOT executable after.
        # None (telemetry off / FDT_PROGRAM_OBS=0) keeps plain jit
        # dispatch, byte-identical to r14.
        self._observatory = (getattr(telemetry, "observatory", None)
                             if telemetry is not None else None)
        self.train_step = self._observe(
            "train:host:k1",
            jax.jit(make_train_step(cfg, state_shardings,
                                    pipeline=pipeline), **donate),
            sig_argnums=(1,), step=self._next_step)
        self._fused_cache: Dict[tuple, Callable] = {}
        # sig_argnums=(1,): eval batches legally vary (text bucket
        # widths) — each width is a counted VARIANT of the one "eval"
        # program, not a retrace
        self.eval_step = self._observe("eval", jax.jit(make_eval_step(cfg)),
                                       sig_argnums=(1,))
        # sharding-drift guard state (telemetry/programs.py): the live
        # state's sharding fingerprint captured after the run's first
        # dispatch, re-checked at epoch boundaries (_check_sharding_drift)
        self._sharding_expect: Optional[str] = None
        self._sharding_detail: Optional[Dict[str, str]] = None
        self.history: Dict[str, List[float]] = {
            "train_acc": [], "test_acc": [], "train_loss": [],
            "test_loss": [], "epoch_time": [], "peak_mem_bytes": []}
        if getattr(cfg, "task", "cls") == "lm":
            # the LM workload's headline metric rides the same history
            # surface (train/metrics.perplexity of the token-weighted
            # epoch loss); "accuracy" already IS next-token accuracy
            self.history["train_ppl"] = []
            self.history["test_ppl"] = []
        self.best_acc = 0.0
        self.recoveries = 0
        # host-side mirror of state.step: reading the device scalar per
        # step would force a sync, so the loop counts steps itself
        # (re-anchored to the real value at every fit()/restore)
        self.global_step = 0
        # blocked (checkpoint/resilience-hook) seconds accumulated since
        # the last live log line — _log_dispatch subtracts them so the
        # printed ex/s is actual step throughput, not wall throughput
        # diluted by a save that happened to land in the window
        self._blocked_since_log = 0.0
        # host seconds blocked in device->host reads since the last step
        # record (the --log_every read-back; --sentinel full's per-dispatch
        # loss fetch) — the record's sync_ms
        self._sync_s = 0.0
        # programs that have already executed once: the FIRST dispatch of
        # each (path, kk) program carries its compile and is recorded as
        # compile=True, so step-time percentiles stay clean of compilation
        self._dispatched: set = set()
        # batches run by the most recent run_epoch call (epoch telemetry)
        self._last_epoch_steps = 0

    def _observe(self, name: str, jitted, sig_argnums=(),
                 step: Optional[Callable[[], int]] = None) -> Callable:
        """Route a jitted program through the compile observatory when
        one is active (telemetry/programs.py); identity otherwise.
        ``step`` gives its first_dispatch_compile span's step."""
        if self._observatory is None:
            return jitted
        return self._observatory.wrap(name, jitted,
                                      sig_argnums=sig_argnums, step=step)

    def _next_step(self) -> int:
        """The first train step of the dispatch about to run."""
        return self.global_step + 1

    def _fused_step(self, kk: int, resident=None) -> Callable:
        """Jitted K-step fused dispatch, cached per (path, kk) — an
        epoch tail shorter than K compiles its own (one-off) program.
        The stream source duck-types the resident interface
        (batch_major=True) and names its path via ``program_key`` so
        the observatory's program table distinguishes
        train:stream:kN from train:resident:kN."""
        key = (getattr(resident, "program_key", "resident")
               if resident is not None else "host", kk)
        fn = self._fused_cache.get(key)
        if fn is None:
            mesh = getattr(resident, "mesh", None)
            fn = jax.jit(
                make_fused_train_step(self.cfg, kk, self._state_shardings,
                                      resident=resident, mesh=mesh,
                                      pipeline=self.pipeline),
                **self._donate)
            # resident signature args: the per-epoch data/order arrays
            # and the start scalar (a regression to a python-int start
            # would surface as a dtype-leak retrace, the r8 bug class)
            fn = self._observe(f"train:{key[0]}:k{kk}", fn,
                               sig_argnums=(1,) if resident is None
                               else (1, 2, 3), step=self._next_step)
            self._fused_cache[key] = fn
        return fn

    def warm_programs(self, state: TrainState, train_loader: LoaderFn,
                      eval_loader: LoaderFn) -> int:
        """Build the run's steady-state programs — compile, or
        deserialize from the persistent executable cache when one is
        installed on the observatory — WITHOUT advancing the training
        state (r17 warm spares: the pre-admission warm, so a claimed
        seat swaps in at restore+catch-up speed instead of paying the
        compile-dominated cold MTTR).  One throwaway dispatch per
        program: the train step may donate its input, so it runs on a
        same-sharding copy of the state and the outputs are discarded.
        Host data path only — the device-resident programs take
        per-epoch data/order arrays and warm naturally at catch-up
        (logged, not guessed around).  Returns how many programs were
        warmed."""
        if self.resident is not None or self.stream is not None:
            self.log("[spare] --data_path resident/stream: the in-graph-"
                     "gather train programs take per-epoch/window data "
                     "arrays and warm at catch-up; only the eval program "
                     "warms now")
        donate = bool(self._donate)

        def _copy(st):
            if not donate:
                return st      # nothing will be donated; no copy needed
            return jax.tree.map(
                lambda x: x.copy() if hasattr(x, "copy") else x, st)

        warmed = 0
        if self.resident is None and self.stream is None:
            loader = train_loader(0)
            it = iter(loader)
            try:
                raw = next(it)
            except StopIteration:
                raw = None
            closer = getattr(loader, "close", None)
            if closer is not None:
                closer()
            if raw is not None:
                if self.k > 1:
                    batch = self.put_stacked(
                        _stack_host_batches([raw] * self.k))
                    self._fused_step(self.k)(_copy(state), batch)
                else:
                    self.train_step(_copy(state), self.put_batch(raw))
                warmed += 1
        ev_loader = eval_loader(0)
        it = iter(ev_loader)
        try:
            raw = next(it)
        except StopIteration:
            raw = None
        closer = getattr(ev_loader, "close", None)
        if closer is not None:
            closer()
        if raw is not None:
            self.eval_step(state, self.put_eval_batch(raw))
            warmed += 1
        return warmed

    def _record_dispatch(self, epoch: int, n: int, kk: int, wall_s: float,
                         dispatch_s: float, data_s: float, block_s: float,
                         program_key: tuple, h2d_s: float = 0.0,
                         sync_s: float = 0.0,
                         fence: Optional[tuple] = None,
                         counters: Optional[dict] = None) -> None:
        """Per-dispatch telemetry: one small host-side record into the
        recorder's ring buffer (nothing on the device, no sync).  The
        first execution of each compiled program is marked compile=True
        so aggregation can exclude compilation from step-time percentiles
        (the observatory times the compile itself: its
        first_dispatch_compile span, telemetry/programs.py)."""
        first = program_key not in self._dispatched
        if first:
            self._dispatched.add(program_key)
        tel = self.telemetry
        if tel is None:
            return
        rec = tel.recorder
        rec.record_step(self.global_step, epoch, n, kk, wall_s * 1e3,
                        dispatch_s * 1e3, kk * self.cfg.batch_size,
                        data_ms=data_s * 1e3, block_ms=block_s * 1e3,
                        compile_=first, h2d_ms=h2d_s * 1e3,
                        sync_ms=sync_s * 1e3, fence=fence,
                        counters=counters)

    def _keep_dispatch_times(self, program_key: tuple) -> bool:
        """Whether this dispatch's telemetry-ONLY clock reads (data
        wait + wall/dispatch decomposition) should be taken at all:
        True when the step record will actually be kept — always for a
        program's first (compile-marked) dispatch, else per the
        --telemetry_every cadence (recorder.next_step_kept).  Sampling
        at this layer is what removes the per-dispatch time.monotonic
        pressure the r12 note flagged; the t_done/t_end reads stay
        unconditional (the live-line blocked accounting needs them
        regardless of telemetry).  A dispatch that crosses a --log_every
        boundary is always timed (_DispatchClock.start's ``due``): its
        record carries the fenced window and is always kept."""
        tel = self.telemetry
        if tel is None:
            return False
        return (program_key not in self._dispatched
                or tel.recorder.next_step_kept())

    def _prof_before(self, kk: int) -> None:
        prof = self.profiler
        if prof is not None and not prof.done:
            prof.before_dispatch(self.global_step, kk)

    def _prof_after(self, metrics) -> None:
        prof = self.profiler
        if prof is not None and prof.active:
            # the fence (one loss readback) runs only when the window is
            # actually closing — steady-state dispatches never sync
            prof.after_dispatch(self.global_step,
                                fence=lambda: float(metrics["loss"]))

    def _observe_state_placement(self, state: TrainState) -> None:
        """After the run's first dispatch (the epoch loops call this
        exactly once — a per-dispatch ``is None`` check guards it): emit
        the per-chip state byte table (kind "memory", scope "state" —
        ``opt_state_bytes_per_chip`` is ROADMAP's ZeRO-sizing number)
        and fingerprint the live shardings for the epoch-boundary drift
        guard.  The fingerprint is of the POST-step state, i.e. what the
        compiled program's output constraint actually produced — the
        thing r11 measured drifting."""
        from faster_distributed_training_tpu.telemetry import programs
        self._sharding_expect = programs.sharding_fingerprint(state)
        self._sharding_detail = (programs.sharding_table(state)
                                 if self.cfg.debug else None)
        tiers = programs.state_bytes_table(state).get(
            "opt_state_tiers") or {}
        if set(tiers) - {"replicated"}:
            # the ZeRO layout is live: say where the opt-state bytes
            # went (sharded over tp / parked in pinned host memory)
            self.log("[memory] opt state per chip: " + ", ".join(
                f"{t}={v['bytes_per_chip'] / 1e6:.1f}MB"
                f"/{v['leaves']} leaves"
                for t, v in sorted(tiers.items())))
        if self.telemetry is not None:
            # the splat must stay a DIRECT state_bytes_table call —
            # scripts/check_telemetry_schema.py resolves its field
            # vocabulary through _SPLAT_SOURCES by callable name
            self.telemetry.recorder.record_event(
                "memory", **programs.state_bytes_table(state))

    def _check_sharding_drift(self, state: TrainState, epoch: int) -> None:
        """Epoch-boundary re-check of the step-1 sharding fingerprint
        (always-on cheap hash; ``--debug`` keeps the per-leaf table so a
        drift names the leaves that moved).  The r11 bug class: XLA
        re-placed donated params between steps until the output pin
        landed — this guard turns a silent re-placement into a loud
        WARNING + ``memory``/``sharding_drift`` event."""
        if self._sharding_expect is None:
            return
        from faster_distributed_training_tpu.telemetry import programs
        got = programs.sharding_fingerprint(state)
        if got == self._sharding_expect:
            return
        changed: list = []
        if self._sharding_detail is not None:
            now = programs.sharding_table(state)
            before = self._sharding_detail
            changed = sorted(p for p in set(now) | set(before)
                             if now.get(p) != before.get(p))[:8]
        import warnings
        msg = (f"train-state sharding DRIFT at epoch {epoch}: "
               f"fingerprint {self._sharding_expect} -> {got}"
               + (f"; changed leaves (first 8): {changed}" if changed
                  else " (re-run with --debug for the per-leaf diff)")
               + " — something re-placed the state between donated "
                 "steps (the r11 params-drift class; check the train "
                 "step's output sharding pin)")
        warnings.warn(msg, stacklevel=2)
        self.log("[memory] WARNING: " + msg)
        if self.telemetry is not None:
            self.telemetry.recorder.record_event(
                "memory", scope="sharding_drift", epoch=epoch,
                expected=self._sharding_expect, got=got,
                changed_leaves=changed)
        # re-anchor on the drifted placement: ONE incident, one warning
        # (not one per remaining epoch), and the next drift is measured
        # against what the state actually is now
        self._observe_state_placement(state)

    def run_epoch(self, state: TrainState, loader: Optional[Iterable],
                  epoch: int = 0, start_step: int = 0) -> tuple:
        if self.stream is not None:
            return self._run_epoch_stream(state, epoch, start_step)
        if self.resident is not None:
            return self._run_epoch_resident(state, epoch, start_step)
        if self.k > 1:
            return self._run_epoch_fused_host(state, loader, epoch,
                                              start_step)
        acc = MetricAccumulator()
        t0 = time.monotonic()
        metrics = None
        res = self.resilience
        # anomaly sentinel (resilience/sentinel.py): quarantined batch
        # positions — pure (epoch, position) set agreed across hosts via
        # the durable ledger — are consumed-and-skipped below, so a
        # post-rollback replay deterministically excludes the batches a
        # loss spike indicted.  None = zero hot-path overhead.
        sent = getattr(res, "sentinel", None) if res is not None else None
        # keep a handle to the prefetch thread's cancel path BEFORE any
        # wrapping: an abnormal loop exit (preemption, injected fault)
        # must not strand the worker blocked on a full queue
        closer = getattr(loader, "close", None)
        if res is not None and res.faults is not None:
            loader = res.faults.wrap_data(loader)
        if start_step:
            # mid-epoch resume: the checkpoint landed after `start_step`
            # batches of this epoch; the loader's order is a pure function
            # of (seed, epoch), so skipping that many batches replays the
            # remainder exactly.  Batches are materialized to be skipped
            # (the loader API yields, it doesn't seek) — host-side work
            # only, no device steps.
            it = iter(loader)
            for _ in itertools.islice(it, start_step):
                pass
            loader = it
            self.log(f"[resume] epoch {epoch}: skipped {start_step} "
                     f"already-trained batches")
        n = start_step
        clock = _DispatchClock(self, epoch, t0, start_step)
        # --log_every N: a live loss/accuracy/throughput line every N
        # steps — the reference's tqdm descriptor observability
        # (resnet50_test.py:560-566) at 1/N its sync cost (tqdm's
        # .item() reads synced EVERY batch; here one device->host
        # readback per N steps, 0 disables).  Emission shares
        # _log_dispatch with the fused paths (kk=1: same line as ever).
        #
        # device_prefetch stages put_batch (H2D transfer ahead of the
        # consuming step — the pin_memory + non_blocking overlap,
        # resnet50_test.py:522, TPU style); uint8 image augmentation runs
        # inside the step itself, keyed by the checkpointed step counter.
        # The while/next form (vs `for batch in ...`) exists so the data
        # wait is observable: the iterator wraps the loader's next() and
        # put_batch in their own trace phases and reports the seconds of
        # each, distinct telemetry fields from the dispatch itself.
        it = device_prefetch(loader, self.put_batch,
                             depth=self.cfg.prefetch_depth)
        try:
            while True:
                clock.start(("host", 1), due=self._log_due(n + 1, 1))
                it.step = clock.step
                try:
                    batch = next(it)
                except StopIteration:
                    break
                if sent is not None and sent.quarantined(epoch, n):
                    # consume-and-skip: the batch is materialized (the
                    # loader API yields, it doesn't seek) but never
                    # dispatched — params/opt-state/step untouched, so
                    # the replayed epoch is bitwise the epoch that never
                    # saw this batch
                    n += 1
                    continue
                with clock.dispatch(1):
                    state, metrics = self.train_step(state, batch)
                acc.add(metrics)
                n += 1
                self.global_step += 1
                state = clock.finish(state, n, 1, metrics, ("host", 1),
                                     (n - 1, 1), h2d_s=it.h2d_s)
        except BaseException:
            # stranded prefetch worker cleanup (Preempted, injected
            # faults, Ctrl-C): cancel + join the loader's thread so an
            # abandoned iterator can never block on a full queue forever
            if closer is not None:
                closer()
            raise
        clock.fence(metrics)
        self._last_epoch_steps = n
        elapsed = time.monotonic() - t0
        return state, acc.summary(), elapsed

    def _log_due(self, n: int, kk: int) -> bool:
        """Whether the dispatch that advanced the epoch to step ``n`` by
        ``kk`` steps crossed a --log_every boundary."""
        log_every = int(self.cfg.log_every or 0)
        return bool(log_every) and (n // log_every) > ((n - kk)
                                                       // log_every)

    def _log_dispatch(self, epoch: int, n: int, kk: int, metrics,
                      last) -> tuple:
        """log_every at dispatch granularity: emit the live line whenever
        this dispatch crossed a log_every boundary.  `last` is (t, n) of
        the previous emission; returns the updated pair.

        The printed ex/s is STEP throughput, not raw wall throughput:
        checkpoint-blocking and resilience-hook seconds measured by the
        dispatch loop since the last line (_blocked_since_log) are
        subtracted from the window, so a cadence save landing mid-window
        no longer reads as a throughput dip (r12 satellite — the raw
        wall number made every save look like a regression in the live
        log while the epoch summary said otherwise)."""
        if not self._log_due(n, kk):
            return last
        last_t, last_n = last
        t_sync = time.monotonic()
        with spans.phase("readback", step=self.global_step - kk + 1):
            loss = float(metrics["loss"])
        now = time.monotonic()
        self._sync_s += now - t_sync
        window = max(now - last_t, 1e-9)
        blocked = min(max(self._blocked_since_log, 0.0), window)
        self._blocked_since_log = 0.0
        exs = (n - last_n) * self.cfg.batch_size / max(window - blocked,
                                                       1e-9)
        line = f"[epoch {epoch}] step {n}: loss={loss:.4f}"
        total = metrics.get("total")
        correct = metrics.get("correct")
        if correct is not None and total is not None and float(total):
            line += f" acc={float(correct) / float(total):.4f}"
        line += f" {exs:.0f} ex/s"
        if blocked >= 0.001:
            line += f" (+{blocked:.2f}s blocked)"
        if kk > 1:
            line += f" (K={kk} fused)"
        self.log(line)
        return now, n

    def _run_epoch_fused_host(self, state: TrainState, loader: Iterable,
                              epoch: int, start_step: int = 0) -> tuple:
        """K>1 on the host data path: group K host batches, stack them
        into one leading-K transfer, advance K steps in one dispatch.
        Kept mainly as the CPU-testable/bitwise-comparable twin of the
        device-resident path (and for datasets that outgrow HBM) — the
        zero-host-work pairing is --data_path resident."""
        acc = MetricAccumulator()
        t0 = time.monotonic()
        metrics = None
        res = self.resilience
        sent = getattr(res, "sentinel", None) if res is not None else None
        closer = getattr(loader, "close", None)
        if res is not None and res.faults is not None:
            loader = res.faults.wrap_data(loader)
        it = iter(loader)
        if start_step:
            # mid-epoch resume: saves land on dispatch boundaries, so
            # start_step is a whole number of dispatches; the skipped
            # batches are materialized host-side only (loader API yields)
            for _ in itertools.islice(it, start_step):
                pass
            self.log(f"[resume] epoch {epoch}: skipped {start_step} "
                     f"already-trained batches")
        n = start_step
        clock = _DispatchClock(self, epoch, t0, start_step)
        try:
            while True:
                # clock reads unconditional here: the program key (and so
                # the compile-marking decision) needs the group's length,
                # which is only known after the islice — one read per K
                # steps is already amortized
                clock.start()
                with clock.phase("data_wait"):
                    group = list(itertools.islice(it, self.k))
                if not group:
                    break
                kk_full = len(group)
                if sent is not None:
                    # quarantined positions drop out of the stacked group
                    # (the order cursor still advances by the FULL group,
                    # so the surviving batches are the identical content
                    # at their identical positions); a shorter group
                    # compiles its own kk program like any epoch tail
                    group = [b for j, b in enumerate(group)
                             if not sent.quarantined(epoch, n + j)]
                    if not group:
                        n += kk_full
                        continue
                kk = len(group)
                t_put = time.monotonic()
                with clock.phase("h2d"):
                    # the K-stacked staging: host stack + ONE transfer
                    batch = self.put_stacked(_stack_host_batches(group))
                h2d_s = time.monotonic() - t_put
                with clock.dispatch(kk):
                    state, metrics = self._fused_step(kk)(state, batch)
                acc.add(metrics)
                n += kk_full
                self.global_step += kk
                state = clock.finish(state, n, kk, metrics, ("host", kk),
                                     (n - kk_full, kk_full), h2d_s=h2d_s)
        except BaseException:
            if closer is not None:
                closer()
            raise
        clock.fence(metrics)
        self._last_epoch_steps = n
        return state, acc.summary(), time.monotonic() - t0

    def _run_epoch_resident(self, state: TrainState, epoch: int,
                            start_step: int = 0) -> tuple:
        """The host-free inner loop: the train split lives on device
        (data/device_resident.py), the epoch order is uploaded once, and
        each iteration is ONE jitted dispatch that gathers, augments and
        trains K consecutive batches.  Steady-state per-dispatch host
        work: a Python loop tick, one scalar arg, and the resilience
        flag poll — no batch bytes, no permutation, no staging.

        Data-iterator fault injection (FDT_FAULT_DATA_AT_BATCH) does not
        apply here — there is no host iterator to wrap; step faults and
        preemption inject exactly as on the host path."""
        resident = self.resident
        acc = MetricAccumulator()
        t0 = time.monotonic()
        metrics = None
        res = self.resilience
        sent = getattr(res, "sentinel", None) if res is not None else None
        # sharded residency re-shards into this epoch's batch-major view
        # here (ONE collective per epoch); the replicated layout returns
        # its static arrays and the order drives the in-graph gather
        data = resident.epoch_arrays(epoch)
        order = resident.epoch_order(epoch)
        n_steps = resident.steps_per_epoch
        if start_step:
            # device-resident resume is a pure SEEK: no host batches are
            # materialized to skip — the next dispatch just starts at
            # start_step's offset into the epoch order
            self.log(f"[resume] epoch {epoch}: seeking to batch "
                     f"{start_step} (device-resident order, no host "
                     f"replay)")
        n = start_step
        clock = _DispatchClock(self, epoch, t0, start_step)
        while n < n_steps:
            kk = min(self.k, n_steps - n)
            # quarantine-aware dispatch plan: the common case is the
            # single full segment [(n, kk)] (sent.plan's fast path);
            # after a spike rollback the window splits around the
            # quarantined positions — one fused dispatch per surviving
            # contiguous run, each seeking its own in-graph start, so
            # the epoch-order cursor algebra stays pure
            segs = (sent.plan(epoch, n, kk) if sent is not None
                    else [(n, kk)])
            if not segs:
                n += kk
                continue
            run = sum(l for _, l in segs)
            key = ("resident", segs[-1][1])
            clock.start(key, due=self._log_due(n + kk, run))
            for s, l in segs:
                with clock.dispatch(l):
                    state, metrics = self._fused_step(l, resident)(
                        state, data, order,
                        jax.numpy.asarray(s, jax.numpy.int32))
                acc.add(metrics)
            n += kk
            self.global_step += run
            state = clock.finish(state, n, run, metrics, key, (n - kk, kk))
        clock.fence(metrics)
        self._last_epoch_steps = n
        return state, acc.summary(), time.monotonic() - t0

    def _run_epoch_stream(self, state: TrainState, epoch: int,
                          start_step: int = 0) -> tuple:
        """The beyond-HBM streaming loop: the split lives ON DISK
        (data/stream/), only a fixed window of batches is device-
        resident, and a background thread refills the next buffer
        (disk mmap gather + H2D) while this loop trains the current one
        — each dispatch gathers batch ``n - base`` from the buffer
        in-graph, the sharded-resident batch-major idiom on a
        window-deep leading axis.

        Mid-epoch resume is a pure SEEK (the refill stream just starts
        at ``start_step``; batch content is a pure function of
        (seed, epoch, batch index)).  The window is CLOSED on every
        exit, normal or abnormal — PrefetchIterator's cancel/drain
        lifecycle reclaims the refill thread exactly like the host
        loader's prefetch worker.  Host-iterator fault injection
        (FDT_FAULT_DATA_AT_BATCH) does not apply (no host iterator to
        wrap — the resident path's precedent); step faults and
        preemption inject as everywhere.

        Timing note: the clock reads bracketing ``buffer_for`` are
        UNCONDITIONAL (``clock.start()`` without a program key, unlike
        the host path's --telemetry_every-gated reads) — the swap wait
        is the stream-stall metric itself and must be measured
        regardless of whether the step record is kept; K>1 amortizes
        them like every other per-dispatch cost."""
        src = self.stream
        acc = MetricAccumulator()
        t0 = time.monotonic()
        metrics = None
        res = self.resilience
        sent = getattr(res, "sentinel", None) if res is not None else None
        n_steps = src.steps_per_epoch
        if start_step:
            self.log(f"[resume] epoch {epoch}: stream seek to batch "
                     f"{start_step} (window refills start there; no "
                     f"host replay)")
        window = src.epoch_window(epoch, start_step)
        n = start_step
        clock = _DispatchClock(self, epoch, t0, start_step)
        # the epoch-INITIAL buffer fill is un-overlapped by construction
        # (nothing trains while the first window loads) — exclude that
        # one wait from the steady-state stall accounting on every
        # epoch, the same way compile-carrying first dispatches are
        epoch_cold = True
        try:
            while n < n_steps:
                clock.start()
                with clock.phase("data_wait"):
                    base, hi, data = window.buffer_for(n)
                kk = min(self.k, n_steps - n, hi - n)
                # quarantine-aware plan (see _run_epoch_resident): the
                # in-graph start is buffer-relative, so each segment
                # dispatches at ``s - base``
                segs = (sent.plan(epoch, n, kk) if sent is not None
                        else [(n, kk)])
                if not segs:
                    n += kk
                    continue
                run = sum(l for _, l in segs)
                key = ("stream", segs[-1][1])
                first = key not in self._dispatched
                for s, l in segs:
                    with clock.dispatch(l):
                        state, metrics = self._fused_step(l, src)(
                            state, data, src.dummy_order,
                            jax.numpy.asarray(s - base, jax.numpy.int32))
                    acc.add(metrics)
                n += kk
                self.global_step += run
                state = clock.finish(state, n, run, metrics, key,
                                     (n - kk, kk))
                if not first and not epoch_cold:
                    # steady-state stall accounting for stream_stall_pct:
                    # compile-carrying first dispatches AND each epoch's
                    # cold initial fill excluded (telemetry-percentile
                    # rule); the denominator stops BEFORE the resilience
                    # hooks — checkpoint/rendezvous time has its own
                    # overhead metric and must not dilute "fraction of
                    # STEP time blocked on data"
                    self._stream_stall_s += clock.t_disp - clock.t_rec
                    self._stream_wall_s += clock.t_hooks - clock.t_rec
                epoch_cold = False
        finally:
            # normal AND abnormal exits reclaim the refill thread (the
            # prefetch-closer contract the host paths honor in except:)
            window.close()
        clock.fence(metrics)
        self._last_epoch_steps = n
        return state, acc.summary(), time.monotonic() - t0

    @property
    def stream_stall_pct(self) -> Optional[float]:
        """Steady-state fraction (percent) of streamed step time spent
        blocked on the data window — the run-level number cli and the
        stream smoke read (None before any steady-state streamed dispatch)."""
        if self.stream is None or self._stream_wall_s <= 0:
            return None
        return 100.0 * self._stream_stall_s / self._stream_wall_s

    def _resilience_hooks(self, state: TrainState, epoch: int,
                          step_in_epoch: int, n_steps: int = 1,
                          metrics=None, group=None) -> TrainState:
        """Per-dispatch resilience work, in hazard order: injected
        faults first (a crash preempts bookkeeping, like the real
        thing), then the sentinel's loss-spike observation, then the
        cross-host-agreed preemption decision (emergency save + clean
        Preempted exit), then cadence checkpointing.  `n_steps` = train
        steps this dispatch advanced (K under the fused dispatch) so
        the goodput step counter stays per-STEP while the polling stays
        per-dispatch.  `metrics`/`group` feed the full-mode sentinel:
        the dispatch's metrics dict and the (start, count) epoch-order
        window it covered — quarantined positions inside the window
        were NOT dispatched; Sentinel.observe re-filters them."""
        res = self.resilience
        step = self.global_step
        res.goodput.count("steps", n_steps)
        if res.faults is not None:
            res.faults.on_step(step)    # may SIGTERM this process / raise
        sent = getattr(res, "sentinel", None)
        if (sent is not None and sent.mode == "full" and metrics is not None
                and group is not None):
            # the ONE per-dispatch device sync --sentinel full buys
            # (its cost is not measured on the chip): the dispatch loss is a
            # replicated global scalar, so every host reads the same
            # value, reaches the same spike verdict, and writes the
            # same quarantine ledger — no cross-host protocol needed.
            # Runs BEFORE the checkpoint hooks so the newest checkpoint
            # always predates the quarantined dispatch and the
            # rollback-replay actually excises it.  May raise LossSpike
            # (restartable; the supervisor replays from the newest
            # valid checkpoint with the indicted batches quarantined).
            t_sync = time.monotonic()
            loss = float(jax.device_get(metrics["loss"]))
            self._sync_s += time.monotonic() - t_sync
            if res.faults is not None:
                loss = res.faults.perturb_loss(step, loss)
            sent.observe(epoch, group[0], group[1], loss, step)
        if res.coordinator is not None:
            # pod health: feed the step clock to the local watchdog and
            # (cadence-gated) poll the peers' FAIL/heartbeat markers —
            # raises PeerFailure/StepTimeout, both restartable, so the
            # whole pod re-enters the supervisor together.  BEFORE the
            # preemption/save hooks: a dead peer makes the collective
            # emergency save (and the sharded commit barrier) unreachable,
            # so failure observation must preempt anything collective.
            # Multi-slice (r14): a failure confined to one foreign slice
            # PARKS here (bounded await_readmission hold) instead of
            # raising, and a rejoining slice drives its catch-up
            # handshake here.
            res.coordinator.check(step)
            # a completed re-admission re-anchors the checkpoint cadence
            # at the pod's agreed release step, so every host's NEXT
            # save tick is the same pure function of the step sequence
            # again (the two-phase commit barrier depends on that)
            align = res.coordinator.consume_cadence_align()
            if align is not None and res.manager is not None:
                res.manager.align_cadence(align)
        # blocking checkpoint work below (emergency save; cadence saves
        # that DRAIN a prior write's commit barrier, up to
        # commit_timeout_s) is legitimate step-thread stalling — suspend
        # the local hang watchdog so a healthy host is never SIGKILLed
        # mid-save (heartbeats keep running; a wedged save is bounded by
        # its own timeout)
        pause = (res.coordinator.pause_watch()
                 if res.coordinator is not None else contextlib.nullcontext())
        with pause:
            if res.preemption is not None and res.preemption.should_stop(step):
                from faster_distributed_training_tpu.resilience import (
                    Preempted)
                res.goodput.count("preemptions")
                if res.manager is not None:
                    # the manager bills the save's duration into the
                    # emergency_save_s segment itself — wrapping it in
                    # goodput.timed here too would double-count the badput
                    res.manager.save(state, step, epoch=epoch,
                                     step_in_epoch=step_in_epoch,
                                     best_acc=self.best_acc, sync=True,
                                     segment="emergency_save_s")
                    self.log(f"[preempt] emergency checkpoint committed at "
                             f"step {step} (epoch {epoch}); exiting cleanly")
                else:
                    self.log(f"[preempt] no checkpoint manager configured — "
                             f"exiting at step {step} WITHOUT an emergency "
                             f"save (set --checkpoint_every to get one)")
                raise Preempted(f"preempted at step {step}", state=state,
                                step=step)
            if res.manager is not None and not (
                    res.coordinator is not None
                    and res.coordinator.saves_suspended):
                # saves_suspended: during a slice's rejoin catch-up (or
                # a survivor's post-hold catch-up) a cadence tick taken
                # here could never commit — the rest of the pod is not
                # taking it — and would only burn the commit-barrier
                # timeout; the cadence re-aligns at the release step
                res.manager.maybe_save(state, step, epoch=epoch,
                                       step_in_epoch=step_in_epoch,
                                       best_acc=self.best_acc)
        return state

    def _save_epoch_checkpoint(self, name: str, state: TrainState,
                               epoch: int) -> None:
        """Epoch-level save (rolling last-good / best-acc), goodput-timed
        when the resilience bundle is active.

        fs-SIMULATED pods (FDT_POD_INDEX seam): jax is single-process
        per simulated host, so this orbax save is NOT collective — every
        host computes the identical full state and concurrent writers on
        one shared path would race mid-rename.  Host 0 writes it alone;
        a REAL pod's save is collective and every host must enter."""
        res = self.resilience
        if (res is not None and res.pod_simulated and res.pod_count > 1
                and res.pod_index != 0):
            return
        if res is not None:
            with res.goodput.timed("checkpoint_blocking_s"):
                ckpt.save_checkpoint(self.cfg.checkpoint_dir, name, state,
                                     epoch, self.best_acc)
            res.goodput.count("saves")
        else:
            ckpt.save_checkpoint(self.cfg.checkpoint_dir, name, state,
                                 epoch, self.best_acc)

    def evaluate(self, state: TrainState, loader: Iterable) -> Dict[str, float]:
        if self._offload_shardings is not None:
            # one host->device transfer per eval epoch (state is constant
            # across eval batches) instead of an in-graph fetch per batch —
            # and ONLY of the leaves eval reads (params + batch_stats);
            # opt_state stays on pinned_host, which is the point of offload
            dev = lambda sh: sh.with_memory_kind("device")  # noqa: E731
            state = state.replace(
                params=jax.tree.map(
                    lambda x, sh: jax.device_put(x, dev(sh)),
                    state.params, self._offload_shardings.params),
                batch_stats=jax.tree.map(
                    lambda x, sh: jax.device_put(x, dev(sh)),
                    state.batch_stats,
                    self._offload_shardings.batch_stats))
        acc = MetricAccumulator()
        t0 = time.monotonic()
        with spans.span("eval", step=self.global_step):
            for batch in device_prefetch(loader, self.put_eval_batch,
                                         depth=self.cfg.prefetch_depth):
                acc.add(self.eval_step(state, batch))
            summary = acc.summary()   # device->host sync fences the timing
        elapsed = time.monotonic() - t0
        # eval throughput made visible per epoch (VERDICT r5 #7): the
        # routing changes this repo makes at eval shapes must not be
        # able to regress inference silently — no cell of the ledger
        # evaluates inside its window (PERF.md 3), so this line, the
        # full-pipeline number per run, is the only place it shows.
        total = summary.get("total_sum")
        if total:
            self.log(f"[eval] {total:.0f} samples in {elapsed:.1f}s "
                     f"({total / max(elapsed, 1e-9):.0f} ex/s)")
        return summary

    def fit(self, state: TrainState, train_loader: LoaderFn,
            eval_loader: LoaderFn, ckpt_name: str = "ckpt",
            start_epoch: int = 0, start_step_in_epoch: int = 0
            ) -> TrainState:
        cfg = self.cfg
        self.recoveries = 0
        consecutive_failures = 0
        recover_name = ckpt_name + "_last"
        res = self.resilience
        # re-anchor the host step mirror to the device truth (one sync,
        # once per fit — the restored step after a supervisor restart)
        self.global_step = int(jax.device_get(state.step))
        # a supervisor restart enters fit with a freshly-restored (host)
        # state whose placement legitimately differs: the drift guard
        # re-anchors after the next dispatch instead of comparing across
        # a restore
        self._sharding_expect = None
        self._sharding_detail = None
        # supervisor restarts re-enter fit on the SAME Trainer and replay
        # from the restored epoch: drop any history entries the replay
        # will re-append, or plots/returned history would duplicate the
        # rolled-back epochs
        for series in self.history.values():
            del series[start_epoch:]
        if res is not None:
            res.goodput.start()
        if cfg.auto_recover:
            # Rollback target is a ROLLING last-good snapshot, separate from
            # the best-accuracy checkpoint (which can be arbitrarily stale
            # after a plateau).  Written unconditionally here so (a) a
            # restore point always exists — once an fp32 epoch goes
            # non-finite the live params are poisoned, "retry from current
            # state" can never converge — and (b) a stale snapshot from a
            # previous run in the same dir can never be resurrected.
            self._save_epoch_checkpoint(recover_name, state, start_epoch - 1)
        epoch = start_epoch
        resume_step = start_step_in_epoch
        while epoch < cfg.epochs:
            # resident mode never builds a host train loader (it would
            # spin up a prefetch thread and materialize batches nobody
            # consumes); eval below stays on the host path either way.
            # The pod step watchdog is armed ONLY around the dispatch
            # loop: eval/restore/checkpoint phases have no step clock to
            # advance and must not be able to false-trigger a hang
            # escalation (heartbeats keep running regardless).
            watch = (res.coordinator.watch_steps()
                     if res is not None and res.coordinator is not None
                     else contextlib.nullcontext())
            with watch:
                state, train_m, elapsed = self.run_epoch(
                    state,
                    None if (self.resident is not None
                             or self.stream is not None)
                    else train_loader(epoch),
                    epoch, start_step=resume_step)
            resumed_mid_epoch, resume_step = resume_step, 0
            if res is not None:
                # in-graph bad-step guard accounting: bad_steps was
                # summed on device across the epoch's dispatches and
                # rode the normal metrics fetch — counting it here costs
                # no extra sync (r24: the guard's verdict is read
                # where the epoch summary is already host-side)
                bad = train_m.get("bad_steps_sum")
                if bad:
                    res.goodput.count("skipped_steps",
                                      int(round(float(bad))))
            # Failure detection (a deliberate addition — the reference's
            # only recovery is manual re-launch with --resume, SURVEY.md
            # §5): a non-finite epoch loss means the run is poisoned; roll
            # back to the last good checkpoint and keep going.
            if "loss" not in train_m:
                if resumed_mid_epoch:
                    # the resume checkpoint landed after this epoch's LAST
                    # train step (the pre-eval window): nothing to replay —
                    # fall through to eval/bookkeeping and move on
                    self.log(f"[resume] epoch {epoch} was already fully "
                             f"trained at checkpoint time; running its "
                             f"eval and continuing")
                else:
                    # zero batches ran — a data/config problem (dataset
                    # smaller than one per-host batch, bad shard), not
                    # divergence; letting auto_recover roll back would burn
                    # recovery slots on an error a retry can never fix
                    raise RuntimeError(
                        f"epoch {epoch} produced no batches — dataset too "
                        f"small for batch_size={cfg.batch_size} x "
                        f"{jax.process_count()} process(es)?")
            if ("loss" in train_m and cfg.auto_recover
                    and not host_finite(train_m.get("loss"))):
                consecutive_failures += 1
                if consecutive_failures > cfg.max_recoveries:
                    raise RuntimeError(
                        f"training diverged {consecutive_failures} times in "
                        f"a row (epoch {epoch}); giving up")
                state, ck_epoch, _ = ckpt.restore_checkpoint(
                    cfg.checkpoint_dir, recover_name, state)
                # 2D/offload policies: put the restored (host numpy)
                # leaves back on their shards instead of letting the
                # next jit place uncommitted arrays
                from faster_distributed_training_tpu.parallel.placement \
                    import place_on_shardings
                state = place_on_shardings(state, self._state_shardings)
                # rollback moved state.step — re-anchor the host mirror
                self.global_step = int(jax.device_get(state.step))
                # ...and the sharding-drift baseline: the restored state's
                # placement is a fresh re-placement, not a drift
                self._sharding_expect = None
                self._sharding_detail = None
                self.log(f"[recover] non-finite loss at epoch {epoch}; "
                         f"restored last-good state from epoch {ck_epoch}, "
                         f"retrying")
                if self.telemetry is not None:
                    # rolled-back epochs emit no `epoch` event (their
                    # loss never counted) but the rollback itself is
                    # part of the run's story
                    self.telemetry.recorder.record_event(
                        "rollback", epoch=epoch,
                        restored_epoch=int(ck_epoch),
                        step=self.global_step)
                self.recoveries += 1
                # epoch += 1 gives the retry a fresh data order.  Note the
                # restore rolls state.step (and the optax schedule position
                # inside opt_state) back to the snapshot's value, so the
                # retried epoch trains at the snapshot's LR — the epoch
                # counter and the schedule deliberately diverge by the
                # rolled-back amount.
                epoch += 1
                continue
            consecutive_failures = 0
            # epoch-boundary re-check of the step-1 sharding fingerprint
            # (the always-on cheap hash; a drift warns loudly + lands a
            # memory/sharding_drift event)
            self._check_sharding_drift(state, epoch)
            if cfg.auto_recover:
                # refresh the rolling last-good snapshot after every finite
                # epoch, so recovery rolls back one epoch, not to the last
                # best-accuracy improvement
                self._save_epoch_checkpoint(recover_name, state, epoch)
            if cfg.debug:
                self._debug_checks(state, epoch)
            test_m = self.evaluate(state, eval_loader(epoch))
            if getattr(cfg, "task", "cls") == "lm":
                # LM headline: perplexity of the exact token-weighted
                # epoch loss (train/metrics.perplexity), train and eval
                from faster_distributed_training_tpu.train.metrics import (
                    perplexity)
                if host_finite(train_m.get("loss")):
                    train_m["perplexity"] = perplexity(train_m["loss"])
                if host_finite(test_m.get("loss")):
                    test_m["perplexity"] = perplexity(test_m["loss"])
                self.history["train_ppl"].append(
                    train_m.get("perplexity", 0.0))
                self.history["test_ppl"].append(
                    test_m.get("perplexity", 0.0))
            self.history["train_acc"].append(train_m.get("accuracy", 0.0))
            self.history["train_loss"].append(train_m.get("loss", 0.0))
            self.history["test_acc"].append(test_m.get("accuracy", 0.0))
            self.history["test_loss"].append(test_m.get("loss", 0.0))
            self.history["epoch_time"].append(elapsed)
            peak = peak_memory_bytes()
            # per-host HBM peak rides the epoch summary AND the
            # telemetry stream (r12 satellite — peak_memory_bytes
            # existed but was only consulted ad hoc); None on backends
            # without runtime memory stats (CPU) stays None in history
            self.history["peak_mem_bytes"].append(peak)
            self.log(
                f"epoch {epoch}: train_loss={train_m.get('loss', 0):.4f} "
                f"train_acc={train_m.get('accuracy', 0):.4f} "
                f"test_loss={test_m.get('loss', 0):.4f} "
                f"test_acc={test_m.get('accuracy', 0):.4f} "
                f"time={elapsed:.1f}s"
                + (f" test_ppl={test_m['perplexity']:.2f}"
                   if "perplexity" in test_m else "")
                + (f" peak_mem={peak / 1e6:.0f}MB" if peak else ""))
            # best-acc-gated full-state checkpoint (resnet50_test.py:663-675)
            if test_m.get("accuracy", 0.0) > self.best_acc:
                self.best_acc = test_m["accuracy"]
                self._save_epoch_checkpoint(ckpt_name, state, epoch)
            if res is not None:
                self.log("[goodput] " + format_goodput(res.goodput))
            if self.telemetry is not None:
                rec = self.telemetry.recorder
                trained = self._last_epoch_steps - resumed_mid_epoch
                ev = {"epoch": epoch, "steps": self._last_epoch_steps,
                      "trained_steps": trained, "wall_s": round(elapsed, 3)}
                if "loss" in train_m:
                    ev["loss"] = train_m["loss"]
                if "accuracy" in train_m:
                    ev["accuracy"] = train_m["accuracy"]
                if trained and elapsed:
                    ev["ex_s"] = round(trained * self.cfg.batch_size
                                       / elapsed, 1)
                if "loss" in test_m:
                    ev["eval_loss"] = test_m["loss"]
                if "accuracy" in test_m:
                    ev["eval_accuracy"] = test_m["accuracy"]
                if "perplexity" in train_m:
                    ev["perplexity"] = train_m["perplexity"]
                if "perplexity" in test_m:
                    ev["eval_perplexity"] = test_m["perplexity"]
                if peak:
                    ev["peak_mem_bytes"] = int(peak)
                rec.record_event("epoch", **ev)
                stats = memory_watermarks()
                if stats is not None:
                    # per-epoch device memory watermark as a memory-kind
                    # event (peak + current bytes in use — backends
                    # without runtime memory stats, e.g. CPU, skip it;
                    # the compile-time memory_analysis in the program
                    # events covers them statically)
                    rec.record_event("memory", scope="epoch", epoch=epoch,
                                     peak_bytes=stats["peak_bytes"],
                                     bytes_in_use=stats["bytes_in_use"])
                if res is not None:
                    # goodput/MTTR snapshot in the same stream — one
                    # file tells the whole run's story
                    rec.record_event("goodput", **res.goodput.summary())
                # flush + epoch marker + (process 0) the pod fold:
                # run-level p50/p95/p99 and the straggler line
                self.telemetry.end_epoch(epoch)
            epoch += 1
        if self.profiler is not None:
            # a --profile_steps window the run never reached the end of
            # (B past the last step) still lands its capture
            self.profiler.close()
        if res is not None and res.manager is not None:
            # drain any in-flight async save so a clean exit never leaves
            # an uncommitted newest checkpoint behind
            res.manager.wait()
        return state

    def _debug_checks(self, state: TrainState, epoch: int) -> None:
        """--debug: the reference's never-enabled NGD `_self_test`
        (ngd_optimizer.py:46,330-345), run for real once per epoch."""
        from faster_distributed_training_tpu.optim.ngd import (
            NGDHyperParams, self_test_all)

        cfg = self.cfg
        res = self_test_all(state.opt_state, NGDHyperParams(
            alpha=cfg.ngd_alpha, rank=cfg.ngd_rank,
            update_period=cfg.ngd_update_period, eta=cfg.ngd_eta))
        if res["checked"] and not res["ok"]:
            self.log(f"[debug] epoch {epoch}: NGD Fisher invariant "
                     f"violations: {res['failures']}")
        elif res["checked"]:
            self.log(f"[debug] epoch {epoch}: NGD invariants OK "
                     f"({res['checked']} factor states)")

    def maybe_resume(self, state: TrainState, ckpt_name: str = "ckpt"
                     ) -> tuple:
        """--resume: restore full state if a checkpoint exists."""
        if self.cfg.resume and ckpt.has_checkpoint(self.cfg.checkpoint_dir,
                                                   ckpt_name):
            state, epoch, best = ckpt.restore_checkpoint(
                self.cfg.checkpoint_dir, ckpt_name, state)
            self.best_acc = best
            self.log(f"resumed from epoch {epoch} (best_acc={best:.4f})")
            return state, epoch + 1
        return state, 0
