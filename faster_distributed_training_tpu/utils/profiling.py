"""Profiling hooks: jax.profiler traces + device memory, replacing the
reference's cuda.max_memory_allocated prints (resnet50_test.py:623-625)."""

from __future__ import annotations

import contextlib
from typing import Callable, Iterator, Optional, Tuple

import jax


def parse_profile_steps(spec: str) -> Optional[Tuple[int, int]]:
    """``--profile_steps A:B`` -> (A, B), None for "".  Steps are
    1-indexed GLOBAL train steps (the checkpointed step counter), A <= B
    inclusive; malformed specs raise ValueError at config time, not at
    step A mid-run."""
    if not spec:
        return None
    a, sep, b = str(spec).partition(":")
    try:
        lo, hi = int(a), int(b)
    except ValueError:
        lo = hi = 0
    if not sep or lo < 1 or hi < lo:
        raise ValueError(
            f"bad --profile_steps {spec!r}; want 'A:B' with 1 <= A <= B "
            f"(1-indexed global train steps, inclusive)")
    return lo, hi


class StepWindowProfiler:
    """Windowed profiler capture: start/stop ``jax.profiler`` around a
    global-step range MID-RUN (``--profile_steps A:B``), instead of
    ``--profile``'s whole-run trace — which past toy scale is unusable
    (gigabytes of timeline for minutes of steady state that all looks
    the same).  The window quantizes to dispatch boundaries: under a
    K-step fused dispatch the trace covers the dispatches that contain
    steps A..B (there is no narrower host-observable boundary).  A run
    resumed past B never starts; resumed inside the window, it captures
    the remainder.

    ``start_fn``/``stop_fn`` are the test seam (default
    ``jax.profiler.start_trace``/``stop_trace``); a profiler failure
    logs and disables itself — observability must never kill training.
    """

    def __init__(self, log_dir: str, start_step: int, stop_step: int,
                 start_fn: Optional[Callable[[str], None]] = None,
                 stop_fn: Optional[Callable[[], None]] = None,
                 log: Callable[[str], None] = print):
        self.log_dir = log_dir
        self.a = int(start_step)
        self.b = int(stop_step)
        self._start = start_fn or (lambda d: jax.profiler.start_trace(d))
        self._stop = stop_fn or jax.profiler.stop_trace
        self._log = log
        self.active = False
        self.done = False
        self.started_at: Optional[int] = None
        self.stopped_at: Optional[int] = None

    def before_dispatch(self, completed_steps: int, n_steps: int = 1
                        ) -> None:
        """Called with the global steps completed so far, before a
        dispatch that will run steps ``completed+1 .. completed+n``."""
        if self.done or self.active:
            return
        if completed_steps >= self.b:
            self.done = True       # resumed past the window: never start
            return
        if completed_steps + n_steps >= self.a:
            try:
                self._start(self.log_dir)
            except Exception as e:
                self._log(f"[profile] could not start the step-window "
                          f"trace ({e!r}); --profile_steps disabled for "
                          f"this run")
                self.done = True
                return
            self.active = True
            self.started_at = completed_steps
            self._log(f"[profile] trace started before step "
                      f"{completed_steps + 1} (window {self.a}:{self.b}) "
                      f"-> {self.log_dir}")

    def after_dispatch(self, completed_steps: int,
                       fence: Optional[Callable[[], None]] = None) -> None:
        """Called after a dispatch with the new completed-step count;
        ``fence`` (e.g. a metrics readback) runs before stop so the
        trace includes the device work of the window's last dispatch."""
        if not self.active or completed_steps < self.b:
            return
        if fence is not None:
            try:
                fence()
            except Exception:
                pass
        self._finish(completed_steps)

    def close(self) -> None:
        """End-of-run/epoch-exhaustion: stop a still-open trace (the run
        ended before step B) so the capture is never lost."""
        if self.active:
            self._finish(None)

    def _finish(self, completed_steps: Optional[int]) -> None:
        try:
            self._stop()
        except Exception as e:
            self._log(f"[profile] stop_trace failed: {e!r}")
        self.active = False
        self.done = True
        self.stopped_at = completed_steps
        at = (f"after step {completed_steps}" if completed_steps is not None
              else "at run end (window unfinished)")
        self._log(f"[profile] trace stopped {at}; view with "
                  f"tensorboard --logdir {self.log_dir}")


@contextlib.contextmanager
def trace_profile(log_dir: Optional[str]) -> Iterator[None]:
    """`with trace_profile('/tmp/trace'):` captures a TensorBoard-viewable
    profiler trace when log_dir is set; no-op otherwise."""
    if not log_dir:
        yield
        return
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def peak_memory_bytes(device: Optional[jax.Device] = None) -> Optional[int]:
    """Peak device memory if the backend exposes runtime stats (TPU
    does; CPU does not and gets None — the Trainer then omits peak_mem
    from its epoch log)."""
    device = device or jax.local_devices()[0]
    stats = getattr(device, "memory_stats", lambda: None)()
    if not stats:
        return None
    return stats.get("peak_bytes_in_use")


def memory_watermarks(device: Optional[jax.Device] = None
                      ) -> Optional[dict]:
    """{"peak_bytes", "bytes_in_use"} from the backend's runtime memory
    stats, or None where they don't exist (CPU) — the
    per-epoch device memory watermark the telemetry ``memory`` events
    carry (train/loop.py)."""
    device = device or jax.local_devices()[0]
    stats = getattr(device, "memory_stats", lambda: None)()
    if not stats:
        return None
    return {"peak_bytes": int(stats.get("peak_bytes_in_use", 0) or 0),
            "bytes_in_use": int(stats.get("bytes_in_use", 0) or 0)}


def compiled_memory_bytes(compiled) -> Optional[int]:
    """Static peak estimate from a compiled executable's memory analysis:
    temp + argument + output − aliased (donated buffers are BOTH an
    argument and an output — counting them twice would overstate a
    donating train step by roughly the whole train state).  Available on
    every backend, including ones without runtime memory_stats."""
    try:
        ma = compiled.memory_analysis()
        if ma is None:
            return None
        total = 0
        for field in ("temp_size_in_bytes", "argument_size_in_bytes",
                      "output_size_in_bytes"):
            total += int(getattr(ma, field, 0) or 0)
        total -= int(getattr(ma, "alias_size_in_bytes", 0) or 0)
        return total if total > 0 else None
    except Exception:
        return None
