"""Fixed-size device-resident window over an on-disk stream dataset.

The beyond-HBM tier between the host loader and full device residency:
the train split lives on disk (reader.py mmaps), and only a fixed
WINDOW of upcoming batches is resident on device at a time.  The window
is double-buffered — while the dispatch loop trains through buffer i
(``window`` batches, gathered in-graph by ``dynamic_index`` exactly like
the sharded-resident batch-major view), a background producer thread is
already disk-gathering AND ``device_put``-ing buffer i+1, so the H2D
stream hides under compute.  The producer rides
:class:`~faster_distributed_training_tpu.data.loader.PrefetchIterator`
(depth 1), inheriting its cancel/drain lifecycle: an abnormal epoch exit
(injected fault, preemption, crash) closes the window and the producer
thread is cancelled, drained and joined — never left blocked on a full
queue (the r8 contract, re-used rather than re-invented).

Batch order is ``loader.pod_epoch_order``'s pure ``(seed, epoch, step)``
algebra — identical to both resident layouts and the host loader — and
host ``pi`` materializes ONLY its own ``local_bs`` rows of each global
batch (per-host file reads; the device buffer is assembled with
``make_array_from_process_local_data`` on real pods).  Mid-epoch resume
is therefore a pure SEEK: ``epoch_window(epoch, start_step)`` begins the
refill stream at ``start_step`` and batch contents are a function of the
batch index alone, so a killed-at-N streamed run resumes bitwise on the
uninterrupted reference (tests/test_stream.py pins this against the
resident path).

Telemetry: each refill emits a ``stream_refill`` event (+ a
``stream_refill`` span from the producer thread, so the cost also lands
in the span breakdown / XLA trace vocabulary), and each buffer swap the
consumer had to WAIT for emits a ``stream_stall`` event — the numerator
of ``Trainer.stream_stall_pct`` (<1% steady-state target; not measured
on the chip)."""

from __future__ import annotations

import time
import warnings
from typing import Dict, Optional, Tuple

import jax
import numpy as np

from faster_distributed_training_tpu.data.loader import (PrefetchIterator,
                                                         pod_epoch_order)
from faster_distributed_training_tpu.telemetry import spans


class DiskStreamSource:
    """Run-scoped streaming source: owns the reader + window geometry.

    Duck-types the fused-dispatch ``resident`` interface with
    ``batch_major=True`` (train/steps.py gathers by ``dynamic_index`` on
    the unsharded leading axis), so the stream path reuses the resident
    scan program shape — only the leading axis is ``window`` batches
    deep instead of a whole epoch.

    ``process_index``/``process_count`` default to the real runtime and
    are the simulation seam the tier-1 tests use (a single process
    materializes any simulated host's buffer and checks it byte-equal
    to ``pod_epoch_order``'s slice)."""

    batch_major = True
    program_key = "stream"

    def __init__(self, dataset, batch_size: int, seed: int = 0,
                 mesh=None, shuffle: bool = True, window_batches: int = 8,
                 steps_per_dispatch: int = 1, max_len: int = 512,
                 process_index: Optional[int] = None,
                 process_count: Optional[int] = None):
        self.dataset = dataset
        self.pc = (jax.process_count() if process_count is None
                   else int(process_count))
        self.pi = (jax.process_index() if process_index is None
                   else int(process_index))
        self.batch_size = int(batch_size)          # GLOBAL batch
        if self.batch_size % self.pc:
            raise ValueError(f"global batch {self.batch_size} not divisible "
                             f"by {self.pc} processes")
        self.local_bs = self.batch_size // self.pc
        self.seed = int(seed)
        self.shuffle = bool(shuffle)
        self.mesh = mesh
        self.max_len = int(max_len)
        self.n = len(dataset)
        self.is_text = bool(getattr(dataset, "is_text", False))
        self.seq_len = (min(int(getattr(dataset, "seq_len", 0) or 0),
                            self.max_len) if self.is_text else 0)
        self.steps_per_epoch = (self.n // self.pc) // self.local_bs
        if self.steps_per_epoch < 1:
            raise ValueError(
                f"stream dataset ({self.n} samples / {self.pc} hosts) "
                f"smaller than one local batch ({self.local_bs}) — "
                f"nothing to train on")
        k = max(int(steps_per_dispatch or 1), 1)
        w = max(int(window_batches or 1), 1)
        if w % k:
            rounded = -(-w // k) * k
            warnings.warn(
                f"stream window of {w} batches is not a multiple of "
                f"steps_per_dispatch={k}; rounding up to {rounded} so "
                f"buffer boundaries stay dispatch-aligned (a mid-group "
                f"boundary would change the K-grouping between a resumed "
                f"and an uninterrupted run)", stacklevel=2)
            w = rounded
        self.window = w            # batches per buffer (x2 double-buffered)
        # per-sample DEVICE bytes: the text flavor materializes
        # tokens + token_types + mask (int32, seq_len wide each) + label
        # into every buffer — 3x the on-disk tokens row plus 4 — so the
        # HBM-budget log line reflects what actually lands on device
        row_dev = (3 * self.seq_len * 4 + 4 if self.is_text
                   else int(dataset.row_bytes()))
        # PEAK device bytes: up to 3 buffers alive at once — one being
        # trained, one staged in the queue, one transiently in flight in
        # the producer's device_put (_EpochWindow docstring)
        self.nbytes = 3 * self.window * self.local_bs * row_dev
        self._sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from faster_distributed_training_tpu.parallel.sharding import (
                batch_spec)
            self._sharding = NamedSharding(mesh, P(None, *batch_spec(mesh)))
        # signature-uniformity placeholder for the fused step's `order`
        # arg (batch_major dispatches never index through it)
        self._dummy_order = None

    @property
    def dummy_order(self):
        if self._dummy_order is None:
            self._dummy_order = jax.device_put(np.zeros(1, np.int32))
        return self._dummy_order

    def epoch_order(self, epoch: int) -> np.ndarray:
        """The epoch's GLOBAL batch stream (host-side): pod_epoch_order's
        flat index array, entry ``b*bs + pi*lbs + j`` = host pi's j-th
        sample of global batch b — the ONE algebra all data paths share."""
        return pod_epoch_order(self.n, epoch, self.seed, self.shuffle,
                               self.pc, self.local_bs)

    def host_buffer(self, order: np.ndarray, base: int, hi: int
                    ) -> Dict[str, np.ndarray]:
        """THIS host's rows of global batches [base, hi) as stacked host
        arrays ``[window, local_bs, ...]`` — the pure (order, range) ->
        bytes function the refill thread runs and the byte-equality
        tests pin directly.  A tail range shorter than the window leaves
        the unused trailing slots zeroed (never consumed: the dispatch
        loop caps at steps_per_epoch)."""
        nb = hi - base
        # order.reshape(steps, pc, lbs)[b, pi] = host pi's rows of batch b
        idx = order.reshape(-1, self.pc, self.local_bs)[base:hi, self.pi]
        rows = self._rows(idx.reshape(-1))
        out = {}
        for k, v in rows.items():
            v = v.reshape((nb, self.local_bs) + v.shape[1:])
            if nb < self.window:
                v = np.concatenate(
                    [v, np.zeros((self.window - nb,) + v.shape[1:],
                                 v.dtype)])
            out[k] = np.ascontiguousarray(v)
        return out

    def _rows(self, flat_idx: np.ndarray) -> Dict[str, np.ndarray]:
        # text goes through encode_batch so the leaf set (tokens/
        # token_types/mask/label) is byte-identical to what the host and
        # resident paths feed the same program — the cross-path bitwise
        # contract; images gather the stored leaves directly
        if self.is_text:
            return dict(self.dataset.encode_batch(flat_idx, self.max_len))
        return self.dataset.gather(flat_idx)

    def _put(self, host: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
        if self._sharding is not None:
            if jax.process_count() > 1:
                return {k: jax.make_array_from_process_local_data(
                            self._sharding, v) for k, v in host.items()}
            return {k: jax.device_put(v, self._sharding)
                    for k, v in host.items()}
        return {k: jax.device_put(v) for k, v in host.items()}

    def epoch_window(self, epoch: int, start_step: int = 0
                     ) -> "_EpochWindow":
        return _EpochWindow(self, epoch, start_step)


class _EpochWindow:
    """One epoch's double-buffered refill stream (see module docstring).

    The producer generator disk-gathers + device_puts one buffer per
    iteration; PrefetchIterator(depth=1) runs it on a background thread
    with the r8 cancel/drain/join lifecycle.  At any moment at most one
    buffer is being trained on, one is staged ready, and one is in
    flight in the producer — the device window is bounded by
    ~3 x window x local_bs rows per host."""

    def __init__(self, src: DiskStreamSource, epoch: int,
                 start_step: int = 0):
        self.src = src
        self.epoch = int(epoch)
        self.start_step = int(start_step)
        self.stall_s = 0.0
        self.stalls = 0
        self.refills = 0
        self.closed = False
        self._cur: Optional[Tuple[int, int, Dict[str, jax.Array]]] = None
        order = src.epoch_order(epoch)
        steps, w = src.steps_per_epoch, src.window

        def produce():
            for base in range(self.start_step, steps, w):
                hi = min(base + w, steps)
                t0 = time.monotonic()
                with spans.span("stream_refill"):
                    host = src.host_buffer(order, base, hi)
                    t1 = time.monotonic()
                    dev = src._put(host)
                t2 = time.monotonic()
                self.refills += 1
                rec = spans.get_recorder()
                if rec is not None:
                    rec.record_event(
                        "stream_refill", epoch=self.epoch, base=base,
                        batches=hi - base,
                        bytes=int(sum(v.nbytes for v in host.values())),
                        read_ms=round((t1 - t0) * 1e3, 3),
                        h2d_ms=round((t2 - t1) * 1e3, 3))
                yield (base, hi, dev)

        self._it = PrefetchIterator(produce(), depth=1)

    def buffer_for(self, n: int) -> Tuple[int, int, Dict[str, jax.Array]]:
        """The device buffer covering batch ``n`` as ``(base, hi, data)``.
        Advancing past the current buffer blocks until the background
        refill has it staged — that wait IS the stream stall the <1%
        target bounds, recorded per swap as a ``stream_stall`` event."""
        cur = self._cur
        if cur is not None and cur[0] <= n < cur[1]:
            return cur
        t0 = time.monotonic()
        try:
            cur = next(self._it)
        except StopIteration:
            raise RuntimeError(
                f"stream window exhausted at batch {n} (epoch "
                f"{self.epoch}: {self.src.steps_per_epoch} steps from "
                f"{self.start_step}) — consumer/producer ranges disagree")
        wait = time.monotonic() - t0
        if cur[0] > n or n >= cur[1]:
            raise RuntimeError(
                f"stream window skew: batch {n} requested, buffer "
                f"[{cur[0]}, {cur[1]}) arrived — the consumer must "
                f"advance monotonically from start_step")
        self.stall_s += wait
        self.stalls += 1
        rec = spans.get_recorder()
        if rec is not None:
            rec.record_event("stream_stall", epoch=self.epoch, step=n,
                             wait_ms=round(wait * 1e3, 3))
        self._cur = cur
        return cur

    def close(self) -> None:
        """Cancel + drain + join the producer (idempotent; safe at any
        point — the Trainer calls it on EVERY epoch exit, normal or
        abnormal, so an injected fault or preemption can never strand
        the refill thread blocked on a full queue)."""
        if self.closed:
            return
        self.closed = True
        self._cur = None
        self._it.close()
