"""DecodeEngine: the AOT program families behind KV-cache generation.

Two observed program families, both enumerated at warmup and FIXED —
the decode twin of the classifier engine's per-bucket predict cells:

  ``<name>:prefill:L<bucket>``  one per prompt bucket, batch 1: full
      causal forward over the padded prompt, returning per-layer K/V,
      the last-real-position logits and the first sampled token;
  ``<name>:decode:P<pages>``    one per page count: a single decode
      step over the WHOLE slot batch with the attention window
      statically sliced to pages*page columns.

Every program routes through the r15 observatory (retrace detector +
compile telemetry) and rides the r17 executable cache when armed, so a
restarted decode replica deserializes its programs in
~``restart_cached_mttr_s`` instead of recompiling.  Ragged request
traffic can therefore never retrace: request length picks a bucket
(data.loader.select_bucket, the training pipeline's one rule), live
sequence length picks a page count, and both domains are finite —
pinned by tests/test_decode.py's program-set test.

The per-bucket cache INSERT programs (scattering prefill K/V into a
slot) are jitted but deliberately NOT observed: they are trivial
scatters whose set is bounded by the bucket list, not a model program
family worth a pin.

The step is synchronous (``np.asarray`` on the sampled tokens) — on
CPU simulation the dispatch is the cost anyway; a TPU deployment would
pipeline host admission against the device step, which changes none of
the program shapes.
"""

from __future__ import annotations

import time
import warnings
from typing import Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from faster_distributed_training_tpu.models.decode import (SamplingCfg,
                                                           decode_spec,
                                                           decode_step,
                                                           prefill)
from faster_distributed_training_tpu.serve.decode.cache import PagedKVCache
from faster_distributed_training_tpu.serve.engine import (_DONATION_WARNING,
                                                          ServingState)


class DecodeEngine:
    """Paged KV-cache generation over one frozen LM variable bundle.

    ``device`` pins the replica to one chip (the SNIPPETS [3] 1D
    replicated layout decode defaults to); ``mesh`` is the model-
    sharded exception for checkpoints that don't fit a chip.  ``donate``
    None = donate: the cache buffers round-trip through every
    step/insert program."""

    def __init__(self, model, state: ServingState, buckets: Sequence[int],
                 batch_size: int = 4, page: int = 16, max_pages: int = 0,
                 sampling: Optional[SamplingCfg] = None,
                 donate: Optional[bool] = None, device=None, mesh=None,
                 name: str = "serve",
                 log: Callable[[str], None] = print):
        import jax

        self.spec = decode_spec(model)
        self.buckets = tuple(sorted({int(b) for b in buckets}))
        self.batch_size = int(batch_size)
        self.page = int(page)
        if max_pages <= 0:
            # auto: room for the longest prompt bucket plus one page of
            # generation headroom, capped by the position table
            import math
            max_pages = math.ceil(
                min(max(self.buckets) + page, self.spec.maxlen) / page)
        self.max_pages = int(max_pages)
        if max(self.buckets) > self.page * self.max_pages:
            raise ValueError(
                f"largest bucket {max(self.buckets)} exceeds the cache "
                f"capacity {self.page * self.max_pages} "
                f"(= page {self.page} x max_pages {self.max_pages})")
        self.sampling = sampling or SamplingCfg()
        self.name = name
        self.device = device
        self.mesh = mesh
        self._log = log
        self.donate = True if donate is None else bool(donate)
        params = state.params["model"]
        if device is not None:
            params = jax.device_put(params, device)
        self._params = params
        self.cache = PagedKVCache(self.spec, self.batch_size, self.page,
                                  self.max_pages)
        if device is not None:
            self.cache.k = jax.device_put(self.cache.k, device)
            self.cache.v = jax.device_put(self.cache.v, device)

        spec, samp = self.spec, self.sampling

        def _prefill(p, tokens, length, req_ids):
            return prefill(spec, samp, p, tokens, length, req_ids)

        self._prefill_jit = jax.jit(_prefill)
        self._decode_jits: Dict[int, object] = {}
        dkw = dict(donate_argnums=(1, 2)) if self.donate else {}
        for pages in range(1, self.max_pages + 1):
            window = pages * self.page

            def _step(p, k, v, token, pos, active, req_ids, _w=window):
                return decode_step(spec, samp, _w, p, k, v, token, pos,
                                   active, req_ids)

            self._decode_jits[pages] = jax.jit(_step, **dkw)
        self._insert_jits: Dict[int, object] = {}
        ikw = dict(donate_argnums=(0, 1)) if self.donate else {}
        for b in self.buckets:

            def _insert(k, v, pk, pv, slot, _L=b):
                k = k.at[:, slot, :, :_L, :].set(pk[:, 0])
                v = v.at[:, slot, :, :_L, :].set(pv[:, 0])
                return k, v

            self._insert_jits[b] = jax.jit(_insert, **ikw)
        self._prefill_compiled: Dict[int, object] = {}
        self._decode_compiled: Dict[int, object] = {}
        self._insert_compiled: Dict[int, object] = {}
        self.steps = 0
        self.prefills = 0

    # -- compilation -------------------------------------------------------

    def _mesh_ctx(self):
        import contextlib
        return self.mesh if self.mesh is not None \
            else contextlib.nullcontext()

    def _observe(self, pname: str, jitted, args, sig_argnums) -> object:
        """engine.InferenceEngine.compile_bucket's observe-else-AOT-else-
        plain-jit ladder, shared by all three program families."""
        from faster_distributed_training_tpu.telemetry import programs
        compiled = None
        with warnings.catch_warnings():
            warnings.filterwarnings("ignore", message=_DONATION_WARNING)
            with self._mesh_ctx():
                obs = programs.get_observatory() if pname else None
                if obs is not None:
                    sig = programs.args_signature(args, sig_argnums)
                    compiled = obs.observe_compile(pname, jitted, args,
                                                   sig=sig)
                if compiled is None:
                    try:
                        compiled = jitted.lower(*args).compile()
                    except Exception as e:
                        if pname:
                            self._log(f"[decode] AOT compile of {pname} "
                                      f"failed ({e!r}); plain jit "
                                      f"dispatch serves it")
                        compiled = jitted
        return compiled

    def _compile_prefill(self, bucket: int) -> None:
        if bucket in self._prefill_compiled:
            return
        args = (self._params,
                np.zeros((1, bucket), np.int32),
                np.ones((1,), np.int32),
                np.zeros((1,), np.int32))
        self._prefill_compiled[bucket] = self._observe(
            f"{self.name}:prefill:L{bucket}", self._prefill_jit, args,
            (1, 2, 3))

    def _compile_decode(self, pages: int) -> None:
        if pages in self._decode_compiled:
            return
        B = self.batch_size
        args = (self._params, self.cache.k, self.cache.v,
                np.zeros((B,), np.int32), np.zeros((B,), np.int32),
                np.zeros((B,), bool), np.zeros((B,), np.int32))
        self._decode_compiled[pages] = self._observe(
            f"{self.name}:decode:P{pages}", self._decode_jits[pages],
            args, (3, 4, 5, 6))

    def _compile_insert(self, bucket: int) -> None:
        if bucket in self._insert_compiled:
            return
        pk = np.zeros((self.spec.n_layers, 1, self.spec.h, bucket,
                       self.spec.d_k), np.dtype(self.cache.k.dtype))
        args = (self.cache.k, self.cache.v, pk, pk,
                np.int32(0))
        self._insert_compiled[bucket] = self._observe(
            "", self._insert_jits[bucket], args, ())

    def warmup(self) -> float:
        """Compile the ENTIRE program set before any request arrives —
        the decode heartbeat timeout never has to cover a compile, and
        with the executable cache armed a restarted replica is serving
        in deserialize time.  Returns wall seconds."""
        t0 = time.monotonic()
        for b in self.buckets:
            self._compile_prefill(b)
            self._compile_insert(b)
        for p in range(1, self.max_pages + 1):
            self._compile_decode(p)
        return time.monotonic() - t0

    # -- the hot path ------------------------------------------------------

    def admit(self, tokens: np.ndarray, bucket: int,
              req_id: int) -> Tuple[int, int]:
        """Prefill one prompt and swap its K/V into a free slot.
        Returns (slot, first_token).  Caller guarantees a free slot
        exists (scheduler admission gate)."""
        import jax

        slot = self.cache.free_slot()
        if slot is None:
            raise RuntimeError("admit called with no free slot")
        t = np.asarray(tokens, np.int32).reshape(-1)[:bucket]
        length = max(len(t), 1)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(t)] = t
        self._compile_prefill(bucket)
        self._compile_insert(bucket)
        args = (padded, np.asarray([length], np.int32),
                np.asarray([req_id], np.int32))
        if self.device is not None:
            args = jax.device_put(args, self.device)
        with self._mesh_ctx():
            pk, pv, _logits, first = self._prefill_compiled[bucket](
                self._params, *args)
            self.cache.k, self.cache.v = self._insert_compiled[bucket](
                self.cache.k, self.cache.v, pk, pv, np.int32(slot))
        first_token = int(np.asarray(first)[0])
        self.cache.admit(slot, req_id, length, first_token)
        self.prefills += 1
        return slot, first_token

    def prefill_logits(self, tokens: np.ndarray,
                       bucket: int) -> np.ndarray:
        """The (vocab,) fp32 logits at the prompt's last position —
        the parity probe tests compare against ``model.apply`` under
        the causal mask (no cache mutation)."""
        t = np.asarray(tokens, np.int32).reshape(-1)[:bucket]
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(t)] = t
        self._compile_prefill(bucket)
        with self._mesh_ctx():
            _pk, _pv, logits, _first = self._prefill_compiled[bucket](
                self._params, padded,
                np.asarray([max(len(t), 1)], np.int32),
                np.zeros((1,), np.int32))
        return np.asarray(logits)[0]

    def step(self) -> Tuple[np.ndarray, int]:
        """One decode step over every active slot.  Returns
        (next_tokens[batch], pages) — callers read next_tokens only at
        active slots.  The cache's slot table is advanced."""
        cache = self.cache
        pages = cache.window_pages()
        self._compile_decode(pages)
        token = cache.tokens.copy()
        pos = cache.lengths.copy()          # the column this step writes
        pos[~cache.active] = 0
        with self._mesh_ctx():
            cache.k, cache.v, nxt = self._decode_compiled[pages](
                self._params, cache.k, cache.v, token,
                pos, cache.active.copy(), cache.req_ids.copy())
        nxt = np.asarray(nxt)
        cache.advance(nxt)
        self.steps += 1
        return nxt, pages

    def active_count(self) -> int:
        return int(self.cache.active.sum())
