"""Flash attention: Pallas TPU forward kernel + recompute backward.

TPU-first replacement for the reference's dense ScaledDotProduct
(transformer.py:180-193).  Design:

  * forward — a Pallas kernel tiled (batch·head, query-block) with K/V
    resident in VMEM: one MXU matmul for scores, row-softmax in fp32,
    one MXU matmul for the context.  Probabilities never touch HBM.
    Attention-prob dropout (training) is an in-kernel index-hash mask
    (ops.attention.dropout_keep) — still no HBM probabilities.
  * backward — On TPU the default inside the monolithic envelope is
    now the SAVED-STATS Pallas kernel pair (r6, VERDICT r5 #3 — the
    L=512 retune): the forward emits the row lse beside the context,
    and the backward rebuilds exactly-normalized probabilities as
    p = exp(s - lse) with delta = Σ dO·out precomputed in XLA from the
    saved primal out — deleting the out-recompute matmul and both
    softmax row sweeps per q-block (5 MXU passes instead of 6) and
    admitting a one-step-larger backward q-tile (_bwd_block_q_stats).
    Residuals grow by lse ([N, Lq] fp32) and out (alive anyway).
    FDT_FLASH_SAVE_STATS=0 restores the r5 recompute-in-backward
    kernel (residuals just (q, k, v, mask, seed); softmax stats
    recomputed per q-block — measured faster than BOTH XLA-derived
    VJPs at every size tried on v5e: L=512 B=64: 6.9 vs 10.2 ms
    dense-VJP; L=2048 B=4: 9.0 vs 11.3/14.3).  Kill-switch
    FDT_DISABLE_PALLAS_BWD=1 restores the measured two-branch VJP
    policy (dense under a ~2 GB score budget — overridable via
    FDT_DENSE_BWD_BUDGET_MB — blockwise scan beyond), which is also
    the off-TPU path.  The monolithic kernels' padding-mask bias is no
    longer H-repeated in XLA: it stays [B, Lk] and heads share their
    batch row through the bias index map (_bias_operand).
  * long context — beyond the monolithic kernels' measured VMEM
    envelope (Lk·D > ~8k·64 fwd / ~4k·64 bwd) the K-BLOCKED
    FlashAttention-2-style kernels take over: grid over (q-tile,
    k-tile) with running softmax stats in VMEM scratch, forward emits
    the row lse, backward = two kernels (dq over the q-grid, dk/dv
    over the k-grid) driven by the saved (out, lse) — O(tile) VMEM,
    NO Lk cap, residuals stay O(L·D).
  * non-TPU targets (tests, CPU sim) use the blockwise path; the
    test-only FDT_FORCE_PALLAS_INTERPRET=1 seam exercises both kernels
    in interpreter mode on CPU.  ops/pallas_target.py owns that
    decision for every kernel in the package: a TPU target never
    interprets.

Head-dim support set (VERDICT r3 #7): the K-blocked kernels require
``D <= 128 or D % 128 == 0`` (`_kblocked_supported` — the running-stat
lane broadcast needs a whole number of 128-lane repeats).  A model
whose head dim violates that (e.g. D=192) AND whose Lk·D exceeds the
monolithic envelope routes to the XLA blockwise formulation — slower
but functionally identical; pinned by `tests/test_attention.py::
TestKernelEnvelopeRouting::test_unsupported_head_dim_routes_to_blockwise`.
Odd
head dims inside the monolithic envelope run the monolithic kernels
as usual (Mosaic pads lanes).

Numerics note (ADVICE r3 #3): under autodiff, when the MONOLITHIC
backward is out of envelope (Lk·D/64 in (4096, 8192]) the forward is
computed by the K-BLOCKED kernel so its lse becomes a residual —
while the same-shape primal-only forward takes the monolithic kernel.
Both are exact streaming softmax, but the accumulation order differs,
so grad-traced vs inference outputs at those shapes diverge by normal
float rounding (~1e-3 bf16 / ~1e-6 fp32).  Intentional trade: saving
the lse avoids any full-row recompute in the backward.

Per-head K/V for supported workloads fits VMEM comfortably (e.g.
L=512, D=64, fp32 → 128 KiB per tensor of the ~16 MiB budget); longer
sequences shard L over the `sp` mesh axis first (ops/ring_attention.py),
so each shard stays VMEM-sized.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from faster_distributed_training_tpu.ops import pallas_target
from faster_distributed_training_tpu.ops.attention import (
    NEG_INF, blockwise_attention, dense_attention_reference, mask_to_bias)


def _use_pallas() -> bool:
    return pallas_target.flash_kernels()


def _pack_seed(dropout_seed, bh0=None) -> jax.Array:
    """(3,) uint32 dropout operand [seed, b0, h0]: the hash seed plus
    the caller's GLOBAL (batch, head) shard offsets.  Head-sharded
    callers (parallel/kernel_shard.py) pass their shard origin as
    ``bh0``; unsharded callers leave it (0, 0), which — together with
    h_glob == local H — makes the in-kernel global index reduce to the
    plain flattened b*H+h bit-for-bit (nothing changes for 1D runs)."""
    seed = (jnp.uint32(0) if dropout_seed is None
            else jnp.asarray(dropout_seed, jnp.uint32))
    if bh0 is None:
        b0 = h0 = jnp.uint32(0)
    else:
        b0 = jnp.asarray(bh0[0], jnp.uint32)
        h0 = jnp.asarray(bh0[1], jnp.uint32)
    return jnp.stack([seed.reshape(()), b0.reshape(()), h0.reshape(())])


def _bh_from(s_ref, n, h_loc: int, h_glob: int):
    """GLOBAL batch*head dropout stream index for local flattened
    instance ``n`` inside a kernel: (b0 + n//h_loc)*h_glob + h0 +
    n%h_loc, with (b0, h0) read from the packed seed operand.  The
    global index keeps the hash-dropout masks placement-invariant when
    the heads are sharded over tp (kernel_shard.flash_attention_sharded)
    — the same contract ops/fused_ffn.py keeps for sharded rows."""
    b0 = s_ref[0, 1].astype(jnp.int32)
    h0 = s_ref[0, 2].astype(jnp.int32)
    return (b0 + n // h_loc) * h_glob + h0 + n % h_loc


def _bh_array(B: int, H: int, seed3: jax.Array, h_glob: int) -> jax.Array:
    """[B,H,1,1] global stream indices — the XLA-path twin of _bh_from
    (blockwise/dense fallbacks take the whole index array at once)."""
    b0 = seed3[1].astype(jnp.int32)
    h0 = seed3[2].astype(jnp.int32)
    return ((b0 + jnp.arange(B, dtype=jnp.int32))[:, None] * h_glob
            + h0 + jnp.arange(H, dtype=jnp.int32)[None, :])[:, :, None, None]


def _bias_operand(key_bias, n_heads: int, lk: int):
    """(bias operand, index_map, has_bias) for the MONOLITHIC kernels.

    The bias stays [B, 1, Lk] and every head reads its batch row through
    the grid index map (n // H) — fusing the mask path into the kernel's
    addressing instead of materializing the H-repeated [B·H, Lk] copy
    the r5 kernels built in XLA per call (the repeat was pure HBM
    traffic + a fusion barrier before the kernel).  key_bias=None keeps
    a single shared zeros row (same block every step — the pipeline
    never re-fetches it) and has_bias=False lets the kernel skip the add
    entirely."""
    if key_bias is None:
        return (jnp.zeros((1, 1, lk), jnp.float32),
                (lambda *idx: (0, 0, 0)), False)
    b = key_bias.astype(jnp.float32)
    b = b.reshape(b.shape[0], 1, lk)
    return b, (lambda n, *idx: (n // n_heads, 0, 0)), True


def _flash_fwd_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                      key_bias: Optional[jax.Array], n_heads: int,
                      block_q: int, dropout_rate: float = 0.0,
                      seed3: Optional[jax.Array] = None,
                      emit_lse: bool = False,
                      h_glob: Optional[int] = None):
    """q/k/v [N, L, D] (N = B·H), key_bias [B, Lk] additive or None
    (heads share their batch row via the bias index map — no H-repeat).

    dropout_rate > 0 applies ops.attention.dropout_keep in-kernel: the
    keep mask is a pure hash of (seed, GLOBAL bh, global q row, k col)
    — seed3 is the _pack_seed [seed, b0, h0] operand and h_glob the
    global head count, so head-sharded shards regenerate the exact
    single-device mask — and the recompute backward regenerates it
    exactly without any HBM mask.

    emit_lse=True additionally returns the row lse [N, Lq] fp32 (stored
    at _KB_LANES lanes like the K-blocked kernels, sliced outside) so
    the saved-stats monolithic backward can skip the in-kernel softmax
    recompute — the L=512 retune (VERDICT r5 #3)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu  # noqa: F401

    from faster_distributed_training_tpu.ops.attention import dropout_keep

    N, Lq, D = q.shape
    Lk = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    block_q = min(block_q, Lq)
    nq = -(-Lq // block_q)
    pad_q = nq * block_q - Lq
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    bias, bias_map, has_bias = _bias_operand(key_bias, n_heads, Lk)
    seed = (seed3 if seed3 is not None
            else _pack_seed(None)).reshape(1, 3).astype(jnp.uint32)
    hg = h_glob if h_glob is not None else n_heads

    def kernel(q_ref, k_ref, v_ref, b_ref, s_ref, o_ref, *lse_ref):
        qb = q_ref[0]                                   # [block_q, D]
        s = jax.lax.dot_general(
            qb, k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [block_q, Lk]
        if has_bias:
            s = s + b_ref[0]
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        if dropout_rate > 0.0:
            bh = _bh_from(s_ref, pl.program_id(0), n_heads, hg)
            qrow = (pl.program_id(1) * block_q
                    + jax.lax.broadcasted_iota(jnp.int32, (block_q, Lk), 0))
            kcol = jax.lax.broadcasted_iota(jnp.int32, (block_q, Lk), 1)
            p = p * dropout_keep(s_ref[0, 0], bh, qrow, kcol, dropout_rate)
        ctx = jnp.dot(p.astype(v_ref.dtype), v_ref[0],
                      preferred_element_type=jnp.float32)
        o_ref[0] = (ctx / l).astype(o_ref.dtype)
        if emit_lse:
            lse_ref[0][0] = jnp.broadcast_to(m + jnp.log(l),
                                             (block_q, _KB_LANES))

    out_specs = [pl.BlockSpec((1, block_q, D), lambda n, i: (n, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((N, nq * block_q, D), q.dtype)]
    if emit_lse:
        out_specs.append(
            pl.BlockSpec((1, block_q, _KB_LANES), lambda n, i: (n, i, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((N, nq * block_q, _KB_LANES), jnp.float32))
    res = pl.pallas_call(
        kernel,
        grid=(N, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda n, i: (n, i, 0)),
            pl.BlockSpec((1, Lk, D), lambda n, i: (n, 0, 0)),
            pl.BlockSpec((1, Lk, D), lambda n, i: (n, 0, 0)),
            pl.BlockSpec((1, 1, Lk), bias_map),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=pallas_target.interpret(),
        name="fdt_flash_fwd_lse" if emit_lse else "fdt_flash_fwd",
    )(q, k, v, bias, seed)
    if emit_lse:
        return res[0][:, :Lq, :], res[1][:, :Lq, 0]
    return res[0][:, :Lq, :]


# ---------------------------------------------------------------------------
# K-blocked (FlashAttention-2-style) kernels — O(tile) VMEM, no Lk cap.
# The monolithic kernels above stay the default inside their measured
# envelope (they were faster at every size tried); these take over beyond
# it, replacing the old fall-off-the-cliff route to the XLA blockwise VJP
# (r2 ladder: 21.4 ms -> 78.8 ms at L=8192).  Running softmax statistics
# are carried in VMEM scratch at 128 lanes (the Mosaic minimum tile; the
# same layout the official jax.experimental TPU kernel uses), all lanes
# holding the same per-row value.  The forward also emits the row LSE so
# the backward kernels need no full-row recompute: residuals become
# (q, k, v, bias, seed, out, lse) — still O(L·D), never O(L²).
# ---------------------------------------------------------------------------

_KB_LANES = 128  # lse/delta/m/l lane width (Mosaic min tile)


def _kb_blocks(lq: int, lk: int):
    """(block_q, block_k) tiles: up to 512 square, degraded to the padded
    problem size; block_k a multiple of 128 (lane tiling), block_q a
    multiple of 8 (sublane tiling)."""
    bq = min(512, max(-(-lq // 8) * 8, 8))
    bk = min(512, max(-(-lk // _KB_LANES) * _KB_LANES, _KB_LANES))
    return bq, bk


def _kblocked_supported(d: int) -> bool:
    # the lane-broadcast of l to the accumulator needs D <= 128 or a
    # whole number of 128-lane repeats
    return d <= _KB_LANES or d % _KB_LANES == 0


def _lanes_to(x128, d: int):
    """[rows, 128] all-equal-lanes -> [rows, d]."""
    if d <= _KB_LANES:
        return x128[:, :d]
    return jnp.tile(x128, (1, d // _KB_LANES))


class _Band:
    """The tiles of a causal band (key j <= query i, and with ``window``
    also i - j < window) on a (bq, bk) tiling of an [Lq, Lk] score
    matrix with q and k indexed from the same origin.  ``k_lo/k_hi`` are
    the first and last key tile a query tile needs, ``q_lo/q_hi`` the
    first and last query tile a key tile feeds; they take a traced grid
    index inside an index map or a kernel (``xp=jnp``) and numpy ranges
    for the static widths ``nkb``/``nqb`` of the two grids.  A grid
    visits ``lo + step`` and skips the steps past ``hi``; its index maps
    clamp those to ``hi``, so a skipped step asks for the block the step
    before it held and nothing is fetched."""

    def __init__(self, bq, bk, nq, nk, window):
        self.bq, self.bk, self.nq, self.nk = bq, bk, nq, nk
        self.window = window
        i, j = np.arange(nq), np.arange(nk)
        self.nkb = int(np.max(self.k_hi(i, np) - self.k_lo(i, np))) + 1
        self.nqb = int(np.max(self.q_hi(j, np) - self.q_lo(j, np))) + 1

    def k_lo(self, i, xp=jnp):
        if self.window is None:
            return i * 0
        return xp.maximum(i * self.bq - (self.window - 1), 0) // self.bk

    def k_hi(self, i, xp=jnp):
        return xp.minimum(((i + 1) * self.bq - 1) // self.bk, self.nk - 1)

    def q_lo(self, j, xp=jnp):
        return xp.minimum((j * self.bk) // self.bq, self.nq - 1)

    def q_hi(self, j, xp=jnp):
        if self.window is None:
            return j * 0 + (self.nq - 1)
        return xp.minimum(((j + 1) * self.bk + self.window - 2) // self.bq,
                          self.nq - 1)

    def keep(self, i, j):
        """[bq, bk] bool: the pairs of tile (i, j) inside the band."""
        rows = i * self.bq + jax.lax.broadcasted_iota(
            jnp.int32, (self.bq, self.bk), 0)
        cols = j * self.bk + jax.lax.broadcasted_iota(
            jnp.int32, (self.bq, self.bk), 1)
        keep = cols <= rows
        if self.window is not None:
            keep &= rows - cols < self.window
        return keep


def _kb_pad(q, k, v, key_bias, bq, bk, band: bool = False):
    """Pad q to bq multiples and k/v/bias to bk multiples (bias pads with
    NEG_INF so padded keys carry ~zero probability).  Under a causal
    ``band`` there is no bias: a padded key lies past every real query
    and the band masks it; one shared zeros block stands in for the
    operand."""
    N, Lq, D = q.shape
    Lk = k.shape[1]
    nq, nk = -(-Lq // bq), -(-Lk // bk)
    pad_q, pad_k = nq * bq - Lq, nk * bk - Lk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if band:
        if key_bias is not None:
            raise ValueError("the causal band takes no key-padding mask "
                             "(packed rows carry none)")
        if pad_k:
            k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
        return q, k, v, jnp.zeros((1, 1, bk), jnp.float32), nq, nk
    if key_bias is None:
        key_bias = jnp.zeros((N, Lk), jnp.float32)
    key_bias = key_bias.astype(jnp.float32)
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
        key_bias = jnp.pad(key_bias, ((0, 0), (0, pad_k)),
                           constant_values=NEG_INF)
    return q, k, v, key_bias.reshape(N, 1, nk * bk), nq, nk


def _flash_fwd_kblocked(q: jax.Array, k: jax.Array, v: jax.Array,
                        key_bias, dropout_rate: float = 0.0,
                        seed3=None, n_heads: int = 1,
                        h_glob: Optional[int] = None,
                        causal: bool = False,
                        window: Optional[int] = None):
    """q [N, L, D] (N = B·H), k/v [N // group, L, D]: each key-value head
    serves ``group`` consecutive query heads through the index map, so
    k/v are never repeated in HBM.  ``causal`` (static) keeps key j <=
    query i, ``window`` (static, with causal) also i - j < window: the
    k grid covers only the band's tiles (``_Band``), a step past the
    band's end is skipped, and the pairs of a visited tile outside the
    band are masked in the kernel.  Returns (out [N, Lq, D],
    lse [N, Lq] fp32).  Grid (N, q-block, k-block), k innermost;
    running (m, l, acc) in VMEM scratch; out and lse written on the
    last k step.  l accumulates PRE-dropout probability mass (softmax-
    then-dropout semantics, transformer.py:190-192), dropout applies to
    the value contraction only — matching every other impl.  seed3 /
    n_heads / h_glob: the _pack_seed global-bh dropout convention."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from faster_distributed_training_tpu.ops.attention import dropout_keep

    N, Lq, D = q.shape
    group = N // k.shape[0]
    scale = 1.0 / math.sqrt(D)
    bq, bk = _kb_blocks(Lq, k.shape[1])
    q, k, v, bias, nq, nk = _kb_pad(q, k, v, key_bias, bq, bk, band=causal)
    band = _Band(bq, bk, nq, nk, window) if causal else None
    _check_band(band, window, group, dropout_rate)
    nkv = band.nkb if band else nk          # k steps a query tile takes
    seed = (seed3 if seed3 is not None
            else _pack_seed(None)).reshape(1, 3).astype(jnp.uint32)
    hg = h_glob if h_glob is not None else n_heads
    kreps = bk // _KB_LANES

    def kernel(q_ref, k_ref, v_ref, b_ref, s_ref, o_ref, lse_ref,
               m_scr, l_scr, acc_scr):
        i, jj = pl.program_id(1), pl.program_id(2)
        j = band.k_lo(i) + jj if band else jj

        @pl.when(jj == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        def tile():
            s = jax.lax.dot_general(
                q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale     # [bq, bk]
            if band:
                s = jnp.where(band.keep(i, j), s, NEG_INF)
            else:
                s = s + b_ref[0]
            m_prev, l_prev = m_scr[...], l_scr[...]             # [bq, 128]
            m_curr = jnp.max(s, axis=-1, keepdims=True)         # [bq, 1]
            m_next = jnp.maximum(m_prev, m_curr)                # [bq, 128]
            p = jnp.exp(s - jnp.tile(m_next, (1, kreps)))
            alpha = jnp.exp(m_prev - m_next)
            l_next = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            if dropout_rate > 0.0:
                bh = _bh_from(s_ref, pl.program_id(0), n_heads, hg)
                qrow = i * bq + jax.lax.broadcasted_iota(jnp.int32,
                                                         (bq, bk), 0)
                kcol = j * bk + jax.lax.broadcasted_iota(jnp.int32,
                                                         (bq, bk), 1)
                p = p * dropout_keep(s_ref[0, 0], bh, qrow, kcol,
                                     dropout_rate)
            acc_scr[...] = (acc_scr[...] * _lanes_to(alpha, D)
                            + jnp.dot(p.astype(v_ref.dtype), v_ref[0],
                                      preferred_element_type=jnp.float32))
            m_scr[...], l_scr[...] = m_next, l_next

        if band:
            pl.when(j <= band.k_hi(i))(tile)
        else:
            tile()

        @pl.when(jj == nkv - 1)
        def _fin():
            l = jnp.maximum(l_scr[...], 1e-30)
            o_ref[0] = (acc_scr[...] / _lanes_to(l, D)).astype(o_ref.dtype)
            lse_ref[0] = m_scr[...] + jnp.log(l)

    if band:
        def kv_map(n, i, jj):
            return (n // group,
                    jnp.minimum(band.k_lo(i) + jj, band.k_hi(i)), 0)
        bias_map = lambda n, i, jj: (0, 0, 0)               # noqa: E731
    else:
        kv_map = lambda n, i, j: (n, j, 0)                  # noqa: E731
        bias_map = lambda n, i, j: (n, 0, j)                # noqa: E731

    out, lse = pl.pallas_call(
        kernel,
        grid=(N, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda n, i, j: (n, i, 0)),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, 1, bk), bias_map),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda n, i, j: (n, i, 0)),
            pl.BlockSpec((1, bq, _KB_LANES), lambda n, i, j: (n, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, nq * bq, D), q.dtype),
            jax.ShapeDtypeStruct((N, nq * bq, _KB_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _KB_LANES), jnp.float32),
            pltpu.VMEM((bq, _KB_LANES), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=pallas_target.interpret(),
        name="fdt_flash_fwd_banded" if band else "fdt_flash_fwd_kblocked",
    )(q, k, v, bias, seed)
    return out[:, :Lq], lse[:, :Lq, 0]


def _check_band(band, window, group: int, dropout_rate: float) -> None:
    """What the K-blocked kernels serve: a window only inside a causal
    band, and grouped key-value heads and the band without attention
    dropout (its hash streams are numbered by query head)."""
    if band is None and (window is not None or group != 1):
        raise ValueError("a window or grouped key-value heads need "
                         "causal=True (the banded kernels)")
    if band is not None and dropout_rate > 0.0:
        raise ValueError("the banded kernels take no attention dropout")


def _flash_bwd_kblocked(q, k, v, key_bias, seed3, dropout_rate,
                        out, lse, h_glob: Optional[int] = None,
                        causal: bool = False,
                        window: Optional[int] = None):
    """FA-2-style backward: two k-blocked kernels (dq over the q-grid,
    dk/dv over the k-grid), both O(tile) VMEM — no Lk cap.  Uses the
    forward-saved lse, so probabilities come back exactly normalized
    (p/l = exp(s - lse)) with no in-kernel row sweep; delta = Σ dO·out
    is precomputed in XLA.  q [B, H, L, D], k/v [B, H // group, L, D];
    under ``causal`` (and ``window``) both grids cover only the band's
    tiles, as the forward's does, and dk/dv of a key-value head sum over
    the ``group`` query heads it serves inside the dk/dv kernel's inner
    grid axis.  Returns run(g)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from faster_distributed_training_tpu.ops.attention import dropout_keep

    B, H, Lq, D = q.shape
    Hkv, Lk = k.shape[1], k.shape[2]
    group = H // Hkv
    N, Nkv = B * H, B * Hkv
    scale = 1.0 / math.sqrt(D)
    n3 = lambda x: x.reshape(-1, x.shape[2], x.shape[3])  # noqa: E731
    qn, kn, vn, on = n3(q), n3(k), n3(v), n3(out)
    kb = jnp.repeat(key_bias, H, axis=0) if key_bias is not None else None
    bq, bk = _kb_blocks(Lq, Lk)
    qp, kp, vp, bias, nq, nk = _kb_pad(qn, kn, vn, kb, bq, bk, band=causal)
    band = _Band(bq, bk, nq, nk, window) if causal else None
    _check_band(band, window, group, dropout_rate)
    nkv = band.nkb if band else nk      # k steps of a query tile (dq)
    nqv = band.nqb if band else nq      # q steps of a key tile (dk/dv)
    Lqp = nq * bq
    seed = (seed3 if seed3 is not None
            else _pack_seed(None)).reshape(1, 3).astype(jnp.uint32)
    hg = h_glob if h_glob is not None else H
    kreps = bk // _KB_LANES

    def pad_q_rows(x):
        return (jnp.pad(x, ((0, 0), (0, Lqp - Lq)) + ((0, 0),) * (x.ndim - 2))
                if Lqp != Lq else x)

    # lse/delta at 128 lanes (all lanes equal) — the input-side twin of
    # the scratch layout; the broadcast is transient O(L·128), not O(L²)
    lse128 = jnp.broadcast_to(pad_q_rows(lse)[..., None],
                              (N, Lqp, _KB_LANES))

    def common_block(q_blk, k_blk, b_blk, lse_blk, i, j):
        s = jax.lax.dot_general(
            q_blk, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if band:
            s = jnp.where(band.keep(i, j), s, NEG_INF)
        else:
            s = s + b_blk
        return jnp.exp(s - jnp.tile(lse_blk, (1, kreps)))  # p / l

    def dq_kernel(q_ref, k_ref, v_ref, b_ref, do_ref, lse_ref, dl_ref,
                  s_ref, dq_ref, dq_scr):
        i, jj = pl.program_id(1), pl.program_id(2)
        j = band.k_lo(i) + jj if band else jj

        @pl.when(jj == 0)
        def _init():
            dq_scr[...] = jnp.zeros_like(dq_scr)

        def tile():
            p = common_block(q_ref[0], k_ref[0], b_ref[0], lse_ref[0], i, j)
            do = do_ref[0].astype(jnp.float32)
            dpterm = jax.lax.dot_general(
                do, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)             # [bq, bk]
            if dropout_rate > 0.0:
                bh = _bh_from(s_ref, pl.program_id(0), H, hg)
                qrow = i * bq + jax.lax.broadcasted_iota(jnp.int32,
                                                         (bq, bk), 0)
                kcol = j * bk + jax.lax.broadcasted_iota(jnp.int32,
                                                         (bq, bk), 1)
                dpterm = dpterm * dropout_keep(s_ref[0, 0], bh, qrow, kcol,
                                               dropout_rate)
            ds = p * (dpterm - jnp.tile(dl_ref[0], (1, kreps))) * scale
            dq_scr[...] += jnp.dot(ds.astype(k_ref.dtype), k_ref[0],
                                   preferred_element_type=jnp.float32)

        if band:
            pl.when(j <= band.k_hi(i))(tile)
        else:
            tile()

        @pl.when(jj == nkv - 1)
        def _fin():
            dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)

    def dkv_kernel(q_ref, k_ref, v_ref, b_ref, do_ref, lse_ref, dl_ref,
                   s_ref, dk_ref, dv_ref, dk_scr, dv_scr):
        # the inner axis walks the group's query heads, and under each
        # the query tiles this key tile feeds
        j, t = pl.program_id(1), pl.program_id(2)
        i = band.q_lo(j) + t % nqv if band else t

        @pl.when(t == 0)
        def _init():
            dk_scr[...] = jnp.zeros_like(dk_scr)
            dv_scr[...] = jnp.zeros_like(dv_scr)

        def tile():
            p = common_block(q_ref[0], k_ref[0], b_ref[0], lse_ref[0], i, j)
            do = do_ref[0].astype(jnp.float32)
            dpterm = jax.lax.dot_general(
                do, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)             # [bq, bk]
            if dropout_rate > 0.0:
                bh = _bh_from(s_ref, pl.program_id(0), H, hg)
                qrow = i * bq + jax.lax.broadcasted_iota(jnp.int32,
                                                         (bq, bk), 0)
                kcol = j * bk + jax.lax.broadcasted_iota(jnp.int32,
                                                         (bq, bk), 1)
                keep = dropout_keep(s_ref[0, 0], bh, qrow, kcol,
                                    dropout_rate)
                pt = p * keep
                dpterm = dpterm * keep
            else:
                pt = p
            dv_scr[...] += jax.lax.dot_general(
                pt.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)             # [bk, D]
            ds = p * (dpterm - jnp.tile(dl_ref[0], (1, kreps))) * scale
            dk_scr[...] += jax.lax.dot_general(
                ds, q_ref[0].astype(jnp.float32), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)             # [bk, D]

        if band:
            pl.when(i <= band.q_hi(j))(tile)
        else:
            tile()

        @pl.when(t == group * nqv - 1)
        def _fin():
            dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
            dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    if band:
        def kv_of_q(n, i, jj):
            return (n // group,
                    jnp.minimum(band.k_lo(i) + jj, band.k_hi(i)), 0)

        def q_of_kv(n, j, t):
            return (n * group + t // nqv,
                    jnp.minimum(band.q_lo(j) + t % nqv, band.q_hi(j)), 0)
        bias_of_q = bias_of_kv = lambda n, a, b: (0, 0, 0)   # noqa: E731
    else:
        kv_of_q = lambda n, i, j: (n, j, 0)                  # noqa: E731
        q_of_kv = lambda n, j, i: (n, i, 0)                  # noqa: E731
        bias_of_q = lambda n, i, j: (n, 0, j)                # noqa: E731
        bias_of_kv = lambda n, j, i: (n, 0, j)               # noqa: E731

    interp = pallas_target.interpret()

    def run(g):
        gn = pad_q_rows(n3(g))
        delta = jnp.sum(gn.astype(jnp.float32)
                        * pad_q_rows(on).astype(jnp.float32),
                        axis=-1)                             # [N, Lqp]
        delta128 = jnp.broadcast_to(delta[..., None], (N, Lqp, _KB_LANES))
        dq = pl.pallas_call(
            dq_kernel,
            grid=(N, nq, nkv),
            in_specs=[
                pl.BlockSpec((1, bq, D), lambda n, i, j: (n, i, 0)),
                pl.BlockSpec((1, bk, D), kv_of_q),
                pl.BlockSpec((1, bk, D), kv_of_q),
                pl.BlockSpec((1, 1, bk), bias_of_q),
                pl.BlockSpec((1, bq, D), lambda n, i, j: (n, i, 0)),
                pl.BlockSpec((1, bq, _KB_LANES), lambda n, i, j: (n, i, 0)),
                pl.BlockSpec((1, bq, _KB_LANES), lambda n, i, j: (n, i, 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec((1, bq, D), lambda n, i, j: (n, i, 0)),
            out_shape=jax.ShapeDtypeStruct((N, Lqp, D), jnp.float32),
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
            interpret=interp,
            name="fdt_flash_bwd_dq_banded" if band else "fdt_flash_bwd_dq",
        )(qp, kp, vp, bias, gn, lse128, delta128, seed)
        dk, dv = pl.pallas_call(
            dkv_kernel,
            grid=(Nkv, nk, group * nqv),
            in_specs=[
                pl.BlockSpec((1, bq, D), q_of_kv),
                pl.BlockSpec((1, bk, D), lambda n, j, i: (n, j, 0)),
                pl.BlockSpec((1, bk, D), lambda n, j, i: (n, j, 0)),
                pl.BlockSpec((1, 1, bk), bias_of_kv),
                pl.BlockSpec((1, bq, D), q_of_kv),
                pl.BlockSpec((1, bq, _KB_LANES), q_of_kv),
                pl.BlockSpec((1, bq, _KB_LANES), q_of_kv),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=[
                pl.BlockSpec((1, bk, D), lambda n, j, i: (n, j, 0)),
                pl.BlockSpec((1, bk, D), lambda n, j, i: (n, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((Nkv, nk * bk, D), jnp.float32),
                jax.ShapeDtypeStruct((Nkv, nk * bk, D), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                            pltpu.VMEM((bk, D), jnp.float32)],
            interpret=interp,
            name="fdt_flash_bwd_dkv_banded" if band else "fdt_flash_bwd_dkv",
        )(qp, kp, vp, bias, gn, lse128, delta128, seed)
        shape4 = lambda x, h, L: x[:, :L].reshape(B, h, L, D)  # noqa: E731
        return (shape4(dq, H, Lq).astype(q.dtype),
                shape4(dk, Hkv, Lk).astype(k.dtype),
                shape4(dv, Hkv, Lk).astype(v.dtype))

    return run


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_core(q, k, v, key_bias, seed3, block_q, dropout_rate,
                save_stats, h_glob):
    return _flash_impl(q, k, v, key_bias, seed3, block_q,
                       dropout_rate, h_glob)


def _fwd_kernel_fits(block_q: int, lk: int, d: int = 64) -> bool:
    """Empirical envelope (see _FWD_KERNEL_MAX_LK, scaled by 64/D) plus
    a tile-size bound so large-but-fitting Lk shrinks the q-tile."""
    return (lk * max(d, 1) <= _FWD_KERNEL_MAX_LK * 64
            and 3 * block_q * lk * 4 <= 6 * 1024 * 1024)


def _shrink_block_q(block_q: int, lk: int, d: int) -> int:
    """Halve the q-tile (floor 32) until the monolithic forward fits —
    ONE policy shared by the primal route (_flash_impl) and the
    saved-stats route selection (_flash_fwd), so they can never diverge
    on which tile the kernel would actually run."""
    while block_q > 32 and not _fwd_kernel_fits(block_q, lk, d):
        block_q //= 2
    return block_q


def _flash_impl(q, k, v, key_bias, seed3, block_q, dropout_rate,
                h_glob=None):
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    block_q = _shrink_block_q(block_q, Lk, D)
    if _use_pallas():
        n3 = lambda x: x.reshape(B * H, x.shape[2], x.shape[3])  # noqa: E731
        if _fwd_kernel_fits(block_q, Lk, D):
            out = _flash_fwd_pallas(n3(q), n3(k), n3(v), key_bias, H,
                                    block_q, dropout_rate, seed3,
                                    h_glob=h_glob)
            return out.reshape(B, H, Lq, D)
        if _kblocked_supported(D):
            kb = (jnp.repeat(key_bias, H, axis=0)
                  if key_bias is not None else None)
            out, _ = _flash_fwd_kblocked(n3(q), n3(k), n3(v), kb,
                                         dropout_rate, seed3,
                                         n_heads=H, h_glob=h_glob)
            return out.reshape(B, H, Lq, D)
    mask = None
    if key_bias is not None:
        mask = (key_bias > NEG_INF / 2).astype(jnp.int32)[:, None, None, :]
    seed3 = seed3 if seed3 is not None else _pack_seed(None)
    return blockwise_attention(
        q, k, v, mask, dropout_rate=dropout_rate, dropout_seed=seed3[0],
        dropout_bh=_bh_array(B, H, seed3, h_glob or H))


def _save_stats_enabled(save_stats=None) -> bool:
    """Monolithic saved-(out, lse) backward (the L=512 retune) — default
    ON; FDT_FLASH_SAVE_STATS=0 restores the in-kernel-recompute backward
    for A/B measurement.  An explicit save_stats (the model passes False
    inside rematted attention regions — see flash_attention's docstring)
    overrides the env default."""
    if save_stats is not None:
        return bool(save_stats)
    return os.environ.get("FDT_FLASH_SAVE_STATS", "1") != "0"


def _flash_fwd(q, k, v, key_bias, seed3, block_q, dropout_rate,
               save_stats, h_glob):
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    pallas_bwd = (_use_pallas()
                  and os.environ.get("FDT_DISABLE_PALLAS_BWD") != "1")
    # When the gradient will need the k-blocked backward (monolithic bwd
    # out of envelope), run the k-blocked forward HERE so its lse/out
    # become residuals — the backward then skips any full-row recompute.
    if pallas_bwd and _kblocked_supported(D) and not _bwd_kernel_fits(Lq, Lk,
                                                                      D):
        n3 = lambda x: x.reshape(B * H, x.shape[2], x.shape[3])  # noqa: E731
        kb = (jnp.repeat(key_bias, H, axis=0)
              if key_bias is not None else None)
        out, lse = _flash_fwd_kblocked(n3(q), n3(k), n3(v), kb,
                                       dropout_rate, seed3,
                                       n_heads=H, h_glob=h_glob)
        out = out.reshape(B, H, Lq, D)
        return out, (q, k, v, key_bias, seed3, out, lse)
    # Monolithic-envelope autodiff (VERDICT r5 #3, the flash-routed
    # bs64/seq512 shape): emit the row lse from the forward so the
    # monolithic backward skips its in-kernel softmax recompute AND the
    # out-recompute matmul (delta comes from the saved primal out) —
    # one fewer [bq,Lk]x[Lk,D] MXU pass and two fewer row sweeps per
    # q-block, and the smaller transient set buys a larger backward
    # q-tile (_bwd_block_q_stats: 512 vs 256 at Lk=512 — half the grid
    # steps per (b,h) instance).
    bq = _shrink_block_q(block_q, Lk, D)
    if (pallas_bwd and _save_stats_enabled(save_stats)
            and _bwd_kernel_fits(Lq, Lk, D)
            and _fwd_kernel_fits(bq, Lk, D)):
        n3 = lambda x: x.reshape(B * H, x.shape[2], x.shape[3])  # noqa: E731
        out, lse = _flash_fwd_pallas(n3(q), n3(k), n3(v), key_bias, H, bq,
                                     dropout_rate, seed3,
                                     emit_lse=True, h_glob=h_glob)
        out = out.reshape(B, H, Lq, D)
        return out, (q, k, v, key_bias, seed3, out, lse)
    return (_flash_impl(q, k, v, key_bias, seed3, block_q,
                        dropout_rate, h_glob),
            (q, k, v, key_bias, seed3, None, None))


# Backward-policy budget for the DENSE-VJP branch.  The dense backward
# holds ~3 score-shaped fp32 tensors at peak (the saved probabilities
# residual plus the ds/dp transients), so the comparison below multiplies
# scores_bytes by 3.  Measured on v5e (6L d512 transformer, bs=64, L=512):
# full step 95 ms dense-bwd vs 163 ms blockwise-bwd; the blockwise VJP's
# scan recompute only pays off once sequences outgrow this budget.
# The default assumes a v5e-class chip (16 GB HBM) with the rest of the
# step's working set resident; on smaller-memory platforms, or when the
# model/optimizer state crowds HBM, override without editing source via
# FDT_DENSE_BWD_BUDGET_MB (0 forces the blockwise VJP everywhere).
_DENSE_BWD_BUDGET_BYTES = 2 << 30


def _dense_bwd_budget_bytes() -> int:
    mb = os.environ.get("FDT_DENSE_BWD_BUDGET_MB")
    if mb is not None:
        return int(mb) << 20
    return _DENSE_BWD_BUDGET_BYTES


# The MONOLITHIC kernels keep the whole K/V (and for the backward, the
# dk/dv accumulators) VMEM-resident per (batch*head) grid cell, and
# Pallas double-buffers every input/output block — so their envelope is
# set by Lk·D, nearly independent of the q-tile.  Byte models
# underpredicted the compiler's scoped-vmem accounting (observed
# 16.0-16.2 MB right at the limit), so the caps below are EMPIRICAL,
# validated on v5e at D=64: each cap compiles and runs; the next power
# of two OOMs scoped vmem.  K/V residency scales linearly with the head
# dim, so the fit checks scale the cap by 64/D (ADVICE r2: a D=128
# model at Lk near the cap must route away instead of OOMing scoped
# VMEM at compile time).  Beyond the envelope the K-BLOCKED
# (FlashAttention-2-style) kernels below take over — O(tile) VMEM, no
# Lk cap; the XLA blockwise formulation remains the non-TPU path.
_FWD_KERNEL_MAX_LK = 8192   # at D=64; scaled by 64/D in _fwd_kernel_fits
_BWD_KERNEL_MAX_LK = 4096   # at D=64; scaled by 64/D in _bwd_kernel_fits


def _bwd_block_q(lq: int, lk: int) -> int:
    """q-tile for the backward kernel: ~6 fp32 score-shaped transients
    live at once, so shrink the tile as Lk grows.  The small-Lq clamp is
    rounded up to a sublane multiple of 8 — Mosaic tiling rejects or
    badly pads odd tile heights (padding already handles Lq % bq)."""
    clamp = -(-max(lq, 32) // 8) * 8
    for cand in (512, 256, 128, 64):
        if 6 * cand * lk * 4 <= 6 * 1024 * 1024:
            return min(cand, clamp)
    return 64


def _bwd_kernel_fits(lq: int, lk: int, d: int = 64) -> bool:
    return lk * max(d, 1) <= _BWD_KERNEL_MAX_LK * 64


def _bwd_block_q_stats(lq: int, lk: int) -> int:
    """q-tile for the SAVED-STATS backward kernel: dropping the softmax
    and out recompute leaves ~5 fp32 score-shaped transients at peak
    (s/p, pt, dpterm, ds, keep) instead of the recompute kernel's ~6, so
    the same 6 MB budget admits one tile size up — at Lk=512 that is
    bq=512 (vs 256): one q-block per (b,h) grid instance instead of two,
    halving the per-instance grid overhead the r5 attribution measured
    at the bs64/seq512 config."""
    clamp = -(-max(lq, 32) // 8) * 8
    for cand in (512, 256, 128, 64):
        if 5 * cand * lk * 4 <= 6 * 1024 * 1024:
            return min(cand, clamp)
    return 64


def _flash_bwd_pallas_stats(q, k, v, key_bias, seed3, dropout_rate,
                            out, lse, h_glob: Optional[int] = None):
    """Monolithic saved-stats backward (the L=512 retune, VERDICT r5
    #3): K/V stay VMEM-resident like _flash_bwd_pallas, but the softmax
    is NOT recomputed — probabilities come back exactly normalized from
    the forward-saved lse (p = exp(s - lse)), and delta = Σ dO·out is
    precomputed in XLA from the saved primal out.  Per q-block that
    deletes the out-recompute matmul ([bq,Lk]×[Lk,D]) and both row
    sweeps (max, sum) of the recompute kernel — 5 MXU passes instead of
    6 — at the price of the lse residual ([N,Lq] fp32, ~2 KB per (b,h)
    at L=512) and reading out back (alive anyway as the primal).
    q..v [B, H, L, D]; lse [N, Lq] fp32; returns run(g)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu  # noqa: F401

    from faster_distributed_training_tpu.ops.attention import dropout_keep

    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    N = B * H
    scale = 1.0 / math.sqrt(D)
    nq3 = lambda x: x.reshape(N, x.shape[2], x.shape[3])  # noqa: E731
    qn, kn, vn, on = nq3(q), nq3(k), nq3(v), nq3(out)
    bias, bias_map, has_bias = _bias_operand(key_bias, H, Lk)
    seed = (seed3 if seed3 is not None
            else _pack_seed(None)).reshape(1, 3).astype(jnp.uint32)
    hg = h_glob if h_glob is not None else H

    bq = _bwd_block_q_stats(Lq, Lk)
    nq = -(-Lq // bq)
    pad_q = nq * bq - Lq

    def pad_rows(x):
        return (jnp.pad(x, ((0, 0), (0, pad_q)) + ((0, 0),) * (x.ndim - 2))
                if pad_q else x)

    # lse/delta at _KB_LANES all-equal lanes — the proven K-blocked input
    # layout; transient O(L·128), never O(L²)
    lse128 = jnp.broadcast_to(pad_rows(lse)[..., None],
                              (N, nq * bq, _KB_LANES))

    def kernel(q_ref, k_ref, v_ref, b_ref, do_ref, lse_ref, dl_ref, s_ref,
               dq_ref, dk_ref, dv_ref):
        i = pl.program_id(1)
        qb = q_ref[0]                                      # [bq, D]
        do = do_ref[0].astype(jnp.float32)                 # [bq, D]
        kk = k_ref[0]                                      # [Lk, D]
        vv = v_ref[0]
        s = jax.lax.dot_general(
            qb, kk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [bq, Lk]
        if has_bias:
            s = s + b_ref[0]
        p = jnp.exp(s - lse_ref[0][:, :1])                 # normalized probs
        dpterm = jax.lax.dot_general(
            do, vv.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bq, Lk]
        if dropout_rate > 0.0:
            bh = _bh_from(s_ref, pl.program_id(0), H, hg)
            qrow = (i * bq
                    + jax.lax.broadcasted_iota(jnp.int32, (bq, Lk), 0))
            kcol = jax.lax.broadcasted_iota(jnp.int32, (bq, Lk), 1)
            keep = dropout_keep(s_ref[0, 0], bh, qrow, kcol, dropout_rate)
            pt = p * keep
            dpterm = dpterm * keep
        else:
            pt = p
        ds = p * (dpterm - dl_ref[0][:, :1]) * scale       # [bq, Lk]
        dq_ref[0] = jnp.dot(ds.astype(kk.dtype), kk,
                            preferred_element_type=jnp.float32
                            ).astype(dq_ref.dtype)
        dk_blk = jax.lax.dot_general(
            ds, qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [Lk, D]
        dv_blk = jax.lax.dot_general(
            pt.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [Lk, D]

        @pl.when(i == 0)
        def _init():
            dk_ref[0] = dk_blk.astype(dk_ref.dtype)
            dv_ref[0] = dv_blk.astype(dv_ref.dtype)

        @pl.when(i > 0)
        def _acc():
            dk_ref[0] += dk_blk.astype(dk_ref.dtype)
            dv_ref[0] += dv_blk.astype(dv_ref.dtype)

    qp = pad_rows(qn)

    def run(g):
        gn = nq3(g)
        gp = pad_rows(gn)
        delta = jnp.sum(gp.astype(jnp.float32)
                        * pad_rows(on).astype(jnp.float32),
                        axis=-1)                           # [N, Lqp]
        delta128 = jnp.broadcast_to(delta[..., None],
                                    (N, nq * bq, _KB_LANES))
        dq, dk, dv = pl.pallas_call(
            kernel,
            grid=(N, nq),
            in_specs=[
                pl.BlockSpec((1, bq, D), lambda n, i: (n, i, 0)),
                pl.BlockSpec((1, Lk, D), lambda n, i: (n, 0, 0)),
                pl.BlockSpec((1, Lk, D), lambda n, i: (n, 0, 0)),
                pl.BlockSpec((1, 1, Lk), bias_map),
                pl.BlockSpec((1, bq, D), lambda n, i: (n, i, 0)),
                pl.BlockSpec((1, bq, _KB_LANES), lambda n, i: (n, i, 0)),
                pl.BlockSpec((1, bq, _KB_LANES), lambda n, i: (n, i, 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=[
                pl.BlockSpec((1, bq, D), lambda n, i: (n, i, 0)),
                pl.BlockSpec((1, Lk, D), lambda n, i: (n, 0, 0)),
                pl.BlockSpec((1, Lk, D), lambda n, i: (n, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((N, nq * bq, D), jnp.float32),
                jax.ShapeDtypeStruct((N, Lk, D), jnp.float32),
                jax.ShapeDtypeStruct((N, Lk, D), jnp.float32),
            ],
            interpret=pallas_target.interpret(),
            name="fdt_flash_bwd_fused",
        )(qp, kn, vn, bias, gp, lse128, delta128, seed)
        shape4 = lambda x, L: x.reshape(B, H, L, D)  # noqa: E731
        return (shape4(dq[:, :Lq], Lq).astype(q.dtype),
                shape4(dk, Lk).astype(k.dtype),
                shape4(dv, Lk).astype(v.dtype))

    return run


def _flash_bwd_pallas(q, k, v, key_bias, seed3, dropout_rate,
                      block_q, h_glob: Optional[int] = None):
    """Pallas backward kernel: dq/dk/dv with softmax stats RECOMPUTED
    per q-block inside the kernel (K/V stay VMEM-resident, so the full
    [block_q, Lk] score row costs one MXU matmul — no saved lse needed
    and residuals stay (q, k, v, bias, seed)).

    Math (m cancels out of out = acc/l, so treating it constant is
    exact; delta_i = dO_i . out_i):
      p    = exp(s - m),  l = sum_j p,  P~ = p * keep
      dv_j = sum_i (P~_ij / l_i) dO_i
      ds   = p * (keep * (dO V^T) - delta) / l * scale
      dq_i = sum_j ds_ij k_j,   dk_j = sum_i ds_ij q_i
    dk/dv accumulate across q-blocks by revisiting their (n)-indexed
    output block — the TPU grid runs sequentially, i innermost.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu  # noqa: F401

    from faster_distributed_training_tpu.ops.attention import dropout_keep

    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    N = B * H
    scale = 1.0 / math.sqrt(D)
    nq3 = lambda x: x.reshape(N, x.shape[2], x.shape[3])  # noqa: E731
    qn, kn, vn = nq3(q), nq3(k), nq3(v)

    bias, bias_map, has_bias = _bias_operand(key_bias, H, Lk)
    seed = (seed3 if seed3 is not None
            else _pack_seed(None)).reshape(1, 3).astype(jnp.uint32)
    hg = h_glob if h_glob is not None else H

    # backward holds ~4 score-shaped fp32 tiles (s/p, dpterm, ds, keep):
    # budget the q-tile so tiles + the resident K/V stay inside the
    # ~16 MB scoped-VMEM limit (measured: bq=128 at Lk=8192 overflows
    # by 192 KB).  _bwd_kernel_fits gates callers beyond the envelope.
    bq = _bwd_block_q(Lq, Lk)
    nq = -(-Lq // bq)
    pad_q = nq * bq - Lq

    def kernel(q_ref, k_ref, v_ref, b_ref, do_ref, s_ref,
               dq_ref, dk_ref, dv_ref):
        i = pl.program_id(1)
        qb = q_ref[0]                                      # [bq, D]
        do = do_ref[0].astype(jnp.float32)                 # [bq, D]
        kk = k_ref[0]                                      # [Lk, D]
        vv = v_ref[0]
        s = jax.lax.dot_general(
            qb, kk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [bq, Lk]
        if has_bias:
            s = s + b_ref[0]
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        if dropout_rate > 0.0:
            bh = _bh_from(s_ref, pl.program_id(0), H, hg)
            qrow = (i * bq
                    + jax.lax.broadcasted_iota(jnp.int32, (bq, Lk), 0))
            kcol = jax.lax.broadcasted_iota(jnp.int32, (bq, Lk), 1)
            keep = dropout_keep(s_ref[0, 0], bh, qrow, kcol, dropout_rate)
            pt = p * keep
        else:
            keep = None
            pt = p
        out = jnp.dot(pt.astype(vv.dtype), vv,
                      preferred_element_type=jnp.float32) / l   # [bq, D]
        delta = jnp.sum(do * out, axis=-1, keepdims=True)       # [bq, 1]
        dpterm = jax.lax.dot_general(
            do, vv.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [bq, Lk]
        if keep is not None:
            dpterm = dpterm * keep
        ds = p * (dpterm - delta) / l * scale                   # [bq, Lk]
        dq_ref[0] = jnp.dot(ds.astype(kk.dtype), kk,
                            preferred_element_type=jnp.float32
                            ).astype(dq_ref.dtype)
        dk_blk = jax.lax.dot_general(
            ds, qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [Lk, D]
        dv_blk = jax.lax.dot_general(
            (pt / l).astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [Lk, D]

        @pl.when(i == 0)
        def _init():
            dk_ref[0] = dk_blk.astype(dk_ref.dtype)
            dv_ref[0] = dv_blk.astype(dv_ref.dtype)

        @pl.when(i > 0)
        def _acc():
            dk_ref[0] += dk_blk.astype(dk_ref.dtype)
            dv_ref[0] += dv_blk.astype(dv_ref.dtype)

    qp = jnp.pad(qn, ((0, 0), (0, pad_q), (0, 0))) if pad_q else qn

    def run(g):
        gn = nq3(g)
        gp = (jnp.pad(gn, ((0, 0), (0, pad_q), (0, 0))) if pad_q else gn)
        dq, dk, dv = pl.pallas_call(
            kernel,
            grid=(N, nq),
            in_specs=[
                pl.BlockSpec((1, bq, D), lambda n, i: (n, i, 0)),
                pl.BlockSpec((1, Lk, D), lambda n, i: (n, 0, 0)),
                pl.BlockSpec((1, Lk, D), lambda n, i: (n, 0, 0)),
                pl.BlockSpec((1, 1, Lk), bias_map),
                pl.BlockSpec((1, bq, D), lambda n, i: (n, i, 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=[
                pl.BlockSpec((1, bq, D), lambda n, i: (n, i, 0)),
                pl.BlockSpec((1, Lk, D), lambda n, i: (n, 0, 0)),
                pl.BlockSpec((1, Lk, D), lambda n, i: (n, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((N, nq * bq, D), jnp.float32),
                jax.ShapeDtypeStruct((N, Lk, D), jnp.float32),
                jax.ShapeDtypeStruct((N, Lk, D), jnp.float32),
            ],
            interpret=pallas_target.interpret(),
            name="fdt_flash_bwd_recompute",
        )(qp, kn, vn, bias, gp, seed)
        shape4 = lambda x, L: x.reshape(B, H, L, D)  # noqa: E731
        return (shape4(dq[:, :Lq], Lq).astype(q.dtype),
                shape4(dk, Lk).astype(k.dtype),
                shape4(dv, Lk).astype(v.dtype))

    return run


def _flash_bwd(block_q, dropout_rate, save_stats, h_glob, res, g):
    q, k, v, key_bias, seed3, out, lse = res
    mask = None
    if key_bias is not None:
        mask = (key_bias > NEG_INF / 2).astype(jnp.int32)[:, None, None, :]
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    scores_bytes = 4 * B * H * Lq * Lk
    # every branch regenerates the forward's dropout mask from
    # (seed, bh, q, k) indices — identical by construction (dropout_keep)
    if out is not None and _bwd_kernel_fits(Lq, Lk, D) and \
            _save_stats_enabled(save_stats):
        # in-envelope saved-stats route: the forward emitted (out, lse)
        # from the monolithic kernel, so the monolithic backward skips
        # its in-kernel softmax/out recompute (the L=512 retune)
        dq, dk, dv = _flash_bwd_pallas_stats(q, k, v, key_bias,
                                             seed3, dropout_rate,
                                             out, lse, h_glob=h_glob)(g)
    elif out is not None:
        # the forward took the k-blocked route (monolithic envelope
        # exceeded) and saved (out, lse): finish with the k-blocked
        # FA-2-style kernels — no Lk cap, O(tile) VMEM
        dq, dk, dv = _flash_bwd_kblocked(q, k, v, key_bias, seed3,
                                         dropout_rate, out, lse,
                                         h_glob=h_glob)(g)
    elif (_use_pallas() and os.environ.get("FDT_DISABLE_PALLAS_BWD") != "1"
            and _bwd_kernel_fits(Lq, Lk, D)):
        # On TPU the monolithic backward kernel wins at EVERY measured
        # size within its VMEM envelope (v5e bf16 fwd+bwd, interleaved
        # re-measure: L=2048 B=4 H=8: 9.0 ms vs 11.3 dense-VJP / 14.3
        # blockwise-VJP; L=512 B=64 H=8: 6.9 ms vs 10.2 dense-VJP)
        # while keeping O(L·block) memory — so it is the default inside
        # the envelope; the k-blocked branch above covers everything
        # beyond it.
        dq, dk, dv = _flash_bwd_pallas(q, k, v, key_bias, seed3,
                                       dropout_rate, block_q,
                                       h_glob=h_glob)(g)
    else:
        seed0 = (seed3 if seed3 is not None else _pack_seed(None))
        bh = _bh_array(B, H, seed0, h_glob or H)
        if 3 * scores_bytes <= _dense_bwd_budget_bytes():
            _, vjp = jax.vjp(
                lambda q_, k_, v_: dense_attention_reference(
                    q_, k_, v_, mask, dropout_rate=dropout_rate,
                    dropout_seed=seed0[0], dropout_bh=bh),
                q, k, v)
        else:
            # long context off-TPU: recompute-in-backward via the
            # blockwise formulation keeps peak memory O(L*block) at the
            # price of the scan recompute
            _, vjp = jax.vjp(
                lambda q_, k_, v_: blockwise_attention(
                    q_, k_, v_, mask, dropout_rate=dropout_rate,
                    dropout_seed=seed0[0], dropout_bh=bh),
                q, k, v)
        dq, dk, dv = vjp(g)
    return dq, dk, dv, None, None


_flash_core.defvjp(_flash_fwd, _flash_bwd)


def _auto_block_q(lq: int, lk: int) -> int:
    """Largest q-block in {1024..128} whose fp32 score tile (block_q x Lk)
    stays within ~8 MB of VMEM — measured on v5e @ L=2048 D=64 bf16:
    block_q=1024 runs ~20-25% faster than the 128 default (2.7-2.8 vs
    3.4-3.9 ms), and the budget degrades the block gracefully as the
    context grows (Lk=4096 -> 512, 8192 -> 256, 16384 -> 128)."""
    budget = 8 * 1024 * 1024
    for bq in (1024, 512, 256, 128):
        if bq * lk * 4 <= budget:
            return min(bq, max(lq, 128))
    return 128


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    mask: Optional[jax.Array] = None,
                    block_q: Optional[int] = None,
                    dropout_rate: float = 0.0,
                    dropout_seed: Optional[jax.Array] = None,
                    save_stats: Optional[bool] = None,
                    bh0=None,
                    h_glob: Optional[int] = None) -> jax.Array:
    """Drop-in for dense_attention (models/transformer.py:101-111),
    INCLUDING attention-prob dropout (transformer.py:190-192): the keep
    mask is an index hash (ops.attention.dropout_keep) computed inside
    the kernel, so probabilities still never touch HBM.

    q/k/v: [B, H, L, D].  mask: None or a key-padding mask broadcastable
    to [B, 1, 1, Lk] (mask==0 masked) — full [B,H,Lq,Lk] masks should use
    blockwise_attention directly.  block_q: q-tile rows; None picks the
    largest tile whose score buffer fits VMEM (_auto_block_q).
    dropout_rate/dropout_seed: training-path prob dropout; pass a fresh
    u32 seed per step (e.g. jax.random.bits of the step's dropout rng).
    save_stats: the monolithic saved-(out, lse) backward toggle — None
    follows the FDT_FLASH_SAVE_STATS env default (on).  Pass False when
    this call sits INSIDE a rematted region whose replay recomputes
    custom_vjp residuals (models/transformer.py does for the layer/
    attn_out/dots policies): out/lse residuals would force the forward
    kernel to re-run in the replay, whereas the recompute backward's
    input-only residuals let XLA DCE the replayed kernel entirely.
    bh0/h_glob: head-sharded callers (parallel/kernel_shard.py running
    this kernel per-shard under shard_map) pass their GLOBAL (batch,
    head) shard origin and the global head count so the in-kernel
    dropout hashes GLOBAL stream indices — masks stay placement-
    invariant; the defaults reduce to the local indices bit-for-bit.
    """
    if block_q is None:
        block_q = _auto_block_q(q.shape[2], k.shape[2])
    key_bias = None
    if mask is not None:
        kb = jnp.asarray(mask)
        if kb.ndim == 4:                     # [B,1,1,Lk] -> [B,Lk]
            kb = kb.reshape(kb.shape[0], kb.shape[-1])
        kb = jnp.broadcast_to(kb, (q.shape[0], k.shape[2]))
        key_bias = mask_to_bias(kb)
    return _flash_core(q, k, v, key_bias, _pack_seed(dropout_seed, bh0),
                       block_q, float(dropout_rate), save_stats,
                       h_glob if h_glob is not None else int(q.shape[1]))


# ---------------------------------------------------------------------------
# Causal band with grouped key-value heads: the decoder's attention
# (models/decoder.py).  On a TPU target (and under the interpret seam) the
# banded K-blocked kernels above at every length; elsewhere the XLA
# blockwise twin with the same band.
# ---------------------------------------------------------------------------

# What the forward kernel hands its backward besides q, k, v, by
# checkpoint_name: out [B, H, L, D] and lse [B * H, L] float32 (the row
# sums' logs WITHOUT the kernel's 128 lanes).  A remat policy that saves
# these names (models/decoder.py) never runs the forward kernel in its
# replay; without a policy the names are the identity.  The blockwise twin
# goes through autodiff, has no such residuals and names nothing.
BANDED_OUT, BANDED_LSE = BANDED_RESIDUALS = (
    "banded_attn_out", "banded_attn_lse")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _banded_core(q, k, v, window):
    return _banded_fwd(q, k, v, window)[0]


def _banded_fwd(q, k, v, window):
    B, H, Lq, D = q.shape
    n3 = lambda x: x.reshape(-1, x.shape[2], x.shape[3])  # noqa: E731
    out, lse = _flash_fwd_kblocked(n3(q), n3(k), n3(v), None, n_heads=H,
                                   causal=True, window=window)
    out = checkpoint_name(out.reshape(B, H, Lq, D), BANDED_OUT)
    lse = checkpoint_name(lse, BANDED_LSE)
    return out, (q, k, v, out, lse)


def _banded_bwd(window, res, g):
    q, k, v, out, lse = res
    return _flash_bwd_kblocked(q, k, v, None, None, 0.0, out, lse,
                               causal=True, window=window)(g)


_banded_core.defvjp(_banded_fwd, _banded_bwd)


def banded_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     window: Optional[int] = None) -> jax.Array:
    """Causal self-attention of packed rows: query i sees key j <= i and,
    with ``window``, only i - j < window.  q [B, H, L, D]; k/v
    [B, H // group, L, D], each key-value head serving ``group``
    consecutive query heads.  No mask operand and no dropout: the band
    comes from the positions alone.  A window at least as long as the
    row is the full causal band."""
    B, H, L, D = q.shape
    group = H // k.shape[1]
    if H != group * k.shape[1] or k.shape[2] != L:
        raise ValueError(f"banded_attention: q {q.shape} against k "
                         f"{k.shape}: query heads must be a multiple of "
                         f"the key-value heads, on rows of one length")
    if window is not None and window >= L:
        window = None
    if _use_pallas() and _kblocked_supported(D):
        return _banded_core(q, k, v, window)
    if group > 1:
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    return blockwise_attention(q, k, v, None, causal=True, window=window)
