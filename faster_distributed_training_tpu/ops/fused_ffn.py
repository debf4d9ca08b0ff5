"""Fused pre-LN FFN sublayer: one Pallas kernel for
LN -> Dense(d_ff) -> GELU -> dropout -> Dense(d_model) -> dropout -> +residual.

Motivation (VERDICT r4 #1 "attack the gap"): the round-5 identity-LN
probe measured the transformer's 13 LayerNorm sites at ~7.5 ms of the
112 ms step @ bs256/seq256 (an identity-LayerNorm A/B on the chip in
r5, not measured since) — pure HBM round-trips, which XLA cannot fuse into
the adjacent GEMMs (reductions only fuse with elementwise consumers,
never into a dot).  This kernel computes the WHOLE pre-LN FFN sublayer
of `models/transformer.py::EncoderLayer` per row-block with every
intermediate (LN output, d_ff hidden, GELU, dropout masks, residual sum)
living only in VMEM: HBM traffic drops from ~5 tensor round-trips to
read-h + write-out.

Design:
  * forward — Pallas kernel, grid over row blocks; weights VMEM-resident
    ((512,1024)+(1024,512) bf16 = 2 MiB of the ~16 MiB budget).  LN runs
    in fp32 with the reference's exact semantics (TorchLayerNorm,
    transformer.py:230-242: UNBIASED variance, eps added to the std);
    GEMMs accumulate fp32 on the MXU; GELU is the exact erf form
    (torch nn.GELU default); both dropout sites are the stateless
    index-hash masks of `ops/dropout.py` (murmur3 finalizer over
    seed ^ global-flat-index, keep iff top-16 bits < t, survivor scale
    GRID/t applied in fp32) so the backward can regenerate them
    bit-exactly from the two u32 seeds.
  * backward — ``jax.custom_vjp`` whose residuals are the INPUTS only
    (h, LN params, weights, seeds); the bwd pass is ``jax.vjp`` of the
    pure-XLA reference forward below, so gradients are correct by
    construction and the big dW GEMMs run as single XLA dots (measured
    at ~82% MFU on this chip — a hand-tiled Pallas accumulation would
    be slower).  This also makes the sublayer remat-free: nothing
    FFN-shaped is ever saved for backward.
  * off-TPU the kernel runs in Pallas interpret mode (tests); the model
    integration gates the kernel behind ``ffn_impl="pallas"`` and keeps
    the Flax composition as the default/ablation arm.

Numerics note: the kernel's GELU/dropout/second-GEMM chain runs in fp32
until the final cast while the Flax composition casts to bf16 between
every op, so kernel-vs-Flax outputs differ by normal bf16 rounding
(~1e-2 relative on bf16 activations); kernel-vs-REFERENCE-fn (same op
order) agrees to fp32/bf16 tolerance and is what the tests pin.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from faster_distributed_training_tpu.ops import pallas_target
from faster_distributed_training_tpu.ops.dropout import (guard_index_ceiling,
                                                         keep_factor_rows)
from faster_distributed_training_tpu.ops.layernorm import (torch_layernorm,
                                                           torch_layernorm_f32)

try:
    from jax.experimental import pallas as pl
except ImportError:  # pragma: no cover
    pl = None


def _erf_f32(x: jax.Array) -> jax.Array:
    """erf via the Abramowitz-Stegun 7.1.26 polynomial (|err| measured
    4.2e-7 in fp32, far below bf16's ~8e-3 resolution) — Mosaic has no
    erf primitive, so the
    kernel AND the reference/backward fn share this implementation (they
    must agree bit-for-bit for the vjp-of-reference backward to see the
    forward's exact activations)."""
    a1, a2, a3 = np.float32(0.254829592), np.float32(-0.284496736), \
        np.float32(1.421413741)
    a4, a5, p = np.float32(-1.453152027), np.float32(1.061405429), \
        np.float32(0.3275911)
    s = jnp.sign(x)
    ax = jnp.abs(x)
    t = 1.0 / (1.0 + p * ax)
    y = 1.0 - (((((a5 * t + a4) * t) + a3) * t + a2) * t + a1) * t \
        * jnp.exp(-ax * ax)
    return s * y


def _gelu_f32(h1: jax.Array) -> jax.Array:
    """Exact-form GELU (torch nn.GELU default) on fp32 pre-activations."""
    return 0.5 * h1 * (1.0 + _erf_f32(h1 * np.float32(1.0 / np.sqrt(2.0))))


# TorchLayerNorm's fp32 core — ONE definition shared with the Flax
# module (ops/layernorm.py), so kernel and model can't desynchronize.
# The Pallas kernel traces the PURE primal (Mosaic never differentiates
# it); the XLA reference fn — which the custom_vjp backward jax.vjp's —
# uses the saved-stats form so the recompute backward's inner LN also
# saves (mean, rstd) instead of re-deriving the rsqrt chain.  Both share
# one forward definition, so kernel-vs-reference outputs stay identical.
_ln_f32 = torch_layernorm_f32
_ln_saved = torch_layernorm


# the mask stream lives in ops/dropout.py (one source of truth); this
# module addresses it by GLOBAL row id (see _global_rows): masks depend
# only on (seed, global position), never on sharding/placement
_keep_rows = keep_factor_rows


def _global_rows(r_local: jax.Array, b0, s0, l_loc: int,
                 l_glob: int) -> jax.Array:
    """Map LOCAL flattened row indices to GLOBAL row ids.

    The (possibly sharded) activation is (B_local, L_local, d) flattened
    to rows r = b_local * l_loc + s_local; the shard starts at batch
    offset ``b0`` and sequence offset ``s0`` of a global (B, l_glob, d)
    tensor.  Unsharded callers use the defaults b0=s0=0, l_loc=l_glob=1,
    which reduce to g == r (the plain contiguous stream)."""
    r = r_local.astype(jnp.uint32)
    return ((jnp.uint32(b0) + r // jnp.uint32(l_loc)) * jnp.uint32(l_glob)
            + jnp.uint32(s0) + r % jnp.uint32(l_loc))


def ffn_sublayer_reference(h: jax.Array, ln_scale: jax.Array,
                           ln_bias: jax.Array, w1: jax.Array, b1: jax.Array,
                           w2: jax.Array, b2: jax.Array,
                           hid_seed: jax.Array, out_seed: jax.Array,
                           rate_hidden: float, rate_conn: float,
                           eps: float = 1e-6, b0=0, s0=0,
                           l_loc: int = 1, l_glob: int = 1) -> jax.Array:
    """Pure-XLA oracle with the kernel's exact op order and dtypes.
    Weights in Flax Dense layout (in, out).  Also the bwd math source:
    the custom_vjp backward is jax.vjp of THIS function.  b0/s0/l_loc/
    l_glob address the global dropout index space for sharded callers
    (defaults = unsharded)."""
    lead = h.shape[:-1]
    d = h.shape[-1]
    x32 = h.reshape(-1, d).astype(jnp.float32)
    n_rows = x32.shape[0]
    grows = _global_rows(lax.iota(jnp.uint32, n_rows), b0, s0, l_loc, l_glob)
    f = _ln_saved(x32, ln_scale.astype(jnp.float32),
                  ln_bias.astype(jnp.float32), eps).astype(h.dtype)
    h1 = jnp.dot(f, w1, preferred_element_type=jnp.float32) \
        + b1.astype(jnp.float32)
    a = _gelu_f32(h1)
    if rate_hidden > 0.0:
        a = a * _keep_rows(hid_seed, grows, a.shape[1], rate_hidden)
    a = a.astype(h.dtype)
    f2 = jnp.dot(a, w2, preferred_element_type=jnp.float32) \
        + b2.astype(jnp.float32)
    if rate_conn > 0.0:
        f2 = f2 * _keep_rows(out_seed, grows, f2.shape[1], rate_conn)
    out = x32 + f2
    return out.astype(h.dtype).reshape(*lead, d)


def _ffn_kernel(h_ref, lns_ref, lnb_ref, w1_ref, b1_ref, w2_ref, b2_ref,
                seeds_ref, o_ref, *, block_rows: int,
                rate_hidden: float, rate_conn: float, eps: float,
                l_loc: int, l_glob: int):
    row0 = pl.program_id(0) * block_rows
    x32 = h_ref[...].astype(jnp.float32)
    rows = x32.shape[0]
    f = _ln_f32(x32, lns_ref[...].astype(jnp.float32),
                lnb_ref[...].astype(jnp.float32), eps).astype(h_ref.dtype)
    h1 = jax.lax.dot(f, w1_ref[...],
                     preferred_element_type=jnp.float32) \
        + b1_ref[...].astype(jnp.float32)
    a = _gelu_f32(h1)
    if rate_hidden > 0.0 or rate_conn > 0.0:
        # (rows, 1) — Mosaic wants >=2D iota; keep_factor_rows reshapes
        r_local = (jnp.uint32(row0)
                   + lax.broadcasted_iota(jnp.uint32, (rows, 1), 0))
        grows = _global_rows(r_local, seeds_ref[0, 2], seeds_ref[0, 3],
                             l_loc, l_glob)
    if rate_hidden > 0.0:
        a = a * _keep_rows(seeds_ref[0, 0], grows, a.shape[1], rate_hidden)
    a = a.astype(h_ref.dtype)
    f2 = jax.lax.dot(a, w2_ref[...],
                     preferred_element_type=jnp.float32) \
        + b2_ref[...].astype(jnp.float32)
    if rate_conn > 0.0:
        f2 = f2 * _keep_rows(seeds_ref[0, 1], grows, f2.shape[1], rate_conn)
    o_ref[...] = (x32 + f2).astype(o_ref.dtype)


# Static VMEM budget for the kernel's resident set (ADVICE r5 low): both
# weight matrices + the fp32 hidden/row tiles must fit scoped VMEM or
# Mosaic dies with an opaque compile error at large --d_model/--d_ff.
# 12 MiB of the ~16 MiB budget leaves margin for Pallas double-buffering
# of the in/out row blocks; the default 512/1024 config sits at ~5.6 MiB.
_FFN_VMEM_BUDGET = 12 * 1024 * 1024


def _ffn_vmem_bytes(d: int, d_ff: int, w_bytes: int,
                    block_rows: int) -> int:
    """Resident-set model: w1+w2 at their dtype, fp32 hidden pair
    (pre-GELU + activation), and the x32/LN/out fp32 row tiles."""
    return (2 * d * d_ff * w_bytes
            + 2 * block_rows * d_ff * 4
            + 3 * block_rows * d * 4)


def ffn_kernel_fits_vmem(d: int, d_ff: int, w_bytes: int = 2) -> bool:
    """True iff the kernel fits the VMEM budget at its SMALLEST row tile
    — the static go/no-go check build_model mirrors (falling back to the
    flax composition, like the tp-mesh fallback) before handing the
    model a kernel that cannot compile."""
    return _ffn_vmem_bytes(d, d_ff, w_bytes, 32) <= _FFN_VMEM_BUDGET


def _ffn_fwd_pallas(h2d, ln_scale, ln_bias, w1, b1, w2, b2, seeds,
                    rate_hidden, rate_conn, eps, l_loc, l_glob,
                    block_rows=256):
    B, d = h2d.shape
    d_ff = w1.shape[1]
    w_bytes = jnp.dtype(w1.dtype).itemsize
    block_rows = min(block_rows, B)
    # degrade the row tile before giving up: the hidden tiles scale with
    # block_rows, so halving buys headroom down to the 32-row floor
    while (block_rows > 32
           and _ffn_vmem_bytes(d, d_ff, w_bytes,
                               block_rows) > _FFN_VMEM_BUDGET):
        block_rows //= 2
    if _ffn_vmem_bytes(d, d_ff, w_bytes, block_rows) > _FFN_VMEM_BUDGET:
        import warnings
        warnings.warn(
            f"fused FFN kernel resident set for d_model={d}, d_ff={d_ff} "
            f"exceeds the ~{_FFN_VMEM_BUDGET >> 20} MiB VMEM budget even "
            f"at the minimum row tile; computing this sublayer with the "
            f"XLA reference path instead (same math, default autodiff)",
            stacklevel=2)
        return ffn_sublayer_reference(
            h2d, ln_scale, ln_bias, w1, b1, w2, b2, seeds[0, 0],
            seeds[0, 1], rate_hidden, rate_conn, eps, seeds[0, 2],
            seeds[0, 3], l_loc, l_glob)
    nb = -(-B // block_rows)
    pad = nb * block_rows - B
    if pad:
        # NOTE: padded rows still hash dropout indices past B*d — fine,
        # they are sliced away and real rows' indices are unaffected.
        h2d = jnp.pad(h2d, ((0, pad), (0, 0)))
    kern = functools.partial(_ffn_kernel, block_rows=block_rows,
                             rate_hidden=rate_hidden, rate_conn=rate_conn,
                             eps=eps, l_loc=l_loc, l_glob=l_glob)
    out = pl.pallas_call(
        kern,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((d, d_ff), lambda i: (0, 0)),
            pl.BlockSpec((1, d_ff), lambda i: (0, 0)),
            pl.BlockSpec((d_ff, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, 4), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * block_rows, d), h2d.dtype),
        interpret=pallas_target.interpret(),
        name="fdt_fused_ffn_fwd",
    )(h2d, ln_scale.reshape(1, d), ln_bias.reshape(1, d), w1,
      b1.reshape(1, d_ff), w2, b2.reshape(1, d), seeds)
    return out[:B] if pad else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(11, 12, 13, 14, 15))
def _ffn_core(h, ln_scale, ln_bias, w1, b1, w2, b2,
              hid_seed, out_seed, b0, s0,
              rate_hidden: float, rate_conn: float, eps: float,
              l_loc: int, l_glob: int):
    lead = h.shape[:-1]
    d = h.shape[-1]
    seeds = jnp.stack([jnp.asarray(hid_seed, jnp.uint32),
                       jnp.asarray(out_seed, jnp.uint32),
                       jnp.asarray(b0, jnp.uint32),
                       jnp.asarray(s0, jnp.uint32)]).reshape(1, 4)
    out = _ffn_fwd_pallas(h.reshape(-1, d), ln_scale, ln_bias, w1, b1,
                          w2, b2, seeds, rate_hidden, rate_conn, eps,
                          l_loc, l_glob)
    return out.reshape(*lead, d)


def _ffn_vjp_fwd(h, ln_scale, ln_bias, w1, b1, w2, b2, hid_seed, out_seed,
                 b0, s0, rate_hidden, rate_conn, eps, l_loc, l_glob):
    out = _ffn_core(h, ln_scale, ln_bias, w1, b1, w2, b2,
                    hid_seed, out_seed, b0, s0,
                    rate_hidden, rate_conn, eps, l_loc, l_glob)
    # residuals: INPUTS only — nothing FFN-shaped is saved (the whole
    # sublayer is recomputed by the reference fn inside the bwd vjp)
    return out, (h, ln_scale, ln_bias, w1, b1, w2, b2, hid_seed, out_seed,
                 b0, s0)


def _ffn_vjp_bwd(rate_hidden, rate_conn, eps, l_loc, l_glob, res, g):
    (h, ln_scale, ln_bias, w1, b1, w2, b2, hid_seed, out_seed,
     b0, s0) = res
    _, vjp = jax.vjp(
        lambda h_, s_, bi_, w1_, b1_, w2_, b2_: ffn_sublayer_reference(
            h_, s_, bi_, w1_, b1_, w2_, b2_, hid_seed, out_seed,
            rate_hidden, rate_conn, eps, b0, s0, l_loc, l_glob),
        h, ln_scale, ln_bias, w1, b1, w2, b2)
    zero = np.zeros((), jax.dtypes.float0)
    return (*vjp(g), zero, zero, zero, zero)


_ffn_core.defvjp(_ffn_vjp_fwd, _ffn_vjp_bwd)


def pack_scales(quant_scales) -> jax.Array:
    """THE (4,) fp32 scales operand every fused-FFN shard_map layer
    ships to the generalized kernel: [sx1, sw1, sx2, sw2] stacked from
    traced scalars, or zeros(4) when quantization is off (None).  One
    definition so fused_ffn_sublayer_sharded, ffn_core_generalized and
    parallel/kernel_shard.fused_ffn_sublayer_tp can never disagree on
    the operand layout."""
    if quant_scales is None:
        return jnp.zeros((4,), jnp.float32)
    return jnp.stack([jnp.asarray(s, jnp.float32).reshape(())
                      for s in quant_scales])


def fused_ffn_sublayer(h, ln_scale, ln_bias, w1, b1, w2, b2,
                       hid_seed, out_seed,
                       rate_hidden: float = 0.0, rate_conn: float = 0.0,
                       eps: float = 1e-6):
    """out = h + drop(Dense2(drop(gelu(Dense1(LN(h)))))) in ONE Pallas
    kernel (see module docstring).  h: (..., d_model); weights in Flax
    (in, out) layout; seeds: u32 scalars (ignored when the static rates
    are 0 — pass anything).  Gradients flow to h, LN params, weights and
    biases; seeds are non-differentiable.  Dropout indices are the plain
    contiguous stream (global offsets are the sharded wrapper's job)."""
    if rate_hidden > 0.0 or rate_conn > 0.0:
        # loud guard on the documented 2^32 index ceiling (was a
        # docstring-only caveat): rows x the widest ACTIVE mask must
        # fit the uint32 stream — a rate-0 site draws no mask, so its
        # width must not be able to reject a legal config
        rows = int(np.prod(h.shape[:-1]))
        width = max(int(w1.shape[1]) if rate_hidden > 0.0 else 0,
                    int(h.shape[-1]) if rate_conn > 0.0 else 0)
        guard_index_ceiling(rows * width, site="fused FFN dropout")
    return _ffn_core(h, ln_scale, ln_bias, w1, b1, w2, b2,
                     hid_seed, out_seed, jnp.uint32(0), jnp.uint32(0),
                     rate_hidden, rate_conn, eps, 1, 1)


def fused_ffn_sublayer_sharded(h, ln_scale, ln_bias, w1, b1, w2, b2,
                               hid_seed, out_seed, mesh,
                               rate_hidden: float = 0.0,
                               rate_conn: float = 0.0,
                               eps: float = 1e-6,
                               quant_fmt: Optional[str] = None,
                               quant_scales=None,
                               grad_fmt: Optional[str] = None):
    """SPMD wrapper: the kernel runs PER SHARD under ``jax.shard_map``
    over the mesh's data axes (batch over dp/fsdp, sequence over sp),
    weights replicated (an fsdp/ZeRO-3-sharded weight is all-gathered by
    the partitioner at the shard_map boundary — the same gather the Flax
    path's dot would trigger).  Each shard addresses the GLOBAL dropout
    index space through its (batch, sequence) offsets — the same
    placement-invariance convention as every other sharded dropout
    consumer (ops/attention.py dropout_keep): masks depend only on
    (seed, global position), so the SAME global batch draws the SAME
    masks on dp=1, dp=4 or dp=8, bit-for-bit.  The global index space is
    uint32 — the contract holds up to 2^32 elements per activation
    tensor (see ops.dropout.keep_factor_rows for the documented wrap
    behavior past it).  tp-SHARDED FFN weights take the Megatron
    column-then-row decomposition in parallel/kernel_shard.py instead
    (this wrapper keeps the weights replicated).

    ``quant_fmt``/``quant_scales``/``grad_fmt`` (r19): run the two GEMMs
    quantized in-kernel through the generalized core; returns
    ``(out, amax2)`` with amax2 the GLOBAL (2,) [amax_f, amax_a] for the
    delayed-scaling history roll (pmax'd over every sharded axis)."""
    from jax.sharding import PartitionSpec as P

    batch_axes = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names
                       and mesh.shape[a] > 1)
    seq_axis = "sp" if ("sp" in mesh.axis_names
                        and mesh.shape["sp"] > 1) else None
    if not batch_axes and seq_axis is None:
        if quant_fmt is not None:
            return ffn_core_generalized(
                h, ln_scale, ln_bias, w1, b1, w2, b2, hid_seed, out_seed,
                0, 0, 0, rate_hidden, rate_conn, eps, 1, 1,
                dff_glob=int(w1.shape[1]), quant_fmt=quant_fmt,
                quant_scales=quant_scales, grad_fmt=grad_fmt)
        return fused_ffn_sublayer(h, ln_scale, ln_bias, w1, b1, w2, b2,
                                  hid_seed, out_seed, rate_hidden,
                                  rate_conn, eps)
    if h.ndim != 3:
        raise ValueError("fused_ffn_sublayer_sharded expects (B, L, d) "
                         f"activations, got shape {h.shape}")
    if rate_hidden > 0.0 or rate_conn > 0.0:
        # the wrap behavior this guard replaces was only documented:
        # global rows (B*L) x the widest ACTIVE mask must fit uint32
        # or distant shards would silently share mask bits (rate-0
        # sites draw no mask and must not reject a legal config)
        width = max(int(w1.shape[1]) if rate_hidden > 0.0 else 0,
                    int(h.shape[-1]) if rate_conn > 0.0 else 0)
        guard_index_ceiling(int(h.shape[0]) * int(h.shape[1]) * width,
                            site="fused FFN dropout (sharded)")
    data_spec = P(batch_axes if len(batch_axes) != 1 else batch_axes[0],
                  seq_axis, None)
    rep = P(None)
    sp_size = mesh.shape[seq_axis] if seq_axis else 1
    sharded_axes = batch_axes + ((seq_axis,) if seq_axis else ())

    def per_shard(h_, lns_, lnb_, w1_, b1_, w2_, b2_, s1_, s2_, scales_):
        b_loc, l_loc = h_.shape[0], h_.shape[1]
        bi = jnp.uint32(0)
        for ax in batch_axes:
            bi = bi * jnp.uint32(mesh.shape[ax]) \
                + jax.lax.axis_index(ax).astype(jnp.uint32)
        b0 = bi * jnp.uint32(b_loc)
        s0 = (jax.lax.axis_index(seq_axis).astype(jnp.uint32)
              * jnp.uint32(l_loc) if seq_axis else jnp.uint32(0))
        if quant_fmt is None:
            out = _ffn_core(h_, lns_, lnb_, w1_, b1_, w2_, b2_, s1_, s2_,
                            b0, s0, rate_hidden, rate_conn, eps,
                            l_loc, l_loc * sp_size)
            return out, jnp.zeros((2,), jnp.float32)
        qscales = tuple(scales_[i] for i in range(4))
        out, amax2 = ffn_core_generalized(
            h_, lns_, lnb_, w1_, b1_, w2_, b2_, s1_, s2_, b0, s0, 0,
            rate_hidden, rate_conn, eps, l_loc, l_loc * sp_size,
            dff_glob=int(w1_.shape[1]), quant_fmt=quant_fmt,
            quant_scales=qscales, grad_fmt=grad_fmt,
            grad_axes=sharded_axes)
        # globalize the per-tensor amaxes: every shard sees a slice of
        # the same logical tensors, so the (2,) output is pmax'd over
        # every sharded axis and leaves the boundary truly replicated.
        # stop_gradient first: amaxes feed the scale-history roll, not
        # the loss, and pmax has no differentiation rule
        amax2 = jax.lax.stop_gradient(amax2)
        for ax in sharded_axes:
            amax2 = jax.lax.pmax(amax2, ax)
        return out, amax2

    out, amax2 = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(data_spec, rep, rep, rep, rep, rep, rep, P(), P(), P()),
        out_specs=(data_spec, P()),
        # the pallas_call's out_shape carries no varying-mesh-axes info,
        # so VMA checking cannot see through it
        check_vma=False,
    )(h, ln_scale, ln_bias, w1, b1, w2, b2,
      jnp.asarray(hid_seed, jnp.uint32), jnp.asarray(out_seed, jnp.uint32),
      pack_scales(quant_scales if quant_fmt is not None else None))
    if quant_fmt is None:
        return out
    return out, amax2


# ---------------------------------------------------------------------------
# r19: the generalized core behind the shard_map kernel layer
# (parallel/kernel_shard.py) and the quantized fused-FFN composition.
#
# Two orthogonal extensions of the kernel above, parameterized statically
# so they compose (quant x partial x column offsets):
#   * quant (fmt != None) — the two GEMMs run on int8/fp8 operands with
#     per-tensor delayed scales (ops/quant.py recipe): the x side (LN
#     output / hidden activation) is quantized IN-KERNEL at the delayed
#     scale, the weights arrive pre-quantized, and the kernel emits the
#     two current-step amaxes (max-accumulated across the row-block
#     grid) so the caller can roll the histories — recombining the
#     LN/dropout fusion with the r13 quantized GEMMs (the kernel was
#     bf16-only under quant before this).
#   * partial (Megatron column-then-row tp tile) — w1 is a COLUMN shard
#     [d, d_ff/tp], w2 the matching ROW shard [d_ff/tp, d]; the kernel
#     computes LN -> GEMM1 -> GELU -> hidden dropout (addressing global
#     d_ff columns via c0/dff_glob) -> GEMM2 and stops BEFORE b2 / the
#     connection dropout / the residual, emitting the fp32 partial the
#     wrapper psums over tp — exactly ONE collective per sublayer, no
#     per-step weight gather.
#
# The backward for every combination is jax.vjp of ONE pure-XLA oracle
# (_ffn_body_reference) with the kernel's exact op order; the quant
# GEMMs inside it are ops.quant.quant_dot custom_vjp calls, so the
# straight-through estimator (and the optional fp8-E5M2 quantized
# gradient GEMMs) come along by construction.
# ---------------------------------------------------------------------------


def _ffn_body_reference(h, ln_scale, ln_bias, w1, b1, w2, b2,
                        hid_seed, out_seed, rate_hidden, rate_conn, eps,
                        b0, s0, l_loc, l_glob, c0=0, dff_glob=0,
                        partial=False, quant=None, return_amax=False):
    """The generalized pure-XLA oracle (op order == the generalized
    kernel).  ``quant``: None or (fmt, sx1, sw1, sx2, sw2, grad_fmt,
    grad_axes) — scales are traced scalars, the rest static.  partial:
    stop before b2/connection-dropout/residual and return the fp32
    GEMM2 product.  return_amax: also return the (2,) [amax_f, amax_a]
    current-step amaxes (zeros when quant is None)."""
    from faster_distributed_training_tpu.ops.quant import (quant_dot,
                                                           tensor_amax)

    lead = h.shape[:-1]
    d = h.shape[-1]
    x32 = h.reshape(-1, d).astype(jnp.float32)
    n_rows = x32.shape[0]
    grows = _global_rows(lax.iota(jnp.uint32, n_rows), b0, s0, l_loc, l_glob)
    f = _ln_saved(x32, ln_scale.astype(jnp.float32),
                  ln_bias.astype(jnp.float32), eps).astype(h.dtype)
    amax_f = amax_a = jnp.float32(0.0)
    if quant is not None:
        fmt, sx1, sw1, sx2, sw2, gfmt, gaxes = quant
        if return_amax:
            amax_f = tensor_amax(f)
        h1 = quant_dot(f, w1, sx1, sw1, fmt, use_pallas=False,
                       grad_fmt=gfmt, grad_axes=gaxes
                       ).astype(jnp.float32) + b1.astype(jnp.float32)
    else:
        h1 = jnp.dot(f, w1, preferred_element_type=jnp.float32) \
            + b1.astype(jnp.float32)
    a = _gelu_f32(h1)
    if rate_hidden > 0.0:
        a = a * _keep_rows(hid_seed, grows, a.shape[1], rate_hidden,
                           c0, dff_glob)
    a = a.astype(h.dtype)
    if quant is not None:
        if return_amax:
            amax_a = tensor_amax(a)
        f2 = quant_dot(a, w2, sx2, sw2, fmt, use_pallas=False,
                       grad_fmt=gfmt, grad_axes=gaxes).astype(jnp.float32)
    else:
        f2 = jnp.dot(a, w2, preferred_element_type=jnp.float32)
    if partial:
        out = f2.reshape(*lead, d)
    else:
        f2 = f2 + b2.astype(jnp.float32)
        if rate_conn > 0.0:
            f2 = f2 * _keep_rows(out_seed, grows, f2.shape[1], rate_conn)
        out = (x32 + f2).astype(h.dtype).reshape(*lead, d)
    if return_amax:
        return out, jnp.stack([amax_f, amax_a])
    return out


_AMAX_TILE = (8, 128)   # fp32 min tile: the per-block amax output


def _ffn_kernel2(h_ref, lns_ref, lnb_ref, w1_ref, b1_ref, w2_ref, b2_ref,
                 seeds_ref, scales_ref, o_ref, *amax_refs, block_rows: int,
                 rate_hidden: float, rate_conn: float, eps: float,
                 l_loc: int, l_glob: int, dff_glob: int, fmt,
                 partial: bool):
    """The generalized row-block kernel (see the section comment).
    seeds_ref (1, 5) SMEM u32: [hid_seed, out_seed, b0, s0, c0];
    scales_ref (1, 4) fp32: the RAW delayed scales [sx1, sw1, sx2, sw2]
    (quant only) — the kernel derives each GEMM's descale 1/(sx·sw)
    itself, callers never pass precomputed inverses."""
    from faster_distributed_training_tpu.ops.quant import QMAX

    row0 = pl.program_id(0) * block_rows
    x32 = h_ref[...].astype(jnp.float32)
    rows = x32.shape[0]
    f = _ln_f32(x32, lns_ref[...].astype(jnp.float32),
                lnb_ref[...].astype(jnp.float32), eps).astype(h_ref.dtype)

    def qdot(x, wq_ref, sx, inv):
        # mirror ops.quant.quant_dot's round-trip exactly: quantize the
        # compute-dtype operand, contract, descale in fp32, ONE cast to
        # the compute dtype, upcast f32 for the bias/GELU chain
        xs = x.astype(jnp.float32) * sx
        if fmt == "int8":
            xq = jnp.clip(jnp.round(xs), -QMAX["int8"],
                          QMAX["int8"]).astype(jnp.int8)
            acc = jax.lax.dot(xq, wq_ref[...],
                              preferred_element_type=jnp.int32
                              ).astype(jnp.float32)
        else:
            qmax = QMAX["fp8"]
            xq = jnp.clip(xs, -qmax, qmax).astype(jnp.float8_e4m3fn)
            acc = jax.lax.dot(xq.astype(jnp.float32),
                              wq_ref[...].astype(jnp.float32),
                              preferred_element_type=jnp.float32)
        return (acc * inv).astype(h_ref.dtype).astype(jnp.float32)

    if fmt is not None:
        amax_blk_f = jnp.max(jnp.abs(f.astype(jnp.float32)))
        h1 = qdot(f, w1_ref, scales_ref[0, 0],
                  1.0 / (scales_ref[0, 0] * scales_ref[0, 1])) \
            + b1_ref[...].astype(jnp.float32)
    else:
        h1 = jax.lax.dot(f, w1_ref[...],
                         preferred_element_type=jnp.float32) \
            + b1_ref[...].astype(jnp.float32)
    a = _gelu_f32(h1)
    if rate_hidden > 0.0 or rate_conn > 0.0:
        r_local = (jnp.uint32(row0)
                   + lax.broadcasted_iota(jnp.uint32, (rows, 1), 0))
        grows = _global_rows(r_local, seeds_ref[0, 2], seeds_ref[0, 3],
                             l_loc, l_glob)
    if rate_hidden > 0.0:
        a = a * _keep_rows(seeds_ref[0, 0], grows, a.shape[1],
                           rate_hidden, seeds_ref[0, 4], dff_glob)
    a = a.astype(h_ref.dtype)
    if fmt is not None:
        amax_blk_a = jnp.max(jnp.abs(a.astype(jnp.float32)))
        f2 = qdot(a, w2_ref, scales_ref[0, 2],
                  1.0 / (scales_ref[0, 2] * scales_ref[0, 3]))
    else:
        f2 = jax.lax.dot(a, w2_ref[...],
                         preferred_element_type=jnp.float32)
    if partial:
        o_ref[...] = f2.astype(o_ref.dtype)
    else:
        f2 = f2 + b2_ref[...].astype(jnp.float32)
        if rate_conn > 0.0:
            f2 = f2 * _keep_rows(seeds_ref[0, 1], grows, f2.shape[1],
                                 rate_conn)
        o_ref[...] = (x32 + f2).astype(o_ref.dtype)
    if fmt is not None:
        # one (8, 128) tile of this row block's amax per grid step,
        # max-reduced outside the kernel: Mosaic cannot store a scalar
        # into a VMEM ref, and max is order-free, so the reduced value
        # is bit-equal to a running in-kernel max
        af_ref, aa_ref = amax_refs
        af_ref[...] = jnp.full(af_ref.shape, amax_blk_f, jnp.float32)
        aa_ref[...] = jnp.full(aa_ref.shape, amax_blk_a, jnp.float32)


def _ffn_fwd_pallas2(h2d, ln_scale, ln_bias, w1, b1, w2, b2, seeds,
                     scales, rate_hidden, rate_conn, eps, l_loc, l_glob,
                     dff_glob, fmt, grad_fmt, grad_axes, partial,
                     block_rows=256):
    """Generalized forward dispatch: the Pallas kernel when the resident
    set fits VMEM (weights pre-quantized to 1 byte/elem under quant),
    the oracle otherwise (warned).  Returns (out2d, amax2) — amax2 is
    (2,) fp32 [amax_f, amax_a], zeros when fmt is None."""
    B, d = h2d.shape
    d_ff = w1.shape[1]
    d_out = w2.shape[1]
    w_bytes = 1 if fmt is not None else jnp.dtype(w1.dtype).itemsize
    block_rows = min(block_rows, B)
    while (block_rows > 32
           and _ffn_vmem_bytes(d, d_ff, w_bytes,
                               block_rows) > _FFN_VMEM_BUDGET):
        block_rows //= 2
    if _ffn_vmem_bytes(d, d_ff, w_bytes, block_rows) > _FFN_VMEM_BUDGET:
        import warnings
        warnings.warn(
            f"fused FFN kernel resident set for d_model={d}, d_ff={d_ff} "
            f"exceeds the ~{_FFN_VMEM_BUDGET >> 20} MiB VMEM budget even "
            f"at the minimum row tile; computing this sublayer with the "
            f"XLA reference path instead (same math, default autodiff)",
            stacklevel=2)
        quant = (None if fmt is None else
                 (fmt, scales[0], scales[1], scales[2], scales[3],
                  grad_fmt, grad_axes))
        return _ffn_body_reference(
            h2d, ln_scale, ln_bias, w1, b1, w2, b2, seeds[0, 0],
            seeds[0, 1], rate_hidden, rate_conn, eps, seeds[0, 2],
            seeds[0, 3], l_loc, l_glob, seeds[0, 4], dff_glob,
            partial, quant, return_amax=True)
    if fmt is not None:
        # weights quantize ONCE per call at their delayed scales — the
        # kernel sees 1-byte operands (and the quantize sits inside the
        # custom_vjp boundary, so the straight-through estimator in the
        # reference backward bridges the rounding)
        from faster_distributed_training_tpu.ops.quant import quantize
        w1 = quantize(w1, scales[1], fmt)
        w2 = quantize(w2, scales[3], fmt)
    nb = -(-B // block_rows)
    pad = nb * block_rows - B
    if pad:
        h2d = jnp.pad(h2d, ((0, pad), (0, 0)))
    kern = functools.partial(_ffn_kernel2, block_rows=block_rows,
                             rate_hidden=rate_hidden, rate_conn=rate_conn,
                             eps=eps, l_loc=l_loc, l_glob=l_glob,
                             dff_glob=dff_glob, fmt=fmt, partial=partial)
    out_specs = [pl.BlockSpec((block_rows, d_out), lambda i: (i, 0))]
    out_dtype = jnp.float32 if partial else h2d.dtype
    out_shape = [jax.ShapeDtypeStruct((nb * block_rows, d_out), out_dtype)]
    if fmt is not None:
        out_specs += [pl.BlockSpec(_AMAX_TILE, lambda i: (i, 0))] * 2
        out_shape += [jax.ShapeDtypeStruct(
            (nb * _AMAX_TILE[0], _AMAX_TILE[1]), jnp.float32)] * 2
    res = pl.pallas_call(
        kern,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((d, d_ff), lambda i: (0, 0)),
            pl.BlockSpec((1, d_ff), lambda i: (0, 0)),
            pl.BlockSpec((d_ff, d_out), lambda i: (0, 0)),
            pl.BlockSpec((1, d_out), lambda i: (0, 0)),
            pl.BlockSpec((1, 5), lambda i: (0, 0)),
            pl.BlockSpec((1, 4), lambda i: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=pallas_target.interpret(),
        name="fdt_fused_ffn_fwd_general",
    )(h2d, ln_scale.reshape(1, d), ln_bias.reshape(1, d), w1,
      b1.reshape(1, d_ff), w2, b2.reshape(1, d_out), seeds,
      scales.reshape(1, 4))
    if fmt is not None:
        out, af, aa = res
        amax2 = jnp.stack([jnp.max(af), jnp.max(aa)])
    else:
        out = res[0] if isinstance(res, (list, tuple)) else res
        amax2 = jnp.zeros((2,), jnp.float32)
    return (out[:B] if pad else out), amax2


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11, 12, 13,
                                                    14, 15, 16, 17, 18))
def _ffn_core2(h, ln_scale, ln_bias, w1, b1, w2, b2, seeds, scales,
               rate_hidden: float, rate_conn: float, eps: float,
               l_loc: int, l_glob: int, dff_glob: int, fmt,
               grad_fmt, grad_axes, partial: bool):
    """Generalized fused-FFN core: returns (out, amax2).  seeds (1, 5)
    u32 [hid_seed, out_seed, b0, s0, c0]; scales (4,) fp32 [sx1, sw1,
    sx2, sw2] (zeros when fmt is None).  partial=True emits the fp32
    GEMM2 product (pre-b2/connection-dropout/residual) for the tp
    psum."""
    lead = h.shape[:-1]
    d = h.shape[-1]
    out2d, amax2 = _ffn_fwd_pallas2(
        h.reshape(-1, d), ln_scale, ln_bias, w1, b1, w2, b2, seeds,
        scales, rate_hidden, rate_conn, eps, l_loc, l_glob, dff_glob,
        fmt, grad_fmt, grad_axes, partial)
    return out2d.reshape(*lead, out2d.shape[-1]), amax2


def _ffn_vjp2_fwd(h, ln_scale, ln_bias, w1, b1, w2, b2, seeds, scales,
                  rate_hidden, rate_conn, eps, l_loc, l_glob, dff_glob,
                  fmt, grad_fmt, grad_axes, partial):
    out = _ffn_core2(h, ln_scale, ln_bias, w1, b1, w2, b2, seeds, scales,
                     rate_hidden, rate_conn, eps, l_loc, l_glob, dff_glob,
                     fmt, grad_fmt, grad_axes, partial)
    # residuals: INPUTS only — the recompute-backward contract of
    # _ffn_core carries over to every quant/partial combination
    return out, (h, ln_scale, ln_bias, w1, b1, w2, b2, seeds, scales)


def _ffn_vjp2_bwd(rate_hidden, rate_conn, eps, l_loc, l_glob, dff_glob,
                  fmt, grad_fmt, grad_axes, partial, res, g):
    h, ln_scale, ln_bias, w1, b1, w2, b2, seeds, scales = res
    g_out, _g_amax = g          # the amax outputs feed state, not loss
    quant = (None if fmt is None else
             (fmt, scales[0], scales[1], scales[2], scales[3],
              grad_fmt, grad_axes))
    _, vjp = jax.vjp(
        lambda h_, s_, bi_, w1_, b1_, w2_, b2_: _ffn_body_reference(
            h_, s_, bi_, w1_, b1_, w2_, b2_, seeds[0, 0], seeds[0, 1],
            rate_hidden, rate_conn, eps, seeds[0, 2], seeds[0, 3],
            l_loc, l_glob, seeds[0, 4], dff_glob, partial, quant),
        h, ln_scale, ln_bias, w1, b1, w2, b2)
    zero = np.zeros(np.shape(seeds), jax.dtypes.float0)
    return (*vjp(g_out), zero, jnp.zeros_like(scales))


_ffn_core2.defvjp(_ffn_vjp2_fwd, _ffn_vjp2_bwd)


def ffn_core_generalized(h, ln_scale, ln_bias, w1, b1, w2, b2,
                         hid_seed, out_seed, b0, s0, c0,
                         rate_hidden: float, rate_conn: float,
                         eps: float, l_loc: int, l_glob: int,
                         dff_glob: int = 0, quant_fmt=None,
                         quant_scales=None, grad_fmt=None,
                         grad_axes: tuple = (), partial: bool = False):
    """The shard_map layer's entry to the generalized core (parallel/
    kernel_shard.py runs this per shard; models/transformer.py calls it
    directly for the unsharded quantized composition).  Returns
    (out, amax2) with amax2 = (2,) fp32 [amax_f, amax_a] current-step
    amaxes (zeros when quant_fmt is None).  b0/s0/c0: global batch-row
    / sequence / d_ff-column offsets of this shard; quant_scales:
    (sx1, sw1, sx2, sw2) traced scalars when quant_fmt is set."""
    seeds = jnp.stack([jnp.asarray(hid_seed, jnp.uint32),
                       jnp.asarray(out_seed, jnp.uint32),
                       jnp.asarray(b0, jnp.uint32),
                       jnp.asarray(s0, jnp.uint32),
                       jnp.asarray(c0, jnp.uint32)]).reshape(1, 5)
    scales = pack_scales(quant_scales if quant_fmt is not None else None)
    return _ffn_core2(h, ln_scale, ln_bias, w1, b1, w2, b2, seeds,
                      scales, float(rate_hidden), float(rate_conn),
                      float(eps), int(l_loc), int(l_glob),
                      int(dff_glob) if dff_glob else int(w1.shape[1]),
                      quant_fmt, grad_fmt, tuple(grad_axes),
                      bool(partial))


