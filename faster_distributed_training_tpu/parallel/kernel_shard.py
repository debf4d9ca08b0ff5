"""ONE shard_map wrapper layer: every Pallas kernel runs per shard.

The repo's recurring measured caveat (recorded three times: flash
attention r11, the monolithic fused-FFN kernel r11, the quant-matmul
kernel r13) was that Pallas custom calls don't partition over the tp
axis, so every 2D ``(dp, tp)`` mesh silently rerouted the hand-written
kernels — the paper's "faster" lever — to slower XLA/flax fallbacks.
This module is the single layer that closes the gap: each kernel runs
PER SHARD under ``shard_map`` on operands that are already tp-sharded
the way the r11 TP param rules lay them out, so the kernel wins and
the 2D-mesh wins compose instead of excluding each other.

Decompositions (one per recovered kernel):

* **flash attention — head-sharded** (``flash_attention_sharded``):
  heads divide tp, so each device runs the monolithic/K-blocked flash
  kernel on its local ``H/tp`` heads with batch over the data axes.
  Zero collectives inside the sublayer (attention is embarrassingly
  parallel over heads); the in-kernel hash dropout addresses GLOBAL
  ``(b, h)`` stream indices via the kernels' ``bh0``/``h_glob``
  plumbing, so masks stay placement-invariant.
* **fused FFN — Megatron column-then-row** (``fused_ffn_sublayer_tp``):
  w1 arrives column-sharded ``[d, d_ff/tp]``, w2 row-sharded
  ``[d_ff/tp, d]`` (exactly the r11 ``_TP_RULES`` layout — NO per-step
  weight gather, the exact failure the old fallback existed to avoid).
  Each shard runs the generalized kernel in PARTIAL mode (LN -> GEMM1
  -> GELU -> hidden dropout on global d_ff columns -> GEMM2, stopping
  before b2), then ONE ``psum`` over tp inside the shard_map boundary
  recombines the row-parallel products; b2 + connection dropout +
  residual apply on each shard's OWN sequence slice, so the output
  leaves the boundary sequence-sharded over tp (Megatron-SP: the psum
  + slice is a reduce-scatter in XLA's hands) and — critically for
  ``check_vma=False`` autodiff — every mesh axis appears in the out
  spec, keeping the transpose's cotangent psums correct.
* **quant matmul — column/row per TP rule** (``quant_dense_sharded``):
  each QuantDense site names the kernel dim its TP rule shards
  (``tp_dim``); column-parallel sites contract locally and emit
  tp-sharded output columns, row-parallel sites contract their local
  K rows and ``psum`` once — the Pallas quant kernel (or the XLA
  reference off-TPU, same math) runs per-shard either way, and the
  delayed per-tensor scales stay GLOBAL scalars (amax reductions
  happen outside the boundary on the logical arrays, unchanged).

Data axes (PR 21): a Mosaic kernel cannot be partitioned by XLA at all
— not over tp and not over a plain data axis — so on ANY mesh of more
than one device every kernel must sit inside ``shard_map``.  The same
three wrappers therefore also serve meshes WITHOUT a tp axis
(``data_sharded``): flash and the quant GEMM run with batch rows over
dp/fsdp and nothing else split, and ``fused_mlp_sharded`` does the same
for the Pallas classifier head (batch-sharded rows, replicated
weights) on every mesh.  (The fused-FFN kernel's data-axis twin is
``ops.fused_ffn.fused_ffn_sublayer_sharded``.)  The data-axis routes
have no XLA fallback to be killed into, so ``FDT_KERNEL_SHARD`` does
not gate them.

Enablement: the tp layer is ON by default; ``FDT_KERNEL_SHARD=0`` kills
it, restoring the r11/r13 warned capability fallbacks — which also
makes the kill switch the A/B seam (kernel-via-shard_map vs forced
fallback; not measured on the chip, ROADMAP D3).  Non-dividing shapes
(heads/d_ff/seq not divisible by tp) take the same registered warned
fallbacks; ``scripts/check_kernel_routing.py`` (tier-1) lints that no
NEW call site reaches a Pallas kernel entry point outside this layer
or those registered fallbacks.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from faster_distributed_training_tpu.parallel.mesh import axis_size, tp_size

ENV_KILL = "FDT_KERNEL_SHARD"


def enabled() -> bool:
    """FDT_KERNEL_SHARD=0 kill switch (read per call so child processes
    and tests can flip it): False restores the pre-r19 warned
    capability fallbacks on tp meshes."""
    return os.environ.get(ENV_KILL, "1") != "0"


def _shard_map(f, mesh: Mesh, in_specs, out_specs):
    # check_vma=False: a pallas_call's out_shape carries no
    # varying-mesh-axes info, so VMA checking cannot see through it
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def data_sharded(mesh: Optional[Mesh]) -> bool:
    """True on a mesh of more than one device with no tp axis to split:
    kernels there run per shard with batch rows over the data axes
    (and replicated over any other axis)."""
    return mesh is not None and mesh.size > 1 and tp_size(mesh) == 1


def _batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names
                 and mesh.shape[a] > 1)


def _lead(batch: Tuple[str, ...]):
    if not batch:
        return None
    return batch if len(batch) != 1 else batch[0]


def _batch_index(mesh: Mesh, batch: Tuple[str, ...]) -> jax.Array:
    """Row-major flat index of this device's batch-shard — the same
    convention fused_ffn_sublayer_sharded uses, so the two layers'
    global-row addressing can never disagree."""
    bi = jnp.uint32(0)
    for ax in batch:
        bi = bi * jnp.uint32(mesh.shape[ax]) \
            + lax.axis_index(ax).astype(jnp.uint32)
    return bi


# ---------------------------------------------------------------------------
# flash attention: head-sharded over tp, batch over the data axes
# ---------------------------------------------------------------------------

def flash_serviceable(mesh: Optional[Mesh], n_heads: int) -> bool:
    """True when the head-sharded flash wrapper can serve this mesh:
    the layer is enabled and the heads divide tp.  (Sequence length is
    untouched — each shard sees full rows.)"""
    tp = tp_size(mesh)
    return enabled() and tp > 1 and n_heads % tp == 0


def flash_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array,
                            mask: Optional[jax.Array], mesh: Mesh,
                            dropout_rate: float = 0.0,
                            dropout_seed: Optional[jax.Array] = None,
                            save_stats: Optional[bool] = None
                            ) -> jax.Array:
    """[B,H,L,D] flash attention with H sharded over tp (when the mesh
    has one) and B over the data axes — each device runs the flash
    Pallas kernel (or its
    off-TPU blockwise twin, same routing as the unsharded call) on its
    local heads.  Dropout masks address GLOBAL (b, h) stream indices
    (ops/flash_attention._pack_seed), so the SAME seed draws the SAME
    pattern at any tp/dp layout — the placement-invariance contract
    every sharded dropout consumer in this repo keeps."""
    from faster_distributed_training_tpu.ops.flash_attention import (
        flash_attention)

    B, H, L, D = q.shape
    tp = tp_size(mesh)
    if H % tp:
        raise ValueError(
            f"flash_attention_sharded needs a tp axis whose size divides "
            f"the head count (H={H}, mesh={dict(mesh.shape)}) — build_model "
            f"routes non-dividing shapes to the warned fallback instead")
    batch = _batch_axes(mesh)
    lead = _lead(batch)
    qkv_spec = P(lead, "tp" if tp > 1 else None, None, None)
    b_shards = 1
    for a in batch:
        b_shards *= mesh.shape[a]
    b_loc, h_loc = B // b_shards, H // tp

    key_mask = None
    if mask is not None:
        m = jnp.asarray(mask)
        if m.ndim == 4:                      # [B,1,1,L] -> [B,L]
            m = m.reshape(B, m.shape[-1])
        key_mask = jnp.broadcast_to(m, (B, k.shape[2]))

    has_mask = key_mask is not None
    has_drop = dropout_rate > 0.0

    args, specs = [q, k, v], [qkv_spec] * 3
    if has_mask:
        args.append(key_mask)
        specs.append(P(lead, None))
    if has_drop:
        args.append(jnp.asarray(dropout_seed if dropout_seed is not None
                                else 0, jnp.uint32))
        specs.append(P())

    def call(q_, k_, v_, *rest):
        rest = list(rest)
        mask_ = rest.pop(0) if has_mask else None
        seed_ = rest.pop(0) if has_drop else None
        b0 = _batch_index(mesh, batch) * jnp.uint32(b_loc)
        h0 = (lax.axis_index("tp").astype(jnp.uint32) * jnp.uint32(h_loc)
              if tp > 1 else jnp.uint32(0))
        return flash_attention(q_, k_, v_, mask=mask_,
                               dropout_rate=dropout_rate,
                               dropout_seed=seed_,
                               save_stats=save_stats,
                               bh0=(b0, h0), h_glob=H)

    return _shard_map(call, mesh, tuple(specs), qkv_spec)(*args)


# ---------------------------------------------------------------------------
# classifier MLP head: batch rows over the data axes, weights replicated
# ---------------------------------------------------------------------------

def fused_mlp_sharded(x: jax.Array, w1: jax.Array, b1: jax.Array,
                      w2: jax.Array, b2: jax.Array, mesh: Mesh) -> jax.Array:
    """The Pallas classifier head (ops.fused_mlp.fused_mlp_pallas) on a
    mesh of more than one device: x [B, d] arrives with its rows over
    the data axes and each device runs the kernel on its own rows; the
    small head weights are replicated at the boundary (whatever rule
    laid them out).  The head has no dropout, so there is no global
    index to address."""
    from faster_distributed_training_tpu.ops.fused_mlp import (
        fused_mlp_pallas)

    rows = P(_lead(_batch_axes(mesh)), None)
    return _shard_map(fused_mlp_pallas, mesh,
                      (rows, P(), P(), P(), P()), rows)(x, w1, b1, w2, b2)


# ---------------------------------------------------------------------------
# fused FFN: Megatron column-then-row over tp
# ---------------------------------------------------------------------------

def ffn_tp_serviceable(mesh: Optional[Mesh], d_ff: int,
                       seq_len: int) -> bool:
    """True when the column/row-sharded fused-FFN wrapper can serve:
    layer enabled, d_ff divides tp (the column/row split) and the
    sequence divides sp*tp (the output leaves sequence-sharded over tp
    inside any dedicated-sp sharding)."""
    tp = tp_size(mesh)
    if not (enabled() and tp > 1 and d_ff % tp == 0):
        return False
    sp = axis_size(mesh, "sp")
    return seq_len % (sp * tp) == 0


def fused_ffn_sublayer_tp(h, ln_scale, ln_bias, w1, b1, w2, b2,
                          hid_seed, out_seed, mesh: Mesh,
                          rate_hidden: float = 0.0,
                          rate_conn: float = 0.0, eps: float = 1e-6,
                          quant_fmt: Optional[str] = None,
                          quant_scales=None,
                          grad_fmt: Optional[str] = None):
    """The Megatron column-then-row fused-FFN sublayer on a tp mesh
    (module docstring).  h: GLOBAL (B, L, d); weights GLOBAL logical
    shapes, tp-sharded per the r11 rules (w1 on d_ff columns, w2 on
    d_ff rows — the shard_map in_specs consume those shards in place).
    Returns ``out`` — or ``(out, amax2)`` when quant_fmt is set, with
    amax2 the global (2,) [amax_f, amax_a] for the delayed-scaling
    history roll."""
    from faster_distributed_training_tpu.ops.dropout import (
        guard_index_ceiling, keep_factor_rows)
    from faster_distributed_training_tpu.ops.fused_ffn import (
        ffn_core_generalized, pack_scales)

    if h.ndim != 3:
        raise ValueError("fused_ffn_sublayer_tp expects (B, L, d) "
                         f"activations, got shape {h.shape}")
    B, L, d = h.shape
    d_ff = w1.shape[1]
    tp = tp_size(mesh)
    if not ffn_tp_serviceable(mesh, d_ff, L):
        raise ValueError(
            f"fused_ffn_sublayer_tp cannot serve d_ff={d_ff}, seq={L} on "
            f"mesh {dict(mesh.shape)} — build_model routes such shapes "
            f"to the warned flax fallback instead")
    if rate_hidden > 0.0 or rate_conn > 0.0:
        width = max(d_ff if rate_hidden > 0.0 else 0,
                    d if rate_conn > 0.0 else 0)
        guard_index_ceiling(B * L * width,
                            site="fused FFN dropout (tp-sharded)")
    batch = _batch_axes(mesh)
    lead = _lead(batch)
    sp = axis_size(mesh, "sp")
    seq_in = "sp" if sp > 1 else None
    seq_out = ("sp", "tp") if sp > 1 else "tp"
    b_shards = 1
    for a in batch:
        b_shards *= mesh.shape[a]
    b_loc = B // b_shards
    l_in = L // sp                # rows per shard entering the kernel
    l_out = l_in // tp            # rows per shard leaving (seq over tp)
    dff_loc = d_ff // tp

    rep = P(None)
    h_spec = P(lead, seq_in, None)
    out_spec = P(lead, seq_out, None)

    def per_shard(h_, lns_, lnb_, w1_, b1_, w2_, b2_, s1_, s2_, scales_):
        b0 = _batch_index(mesh, batch) * jnp.uint32(b_loc)
        t = lax.axis_index("tp").astype(jnp.uint32)
        s0_in = (lax.axis_index("sp").astype(jnp.uint32)
                 * jnp.uint32(l_in) if seq_in else jnp.uint32(0))
        c0 = t * jnp.uint32(dff_loc)
        qscales = (tuple(scales_[i] for i in range(4))
                   if quant_fmt is not None else None)
        partial, amax2 = ffn_core_generalized(
            h_, lns_, lnb_, w1_, b1_, w2_, b2_, s1_, s2_, b0, s0_in, c0,
            rate_hidden, 0.0, eps, l_in, l_in * sp, dff_glob=d_ff,
            quant_fmt=quant_fmt, quant_scales=qscales, grad_fmt=grad_fmt,
            grad_axes=(batch + (("sp",) if seq_in else ()) + ("tp",)
                       if quant_fmt is not None else ()),
            partial=True)
        # the ONE tp collective of the sublayer: recombine the
        # row-parallel GEMM2 products (fp32, psum-of-dequantized is
        # exact-in-structure since descale is linear)
        tot = lax.psum(partial, "tp")
        # b2 + connection dropout + residual on this shard's OWN
        # sequence slice — the output leaves sequence-sharded over tp
        # (psum+slice == reduce-scatter), and every mesh axis appears
        # in the out spec so check_vma=False transposes stay correct
        ti = lax.axis_index("tp")
        f2 = lax.dynamic_slice_in_dim(tot, ti * l_out, l_out, axis=1)
        x_sl = lax.dynamic_slice_in_dim(h_, ti * l_out, l_out, axis=1
                                        ).astype(jnp.float32)
        f2 = f2 + b2_.astype(jnp.float32)
        if rate_conn > 0.0:
            s0_out = s0_in + t * jnp.uint32(l_out)
            grows = ((b0 + lax.iota(jnp.uint32, b_loc))[:, None]
                     * jnp.uint32(L) + s0_out
                     + lax.iota(jnp.uint32, l_out)[None, :]).reshape(-1)
            keep = keep_factor_rows(s2_, grows, d, rate_conn)
            f2 = f2 * keep.reshape(b_loc, l_out, d)
        out = (x_sl + f2).astype(h.dtype)
        if quant_fmt is None:
            return out, amax2
        # per-tensor amaxes globalize here: amax_f is tp-replicated
        # already (every tp shard sees the same LN rows), amax_a is
        # column-sharded — pmax over every sharded axis so the (2,)
        # output is genuinely replicated (its out_spec says so).
        # stop_gradient first: amaxes feed the scale-history roll, not
        # the loss, and pmax has no differentiation rule
        amax2 = lax.stop_gradient(amax2)
        for ax in batch + (("sp",) if seq_in else ()):
            amax2 = lax.pmax(amax2, ax)
        amax2 = lax.pmax(amax2, "tp")
        return out, amax2

    out, amax2 = _shard_map(
        per_shard, mesh,
        (h_spec, rep, rep, P(None, "tp"), P("tp"),
         P("tp", None), rep, P(), P(), P()),
        (out_spec, P()),
    )(h, ln_scale, ln_bias, w1, b1, w2, b2,
      jnp.asarray(hid_seed, jnp.uint32), jnp.asarray(out_seed, jnp.uint32),
      pack_scales(quant_scales if quant_fmt is not None else None))
    if quant_fmt is None:
        return out
    return out, amax2


# ---------------------------------------------------------------------------
# quant matmul: column/row-parallel per the site's TP rule
# ---------------------------------------------------------------------------

def quant_tp_serviceable(mesh: Optional[Mesh], tp_dim: Optional[int],
                         kernel_shape) -> bool:
    """True when a QuantDense site's GEMM can run per-shard: layer
    enabled, the mesh has tp > 1, the site declared its Megatron role
    (tp_dim), and tp divides the sharded kernel dim."""
    tp = tp_size(mesh)
    if not (enabled() and tp > 1 and tp_dim is not None):
        return False
    if tp_dim >= len(kernel_shape):
        return False
    return int(kernel_shape[tp_dim]) % tp == 0


def quant_tp_routed(mesh: Optional[Mesh], tp_dim: Optional[int],
                    kernel_shape, use_pallas) -> bool:
    """The QuantDense routing predicate: shard_map when the site is
    serviceable over tp (or the mesh has only data axes to shard over —
    ``data_sharded``) AND the policy didn't force the registered fallback
    (use_pallas=False — the FDT_KERNEL_SHARD=0 / non-dividing-shape
    path cli.build_model sets)."""
    return (use_pallas is not False
            and (quant_tp_serviceable(mesh, tp_dim, kernel_shape)
                 or data_sharded(mesh)))


def quant_dense_sharded(x2d: jax.Array, kernel: jax.Array,
                        sx: jax.Array, sw: jax.Array, fmt: str,
                        mesh: Mesh, tp_dim: int,
                        grad_fmt: Optional[str] = None) -> jax.Array:
    """One QuantDense GEMM per-shard.  On a mesh without tp
    (``data_sharded``) rows are batch-sharded, the kernel replicated and
    nothing is reduced; the rest describes tp meshes.  x2d: [M, K] (rows
    batch-sharded over the data axes); kernel: (K, *feats) with feats
    dim ``tp_dim`` tp-sharded (column-parallel) or ``tp_dim == 0``
    (K tp-sharded, row-parallel — x2d's columns arrive tp-sharded the
    way the model's activation annotations lay them out, and ONE psum
    recombines the partial products).  Scales are GLOBAL per-tensor
    scalars (replicated).  Returns the flat [M, prod(feats)] result."""
    from faster_distributed_training_tpu.ops.quant import quant_dot

    tp = tp_size(mesh)
    batch = _batch_axes(mesh)
    lead = _lead(batch)
    ndim = kernel.ndim
    feats = kernel.shape[1:]
    row = tp > 1 and tp_dim == 0
    col = tp_dim if tp > 1 else None     # the tp-sharded kernel dim
    w_spec = P(*[("tp" if i == col else None) for i in range(ndim)])
    if row:
        x_spec = P(lead, "tp")
        out_spec = P(lead, *([None] * len(feats)))
        g_axes = batch
    else:
        x_spec = P(lead, None)
        out_spec = P(lead, *[("tp" if i == col else None)
                             for i in range(1, ndim)])
        g_axes = batch + (("tp",) if tp > 1 else ())

    def per_shard(x_, w_, scales_):
        w2d = w_.reshape(w_.shape[0], -1)
        # (1,)-shaped scale slices, NOT scalars: rank-0 custom_vjp
        # residuals break this jax's shard_map linearization (the
        # inferred residual out-names can't attach to a rank-0 aval)
        y = quant_dot(x_, w2d, scales_[0:1], scales_[1:2], fmt,
                      grad_fmt=grad_fmt, grad_axes=g_axes)
        if row:
            # row-parallel: partial products over the local K rows —
            # the site's single tp collective (descale is linear, so
            # psum-of-dequantized equals dequantize-of-psum up to fp32
            # summation order)
            y = lax.psum(y, "tp")
        return y.reshape((x_.shape[0],) + w_.shape[1:])

    # scales travel as ONE (2,) vector: rank-0 replicated operands trip
    # this jax's shard_map transpose spec check on the cotangent side
    scales = jnp.stack([jnp.asarray(sx, jnp.float32).reshape(()),
                        jnp.asarray(sw, jnp.float32).reshape(())])
    out = _shard_map(per_shard, mesh, (x_spec, w_spec, P(None)),
                     out_spec)(x2d, kernel, scales)
    return out.reshape(x2d.shape[0], int(np.prod(feats)))
