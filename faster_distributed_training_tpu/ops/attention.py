"""Blockwise (online-softmax / flash-style) attention in pure JAX.

The reference's attention is the O(L²)-memory dense ScaledDotProduct
(transformer.py:180-193): it materializes the full [B,H,Lq,Lk] score and
probability tensors.  Blockwise attention streams over key/value blocks
with running (max, sum, accumulator) statistics, so peak memory is
O(Lq·block_k) — this is the long-context enabler and the shared math for
both the Pallas TPU kernel (ops/flash_attention.py) and ring
sequence-parallel attention (ops/ring_attention.py).

Mask convention matches models/transformer.py: mask==0 → masked out,
broadcastable to [B, H, Lq, Lk] (typically a [B,1,1,Lk] padding mask).
Softmax statistics are kept in fp32 regardless of input dtype.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e9  # matches models/transformer.py masking constant


def mask_to_bias(mask: Optional[jax.Array], dtype=jnp.float32
                 ) -> Optional[jax.Array]:
    """mask (…==0 masked) -> additive bias (0 keep, NEG_INF drop)."""
    if mask is None:
        return None
    return jnp.where(mask == 0, jnp.asarray(NEG_INF, dtype),
                     jnp.asarray(0.0, dtype))


# ------------------------------------------------- stateless hash dropout
# Attention-prob dropout for paths that never materialize the probability
# tensor (flash / blockwise / ring / ulysses): the keep decision for score
# element (bh, q, k) is a pure function of (seed, bh, q, k), so the
# forward kernel and any recompute-in-backward formulation regenerate the
# IDENTICAL mask from indices alone — no [B,H,Lq,Lk] mask tensor ever
# lives in HBM, and no RNG state threads through the scan.  The mixer is
# murmur3's 32-bit finalizer (full avalanche), plenty for dropout; every
# op (xor/shift/mul on u32) lowers on both XLA and Mosaic/Pallas-TPU.
# Matches the reference's dropout-after-softmax placement
# (transformer.py:190-192): the softmax normalizer uses ALL probabilities,
# the dropped ones are zeroed only in the value contraction.

def _fmix32(x: jax.Array) -> jax.Array:
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def dropout_keep(seed: jax.Array, bh: jax.Array, q_idx: jax.Array,
                 k_idx: jax.Array, rate: float) -> jax.Array:
    """fp32 keep/(1-rate) factor, broadcast over bh/q_idx/k_idx.

    seed: u32 scalar (one fresh value per step, e.g. jax.random.bits of
    the step's dropout rng); bh / q_idx / k_idx: integer index arrays
    broadcastable to the score block's shape (GLOBAL indices — sharded
    callers add their shard offsets so placement doesn't change the
    pattern); rate: static python float in [0, 1)."""
    h = _fmix32(seed.astype(jnp.uint32) ^ bh.astype(jnp.uint32))
    h = _fmix32(h ^ q_idx.astype(jnp.uint32))
    h = _fmix32(h ^ k_idx.astype(jnp.uint32))
    thresh = jnp.uint32(min(int((1.0 - rate) * 4294967296.0), 4294967295))
    return (h < thresh).astype(jnp.float32) / (1.0 - rate)


def online_block_update(q: jax.Array, k_blk: jax.Array, v_blk: jax.Array,
                        bias_blk: Optional[jax.Array],
                        m: jax.Array, l: jax.Array, acc: jax.Array,
                        scale: float,
                        keep_blk: Optional[jax.Array] = None
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One online-softmax accumulation step.

    q [..., Lq, D], k_blk/v_blk [..., Bk, D], bias_blk broadcastable to
    [..., Lq, Bk]; m/l [..., Lq] fp32 running max / normalizer,
    acc [..., Lq, D] fp32 running numerator.  keep_blk: optional
    pre-scaled dropout factor (dropout_keep output) broadcastable to
    [..., Lq, Bk] — applied to the value contraction only, NOT to the
    normalizer, which is softmax-then-dropout semantics
    (transformer.py:190-192).  Returns updated (m, l, acc).
    """
    s = jnp.einsum("...qd,...kd->...qk", q, k_blk,
                   preferred_element_type=jnp.float32) * scale
    if bias_blk is not None:
        s = s + bias_blk.astype(jnp.float32)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # exp(NEG_INF - m_new) underflows to 0, so fully-masked columns drop out
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = p if keep_blk is None else p * keep_blk
    acc_new = acc * corr[..., None] + jnp.einsum(
        "...qk,...kd->...qd", pv.astype(v_blk.dtype), v_blk,
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def finalize(m: jax.Array, l: jax.Array, acc: jax.Array,
             dtype) -> jax.Array:
    """acc / l with fully-masked-row protection (returns 0 there)."""
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(dtype)


def init_carry(q: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    # accumulators are DERIVED from q (0*q) rather than freshly created:
    # under shard_map, constants carry no varying-manual-axes while the
    # scan-body outputs vary over the mesh axes, and lax.scan requires the
    # carry types (incl. VMA sets) to match — deriving from q gives the
    # carry q's full VMA set (same trick as ops/ring_attention.py).
    zeros = q.astype(jnp.float32) * 0.0
    m = zeros[..., 0] - jnp.inf
    l = zeros[..., 0]
    acc = zeros
    return m, l, acc


def bh_index(B: int, H: int) -> jax.Array:
    """[B,H,1,1] flattened batch*head index — the dropout stream id every
    attention path (Pallas grid n, blockwise, dense, ring, ulysses)
    agrees on; sharded callers offset it to global coordinates."""
    return (jnp.arange(B, dtype=jnp.int32)[:, None] * H
            + jnp.arange(H, dtype=jnp.int32)[None, :])[:, :, None, None]


@partial(jax.jit, static_argnames=("block_k", "causal", "dropout_rate",
                                   "window"))
def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        mask: Optional[jax.Array] = None,
                        block_k: int = 128,
                        causal: bool = False,
                        dropout_rate: float = 0.0,
                        dropout_seed: Optional[jax.Array] = None,
                        dropout_bh: Optional[jax.Array] = None,
                        window: Optional[int] = None
                        ) -> jax.Array:
    """Streaming attention over key blocks via lax.scan.

    q [B,H,Lq,D], k/v [B,H,Lk,D], mask broadcastable to [B,H,Lq,Lk]
    (mask==0 masked).  Numerically equal to dense softmax attention;
    with dropout_rate > 0 (training), equal to softmax-then-hash-dropout
    (dense_attention_reference with the same seed).

    causal=True applies the lower-triangular constraint ANALYTICALLY per
    key block (an [Lq, block_k] bias built inside the scan body from the
    block's key positions) — never an [Lq, Lk] tensor, so long-context
    callers (ops/ulysses_attention.py) stay O(L·block_k) in memory.
    Assumes query position i attends key positions <= i with q/k indexed
    from the same origin (Lq == Lk self-attention).  ``window`` (with
    causal) narrows that to the band i - j < window, built the same way.
    """
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    scale = 1.0 / math.sqrt(D)
    block_k = min(block_k, Lk)
    n_blocks = -(-Lk // block_k)
    pad = n_blocks * block_k - Lk

    bias = mask_to_bias(mask)
    if bias is None:
        bias = jnp.zeros((1, 1, 1, Lk), jnp.float32)
    bias = jnp.broadcast_to(bias, (B,) + bias.shape[1:3] + (Lk,))
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        bias = jnp.pad(bias, ((0, 0), (0, 0), (0, 0), (0, pad)),
                       constant_values=NEG_INF)

    # [n, B, H, block, D] blocks as scan sequence
    kb = jnp.moveaxis(k.reshape(B, H, n_blocks, block_k, D), 2, 0)
    vb = jnp.moveaxis(v.reshape(B, H, n_blocks, block_k, D), 2, 0)
    bb = jnp.moveaxis(
        bias.reshape(B, bias.shape[1], bias.shape[2], n_blocks, block_k),
        3, 0)

    q_pos = jnp.arange(Lq, dtype=jnp.int32)
    # dropout_bh lets sharded callers (ops/ulysses_attention.py) pass the
    # GLOBAL [B,H,1,1] stream index so the drop pattern is placement-
    # independent; default is the local flattened b*H+h
    bh = bh_index(B, H) if dropout_bh is None else dropout_bh
    seed = (jnp.uint32(0) if dropout_seed is None
            else dropout_seed.astype(jnp.uint32))

    def body(carry, blk):
        m, l, acc = carry
        k_blk, v_blk, bias_blk, blk_idx = blk
        k_pos = blk_idx * block_k + jnp.arange(block_k, dtype=jnp.int32)
        if causal:
            keep_band = k_pos[None, :] <= q_pos[:, None]
            if window is not None:
                keep_band &= q_pos[:, None] - k_pos[None, :] < window
            cb = jnp.where(keep_band, 0.0, NEG_INF)
            bias_blk = bias_blk + cb[None, None]       # [B,1,Lq,block_k]
        keep = None
        if dropout_rate > 0.0:
            keep = dropout_keep(seed, bh, q_pos[None, None, :, None],
                                k_pos[None, None, None, :], dropout_rate)
        return online_block_update(q, k_blk, v_blk, bias_blk, m, l, acc,
                                   scale, keep_blk=keep), None

    (m, l, acc), _ = lax.scan(
        body, init_carry(q),
        (kb, vb, bb, jnp.arange(n_blocks, dtype=jnp.int32)))
    return finalize(m, l, acc, q.dtype)


def dense_attention_reference(q, k, v, mask=None, dropout_rate: float = 0.0,
                              dropout_seed: Optional[jax.Array] = None,
                              dropout_bh: Optional[jax.Array] = None):
    """O(L²) reference (transformer.py:180-193 semantics).  With
    dropout_rate > 0 applies the same index-hash dropout as the
    blockwise/Pallas paths (softmax first, then drop+rescale).
    ``dropout_bh``: optional GLOBAL [B,H,1,1] stream index for sharded
    callers (parallel/kernel_shard.py head-sharded flash); default is
    the local flattened b*H+h — the blockwise_attention convention."""
    B, H, Lq, _ = q.shape
    Lk = k.shape[2]
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    bias = mask_to_bias(mask)
    if bias is not None:
        s = s + bias
    p = jax.nn.softmax(s, axis=-1)
    if dropout_rate > 0.0:
        seed = (jnp.uint32(0) if dropout_seed is None
                else dropout_seed.astype(jnp.uint32))
        p = p * dropout_keep(seed,
                             bh_index(B, H) if dropout_bh is None
                             else dropout_bh,
                             jnp.arange(Lq, dtype=jnp.int32)[None, None, :,
                                                             None],
                             jnp.arange(Lk, dtype=jnp.int32)[None, None,
                                                             None, :],
                             dropout_rate)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)
