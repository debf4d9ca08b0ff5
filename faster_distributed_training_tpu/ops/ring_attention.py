"""Ring attention: sequence/context parallelism over an `sp` mesh axis.

The reference caps sequence length at 512 and computes O(L²) dense
attention on one device (transformer.py:35,180-193).  Here the sequence
dimension is sharded over the mesh's `sp` axis and K/V shards rotate
around the ring with `lax.ppermute` while each device accumulates
online-softmax statistics for its resident Q shard — attention memory
per device is O(L·L/sp) and the K/V transfers ride the ICI ring,
overlapping with the block computation.  This is the blockwise/ring
attention construction of Liu et al. (Ring Attention with Blockwise
Transformers), built from the same `online_block_update` primitive as
ops/attention.py so the math provably matches dense attention.

Gradients flow through `ppermute` (its transpose is the reverse
rotation), so the backward pass is ring-parallel too; the scan body is
`jax.checkpoint`-ed, keeping residual memory at one K/V shard per step.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from faster_distributed_training_tpu.ops.attention import (
    NEG_INF, bh_index, dropout_keep, finalize, init_carry, mask_to_bias,
    online_block_update)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str,
                   key_bias: Optional[jax.Array] = None,
                   causal: bool = False,
                   dropout_rate: float = 0.0,
                   dropout_seed: Optional[jax.Array] = None,
                   dropout_bh: Optional[jax.Array] = None) -> jax.Array:
    """Ring attention body — call INSIDE shard_map, sequence sharded on
    `axis_name`.

    q/k/v: [B, H, L_local, D] (this device's sequence shard),
    key_bias: [B, L_local] additive key bias (0 keep / NEG_INF drop) for
    this shard's keys, or None.  Returns [B, H, L_local, D].

    dropout_rate > 0 applies attention-prob dropout via the index hash
    (ops.attention.dropout_keep) with GLOBAL (stream, q, k) coordinates
    — sequence positions are already global here (idx/src · L + pos) and
    `dropout_bh` carries the caller's global batch·head index — so the
    pattern equals the dense/flash one for the same seed regardless of
    sp placement.
    """
    B, H, L, D = q.shape
    sp = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    scale = 1.0 / math.sqrt(D)
    perm = [(j, (j + 1) % sp) for j in range(sp)]
    if key_bias is None:
        key_bias = jnp.zeros((B, L), jnp.float32)

    pos = jnp.arange(L, dtype=jnp.int32)
    if dropout_bh is None:
        dropout_bh = bh_index(B, H)
    seed = (jnp.uint32(0) if dropout_seed is None
            else dropout_seed.astype(jnp.uint32))

    @jax.checkpoint
    def body(carry, _):
        k_cur, v_cur, b_cur, src, m, l, acc = carry
        bias = b_cur[:, None, None, :]                    # [B,1,1,L]
        q_pos = idx * L + pos                             # global positions
        k_pos = src * L + pos
        if causal:
            bias = bias + jnp.where(k_pos[None, :] <= q_pos[:, None],
                                    0.0, NEG_INF)[None, None]
        keep = None
        if dropout_rate > 0.0:
            keep = dropout_keep(seed, dropout_bh,
                                q_pos[None, None, :, None],
                                k_pos[None, None, None, :], dropout_rate)
        m, l, acc = online_block_update(q, k_cur, v_cur, bias, m, l, acc,
                                        scale, keep_blk=keep)
        # rotate the K/V shard to the next rank; XLA overlaps the ICI
        # transfer with the next step's matmuls where possible
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        b_cur = lax.ppermute(b_cur, axis_name, perm)
        return (k_cur, v_cur, b_cur, (src - 1) % sp, m, l, acc), None

    # init_carry derives the accumulators from q, giving them q's full
    # varying-manual-axes set (dp AND sp) so the scan carry types stay
    # stable under shard_map's VMA checking
    m0, l0, acc0 = init_carry(q)
    # l0 is a q-derived zeros tensor; adding its [B, L] slice stamps q's
    # VMA set onto the bias without changing its values
    carry0 = (k, v, key_bias.astype(jnp.float32) + l0[:, 0, :],
              idx, m0, l0, acc0)
    (_, _, _, _, m, l, acc), _ = lax.scan(body, carry0, None, length=sp)
    return finalize(m, l, acc, q.dtype)


def _ring_body(q, k, v, axis_name, key_mask=None, causal=False,
               dropout_rate=0.0, dropout_seed=None, dropout_bh=None):
    """sequence_parallel.sp_self_attention body shim: per-shard keep-mask
    -> additive bias (elementwise, so per-shard == global conversion)."""
    key_bias = None if key_mask is None else mask_to_bias(key_mask)
    return ring_attention(q, k, v, axis_name, key_bias=key_bias,
                          causal=causal, dropout_rate=dropout_rate,
                          dropout_seed=dropout_seed, dropout_bh=dropout_bh)


def ring_self_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        mask: Optional[jax.Array], mesh: Mesh,
                        sp_axis: str = "sp",
                        causal: bool = False,
                        dropout_rate: float = 0.0,
                        dropout_seed: Optional[jax.Array] = None
                        ) -> jax.Array:
    """shard_map wrapper: globally-shaped [B,H,L,D] in and out, with L
    sharded over `sp_axis`, B over the data axes, heads over tp when
    divisible (shared scaffolding: ops/sequence_parallel.py).

    mask: None, [B, L], or [B,1,1,L] key-padding mask (mask==0 masked)."""
    from faster_distributed_training_tpu.ops.sequence_parallel import (
        sp_self_attention)

    return sp_self_attention(_ring_body, q, k, v, mask, mesh,
                             sp_axis=sp_axis, causal=causal,
                             dropout_rate=dropout_rate,
                             dropout_seed=dropout_seed)
