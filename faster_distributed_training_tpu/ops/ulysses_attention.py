"""Ulysses-style sequence parallelism: all-to-all head/sequence swap.

The second canonical long-context strategy next to ring attention
(ops/ring_attention.py) — the DeepSpeed-Ulysses construction.  The
sequence axis arrives sharded over the mesh's `sp` axis; one
`lax.all_to_all` re-shards the tensors from sequence-split to
HEAD-split, so every device computes ordinary full-length attention for
H/sp of the heads; a second all_to_all swaps back.

Trade-off vs the ring (why both exist):
  * Ulysses moves each Q/K/V/O tensor twice over the interconnect
    regardless of sp, and needs H % sp == 0 — but the inner attention
    is a plain full-L kernel (here: blockwise online-softmax, so the
    L×L matrix is never materialized) with no per-step collective, and
    its communication volume is O(B·H·L·D/sp) per tensor, independent
    of the number of ring steps.
  * The ring keeps K/V moving hop-by-hop (sp ppermutes) and supports
    any sp; its collectives interleave with compute.
The reference has neither (maxlen capped at 512, dense O(L²) on one
device — transformer.py:35,180-193, SURVEY.md §5 long-context).

Gradients flow through `all_to_all` (its transpose is the reverse
all_to_all), so the backward pass is sequence-parallel too.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from faster_distributed_training_tpu.ops.attention import (bh_index,
                                                           blockwise_attention)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis_name: str,
                      key_mask: Optional[jax.Array] = None,
                      causal: bool = False,
                      dropout_rate: float = 0.0,
                      dropout_seed: Optional[jax.Array] = None,
                      dropout_bh: Optional[jax.Array] = None) -> jax.Array:
    """Ulysses body — call INSIDE shard_map, sequence sharded on `axis_name`.

    q/k/v: [B, H, L_local, D] (this device's sequence shard); H must be
    divisible by the axis size.  key_mask: [B, L_local] boolean/0-1 key
    keep-mask for this shard's keys (0 = masked), or None.
    Returns [B, H, L_local, D].

    dropout_rate > 0 applies attention-prob hash dropout inside the
    inner blockwise attention.  `dropout_bh` is the caller's global
    [B,H_loc,1,1] batch·head index for the PRE-swap heads; after the
    all_to_all this device holds heads [j·H_loc/sp, (j+1)·H_loc/sp) of
    that range (j = this device's sp index), so the matching slice keeps
    the pattern equal to the dense/flash one for the same seed.
    """
    B, H, L_loc, D = q.shape
    sp = lax.axis_size(axis_name)
    if H % sp:
        raise ValueError(f"Ulysses needs heads ({H}) divisible by the "
                         f"sp axis size ({sp}); use ring attention otherwise")

    # seq-sharded [B, H, L/sp, D] -> head-sharded [B, H/sp, L, D]
    def seq_to_head(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = seq_to_head(q), seq_to_head(k), seq_to_head(v)

    mask4 = None
    if key_mask is not None:
        # every device needs the mask for ALL keys once heads are split;
        # stays [B,1,1,L] — the causal constraint is applied analytically
        # per key block inside blockwise_attention, never as an [L,L] mask
        full = lax.all_gather(key_mask, axis_name, axis=1, tiled=True)
        mask4 = (full != 0)[:, None, None, :]                # [B,1,1,L]

    bh_post = None
    if dropout_rate > 0.0:
        if dropout_bh is None:
            dropout_bh = bh_index(B, H)
        j = lax.axis_index(axis_name)
        h_per = H // sp
        # this device's post-swap head slice of the global index table
        bh_post = lax.dynamic_slice_in_dim(dropout_bh, j * h_per, h_per,
                                           axis=1)

    # full-length attention on H/sp heads; blockwise keeps memory O(L·blk)
    out = blockwise_attention(qh, kh, vh, mask=mask4,
                              block_k=min(512, qh.shape[2]),
                              causal=causal, dropout_rate=dropout_rate,
                              dropout_seed=dropout_seed,
                              dropout_bh=bh_post)

    # head-sharded [B, H/sp, L, D] -> seq-sharded [B, H, L/sp, D]
    return lax.all_to_all(out, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)


def ulysses_self_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           mask: Optional[jax.Array], mesh: Mesh,
                           sp_axis: str = "sp",
                           causal: bool = False,
                           dropout_rate: float = 0.0,
                           dropout_seed: Optional[jax.Array] = None
                           ) -> jax.Array:
    """shard_map wrapper mirroring ring_self_attention: globally-shaped
    [B,H,L,D] in/out with L sharded over `sp_axis`, B over the data axes,
    heads over tp when H % (tp * sp) == 0 (shared scaffolding:
    ops/sequence_parallel.py — the per-device head count must still split
    over sp inside the body, hence the extra divisor).

    mask: None, [B, L], or [B,1,1,L] key-padding mask (mask==0 masked)."""
    from faster_distributed_training_tpu.ops.sequence_parallel import (
        sp_self_attention)

    sp = mesh.shape[sp_axis] if sp_axis in mesh.axis_names else 1
    return sp_self_attention(ulysses_attention, q, k, v, mask, mesh,
                             sp_axis=sp_axis, causal=causal,
                             heads_per_shard_divisor=sp,
                             dropout_rate=dropout_rate,
                             dropout_seed=dropout_seed)
