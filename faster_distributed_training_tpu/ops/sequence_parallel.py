"""Shared shard_map scaffolding for the sequence-parallel attention
strategies (ring — ops/ring_attention.py, Ulysses — ops/ulysses_attention.py).

One wrapper owns the mesh policy both strategies share, so it cannot
drift between them:
  * batch over the data axes (dp and/or fsdp),
  * sequence over `sp_axis`,
  * heads over `tp` when present and divisible — head-parallelism inside
    sequence-parallelism,
  * key-padding mask normalized to a [B, L] keep-mask sharded like the
    sequence.

The per-strategy `body` runs INSIDE shard_map on per-device shards with
signature body(q, k, v, axis_name=..., key_mask=None, causal=False).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names)


def sp_self_attention(body: Callable, q: jax.Array, k: jax.Array,
                      v: jax.Array, mask: Optional[jax.Array], mesh: Mesh,
                      sp_axis: str = "sp", causal: bool = False,
                      heads_per_shard_divisor: int = 1,
                      dropout_rate: float = 0.0,
                      dropout_seed: Optional[jax.Array] = None
                      ) -> jax.Array:
    """Globally-shaped [B,H,L,D] in/out with L sharded over `sp_axis`,
    B over the data axes, H over tp when divisible.

    mask: None, [B, L], or [B,1,1,L] key-padding mask (mask==0 masked).
    heads_per_shard_divisor: extra divisibility the strategy needs from
    the per-device head count (Ulysses splits its local heads over sp
    again, so it passes the sp size; the ring passes 1).
    dropout_rate/dropout_seed: attention-prob hash dropout; the wrapper
    hands each body its GLOBAL [B_loc,H_loc,1,1] batch·head stream index
    (built from the dp/fsdp/tp axis indices) so the drop pattern is
    identical to the single-device one for the same seed."""
    B, H, L, D = q.shape
    batch = batch_axes(mesh)
    lead = batch if len(batch) != 1 else batch[0]
    # head-parallelism inside sequence-parallelism — UNLESS the tp axis
    # IS the sequence axis (a 2D (dp, tp) mesh running ring/ulysses over
    # tp, r11): one mesh axis cannot shard both heads and sequence
    tp = (mesh.shape["tp"]
          if "tp" in mesh.axis_names and sp_axis != "tp" else 1)
    head = ("tp" if tp > 1 and H % tp == 0
            and (H // tp) % heads_per_shard_divisor == 0 else None)
    qkv_spec = P(lead, head, sp_axis, None)
    mask_spec = P(lead, sp_axis)

    key_mask = None
    if mask is not None:
        mask = jnp.asarray(mask)
        if mask.ndim == 4:
            mask = mask.reshape(B, mask.shape[-1])
        key_mask = mask

    b_shards = 1
    for a in batch:
        b_shards *= mesh.shape[a]
    b_loc, h_loc = B // b_shards, H // (tp if head else 1)

    def global_bh():
        """[b_loc, h_loc, 1, 1] global b*H+h for this device's shard."""
        b_idx = jnp.int32(0)
        for a in batch:                      # row-major over the data axes
            b_idx = b_idx * mesh.shape[a] + lax.axis_index(a)
        b0 = b_idx * b_loc
        h0 = lax.axis_index("tp") * h_loc if head else jnp.int32(0)
        return ((b0 + jnp.arange(b_loc, dtype=jnp.int32))[:, None] * H
                + (h0 + jnp.arange(h_loc, dtype=jnp.int32))[None, :]
                )[:, :, None, None]

    fn = partial(body, axis_name=sp_axis, causal=causal)
    has_mask = key_mask is not None
    has_drop = dropout_rate > 0.0

    # build the operand list + specs dynamically: the traced dropout seed
    # enters shard_map as an explicit replicated operand, not a closure
    args, specs = [q, k, v], [qkv_spec] * 3
    if has_mask:
        args.append(key_mask)
        specs.append(mask_spec)
    if has_drop:
        seed = (jnp.uint32(0) if dropout_seed is None
                else dropout_seed.astype(jnp.uint32))
        args.append(seed)
        specs.append(P())

    def call(q_, k_, v_, *rest):
        rest = list(rest)
        kw = {}
        if has_mask:
            kw["key_mask"] = rest.pop(0)
        if has_drop:
            kw.update(dropout_rate=dropout_rate, dropout_seed=rest.pop(0),
                      dropout_bh=global_bh())
        return fn(q_, k_, v_, **kw)

    return jax.shard_map(call, mesh=mesh, in_specs=tuple(specs),
                         out_specs=qkv_spec)(*args)
