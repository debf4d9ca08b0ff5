"""Fused 2-layer MLP (linear → ReLU → linear) with hand-written backward.

TPU-native re-design of the reference's ``MLPScratch``
(``transformer.py:292-338``): one ``jax.custom_vjp`` covering both
linears and the activation so the pair of matmuls stays on the MXU with
the ReLU fused into the epilogue.

Reference-semantics notes:
  * weights are stored ``(out, in)`` like ``torch.nn.Linear`` in the
    reference's ``FusedMLP`` (``transformer.py:345-358``); biases are
    broadcast row vectors;
  * the reference's backward contains a *scalar Python loop* over every
    element for the ReLU mask (``transformer.py:323-324``) — a
    deliberate perf bug we fix with a vectorized ``where``;
  * the reference reduces bias gradients with ``mean`` over the batch
    axis (``transformer.py:311,327``), which is mathematically a factor
    1/B off; we default to the correct ``sum`` and expose
    ``mean_bias_grad=True`` for bit-parity experiments;
  * the reference saves the hidden activations for backward
    (``transformer.py:301``); we *recompute* the first linear instead
    (one extra matmul), the same rematerialization stance as the fused
    conv — cheaper in HBM, and XLA overlaps the recompute with the
    cotangent matmuls.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from faster_distributed_training_tpu.ops import pallas_target


def mlp_reference(x: jax.Array, w1: jax.Array, b1: Optional[jax.Array],
                  w2: jax.Array, b2: Optional[jax.Array]) -> jax.Array:
    """Unfused oracle: linear→ReLU→linear with (out,in) weights."""
    h = x @ w1.T + (0.0 if b1 is None else b1)
    a = jax.nn.relu(h)
    return a @ w2.T + (0.0 if b2 is None else b2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def fused_mlp(x: jax.Array, w1: jax.Array, b1: Optional[jax.Array],
              w2: jax.Array, b2: Optional[jax.Array],
              mean_bias_grad: bool = False) -> jax.Array:
    h = x @ w1.T + (0.0 if b1 is None else b1)
    a = jax.nn.relu(h)
    return a @ w2.T + (0.0 if b2 is None else b2)


def _mlp_fwd(x, w1, b1, w2, b2, mean_bias_grad):
    h = x @ w1.T + (0.0 if b1 is None else b1)
    a = jax.nn.relu(h)
    out = a @ w2.T + (0.0 if b2 is None else b2)
    # residuals: inputs only — h and a are recomputed in backward.
    return out, (x, w1, b1, w2, b2)


def _mlp_bwd(mean_bias_grad, res, g):
    x, w1, b1, w2, b2 = res
    # recompute the hidden pre-activation (rematerialization)
    h = x @ w1.T + (0.0 if b1 is None else b1)
    a = jax.nn.relu(h)

    lead = x.shape[:-1]
    gf = g.reshape(-1, g.shape[-1])          # (B*, d_out)
    af = a.reshape(-1, a.shape[-1])          # (B*, d_hidden)
    xf = x.reshape(-1, x.shape[-1])          # (B*, d_in)

    d_w2 = gf.T @ af                          # (d_out, d_hidden)
    d_a = g @ w2                              # (..., d_hidden)
    # vectorized ReLU mask — fixes the scalar loop at transformer.py:323-324
    d_h = jnp.where(h > 0, d_a, 0.0)
    d_hf = d_h.reshape(-1, d_h.shape[-1])
    d_w1 = d_hf.T @ xf                        # (d_hidden, d_in)
    d_x = d_h @ w1

    red = jnp.mean if mean_bias_grad else jnp.sum
    d_b1 = None if b1 is None else red(d_hf, axis=0).reshape(b1.shape)
    d_b2 = None if b2 is None else red(gf, axis=0).reshape(b2.shape)
    del lead
    return d_x, d_w1, d_b1, d_w2, d_b2


fused_mlp.defvjp(_mlp_fwd, _mlp_bwd)


# ---------------------------------------------------------------------------
# Pallas forward kernel — both matmuls + ReLU in one VMEM-resident pass
# ---------------------------------------------------------------------------

def _mlp_kernel(x_ref, w1t_ref, b1_ref, w2t_ref, b2_ref, o_ref):
    """One row-block: h = x@w1ᵀ+b1; out = relu(h)@w2ᵀ+b2.

    The hidden activations live only in VMEM/registers — they are never
    written to HBM, which is the point of fusing (the reference instead
    *saves* them for backward, transformer.py:301)."""
    x = x_ref[...]
    h = jax.lax.dot(x, w1t_ref[...],
                    preferred_element_type=jnp.float32) + b1_ref[...]
    a = jnp.maximum(h, 0.0).astype(x.dtype)
    o = jax.lax.dot(a, w2t_ref[...],
                    preferred_element_type=jnp.float32) + b2_ref[...]
    o_ref[...] = o.astype(o_ref.dtype)


def _mlp_fwd_pallas(x2d: jax.Array, w1: jax.Array, b1: jax.Array,
                    w2: jax.Array, b2: jax.Array,
                    block_b: int = 256) -> jax.Array:
    """x2d [B, d_in]; weights (out, in) like torch.nn.Linear.  Weights are
    passed transposed and fully VMEM-resident (d_model≤1k → ≤4 MiB of the
    ~16 MiB budget); rows are tiled over the grid."""
    from jax.experimental import pallas as pl

    B, d_in = x2d.shape
    d_h, d_out = w1.shape[0], w2.shape[0]
    block_b = min(block_b, B)
    nb = -(-B // block_b)
    pad = nb * block_b - B
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        _mlp_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_b, d_in), lambda i: (i, 0)),
            pl.BlockSpec((d_in, d_h), lambda i: (0, 0)),
            pl.BlockSpec((1, d_h), lambda i: (0, 0)),
            pl.BlockSpec((d_h, d_out), lambda i: (0, 0)),
            pl.BlockSpec((1, d_out), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, d_out), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * block_b, d_out), x2d.dtype),
        interpret=pallas_target.interpret(),
        name="fdt_fused_mlp",
    )(x2d, w1.T, jnp.reshape(b1, (1, d_h)), w2.T, jnp.reshape(b2, (1, d_out)))
    return out[:B] if pad else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def fused_mlp_pallas(x: jax.Array, w1: jax.Array, b1: Optional[jax.Array],
                     w2: jax.Array, b2: Optional[jax.Array],
                     mean_bias_grad: bool = False) -> jax.Array:
    """Pallas-kernel forward of the fused MLP; backward is the same
    recompute-in-backward VJP as ``fused_mlp`` (plain MXU matmuls XLA
    already schedules well).  Interpreter mode runs it on CPU for tests."""
    zero1 = jnp.zeros((w1.shape[0],), x.dtype) if b1 is None else b1
    zero2 = jnp.zeros((w2.shape[0],), x.dtype) if b2 is None else b2
    lead = x.shape[:-1]
    out = _mlp_fwd_pallas(x.reshape(-1, x.shape[-1]), w1, zero1, w2, zero2)
    return out.reshape(*lead, w2.shape[0])


def _mlp_fwd_pallas_vjp(x, w1, b1, w2, b2, mean_bias_grad):
    return fused_mlp_pallas(x, w1, b1, w2, b2, mean_bias_grad), (
        x, w1, b1, w2, b2)


fused_mlp_pallas.defvjp(_mlp_fwd_pallas_vjp, _mlp_bwd)
