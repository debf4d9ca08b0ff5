"""The reference's hand-rolled LayerNorm math, ONE definition.

The reference normalizes with UNBIASED std and adds eps to the std, not
the variance (transformer.py:230-242) — nonstandard on both counts, so
the fp32 core lives here and every consumer delegates:
``models.transformer.TorchLayerNorm`` (the Flax module) and
``ops.fused_ffn`` (the fused FFN-sublayer kernel and its reference/
backward fn).  A semantics change in one place cannot silently
desynchronize the implementations (the checkpoint-interchange guarantee
between ``ffn_impl`` settings depends on them agreeing).

Two entry points:

  * ``torch_layernorm_f32`` — the pure fp32 math under default XLA
    autodiff.  This is what runs INSIDE the Pallas FFN kernel (Mosaic
    traces the primal only) and is the oracle the saved-stats VJP is
    tested against.
  * ``torch_layernorm`` — the same primal wrapped in a ``custom_vjp``
    that saves per-row ``(mean, rstd)`` — two scalars per row — beside
    the input (VERDICT r4/r5 #4: the r5 identity-LN probe measured the
    transformer's 13 LN sites at ~7.5 ms/step @ bs256/seq256 of pure
    HBM round-trips; the fused-FFN recompute-backward attack measured a
    net LOSS, so this is the standard saved-stats alternative).  XLA's
    default autodiff saves the centered input and the rsqrt chain —
    O(rows·d) extra residual traffic per site; here the backward
    rebuilds x̂ from ``(x, mean, rstd)`` with one fused elementwise
    pass, so residual traffic per site drops to the input (alive
    anyway, it feeds the sublayer residual add) plus 2 scalars/row.
    Kill switch ``FDT_LN_SAVED_STATS=0`` restores default autodiff for
    A/B probes (none is in the tree: ROADMAP D3).

The backward math, for y = γ·x̂ + β with x̂ = (x − μ)·r,
r = 1/(σ + eps), σ = √(Σ(x−μ)²/(n−1)) (UNBIASED, n−1):

    gy  = g · γ
    dβ  = Σ_rows g          dγ = Σ_rows g · x̂
    dx  = r·(gy − mean_j gy) − x̂ · Σ_j(gy·x̂) / (σ·(n−1))

(The second term differs from standard LayerNorm's 1/n by the unbiased
n−1, and σ = 1/r − eps re-derives the std from the saved rstd; both are
pinned against XLA autodiff of the raw math by
tests/test_ops.py::TestSavedStatsLayerNorm.)
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp


def torch_layernorm_f32(x32: jax.Array, scale: jax.Array, bias: jax.Array,
                        eps: float) -> jax.Array:
    """fp32 TorchLayerNorm over the last axis: unbiased variance (n-1),
    eps added to the STD.  Inputs and outputs fp32; callers cast.
    Pure math under default autodiff — the in-kernel / oracle form."""
    d = x32.shape[-1]
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.sum(jnp.square(x32 - mean), axis=-1, keepdims=True) / (d - 1)
    return scale * ((x32 - mean) / (jnp.sqrt(var) + eps)) + bias


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ln_saved_stats(x32, scale, bias, eps):
    return torch_layernorm_f32(x32, scale, bias, eps)


def _ln_fwd(x32, scale, bias, eps):
    d = x32.shape[-1]
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.sum(jnp.square(x32 - mean), axis=-1, keepdims=True) / (d - 1)
    std = jnp.sqrt(var) + eps
    # primal via the SAME division expression as torch_layernorm_f32 so
    # the forward is bit-identical to the pure form (the fused-FFN
    # kernel-vs-reference agreement depends on one forward definition);
    # rstd is a residual only
    out = scale * ((x32 - mean) / std) + bias
    return out, (x32, scale, mean, 1.0 / std)


def _ln_bwd(eps, res, g):
    x32, scale, mean, rstd = res
    d = x32.shape[-1]
    xhat = (x32 - mean) * rstd                       # rebuilt, not stored
    # dtype-generic: fp32 from the model callers (they cast), fp64 under
    # the gradcheck-style tests — never downcast the cotangent
    g32 = g.astype(jnp.promote_types(g.dtype, jnp.float32))
    dbias = jnp.sum(g32.reshape(-1, d), axis=0)
    dscale = jnp.sum((g32 * xhat).reshape(-1, d), axis=0)
    gy = g32 * scale
    c1 = jnp.mean(gy, axis=-1, keepdims=True)
    c2 = jnp.sum(gy * xhat, axis=-1, keepdims=True)
    # sigma re-derived from the saved rstd (sigma = 1/r - eps); the
    # unbiased variance makes the projection term 1/(sigma*(d-1)), not
    # the standard 1/(sigma*d)
    sigma = 1.0 / rstd - eps
    dx = rstd * (gy - c1) - xhat * (c2 / (sigma * (d - 1)))
    return dx, dscale, dbias


_ln_saved_stats.defvjp(_ln_fwd, _ln_bwd)


def torch_layernorm(x32: jax.Array, scale: jax.Array, bias: jax.Array,
                    eps: float) -> jax.Array:
    """torch_layernorm_f32 with the saved-stats custom_vjp backward (the
    hot-path form — see module docstring).  FDT_LN_SAVED_STATS=0 falls
    back to the pure function under default autodiff (A/B probes)."""
    if os.environ.get("FDT_LN_SAVED_STATS", "1") == "0":
        return torch_layernorm_f32(x32, scale, bias, eps)
    return _ln_saved_stats(x32, scale, bias, eps)
