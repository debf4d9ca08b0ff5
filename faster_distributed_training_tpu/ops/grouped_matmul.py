"""Grouped matrix products for an expert layer's held experts.

``grouped_matmul(lhs [M, K], rhs [G, K, N], group_sizes [G])`` multiplies
the first ``group_sizes[0]`` rows of ``lhs`` by ``rhs[0]``, the next
``group_sizes[1]`` by ``rhs[1]``, and so on.  The rows past the last group
(``M`` is the static worst case, every token-slot landing here) come back
as zeros, and ``gmm`` visits no tile past the last group.

  ``gmm``     the megablox kernel that ships inside the installed jax
              (``jax.experimental.pallas.ops.tpu.megablox``): a Pallas
              grid over the row tiles the groups cover, the group of each
              tile prefetched as scalars; its backward is the same kernel
              (d lhs) and its transposed twin ``tgmm`` (d rhs).  The
              default on a TPU target.  It leaves the rows past the last
              group unwritten, so every product is written over zeros.
  ``ragged``  ``jax.lax.ragged_dot``: XLA's own lowering; the default
              everywhere else (the CPU tests), and the A/B twin on the
              chip (``impl="ragged"``).

PERF.md section 6 (PR 33) has the chip readings that chose the default.
"""

from __future__ import annotations

import functools
import importlib
from typing import Optional

import jax
import jax.numpy as jnp

from faster_distributed_training_tpu.ops import pallas_target

# (rows, contraction, columns) of a gmm tile: the row tile is the unit in
# which work follows the slots that landed here
GMM_TILING = (512, 1024, 1024)


def resolve_impl(impl: Optional[str] = None) -> str:
    impl = impl or ("gmm" if pallas_target.on_tpu() else "ragged")
    if impl not in ("gmm", "ragged"):
        raise ValueError(f"grouped matmul impl {impl!r}; have gmm, ragged")
    return impl


def _tiling(m: int, k: int, n: int):
    tm, tk, tn = GMM_TILING
    return min(tm, m), min(tk, k), min(tn, n)


def _megablox():
    # the package re-exports its ``gmm`` FUNCTION over the module's name
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm(lhs, rhs, group_sizes, interpret):
    """megablox's kernels under a rule of our own: every product is
    written over zeros (``existing_out``), forward and backward, so the
    rows past the last group are zeros and not whatever the buffer held.
    The library's own rule leaves them unwritten in d lhs too, and that
    would be scattered into the tokens' gradient."""
    backend = _megablox()
    m, k = lhs.shape
    n = rhs.shape[2]
    return backend.gmm(lhs, rhs, group_sizes, lhs.dtype, _tiling(m, k, n),
                       existing_out=jnp.zeros((m, n), lhs.dtype),
                       interpret=interpret)


def _gmm_fwd(lhs, rhs, group_sizes, interpret):
    return _gmm(lhs, rhs, group_sizes, interpret), (lhs, rhs, group_sizes)


def _gmm_bwd(interpret, res, g):
    backend = _megablox()
    lhs, rhs, group_sizes = res
    m, k = lhs.shape
    n = rhs.shape[2]
    tiling = _tiling(m, k, n)
    d_lhs = backend.gmm(g, rhs, group_sizes, lhs.dtype, tiling,
                        existing_out=jnp.zeros((m, k), lhs.dtype),
                        transpose_rhs=True, interpret=interpret)
    d_rhs = backend.tgmm(lhs.swapaxes(0, 1), g, group_sizes, rhs.dtype,
                         tiling, num_actual_groups=rhs.shape[0],
                         interpret=interpret)
    return d_lhs, d_rhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   impl: Optional[str] = None) -> jax.Array:
    """[M, K] x [G, K, N] -> [M, N] in ``lhs``'s dtype, accumulated in
    float32; rows past ``sum(group_sizes)`` are zero."""
    impl = resolve_impl(impl)
    group_sizes = group_sizes.astype(jnp.int32)
    rhs = rhs.astype(lhs.dtype)
    if impl == "ragged":
        # on the TPU XLA's lowering leaves the rows past the last group
        # as it found them (NaN in the cell's first run, PERF.md section
        # 6): a select on both sides keeps them, and what they would
        # send back, at zero
        landed = (jnp.arange(lhs.shape[0], dtype=jnp.int32)[:, None]
                  < jnp.sum(group_sizes))
        out = jax.lax.ragged_dot(jnp.where(landed, lhs, 0), rhs, group_sizes,
                                 preferred_element_type=jnp.float32)
        return jnp.where(landed, out, 0).astype(lhs.dtype)
    return _gmm(lhs, rhs, group_sizes, pallas_target.interpret())
