"""Quantized-training matmuls: int8 / fp8 GEMMs with per-tensor
delayed scaling (the ROADMAP "close the MFU gap with low-precision
compute" lever).

The r5 chip readings put the transformer at 29.1% MFU against a 35%
bf16 GEMM ceiling (not measured since; no cell of the ledger runs the
encoder, PERF.md 7 row 0b) — ~6 points of headroom at this precision.  The
MXU's int8/fp8 throughput is ~2x its bf16 peak, so the big remaining
lever is dropping the GEMM operand precision while keeping fp32
accumulation.  This module follows the established low-precision
training recipe:

  * **per-tensor delayed scaling** (FP8-LM / NVIDIA Transformer Engine
    style): each quantized tensor site keeps a short amax HISTORY; the
    scale used at step t is derived from the history of steps < t (so
    quantization is a cheap elementwise multiply+round with no
    serialized reduction before the GEMM), and step t's amax is pushed
    into the history for step t+1.  The history/scale state lives in
    the model's ``batch_stats`` collection — the existing cross-step
    statistics channel — so the r8 fused-dispatch carry, checkpointing
    and kill-at-N bitwise resume all carry it with ZERO new plumbing
    (exactly like the loss-scale/NGD state already in the carry).
  * **symmetric quantization with fp32 accumulation** (LLM.int8()-style
    per-tensor scaling): int8 GEMMs accumulate int32, fp8 GEMMs
    accumulate fp32, and the combined ``sx*sw`` dequant scale is applied
    once on the fp32 accumulator.
  * **quantized backward residuals**: ``quant_dot``'s custom_vjp saves
    the QUANTIZED operands (1 byte/elem) and dequantizes them inside the
    backward — the gradient GEMMs themselves run in the compute dtype
    (straight-through estimator through the rounding), so training
    dynamics stay close to the full-precision path while forward GEMMs
    and residual memory take the low-precision win.  ``--quant_grad
    fp8_e5m2`` (r19) completes the FP8-LM recipe: the cotangent is
    quantized to the wide-range E5M2 grid at a just-in-time per-tensor
    scale and BOTH gradient GEMMs run on quantized operands (the
    quantized-dW path).

Kernel routing follows the repo's Pallas idioms (ops/fused_ffn.py):
the tiled Pallas kernel runs only on TPU, respects a static VMEM-fit
guard (``quant_kernel_fits_vmem``) with a degrading row tile, and falls
back WARNED to the XLA reference path — same math, ``lax.dot_general``
on the quantized operands — on unsupported shapes.  On tp meshes the
kernel runs PER-SHARD on the Megatron column/row-sharded weight tiles
through the shard_map layer (parallel/kernel_shard.py, r19); the old
XLA-reference reroute survives only as the registered warned fallback
(FDT_KERNEL_SHARD=0 or non-dividing shapes).  ``FDT_QUANT=0`` kills
quantization entirely — every site computes the plain full-precision
matmul.

Determinism contract: quantization is round-to-nearest (no stochastic
rounding), amaxes are plain max-reductions, and the scale state rides
the train-state carry — so K=4 fused dispatch is bitwise-equal to K=1
and a kill-at-N resume is bitwise-equal to the uninterrupted run
(pinned by tests/test_quant.py).
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from faster_distributed_training_tpu.ops import pallas_target

try:
    from jax.experimental import pallas as pl
except ImportError:  # pragma: no cover
    pl = None

ENV_KILL = "FDT_QUANT"

# symmetric-quantization grids: the largest magnitude each format
# represents.  int8 uses 127 (not 128) so the grid is symmetric; fp8
# uses the finite max of each IEEE-ish variant (E4M3 has no inf and
# tops out at 448; E5M2 keeps inf/nan and tops out at 57344 — the
# wide-range variant the fp8 literature reserves for GRADIENTS).
QMAX = {"int8": 127.0,
        "fp8": 448.0,        # forward operands ride E4M3
        "fp8_e4m3": 448.0,
        "fp8_e5m2": 57344.0}

_FMTS = ("int8", "fp8")


def quant_enabled() -> bool:
    """The FDT_QUANT=0 kill switch (read per call so tests can flip it):
    False means every quantized site computes plain full-precision."""
    return os.environ.get(ENV_KILL, "1") != "0"


# -- pure scale-state helpers (the delayed-scaling recipe) ----------------

def fresh_amax_history(length: int = 16) -> jax.Array:
    """Zero-initialized amax history — scale_from_history treats the
    all-zero history as "never observed" and returns scale 1.0."""
    return jnp.zeros((int(length),), jnp.float32)


def update_amax_history(history: jax.Array, amax: jax.Array) -> jax.Array:
    """Push the newest amax in at index 0, shifting the rest (the oldest
    falls off).  Pure, shapes static — safe inside the fused-dispatch
    scan."""
    amax = jnp.asarray(amax, jnp.float32).reshape(1)
    return jnp.concatenate([amax, history[:-1]])


def scale_from_history(history: jax.Array, fmt: str,
                       margin: float = 1.0) -> jax.Array:
    """Delayed scale for the NEXT quantization: qmax / (margin * running
    amax), where the running amax is the max over the history window
    (Transformer Engine's "max" amax_compute_algo).  An all-zero history
    (fresh state, or a genuinely all-zero tensor) yields scale 1.0 —
    quantizing zeros is exact at any scale, and the first real step
    seeds the history for the second."""
    return _scale_from_amax(jnp.max(history) * jnp.float32(margin), fmt)


def _scale_from_amax(amax: jax.Array, fmt: str) -> jax.Array:
    """THE amax→scale formula (zero-amax → identity scale, 1e-30
    floor): shared by the delayed forward scales (scale_from_history)
    and the just-in-time gradient scales (_jit_grad_scale) so the two
    recipes can never drift on the clamp/zero-guard convention."""
    qmax = QMAX[fmt]
    return jnp.where(amax > 0.0, qmax / jnp.maximum(amax, 1e-30),
                     jnp.float32(1.0)).astype(jnp.float32)


def tensor_amax(x: jax.Array) -> jax.Array:
    """Current-step amax in fp32 (computed on the pre-quantization
    values; fp16/bf16 inputs are upcast first so the reduction can't
    overflow or lose the true max to rounding)."""
    return jnp.max(jnp.abs(x.astype(jnp.float32)))


# -- quant/dequant helpers (pure, shared by kernel + reference) -----------

def quantize_int8(x: jax.Array, scale: jax.Array) -> jax.Array:
    """Symmetric int8: q = clip(round(x * scale), ±127).  jnp.round is
    round-half-even — deterministic across backends, which the bitwise
    K-dispatch/resume pins need (stochastic rounding would too, but
    only with key threading this recipe doesn't require)."""
    xs = x.astype(jnp.float32) * scale
    return jnp.clip(jnp.round(xs), -QMAX["int8"],
                    QMAX["int8"]).astype(jnp.int8)


def quantize_fp8(x: jax.Array, scale: jax.Array,
                 variant: str = "e4m3") -> jax.Array:
    """fp8 quantization: scale into the format's representable range,
    clip to the finite max (E4M3 has no inf — an unclipped overflow
    would land on NaN), and cast (round-to-nearest-even)."""
    dt = jnp.float8_e4m3fn if variant == "e4m3" else jnp.float8_e5m2
    qmax = QMAX[f"fp8_{variant}"]
    xs = jnp.clip(x.astype(jnp.float32) * scale, -qmax, qmax)
    return xs.astype(dt)


def quantize(x: jax.Array, scale: jax.Array, fmt: str) -> jax.Array:
    if fmt == "int8":
        return quantize_int8(x, scale)
    if fmt in ("fp8", "fp8_e4m3"):
        return quantize_fp8(x, scale, "e4m3")
    if fmt == "fp8_e5m2":
        return quantize_fp8(x, scale, "e5m2")
    raise ValueError(f"unknown quant format {fmt!r}; have int8/fp8"
                     f"/fp8_e4m3/fp8_e5m2")


def dequantize(q: jax.Array, scale: jax.Array,
               dtype=jnp.float32) -> jax.Array:
    """x ≈ q / scale.  The inverse is multiplied in fp32 and cast once —
    the same one-rounding discipline as ops/dropout.py's keep factors."""
    return (q.astype(jnp.float32) * (1.0 / scale)).astype(dtype)


# -- the quantized GEMM ---------------------------------------------------

def _acc_dtype(fmt: str):
    # int8 pairs accumulate exactly in int32 (the MXU's s8xs8->s32 path;
    # float accumulation would round past 2^24); fp8 accumulates fp32
    return jnp.int32 if fmt == "int8" else jnp.float32


def _dot_q(xq: jax.Array, wq: jax.Array, fmt: str) -> jax.Array:
    """The quantized-operand contraction, fp32 result (pre-descale).
    int8: s8 x s8 -> s32 exactly.  fp8: operands upcast to fp32 for the
    XLA path — every fp8 value is exactly representable in fp32, so this
    IS "fp8 operands, fp32 accumulation" math; on hardware with native
    fp8 MXU paths XLA may lower the fused cast+dot directly."""
    if fmt == "int8":
        acc = lax.dot_general(xq, wq, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32)
    return lax.dot_general(xq.astype(jnp.float32), wq.astype(jnp.float32),
                           (((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def quant_dot_reference(xq: jax.Array, wq: jax.Array, sx: jax.Array,
                        sw: jax.Array, fmt: str, out_dtype) -> jax.Array:
    """XLA-reference quantized GEMM on ALREADY-QUANTIZED operands:
    out = (xq · wq) / (sx*sw), accumulated per _dot_q, descaled in fp32,
    one final cast.  This is both the off-TPU/fallback compute path and
    the oracle the Pallas kernel is pinned against."""
    acc = _dot_q(xq, wq, fmt)
    inv = 1.0 / (sx.astype(jnp.float32) * sw.astype(jnp.float32))
    return (acc * inv).astype(out_dtype)


# Static VMEM budget for the Pallas kernel's resident set, patterned on
# ops/fused_ffn.py: the quantized weight matrix stays VMEM-resident
# across the row-block grid; each block holds its quantized x rows, the
# accumulator tile and the fp32/output tile.
_QUANT_VMEM_BUDGET = 12 * 1024 * 1024


def _quant_vmem_bytes(k: int, n: int, block_rows: int) -> int:
    """Resident-set model at 1 byte/elem quantized operands: wq (k,n) +
    xq block (block,k) + int32/fp32 accumulator and out tiles
    (2 * block * n * 4)."""
    return k * n + block_rows * k + 2 * block_rows * n * 4


def quant_kernel_fits_vmem(k: int, n: int) -> bool:
    """Static go/no-go at the SMALLEST row tile — the check callers
    mirror before handing shapes to the kernel (the
    ffn_kernel_fits_vmem idiom)."""
    return _quant_vmem_bytes(k, n, 32) <= _QUANT_VMEM_BUDGET


def _quant_matmul_kernel(xq_ref, wq_ref, inv_ref, o_ref, *, fmt: str):
    if fmt == "int8":
        acc = lax.dot(xq_ref[...], wq_ref[...],
                      preferred_element_type=jnp.int32).astype(jnp.float32)
    else:
        acc = lax.dot(xq_ref[...].astype(jnp.float32),
                      wq_ref[...].astype(jnp.float32),
                      preferred_element_type=jnp.float32)
    o_ref[...] = (acc * inv_ref[0, 0]).astype(o_ref.dtype)


def quant_dot_pallas(xq: jax.Array, wq: jax.Array, sx: jax.Array,
                     sw: jax.Array, fmt: str, out_dtype,
                     block_rows: int = 256) -> jax.Array:
    """Tiled Pallas quantized GEMM: grid over row blocks of xq, wq
    VMEM-resident, per-block ``dot`` with int32/fp32 accumulation and
    one fused descale.  Falls back (warned) to the XLA reference when
    even the minimum row tile busts the VMEM budget.  Off-TPU the
    kernel runs in interpret mode — test-only; production off-TPU
    callers route to quant_dot_reference (quant_dot below does)."""
    m, k = xq.shape
    n = wq.shape[1]
    br = min(block_rows, max(m, 1))
    while br > 32 and _quant_vmem_bytes(k, n, br) > _QUANT_VMEM_BUDGET:
        br //= 2
    if pl is None or _quant_vmem_bytes(k, n, br) > _QUANT_VMEM_BUDGET:
        import warnings
        warnings.warn(
            f"quant matmul kernel resident set for K={k}, N={n} exceeds "
            f"the ~{_QUANT_VMEM_BUDGET >> 20} MiB VMEM budget even at "
            f"the minimum row tile; computing this GEMM with the XLA "
            f"reference path instead (same math)", stacklevel=2)
        return quant_dot_reference(xq, wq, sx, sw, fmt, out_dtype)
    nb = -(-m // br)
    pad = nb * br - m
    if pad:
        xq = jnp.pad(xq, ((0, pad), (0, 0)))
    inv = (1.0 / (sx.astype(jnp.float32)
                  * sw.astype(jnp.float32))).reshape(1, 1)
    out = pl.pallas_call(
        functools.partial(_quant_matmul_kernel, fmt=fmt),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((br, k), lambda i: (i, 0)),
            pl.BlockSpec((k, n), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * br, n), out_dtype),
        interpret=pallas_target.interpret(),
        name="fdt_quant_matmul",
    )(xq, wq, inv)
    return out[:m] if pad else out


# -- differentiable site op ----------------------------------------------
#
# quant_dot(x, w, sx, sw): quantize both operands at the given DELAYED
# scales, contract at low precision, descale.  custom_vjp residuals are
# the QUANTIZED tensors (the memory win); the backward dequantizes them
# and runs the two gradient GEMMs in the cotangent's dtype — the
# straight-through estimator through the rounding, so d/dx passes
# through quantize∘dequantize as identity (at the dequantized values).
# grad_fmt="fp8_e5m2" (r19, the FP8-LM completion) additionally
# quantizes the incoming COTANGENT to the wide-range E5M2 grid with
# just-in-time per-tensor scaling and runs BOTH gradient GEMMs on
# quantized operands — dW contracts the saved xq against gq directly
# (the quantized-dW path), dx contracts gq against the saved wq.

_GRAD_FMTS = (None, "fp8_e5m2")


def _jit_grad_scale(amax: jax.Array, fmt: str) -> jax.Array:
    """Just-in-time (current-tensor) scale for gradient quantization:
    gradients exist only inside the backward, where no carried history
    can be updated — so their scale comes from THIS tensor's amax (the
    deterministic "current scaling" variant of the delayed recipe; the
    forward operands keep their delayed history scales).  Same
    amax→scale formula as the forward (_scale_from_amax)."""
    return _scale_from_amax(amax, fmt)


def _dot_q_mixed(a: jax.Array, b: jax.Array, dims) -> jax.Array:
    """Quantized-operand contraction with arbitrary dims, fp32 result.
    int8 x int8 pairs take the exact s8xs8->s32 path; any fp8 operand
    (every fp8/int8 value is exactly representable in fp32) upcasts."""
    if a.dtype == jnp.int8 and b.dtype == jnp.int8:
        return lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.int32
                               ).astype(jnp.float32)
    return lax.dot_general(a.astype(jnp.float32), b.astype(jnp.float32),
                           (dims, ((), ())),
                           preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _quant_dot_core(x, w, sx, sw, fmt: str, use_pallas: bool,
                    grad_fmt: Optional[str], grad_axes: tuple):
    xq = quantize(x, sx, fmt)
    wq = quantize(w, sw, fmt)
    if use_pallas:
        return quant_dot_pallas(xq, wq, sx, sw, fmt, x.dtype)
    return quant_dot_reference(xq, wq, sx, sw, fmt, x.dtype)


def _quant_dot_fwd(x, w, sx, sw, fmt, use_pallas, grad_fmt, grad_axes):
    # quantize ONCE: the same arrays feed the GEMM and become the
    # residuals (1 byte/elem instead of 2/4, the quantized-training
    # residual-memory win) — no reliance on CSE to dedupe a second
    # quantize subgraph
    xq = quantize(x, sx, fmt)
    wq = quantize(w, sw, fmt)
    dot = quant_dot_pallas if use_pallas else quant_dot_reference
    return dot(xq, wq, sx, sw, fmt, x.dtype), (xq, wq, sx, sw)


def _quant_dot_bwd(fmt, use_pallas, grad_fmt, grad_axes, res, g):
    xq, wq, sx, sw = res
    if grad_fmt is not None:
        # fp8-E5M2 gradient quantization + quantized dW/dx path: the
        # cotangent rides the wide-range grid (E5M2 keeps inf/nan and
        # tops at 57344 — the variant the fp8 literature reserves for
        # gradients) at a just-in-time per-tensor scale, and both
        # gradient GEMMs contract quantized operands with fp32
        # accumulation.  grad_axes: mesh axes this op runs sharded over
        # (parallel/kernel_shard.py) — the amax is pmax'd over them so
        # the per-TENSOR scale stays placement-invariant.
        amax_g = tensor_amax(g)
        for ax in grad_axes:
            amax_g = lax.pmax(amax_g, ax)
        sg = _jit_grad_scale(amax_g, grad_fmt)
        gq = quantize(g, sg, grad_fmt)
        dx = (_dot_q_mixed(gq, wq, ((1,), (1,)))
              * (1.0 / (sg * sw.astype(jnp.float32)))).astype(g.dtype)
        dw = (_dot_q_mixed(xq, gq, ((0,), (0,)))
              * (1.0 / (sx.astype(jnp.float32) * sg))).astype(g.dtype)
        return dx, dw, jnp.zeros_like(sx), jnp.zeros_like(sw)
    x_deq = dequantize(xq, sx, g.dtype)
    w_deq = dequantize(wq, sw, g.dtype)
    # gradient GEMMs in the compute dtype with fp32 accumulation (the
    # "fwd quantized / bwd high precision" recipe; --quant_grad
    # fp8_e5m2 selects the quantized-gradient branch above)
    dx = lax.dot_general(g, w_deq, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32
                         ).astype(x_deq.dtype)
    dw = lax.dot_general(x_deq, g, (((0,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32
                         ).astype(w_deq.dtype)
    # scales are bookkeeping inputs, not optimization variables
    return dx, dw, jnp.zeros_like(sx), jnp.zeros_like(sw)


_quant_dot_core.defvjp(_quant_dot_fwd, _quant_dot_bwd)


def quant_dot(x: jax.Array, w: jax.Array, sx: jax.Array, sw: jax.Array,
              fmt: str, use_pallas: Optional[bool] = None,
              grad_fmt: Optional[str] = None,
              grad_axes: tuple = ()) -> jax.Array:
    """out[m,n] = dequant(quant(x) · quant(w)) with fp32/int32
    accumulation.  x: (M, K); w: (K, N); sx/sw: fp32 scalar DELAYED
    scales (ops.quant.scale_from_history).  use_pallas None = auto
    (TPU and the shape fits VMEM); the caller may force False (the
    registered warned fallbacks, cli.build_model) — tp meshes route the
    kernel per-shard through parallel/kernel_shard.py instead.
    grad_fmt "fp8_e5m2" quantizes the backward's cotangent (JIT-scaled)
    and contracts the gradient GEMMs on quantized operands; grad_axes
    names the mesh axes a sharded caller runs under (amax pmax)."""
    if fmt not in _FMTS:
        raise ValueError(f"quant_dot fmt must be one of {_FMTS}, "
                         f"got {fmt!r}")
    if grad_fmt not in _GRAD_FMTS:
        raise ValueError(f"quant_dot grad_fmt must be one of "
                         f"{_GRAD_FMTS}, got {grad_fmt!r}")
    if use_pallas is None:
        use_pallas = (pallas_target.on_tpu()
                      and quant_kernel_fits_vmem(x.shape[-1], w.shape[-1]))
    return _quant_dot_core(x, w, jnp.asarray(sx, jnp.float32),
                           jnp.asarray(sw, jnp.float32), fmt,
                           bool(use_pallas), grad_fmt, tuple(grad_axes))


# -- flax site modules ----------------------------------------------------

try:
    from flax import linen as nn

    class QuantDense(nn.Module):
        """Drop-in ``nn.Dense`` with int8/fp8 forward GEMM and delayed
        per-tensor scaling.

        The param tree ("kernel", "bias", same shapes/init) is
        IDENTICAL to nn.Dense so checkpoints interchange between the
        quantized and full-precision models (the _FFNParamMirror
        contract).  The scale state — one amax history per operand —
        lives in the ``batch_stats`` collection: the existing cross-step
        statistics channel already threaded through the train step's
        mutable call, the r8 fused-dispatch carry, checkpoints and the
        kill-at-N bitwise resume, so quantized state inherits every one
        of those contracts with no new plumbing.  When ``batch_stats``
        is immutable (eval), scales come from the stored history and
        nothing updates.

        ``features`` may be an int (Dense) or a tuple (DenseGeneral
        over the last input axis — the fused qkv projection's
        (3, h, d_k)); the GEMM itself is always the flattened 2D
        contraction, which is what the Pallas kernel serves.

        ``frozen_scales`` is the INFERENCE mode (the serve/ subsystem's
        contract): scales come from the RESTORED amax history and the
        history is never rolled — even when the caller passes
        ``batch_stats`` as mutable.  Serving N requests is then
        state-free, the per-request amax reduction disappears from the
        forward, and two identical requests return bitwise-identical
        logits regardless of what was served between them (pinned by
        tests/test_serve.py).  Training keeps the default (False):
        delayed scaling NEEDS the roll.
        """
        features: object            # int or tuple (DenseGeneral-style)
        fmt: str = "int8"
        amax_history_len: int = 16
        margin: float = 1.0
        use_pallas: Optional[bool] = None   # None = auto; False = the
                                            # registered warned fallback
        frozen_scales: bool = False         # True = inference: restored
                                            # amax history used, never
                                            # rolled (serve/engine.py)
        mesh: Optional[object] = None       # tp mesh: the GEMM runs
                                            # per-shard via the r19
                                            # shard_map kernel layer
        tp_dim: Optional[int] = None        # kernel dim sharded on tp
                                            # (0 = Megatron row-parallel,
                                            # >0 = column-parallel); None
                                            # = never shard this site
        grad_fmt: Optional[str] = None      # "fp8_e5m2": quantized
                                            # gradients + dW (quant_dot)
        kernel_init: object = nn.initializers.lecun_normal()
        bias_init: object = nn.initializers.zeros
        dtype: object = jnp.float32
        param_dtype: object = jnp.float32
        amax_cadence: object = None         # parallel.pipeline
                                            # .PipelineTickCtx (r23): on
                                            # a pp>1 mesh this site is
                                            # invoked once per pipeline
                                            # tick — the cadence keeps
                                            # delayed scaling at ONE
                                            # roll per optimizer step
                                            # (scales from the pre-step
                                            # history, pushes max-
                                            # reduced over the real
                                            # microbatches) so the
                                            # scale state matches pp=1
                                            # bitwise.  None (pp=1) =
                                            # the plain roll below

        @nn.compact
        def __call__(self, x: jax.Array) -> jax.Array:
            feats = (self.features if isinstance(self.features, tuple)
                     else (self.features,))
            d_in = x.shape[-1]
            n_out = int(np.prod(feats))
            kernel = self.param("kernel", self.kernel_init,
                                (d_in, *feats), self.param_dtype)
            bias = self.param("bias", self.bias_init, feats,
                              self.param_dtype)
            hist_x = self.variable("batch_stats", "amax_history_x",
                                   fresh_amax_history,
                                   self.amax_history_len)
            hist_w = self.variable("batch_stats", "amax_history_w",
                                   fresh_amax_history,
                                   self.amax_history_len)
            xc = x.astype(self.dtype)
            w2d = kernel.astype(self.dtype).reshape(d_in, n_out)
            lead = xc.shape[:-1]
            x2d = xc.reshape(-1, d_in)
            if not quant_enabled():
                # FDT_QUANT=0: the plain full-precision matmul, scale
                # state untouched (the A/B kill-switch arm)
                out = jnp.dot(x2d, w2d,
                              preferred_element_type=jnp.float32)
            else:
                # delayed scaling: this step QUANTIZES at the scale the
                # history implied BEFORE this step, then records this
                # step's amax for the next one — named for the XLA
                # trace so profiles show the refresh cost under one
                # vocabulary with the telemetry spans
                with jax.named_scope("fdt/quant_scale_refresh"):
                    cad = self.amax_cadence
                    if cad is not None:
                        # pipeline tick cadence: EVERY tick quantizes at
                        # the scales the pre-step history implies (the
                        # same scales pp=1 uses all step), and the
                        # history rolls once — the first real push
                        # rolls, later pushes max-reduce into slot 0,
                        # bubble ticks are skipped entirely (their
                        # recycled data could exceed the true batch
                        # amax).  End-of-step hist == pp=1's bitwise.
                        site = "/".join(str(p) for p in self.scope.path)
                        hx0 = cad.amax_pre(site + ":x", hist_x.value)
                        hw0 = cad.amax_pre(site + ":w", hist_w.value)
                        sx = scale_from_history(hx0, self.fmt,
                                                self.margin)
                        sw = scale_from_history(hw0, self.fmt,
                                                self.margin)
                        if (not self.frozen_scales
                                and self.is_mutable_collection(
                                    "batch_stats")):
                            hist_x.value = cad.amax_push(
                                site + ":x", hist_x.value,
                                tensor_amax(x2d))
                            hist_w.value = cad.amax_push(
                                site + ":w", hist_w.value,
                                tensor_amax(w2d))
                    else:
                        sx = scale_from_history(hist_x.value, self.fmt,
                                                self.margin)
                        sw = scale_from_history(hist_w.value, self.fmt,
                                                self.margin)
                        if (not self.frozen_scales
                                and self.is_mutable_collection(
                                    "batch_stats")):
                            hist_x.value = update_amax_history(
                                hist_x.value, tensor_amax(x2d))
                            hist_w.value = update_amax_history(
                                hist_w.value, tensor_amax(w2d))
                from faster_distributed_training_tpu.parallel import (
                    kernel_shard)
                if kernel_shard.quant_tp_routed(self.mesh, self.tp_dim,
                                                np.shape(kernel),
                                                self.use_pallas):
                    # r19 shard_map layer: the quant GEMM runs per-shard
                    # on the Megatron column/row tile this site's TP
                    # rule implies — the Pallas kernel partitions over
                    # tp instead of falling back to the XLA reference
                    out = kernel_shard.quant_dense_sharded(
                        x2d, kernel.astype(self.dtype), sx, sw, self.fmt,
                        self.mesh, self.tp_dim, grad_fmt=self.grad_fmt
                    ).astype(jnp.float32)
                else:
                    # the registered warned fallback: a tp mesh whose
                    # site can't route through the shard_map layer
                    # (kill switch / non-dividing shape / no tp_dim)
                    # must never hand a logically-global array to the
                    # Pallas kernel — the XLA reference dot partitions
                    # like any other dot
                    from faster_distributed_training_tpu.parallel.mesh \
                        import tp_size as _tp
                    up = False if _tp(self.mesh) > 1 else self.use_pallas
                    out = quant_dot(x2d, w2d, sx, sw, self.fmt,
                                    up, grad_fmt=self.grad_fmt
                                    ).astype(jnp.float32)
            out = out + bias.astype(jnp.float32).reshape(1, n_out)
            return out.astype(self.dtype).reshape(*lead, *feats)

except ImportError:  # pragma: no cover
    pass
