"""Fused Conv2D + BatchNorm with a hand-derived backward.

TPU-native re-design of the reference's ``FusedConvBN2DFunction``
(``resnet.py:72-113``): one ``jax.custom_vjp`` primitive whose forward
saves only ``(X, W, mean, sqrt_var)`` and whose backward applies a
hand-derived BatchNorm backward and the convolution transpose, expressed
so XLA fuses the normalize into the conv epilogue on the MXU.

What the path costs in HBM bytes, as the compiled v5e program shows
(ISSUE 28, ISSUE 30): ``_fused_bwd`` writes ``conv2d(x, w)`` again, as the
reference does (``resnet.py:107-108``), but XLA merges that convolution
with the forward's identical one: the program holds no second
convolution, keeps every conv output ``y`` from forward to backward, and
recomputes the cheap ``relu(normalize(y))`` inside each consumer.  So
that path saves neither bytes nor memory over autodiff; what it buys is
the hand-derived BatchNorm backward.  The step is HBM-bound, and the
lever left is the byte count: for the EXPANDING 1x1 convolution (each
bottleneck's last, ``cin -> 4 cin``) everything that would read ``y``
reads the convolution's input ``x`` in its place, by linearity
(``y = x W``).  The forward (``_expand1x1_forward``) takes BatchNorm's
mean and variance from ``x``'s column sums and its ``[cin, cin]`` Gram
matrix ``S = x^T x``, two passes over ``x``; with the statistics known
before the convolution runs, the normalisation (and the block's ``add``
and ``relu``) is the convolution's epilogue and ``y`` is never written.
The backward (``_expand1x1_bwd``) takes ``S`` and the column sums from
the forward and reads ``x`` where BatchNorm's backward, linear in ``y``,
would read ``y``.  ``conv_bn_train`` picks the path by the kernel's
shape.  The three paths' forwards (this one, ``fused_conv_bn``, plain
autodiff) agree to rounding, not to the bit: this one's statistics are
float32 accumulations over the operands, the others' are taken from ``y``
after its rounding to the compute dtype.

Semantics matched to the reference:
  * BN has no affine γ/β (``resnet.py:85-99``),
  * variance is the *unbiased* estimator (``resnet.py:86``),
  * eps is added to the *standard deviation*, not the variance
    (``denom = sqrt_var + eps``, ``resnet.py:94``), default 1e-3.

Why there is no Pallas kernel here (a deliberate decision, unlike
``ops/flash_attention.py`` / ``fused_mlp_pallas``): the convolution is a
single XLA HLO that the TPU conv emitter tiles onto the MXU, and the BN
normalize is an elementwise chain XLA fuses into that conv's epilogue —
there is no leftover fusion for a hand-written kernel to claim, only the
risk of losing the emitter's layout/pipelining (a hand kernel would pay
a layout copy per array).  The value on this path is in *which arrays
the backward reads*, a differentiation-level decision, not a
kernel-level one.

Differences (deliberate, documented per SURVEY.md §7 "bugs to fix"):
  * layout is NHWC / HWIO (TPU-native) instead of NCHW / OIHW;
  * any stride is supported (reference asserts stride == 1,
    ``resnet.py:120``);
  * the op also returns ``(mean, var)`` so callers can maintain running
    statistics for deterministic eval — the reference uses batch stats
    at eval time (SURVEY.md §7 hard part 2);
  * under ``pjit`` with the batch sharded over a mesh axis, the
    channel reductions are *global* means/vars — i.e. cross-replica
    SyncBN falls out of the SPMD partitioner for free, unlike the
    reference's per-GPU batch stats.
"""

from __future__ import annotations

import functools
from typing import Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

Padding = Union[str, int, Tuple[Tuple[int, int], Tuple[int, int]]]


def _norm_padding(padding: Padding):
    if isinstance(padding, int):
        return ((padding, padding), (padding, padding))
    return padding


def conv2d(x: jax.Array, w: jax.Array, stride: int = 1,
           padding: Padding = 1) -> jax.Array:
    """Plain NHWC conv with HWIO kernel (maps straight onto the MXU)."""
    return lax.conv_general_dilated(
        x, w,
        window_strides=(stride, stride),
        padding=_norm_padding(padding),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _stats_dtype(dtype) -> jnp.dtype:
    """bf16/fp16 statistics are numerically unsafe — promote to at least fp32."""
    return jnp.promote_types(dtype, jnp.float32)


def _bn_stats(y: jax.Array) -> Tuple[jax.Array, jax.Array, float]:
    """(mean, unbiased var, N) over all axes but channel (last), in fp32+.

    Single pass over y (E[y²] − E[y]² instead of a second centered pass):
    one HBM read fewer in the bandwidth-bound train step.  fp32
    accumulation keeps the cancellation benign for BN-scale activations;
    the max(., 0) guards the subtraction's round-off."""
    n = y.size // y.shape[-1]
    y = y.astype(_stats_dtype(y.dtype))
    mean = jnp.mean(y, axis=(0, 1, 2))
    mean_sq = jnp.mean(jnp.square(y), axis=(0, 1, 2))
    # unbiased estimator, matching torch's X.var(unbiased=True) (resnet.py:86)
    var = jnp.maximum(mean_sq - jnp.square(mean), 0.0) * (n / (n - 1))
    return mean, var, n


def _conv_bn_forward(x, w, stride, padding, eps):
    """Shared forward: conv -> batch stats -> normalize.
    Returns (out, y, mean, var) — THE single definition of the numerics."""
    y = conv2d(x, w, stride, padding)
    mean, var, _ = _bn_stats(y)
    out = ((y.astype(mean.dtype) - mean)
           / (jnp.sqrt(var) + eps)).astype(y.dtype)
    return out, y, mean, var


def conv_bn_reference(x: jax.Array, w: jax.Array, stride: int = 1,
                      padding: Padding = 1, eps: float = 1e-3) -> jax.Array:
    """Unfused conv+BN — the autodiff oracle the fused kernel is tested against."""
    return _conv_bn_forward(x, w, stride, padding, eps)[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def fused_conv_bn(x: jax.Array, w: jax.Array, stride: int = 1,
                  padding: Padding = 1, eps: float = 1e-3):
    """Fused conv+BN. Returns ``(out, mean, var)``; ``mean``/``var`` are
    per-channel batch statistics for the caller's running-stat update."""
    out, _, mean, var = _conv_bn_forward(x, w, stride, padding, eps)
    return out, mean, var


def _fused_fwd(x, w, stride, padding, eps):
    out, _, mean, var = _conv_bn_forward(x, w, stride, padding, eps)
    sqrt_var = jnp.sqrt(var)
    # Residuals are (X, W, mean, sqrt_var), as the reference's
    # (resnet.py:107-108).  _fused_bwd asks for y again; XLA serves it the
    # forward's (module docstring).
    return (out, mean, var), (x, w, mean, sqrt_var)


def _fused_bwd(stride, padding, eps, res, cts):
    x, w, mean, sqrt_var = res
    g, _, _ = cts  # cotangents for (out, mean, var); stats are stats-only outputs

    # (1) the conv output again (XLA merges this with the forward's conv and
    # keeps y), through jax.vjp so the same trace yields the conv transpose.
    y, conv_vjp = jax.vjp(lambda x_, w_: conv2d(x_, w_, stride, padding), x, w)

    # (2) hand-derived BatchNorm backward (matches batch_norm_backward,
    # resnet.py:37-69, rewritten vectorized over NHWC), in fp32+:
    #   out_i = (y_i - mu) / s,   s = sqrt(var) + eps,  var unbiased over n.
    n = y.size // y.shape[-1]
    sd = mean.dtype
    y32, g32 = y.astype(sd), g.astype(sd)
    s = sqrt_var + eps
    centered = y32 - mean
    g_sum = jnp.sum(g32, axis=(0, 1, 2))
    # d var: through s = sqrt(var)+eps; note sum_i centered_i = 0 kills the
    # mean-path inside var.
    d_s = -jnp.sum(g32 * centered, axis=(0, 1, 2)) / (s * s)
    # guard: a (near-)constant or cancellation-collapsed channel has
    # sqrt_var == 0; its centered values are ~0 so the d_var term should
    # vanish, not blow up to inf
    d_var = d_s / (2.0 * jnp.maximum(sqrt_var, 1e-12))
    dy = g32 / s + centered * (2.0 * d_var / (n - 1)) - g_sum / (s * n)

    # (3) conv backward through that vjp.
    dx, dw = conv_vjp(dy.astype(y.dtype))
    return dx, dw


fused_conv_bn.defvjp(_fused_fwd, _fused_bwd)


def _expands_1x1(w_shape, stride, padding) -> bool:
    """A 1x1, stride-1, unpadded convolution with more channels out than in:
    where reading the input (cin) is cheaper than reading the output (cout)."""
    kh, kw, cin, cout = w_shape
    return (kh == kw == 1 and stride == 1 and cout > cin
            and _norm_padding(padding) == ((0, 0), (0, 0)))


def _expand1x1_forward(x, w, eps):
    """``_conv_bn_forward`` for ``y = x W`` with the statistics taken from
    ``x``: ``y`` is linear in ``x``, so its mean is ``(xsum / n) W`` and its
    ``E[y^2]`` is ``sum_k W * ((S / n) W)`` per column, with ``xsum`` the
    column sums and ``S = x^T x`` the ``[K, K]`` Gram matrix of the input.
    With the statistics known before the convolution runs, nothing reads
    ``y`` but the normalisation: it becomes the convolution's epilogue (with
    the block's ``add`` and ``relu``) and ``y`` is never written.
    Returns (out, mean, var, S, xsum)."""
    with jax.named_scope("fdt/conv1x1_bn_stats"):
        sd = _stats_dtype(x.dtype)
        hi = lax.Precision.HIGHEST
        n = x.size // x.shape[-1]
        W = w[0, 0].astype(sd)                                     # [K, C]
        # The two passes over x: operands as they are, accumulation in fp32+.
        S = jnp.einsum("nhwk,nhwl->kl", x, x, preferred_element_type=sd)
        xsum = jnp.sum(x.astype(sd), axis=(0, 1, 2))
        # _bn_stats' formula, clamp and unbiased estimator on K x K algebra,
        # which carries its own precision (see _expand1x1_bwd).
        mean = jnp.matmul(xsum / n, W, precision=hi)
        mean_sq = jnp.sum(W * jnp.matmul(S / n, W, precision=hi), axis=0)
        var = jnp.maximum(mean_sq - jnp.square(mean), 0.0) * (n / (n - 1))
    # _conv_bn_forward's last line: y is rounded to its dtype, then normalised.
    y = conv2d(x, w, 1, 0)
    out = ((y.astype(sd) - mean) / (jnp.sqrt(var) + eps)).astype(y.dtype)
    return out, mean, var, S, xsum


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _expand1x1_conv_bn(x: jax.Array, w: jax.Array, eps: float):
    """``fused_conv_bn`` for the expanding 1x1 convolution: neither the
    forward nor the backward touches the convolution's output outside the
    convolution's own fusion.  Equal to ``_conv_bn_forward`` to rounding."""
    return _expand1x1_forward(x, w, eps)[:3]


def _expand1x1_fwd(x, w, eps):
    out, mean, var, S, xsum = _expand1x1_forward(x, w, eps)
    return (out, mean, var), (x, w, mean, jnp.sqrt(var), S, xsum)


def _expand1x1_bwd(eps, res, cts):
    """``_fused_bwd``'s mathematics with ``y = x W`` substituted: with
    ``dy = a g + b y + c`` per channel, every use of ``y`` (the reduction
    ``sum g (y - mean)``, ``dW = x^T dy``, ``dx = dy W^T``) moves onto ``x``,
    four times smaller, and onto ``[K, K]`` / ``[K, C]`` algebra."""
    x, w, mean, sqrt_var, S, xsum = res
    g, _, _ = cts
    with jax.named_scope("fdt/conv1x1_bn_bwd"):
        sd = mean.dtype
        hi = lax.Precision.HIGHEST
        rows = (0, 1, 2)
        n = x.size // x.shape[-1]
        W = w[0, 0].astype(sd)                                     # [K, C]
        # The passes over the big arrays: operands as they are (bf16 in the
        # bf16 program), accumulation in fp32+, as conv_vjp(dy) has it.
        G = jnp.einsum("nhwk,nhwc->kc", x, g, preferred_element_type=sd)
        g_sum = jnp.sum(g.astype(sd), axis=rows)

        # BatchNorm backward per channel (see _fused_bwd), with
        # sum_rows g (y - mean) = sum_k W[k,c] G[k,c] - mean[c] g_sum[c].
        s = sqrt_var + eps
        gy = jnp.sum(W * G, axis=0) - mean * g_sum
        d_s = -gy / (s * s)
        d_var = d_s / (2.0 * jnp.maximum(sqrt_var, 1e-12))   # _fused_bwd's guard
        a = 1.0 / s
        b = 2.0 * d_var / (n - 1)
        c = -b * mean - g_sum / (s * n)

        # The small algebra carries its own precision: it must not hang on
        # the caller's jax_default_matmul_precision.
        Wb = W * b
        dw = G * a + jnp.matmul(S, Wb, precision=hi) + jnp.outer(xsum, c)
        M = jnp.matmul(Wb, W.T, precision=hi)                       # [K, K]
        Wc = jnp.matmul(W, c, precision=hi)                         # [K]

        # dx = g (W a)^T + x M + (W c)^T.  x M + Wc is written once at the
        # input's size and dtype and added in the big product's epilogue.
        xm = (jnp.einsum("nhwk,kl->nhwl", x, M.astype(x.dtype),
                         preferred_element_type=sd) + Wc).astype(x.dtype)
        dx = jnp.einsum("nhwc,kc->nhwk", g, (W * a).astype(x.dtype),
                        preferred_element_type=sd) + xm.astype(sd)
        return dx.astype(x.dtype), dw[None, None].astype(w.dtype)


_expand1x1_conv_bn.defvjp(_expand1x1_fwd, _expand1x1_bwd)


def conv_bn_train(x: jax.Array, w: jax.Array, stride: int = 1,
                  padding: Padding = 1, eps: float = 1e-3,
                  remat: bool = True):
    """Training-mode fused conv+BN returning ``(out, mean, var)``.

    remat=True (default) takes a custom_vjp with the hand-derived
    BatchNorm backward, chosen by the kernel's shape: the expanding 1x1
    convolution (1x1, stride 1, no padding, ``cout > cin``) takes
    ``_expand1x1_conv_bn``, whose forward takes the batch statistics from
    ``x`` (column sums and the ``[cin, cin]`` Gram matrix) and whose
    backward reads ``x`` where the other reads ``y`` (reading or writing
    ``y`` costs ``cout``, reading ``x`` costs ``cin``: only there does
    the algebra save bytes); every other convolution takes
    ``fused_conv_bn``.  remat=False leaves differentiation to autodiff.
    ``fused_conv_bn`` and remat=False share ``_conv_bn_forward`` to the
    bit; the expanding path's forward agrees with it to rounding (mean
    and variance to 1e-5 in float32; in bf16 closer to float64 than the
    shared forward's, which reads ``y`` after its rounding).  Gradients
    agree except at the degenerate var==0 clamp edge, where autodiff
    zeroes the var path and the hand-written backwards bound it
    (tests/test_ops.py)."""
    if remat and _expands_1x1(w.shape, stride, padding):
        return _expand1x1_conv_bn(x, w, eps)
    if remat:
        return fused_conv_bn(x, w, stride, padding, eps)
    out, _, mean, var = _conv_bn_forward(x, w, stride, padding, eps)
    return out, mean, var
