"""ResNet family for 32x32 inputs, TPU-native (NHWC, bf16-friendly).

Re-design of the reference's resnet.py with identical architecture:
  * CIFAR stem — 3x3 conv, stride 1; conv2_x stride 1 (resnet.py:241-243);
  * CELU(alpha=0.075) in the stem and BasicBlock (resnet.py:166,173,190,240),
    ReLU in BottleNeck (resnet.py:204-227);
  * FusedConvBN (no affine, eps added to std) for every stride-1 conv,
    plain Conv+BatchNorm (affine, running stats) for strided convs and
    shortcuts — exactly the reference's split (resnet.py:157-227);
  * torch-style uniform(-1/sqrt(fan_in), 1/sqrt(fan_in)) weight init
    (resnet.py:137-144 and torch's Conv2d/Linear defaults).

Deliberate fixes over the reference (SURVEY.md §7):
  * FusedConvBN keeps running statistics so eval is deterministic
    (reference normalizes with batch stats even at eval, resnet.py:83-100);
  * under pjit with a sharded batch all BN statistics are global —
    cross-replica SyncBN for free;
  * optional `remat` wraps each residual block in jax.checkpoint
    (recompute-in-backward for whole blocks: a memory lever).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from faster_distributed_training_tpu.ops.conv_bn import (conv2d,
                                                         conv_bn_train)

Dtype = Any


def torch_uniform_init(fan_in: int) -> Callable:
    """U(-1/sqrt(fan_in), 1/sqrt(fan_in)) — torch Conv2d/Linear default and
    the reference's FusedConvBN.reset_parameters (resnet.py:137-144)."""
    bound = 1.0 / (fan_in ** 0.5)

    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return init


def celu(x: jax.Array, alpha: float = 0.075) -> jax.Array:
    return nn.celu(x, alpha=alpha)


class FusedConvBNLayer(nn.Module):
    """Conv + BN fused via ops.fused_conv_bn; running stats in `batch_stats`."""
    features: int
    kernel: int
    stride: int = 1
    padding: int = 0
    eps: float = 1e-3            # added to std, resnet.py:94
    momentum: float = 0.1        # torch exp_avg_factor (resnet.py:117)
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    conv_remat: bool = True      # True: ops/conv_bn.py's custom_vjp with
                                 # the hand-derived BatchNorm backward
                                 # (residuals X, W, mean, sqrt_var as the
                                 # reference's, resnet.py:107-108).  In
                                 # the compiled program XLA still keeps
                                 # the conv output y and recomputes the
                                 # normalisation in its consumers; only
                                 # the expanding 1x1 (cout > cin) reads x
                                 # in y's place, by linearity, chosen by
                                 # the kernel's shape in conv_bn_train:
                                 # forward statistics from x's Gram
                                 # matrix (y is never written), backward
                                 # from x.  False: plain autodiff; the
                                 # forwards agree to rounding, not to the
                                 # bit.  Distinct from
                                 # ResNet.remat (block checkpointing); not
                                 # plumbed through the model factories,
                                 # togglable on the layer for experiments

    @nn.compact
    def __call__(self, x: jax.Array, train: bool) -> jax.Array:
        cin = x.shape[-1]
        w = self.param("kernel",
                       torch_uniform_init(cin * self.kernel * self.kernel),
                       (self.kernel, self.kernel, cin, self.features),
                       self.param_dtype)
        ra_mean = self.variable("batch_stats", "mean",
                                lambda: jnp.zeros((self.features,), jnp.float32))
        ra_var = self.variable("batch_stats", "var",
                               lambda: jnp.ones((self.features,), jnp.float32))
        xc, wc = x.astype(self.dtype), w.astype(self.dtype)
        if train:
            out, mean, var = conv_bn_train(xc, wc, self.stride, self.padding,
                                           self.eps, remat=self.conv_remat)
            if not self.is_initializing():
                m = self.momentum
                ra_mean.value = (1 - m) * ra_mean.value + m * mean
                ra_var.value = (1 - m) * ra_var.value + m * var
            return out
        y = conv2d(xc, wc, self.stride, self.padding)
        out = ((y.astype(jnp.float32) - ra_mean.value)
               / (jnp.sqrt(ra_var.value) + self.eps))
        return out.astype(self.dtype)


class ConvBN(nn.Module):
    """Plain conv (no bias) + standard affine BatchNorm — the reference's
    nn.Conv2d + nn.BatchNorm2d pairing for strided convs/shortcuts."""
    features: int
    kernel: int
    stride: int = 1
    padding: int = 0
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, train: bool) -> jax.Array:
        cin = x.shape[-1]
        w = self.param("kernel",
                       torch_uniform_init(cin * self.kernel * self.kernel),
                       (self.kernel, self.kernel, cin, self.features),
                       self.param_dtype)
        y = conv2d(x.astype(self.dtype), w.astype(self.dtype),
                   self.stride, self.padding)
        # torch BatchNorm2d defaults: eps=1e-5, exp_avg_factor=0.1
        return nn.BatchNorm(use_running_average=not train, momentum=0.9,
                            epsilon=1e-5, dtype=self.dtype,
                            param_dtype=self.param_dtype)(y)


class BasicBlock(nn.Module):
    """resnet.py:147-190 — expansion 1, CELU activations."""
    features: int
    stride: int = 1
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    conv_remat: bool = True
    expansion = 1

    @nn.compact
    def __call__(self, x: jax.Array, train: bool) -> jax.Array:
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        fkw = dict(kw, conv_remat=self.conv_remat)
        f = self.features
        if self.stride != 1:
            h = ConvBN(f, 3, self.stride, 1, **kw)(x, train)
            h = celu(h)
            h = FusedConvBNLayer(f * self.expansion, 3, 1, 1, **fkw)(h, train)
        else:
            h = FusedConvBNLayer(f, 3, 1, 1, **fkw)(x, train)
            h = celu(h)
            h = FusedConvBNLayer(f * self.expansion, 3, 1, 1, **fkw)(h, train)
        if self.stride != 1 or x.shape[-1] != f * self.expansion:
            x = ConvBN(f * self.expansion, 1, self.stride, 0, **kw)(x, train)
        return celu(h + x)


class BottleNeck(nn.Module):
    """resnet.py:193-227 — expansion 4, ReLU activations."""
    features: int
    stride: int = 1
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    conv_remat: bool = True
    expansion = 4

    @nn.compact
    def __call__(self, x: jax.Array, train: bool) -> jax.Array:
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        fkw = dict(kw, conv_remat=self.conv_remat)
        f = self.features
        h = FusedConvBNLayer(f, 1, 1, 0, **fkw)(x, train)
        h = nn.relu(h)
        if self.stride != 1:
            h = ConvBN(f, 3, self.stride, 1, **kw)(h, train)
        else:
            h = FusedConvBNLayer(f, 3, 1, 1, **fkw)(h, train)
        h = nn.relu(h)
        h = FusedConvBNLayer(f * self.expansion, 1, 1, 0, **fkw)(h, train)
        if self.stride != 1 or x.shape[-1] != f * self.expansion:
            x = ConvBN(f * self.expansion, 1, self.stride, 0, **kw)(x, train)
        return nn.relu(h + x)


class ResNet(nn.Module):
    """resnet.py:230-283 — stem + 4 stages + global avg pool + fc."""
    block: Any
    stage_sizes: Sequence[int]
    num_classes: int = 10
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    conv_remat: bool = True   # FusedConvBNLayer.conv_remat: the
                          # hand-derived conv+BN backward (the default);
                          # False = plain autodiff conv+BN (bag-of-tricks
                          # ablation arm)
    remat: bool = False   # checkpoint every residual block.  Measured on
                          # v5e @ bs=1024 bf16 NGD: 3196 vs 3858 img/s/chip
                          # — the step is HBM-bound and block-recompute adds
                          # more traffic than it saves, so this stays OFF by
                          # default; it is a memory lever for bigger batches,
                          # not a speed lever (conv_bn.py's backward
                          # recomputes nothing: it reads fewer bytes).

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = True) -> jax.Array:
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        x = FusedConvBNLayer(64, 3, 1, 1, **kw,
                             conv_remat=self.conv_remat)(x, train)
        x = celu(x)
        block_cls = self.block
        if self.remat:
            block_cls = nn.remat(block_cls, static_argnums=(2,))
        for stage, (n_blocks, features, stride) in enumerate(
                zip(self.stage_sizes, (64, 128, 256, 512), (1, 2, 2, 2))):
            for i in range(n_blocks):
                x = block_cls(features, stride if i == 0 else 1, **kw,
                              conv_remat=self.conv_remat)(x, train)
        x = jnp.mean(x, axis=(1, 2))  # AdaptiveAvgPool2d((1,1)) on NHWC
        fan_in = x.shape[-1]
        w = self.param("fc_kernel", torch_uniform_init(fan_in),
                       (fan_in, self.num_classes), self.param_dtype)
        b = self.param("fc_bias", torch_uniform_init(fan_in),
                       (self.num_classes,), self.param_dtype)
        x = x.astype(self.dtype) @ w.astype(self.dtype) + b.astype(self.dtype)
        return x.astype(jnp.float32)  # logits in fp32 for a stable softmax


def _factory(block, sizes):
    def make(num_classes: int = 10, **kw) -> ResNet:
        return ResNet(block=block, stage_sizes=sizes, num_classes=num_classes,
                      **kw)
    return make


resnet18 = _factory(BasicBlock, (2, 2, 2, 2))    # resnet.py:286
resnet34 = _factory(BasicBlock, (3, 4, 6, 3))    # resnet.py:292
resnet50 = _factory(BottleNeck, (3, 4, 6, 3))    # resnet.py:298
resnet101 = _factory(BottleNeck, (3, 4, 23, 3))  # resnet.py:304
resnet152 = _factory(BottleNeck, (3, 8, 36, 3))  # resnet.py:310
