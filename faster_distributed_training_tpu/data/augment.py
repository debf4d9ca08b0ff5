"""Device-side batched augmentation.

The reference's pipeline (resnet50_test.py:301-318): train =
RandomCrop(32, pad 4) + RandomHorizontalFlip + Normalize compiled with
TorchScript; eval = Normalize.  Quirk: the reference samples a random
*choice of 3* of those transforms ONCE at startup — possibly dropping
Normalize for the whole run (SURVEY.md §2).  We fix that (all three,
every step) and note the divergence.

TPU-first design: augmentation is a jittable function of (batch, key)
running on device — selects and a flip fused into the step's prologue,
instead of per-sample host workers.  Nothing in it indexes per sample:
the crop selects among the 2*padding+1 static windows an axis of the
padded batch (see ``random_crop`` for why)."""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from faster_distributed_training_tpu.data.cifar10 import (CIFAR10_MEAN,
                                                          CIFAR10_STD)


def normalize(x: jax.Array, mean=CIFAR10_MEAN, std=CIFAR10_STD) -> jax.Array:
    """uint8 NHWC -> normalized float32."""
    x = x.astype(jnp.float32) / 255.0
    return (x - jnp.asarray(mean)) / jnp.asarray(std)


def random_crop(key: jax.Array, x: jax.Array, padding: int = 4) -> jax.Array:
    """RandomCrop(H, padding=4) for the whole batch, zeros in the padding.

    The crop is separable and an offset takes one of 2*padding+1 values,
    so it is a select among that many STATIC windows of the padded batch,
    rows first, then columns: elementwise, fused by XLA, local to every
    batch shard.  Per-sample indexing (a gather) gives the same bits, but
    XLA:TPU keeps the batch in the lanes and expands it to one sequential
    iteration per image: 28 ms of a 160 ms ResNet step at bs 1024 on a
    v5e against 0.2 ms for the selects (PERF.md section 6, PR 25)."""
    n, h, w, c = x.shape
    pad = ((0, 0), (padding, padding), (padding, padding), (0, 0))
    xp = jnp.pad(x, pad)
    off = jax.random.randint(key, (n, 2), 0, 2 * padding + 1)
    oy = off[:, 0].reshape(n, 1, 1, 1)
    ox = off[:, 1].reshape(n, 1, 1, 1)
    rows = xp[:, :h]
    for k in range(1, 2 * padding + 1):
        rows = jnp.where(oy == k, xp[:, k:k + h], rows)
    out = rows[:, :, :w]
    for k in range(1, 2 * padding + 1):
        out = jnp.where(ox == k, rows[:, :, k:k + w], out)
    return out


def random_flip(key: jax.Array, x: jax.Array) -> jax.Array:
    """Per-sample horizontal flip with p=0.5."""
    flip = jax.random.bernoulli(key, 0.5, (x.shape[0], 1, 1, 1))
    return jnp.where(flip, x[:, :, ::-1, :], x)


def augment_batch(key: jax.Array, x: jax.Array, train: bool = True,
                  padding: int = 4, mean=CIFAR10_MEAN, std=CIFAR10_STD
                  ) -> jax.Array:
    """Full train pipeline (crop+flip+normalize) or eval (normalize)."""
    if not train:
        return normalize(x, mean, std)
    k_crop, k_flip = jax.random.split(key)
    x = normalize(x, mean, std)
    x = random_crop(k_crop, x, padding)
    x = random_flip(k_flip, x)
    return x
