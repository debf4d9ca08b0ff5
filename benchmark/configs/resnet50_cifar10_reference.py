"""Plain reference of the ``resnet50_cifar10`` configuration: ResNet-50 for
32x32 inputs as SuperbTUM/Faster-Distributed-Training ``resnet.py`` has it
(3x3 stride-1 stem with CELU(0.075), [3,4,6,3] bottlenecks with ReLU,
"fused" conv+BN without affine whose eps is added to the standard deviation
of the unbiased variance for every stride-1 convolution, plain conv +
affine BatchNorm for strided convolutions and shortcuts), random crop +
flip + normalisation, static mixup, cross-entropy, and the optimizer of
``benchmark/reference/optim.py``.  Straightforward ``jax.numpy`` in
float32 at ``highest`` precision; imports nothing of the program.

``loss_fn`` is what ``benchmark/reference/steps.py`` differentiates.  Beside
the loss it returns every normalisation's running statistics after this
step, started from mean 0 and variance 1 with the reference repository's
factor 0.1 (``STATS_START``, ``STATS_FACTOR``), in the program's names:
quantities of the forward pass alone, layer by layer.
Inputs are the host batches the feed handed the program (rows of the
benchmark's own data set) and ``--seed``; weights are made here from the
seed.  The random draws of a step (crop offsets, flips, mixup weight and
partner) are made here from the keys the configuration states:
``fold_in(PRNGKey(seed + 1), step)`` for the augmentation and
``fold_in(PRNGKey(seed), step)`` split in two, the first half for mixup.

``precision="fp8"`` is the control: the same reference with both operands
of every convolution and of the classifier rounded to float8 (e4m3, one
scale per tensor), the nearest precision below the bfloat16 the
configuration states.  ``fault="half_batch"`` is the planted fault of a
step that leaves half of the batch out and takes the mean over the rest.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
MEAN = np.asarray([0.4914, 0.4822, 0.4465], np.float32)
STD = np.asarray([0.2023, 0.1994, 0.2010], np.float32)


# -- parameters ---------------------------------------------------------------

def layout(sizes: dict) -> dict:
    """{path: (shape, kind)} in the names the program's tree uses."""
    out = {}

    def conv(path, k, cin, cout):
        out[path + ("kernel",)] = ((k, k, cin, cout), "uniform")

    def affine(path, c):
        out[path + ("BatchNorm_0", "scale")] = ((c,), "scale")
        out[path + ("BatchNorm_0", "bias")] = ((c,), "bias")

    conv(("FusedConvBNLayer_0",), 3, sizes["image"][2], sizes["widths"][0])
    cin, b = sizes["widths"][0], 0
    for stage, (blocks, f) in enumerate(zip(sizes["stage_sizes"],
                                            sizes["widths"])):
        for i in range(blocks):
            stride = sizes["strides"][stage] if i == 0 else 1
            p = (f"BottleNeck_{b}",)
            conv(p + ("FusedConvBNLayer_0",), 1, cin, f)
            if stride != 1:
                conv(p + ("ConvBN_0",), 3, f, f)
                affine(p + ("ConvBN_0",), f)
                conv(p + ("FusedConvBNLayer_1",), 1, f, 4 * f)
            else:
                conv(p + ("FusedConvBNLayer_1",), 3, f, f)
                conv(p + ("FusedConvBNLayer_2",), 1, f, 4 * f)
            if stride != 1 or cin != 4 * f:
                short = "ConvBN_1" if stride != 1 else "ConvBN_0"
                conv(p + (short,), 1, cin, 4 * f)
                affine(p + (short,), 4 * f)
            cin, b = 4 * f, b + 1
    out[("fc_kernel",)] = ((cin, sizes["num_classes"]), "uniform")
    out[("fc_bias",)] = ((sizes["num_classes"],), "bias")
    return out


def init_params(sizes: dict, seed) -> dict:
    """Weights from the seed (an int32, traced or not): kernels
    U(+-1/sqrt(fan_in)), BatchNorm scales round 1 and biases round 0 (not
    exactly 1 and 0, so that a step that mistreats them shows)."""
    root = jax.random.PRNGKey(seed)
    tree = {}
    for i, (path, (shape, kind)) in enumerate(sorted(layout(sizes).items())):
        key = jax.random.fold_in(root, i)
        if kind == "uniform":
            bound = 1.0 / np.sqrt(np.prod(shape[:-1]))
            leaf = jax.random.uniform(key, shape, jnp.float32, -bound, bound)
        elif kind == "scale":
            leaf = 1.0 + jax.random.uniform(key, shape, jnp.float32,
                                            -0.1, 0.1)
        else:
            leaf = jax.random.uniform(key, shape, jnp.float32, -0.1, 0.1)
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = leaf
    return tree


# -- forward ------------------------------------------------------------------

def fp8(x):
    """Round to float8 e4m3 with one scale per tensor; the gradient passes
    straight through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + lax.stop_gradient(q - x)


def conv(x, w, stride, pad, low):
    if low:
        x, w = fp8(x), fp8(w)
    return lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)


STATS_START = {"mean": 0.0, "var": 1.0}
STATS_FACTOR = 0.1


def running(mean, var):
    """The running statistics after one step from ``STATS_START``."""
    return {k: (1.0 - STATS_FACTOR) * STATS_START[k] + STATS_FACTOR * v
            for k, v in (("mean", mean), ("var", var))}


def fused_bn(y, eps=1e-3):
    n = y.size // y.shape[-1]
    mean = jnp.mean(y, axis=(0, 1, 2))
    var = jnp.sum(jnp.square(y - mean), axis=(0, 1, 2)) / (n - 1)
    return (y - mean) / (jnp.sqrt(var) + eps), running(mean, var)


def affine_bn(y, p, eps=1e-5):
    mean = jnp.mean(y, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(y - mean), axis=(0, 1, 2))
    return ((y - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"],
            {"BatchNorm_0": running(mean, var)})


def bottleneck(p, x, f, stride, low):
    """(output, {layer: its running statistics})."""
    cin, stats = x.shape[-1], {}

    def fused(name, x, pad):
        out, stats[name] = fused_bn(conv(x, p[name]["kernel"], 1, pad, low))
        return out

    def affine(name, x, stride, pad):
        out, stats[name] = affine_bn(
            conv(x, p[name]["kernel"], stride, pad, low),
            p[name]["BatchNorm_0"])
        return out

    h = jax.nn.relu(fused("FusedConvBNLayer_0", x, 0))
    if stride != 1:
        h = affine("ConvBN_0", h, stride, 1)
        last = "FusedConvBNLayer_1"
    else:
        h = fused("FusedConvBNLayer_1", h, 1)
        last = "FusedConvBNLayer_2"
    h = fused(last, jax.nn.relu(h), 0)
    if stride != 1 or cin != 4 * f:
        x = affine("ConvBN_1" if stride != 1 else "ConvBN_0", x, stride, 0)
    return jax.nn.relu(h + x), stats


def celu(x, alpha=0.075):
    return jnp.maximum(x, 0.0) + jnp.minimum(
        0.0, alpha * (jnp.exp(jnp.minimum(x, 0.0) / alpha) - 1.0))


def forward(params, x, sizes, low=False):
    """(logits, running statistics of every normalisation)."""
    x, stem = fused_bn(conv(x, params["FusedConvBNLayer_0"]["kernel"],
                            1, 1, low))
    x, stats = celu(x), {"FusedConvBNLayer_0": stem}
    b = 0
    for stage, (blocks, f) in enumerate(zip(sizes["stage_sizes"],
                                            sizes["widths"])):
        for i in range(blocks):
            stride = sizes["strides"][stage] if i == 0 else 1
            # one block's activations at a time in the backward pass, so
            # that float32 at the cell's batch fits the chip
            block = jax.checkpoint(functools.partial(
                bottleneck, f=f, stride=stride, low=low))
            x, stats[f"BottleNeck_{b}"] = block(params[f"BottleNeck_{b}"], x)
            b += 1
    x = jnp.mean(x, axis=(1, 2))
    w = params["fc_kernel"]
    if low:
        x, w = fp8(x), fp8(w)
    return jnp.matmul(x, w, precision=HI) + params["fc_bias"], stats


# -- one step's batch -----------------------------------------------------------

def augment(images_u8, seed, step):
    """Normalise, pad by 4 and crop back at a random offset, flip half."""
    k_crop, k_flip = jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(seed + 1), step))
    x = (images_u8.astype(jnp.float32) / 255.0 - MEAN) / STD
    n, h, w, c = x.shape
    padded = jnp.pad(x, ((0, 0), (4, 4), (4, 4), (0, 0)))
    off = jax.random.randint(k_crop, (n, 2), 0, 9)
    x = jax.vmap(lambda img, o: lax.dynamic_slice(
        img, (o[0], o[1], 0), (h, w, c)))(padded, off)
    flip = jax.random.bernoulli(k_flip, 0.5, (n, 1, 1, 1))
    return jnp.where(flip, x[:, :, ::-1, :], x)


def mixup(x, y, seed, step, alpha):
    k_mix, _ = jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(seed), step))
    k_lam, k_perm = jax.random.split(k_mix)
    lam = jax.random.beta(k_lam, alpha, alpha).astype(jnp.float32)
    partner = jax.random.permutation(k_perm, x.shape[0])
    return lam * x + (1.0 - lam) * x[partner], y, y[partner], lam


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def loss_fn(params, batch, sizes, training, seed, step, low, fault):
    x = augment(batch["image"], seed, step)
    x, y_a, y_b, lam = mixup(x, batch["label"], seed, step,
                             float(training["mixup_alpha"]))
    if fault == "half_batch":
        half = x.shape[0] // 2
        x, y_a, y_b = x[:half], y_a[:half], y_b[:half]
    logits, stats = forward(params, x, sizes, low)
    return (lam * cross_entropy(logits, y_a)
            + (1.0 - lam) * cross_entropy(logits, y_b)), stats
