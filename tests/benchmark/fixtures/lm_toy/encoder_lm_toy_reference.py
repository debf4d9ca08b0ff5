"""Plain reference of the ``encoder_lm_toy`` and ``encoder_lm_0p67b``
fixtures (it reads every size from the configuration's file): the program's
own encoder (``models/transformer.py``) as a causal language model, as
``--task lm --lm_causal`` runs it with dropout off.  Token, learned-position
and segment embeddings scaled by sqrt(d), the sinusoidal table added and the
sum added to the embeddings AGAIN (the reference repository's quirk, kept by
the program); pre-LN blocks (LayerNorm with the unbiased standard deviation,
eps added to it), fused q/k/v, causal softmax attention, exact GELU; a final
LayerNorm; the head tied to the raw token table; the mean next-token
cross-entropy.  Straightforward ``jax.numpy`` in float32 at ``highest``;
imports nothing of the program.  Sizes under the catalog's names.

Each block runs under ``jax.checkpoint``: the backward pass holds one
block's float32 activations at a time, beside the rows between blocks, so
that the reference of a configuration that fills the chip with its state
fits beside it.

``low`` is the control: the operands of every matrix product rounded to the
nearest precision below the one ``training.precision`` states (bfloat16
under ``fp32``; float8 e4m3 with one scale a tensor under ``bf16``).
``fault="half_batch"`` is the planted fault.  No normalisation keeps running
statistics: ``loss_fn`` returns an empty tree of them.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
STATS_START = {}
LN_EPS = 1.0e-6


def layout(sizes: dict) -> dict:
    """{path: (shape, kind)} in the names the program's tree uses."""
    d, ff = sizes["hidden_size"], sizes["intermediate_size"]
    h = sizes["num_attention_heads"]
    out = {("Embeddings_0", "token_embedding"): ((sizes["vocab_size"], d), d),
           ("Embeddings_0", "pos_embedding"): ((sizes["seq_len"], d), d),
           ("Embeddings_0", "segment_embedding"): ((3, d), d)}

    def norm(path):
        out[path + ("scale",)] = ((d,), 0)
        out[path + ("bias",)] = ((d,), None)

    def dense(path, shape, fan_in):
        out[path + ("kernel",)] = (shape, fan_in)
        out[path + ("bias",)] = (shape[1:], None)

    for i in range(sizes["num_hidden_layers"]):
        layer = (f"layer_{i}",)
        norm(layer + ("ln_attn",))
        dense(layer + ("attn", "qkv"), (d, 3, h, d // h), d)
        dense(layer + ("attn", "out"), (d, d), d)
        norm(layer + ("ln_ffn",))
        dense(layer + ("ffn", "Dense_0"), (d, ff), d)
        dense(layer + ("ffn", "Dense_1"), (ff, d), ff)
    norm(("ln_final",))
    return out


def init_params(sizes: dict, seed):
    """Uniform in +-sqrt(3 / fan-in) for tables and kernels, ones for a
    norm's scale, small uniform biases (none starts at an exact zero, so
    every leaf has a gradient to compare)."""
    tree: dict = {}
    key = jax.random.PRNGKey(seed)
    for i, (path, (shape, fan)) in enumerate(sorted(layout(sizes).items())):
        k = jax.random.fold_in(key, i)
        if fan == 0:
            leaf = jnp.ones(shape, jnp.float32)
        else:
            bound = 0.02 if fan is None else math.sqrt(3.0 / fan)
            leaf = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = leaf
    return tree


def sinusoidal_table(length: int, d: int) -> np.ndarray:
    pe = np.zeros((length, d), np.float32)
    position = np.arange(length)[:, None]
    scale = np.exp(np.arange(0, d, 2) * -(math.log(10000.0) / d))
    pe[:, 0::2] = np.sin(position * scale)
    pe[:, 1::2] = np.cos(position * scale)
    return pe


def layer_norm(x, p):
    d = x.shape[-1]
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.sum(jnp.square(x - mean), axis=-1, keepdims=True) / (d - 1)
    return p["scale"] * ((x - mean) / (jnp.sqrt(var) + LN_EPS)) + p["bias"]


def fp8(x):
    """Round to float8 e4m3 with one scale per tensor; the gradient passes
    straight through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + lax.stop_gradient(q - x)


# training.precision -> how the control rounds a product's operands
LOWER = {"fp32": lambda x: x.astype(jnp.bfloat16), "bf16": fp8}


def product(spec, a, b, lower=None):
    if lower is not None:
        a, b = lower(a), lower(b)
    return jnp.einsum(spec, a, b, precision=HI,
                      preferred_element_type=jnp.float32)


def block(p, x, mm):
    length, d = x.shape[1:]
    causal = jnp.tril(jnp.ones((length, length), bool))
    a = layer_norm(x, p["ln_attn"])
    qkv = mm("bld,dthk->blthk", a, p["attn"]["qkv"]["kernel"]) \
        + p["attn"]["qkv"]["bias"]
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]        # (B, L, h, dk)
    scores = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    scores = jnp.where(causal[None, None], scores, -1.0e9)
    ctx = mm("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    ctx = ctx.reshape(ctx.shape[0], length, d)
    x = x + mm("bld,de->ble", ctx, p["attn"]["out"]["kernel"]) \
        + p["attn"]["out"]["bias"]
    f = layer_norm(x, p["ln_ffn"])
    f = mm("bld,df->blf", f, p["ffn"]["Dense_0"]["kernel"]) \
        + p["ffn"]["Dense_0"]["bias"]
    f = jax.nn.gelu(f, approximate=False)
    return x + mm("blf,fd->bld", f, p["ffn"]["Dense_1"]["kernel"]) \
        + p["ffn"]["Dense_1"]["bias"]


def logits_fn(params, tokens, sizes: dict, lower=None):
    """``lower`` rounds the operands of every product (the control)."""
    mm = functools.partial(product, lower=lower)
    d = sizes["hidden_size"]
    length = tokens.shape[1]
    emb = params["Embeddings_0"]
    e = (emb["token_embedding"][tokens] + emb["pos_embedding"][None, :length]
         + emb["segment_embedding"][0]) * math.sqrt(d)
    x = e + (e + jnp.asarray(sinusoidal_table(length, d))[None])
    one_block = jax.checkpoint(functools.partial(block, mm=mm))
    for i in range(sizes["num_hidden_layers"]):
        x = one_block(params[f"layer_{i}"], x)
    x = layer_norm(x, params["ln_final"])
    return mm("bld,vd->blv", x, emb["token_embedding"])


def loss_fn(params, batch, sizes: dict, training: dict, seed, step,
            low: bool = False, fault: str = ""):
    """(mean next-token cross-entropy, {}): position t predicts token
    t + 1; packed rows carry no padding, so every target counts."""
    tokens = batch["tokens"]
    if fault == "half_batch":
        tokens = tokens[:tokens.shape[0] // 2]
    lower = LOWER[training["precision"]] if low else None
    logits = logits_fn(params, tokens, sizes, lower)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked), {}


def train_flops(sizes: dict, batch: int, seq_len: int) -> int:
    """Model FLOPs of one training step: the matrix products of the blocks
    and of the tied head, forward x 3, a multiply-add counted as two; the
    causal half of the attention products counted whole, as they run."""
    d, ff = sizes["hidden_size"], sizes["intermediate_size"]
    per_token = sizes["num_hidden_layers"] * (
        2 * d * 3 * d + 2 * d * d + 4 * d * ff + 4 * seq_len * d)
    per_token += 2 * d * sizes["vocab_size"]
    return 3 * batch * seq_len * per_token
