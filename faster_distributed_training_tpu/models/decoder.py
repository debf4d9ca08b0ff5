"""A decoder-only language model whose sizes come from ONE file.

``--model decoder --decoder_config <file>`` names a JSON file that holds a
published ``config.json``'s keys at its top level (``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``layer_types``, ``num_dense_layers``, ``num_experts``, ...; the benchmark's
configuration files are such files).  ``load_sizes`` reads it; no flag
repeats a width.

The block, for every layer (RMS norms with float32 statistics, no bias
anywhere):

    a = x + RMS_post_attn(Attn(RMS_in(x)))
    y = a + RMS_post_mlp(MLP(RMS_pre_mlp(a)))

``Attn``: grouped-query heads (``num_key_value_heads`` key-value heads, each
serving a group of query heads), a per-head RMS norm of q and k, rotary
positions on the ``sliding_attention`` layers only (``full_attention``
layers carry no position), a causal band — on sliding layers ``i - j <
sliding_window`` — computed by ``ops/flash_attention.banded_attention``
(rows are packed, with no document mask), and a sigmoid output gate before
the output projection.  ``MLP``: SwiGLU of ``intermediate_size`` on the
``num_dense_layers`` leading layers, the expert layer of ``models/moe.py``
after them.  Embedding scaled by sqrt(hidden) (``mup_enabled``), a final RMS
norm, an untied head.

A file that holds a chip's share states it as the benchmark's cut
configurations do: ``num_experts`` is what is HELD, ``published.num_experts``
the router's width; this process holds the FIRST share, experts
``[0, num_experts)`` (a run over several shares is ROADMAP R3's remainder).
``vocab_size`` is the slice's.

``--remat`` keeps, of each block, its input and a short list of named
values, and the backward replays the rest: the attention kernel's own
residuals ``out`` and ``lse`` (``flash_attention.BANDED_RESIDUALS``; 68 MB
a layer and row of 8,192 tokens at 32 heads of 128 in bfloat16, beside the
33.6 MB block input), so the replay never runs the forward kernel a second
time, and the routing decision's integers (``moe.ROUTING_RESIDUALS``).
Projections, norms, rotary, the router's scores and the experts are
recomputed.  ``--remat_policy`` is the encoder's and is not read here.
"""

from __future__ import annotations

import json
import math
from typing import Any, NamedTuple, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from faster_distributed_training_tpu.models.moe import (
    ROUTING_RESIDUALS, ExpertLayer, SwiGLU)
from faster_distributed_training_tpu.ops.flash_attention import (
    BANDED_RESIDUALS, banded_attention)

SLIDING, FULL = "sliding_attention", "full_attention"


class DecoderSizes(NamedTuple):
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    layer_types: Tuple[str, ...]
    num_dense_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    sliding_window: int
    rope_theta: float
    rms_norm_eps: float
    vocab_size: int
    router_width: int            # experts the router scores
    held: int                    # experts held by this share
    lo: int                      # the first held expert's number
    num_experts_per_tok: int
    num_shared_experts: int
    route_scale: float
    route_norm: bool
    embed_scale: float


def load_sizes(path: str) -> DecoderSizes:
    """The sizes of the file at ``path``."""
    with open(path) as f:
        return sizes_from(json.load(f))


def sizes_from(c: dict) -> DecoderSizes:
    held = int(c["num_experts"])
    width = int(c.get("published", {}).get("num_experts", held))
    if width % held:
        raise ValueError(f"{width} routed experts do not divide into "
                         f"shares of the file's {held}")
    if c.get("score_func", "sigmoid") != "sigmoid":
        raise ValueError(f"score_func {c['score_func']!r}: the expert "
                         f"layer routes by sigmoid scores")
    layers = tuple(c["layer_types"])
    if len(layers) != int(c["num_hidden_layers"]) \
            or set(layers) - {SLIDING, FULL}:
        raise ValueError(f"layer_types {layers} against num_hidden_layers "
                         f"{c['num_hidden_layers']}")
    d = int(c["hidden_size"])
    return DecoderSizes(
        hidden_size=d, intermediate_size=int(c["intermediate_size"]),
        moe_intermediate_size=int(c["moe_intermediate_size"]),
        layer_types=layers, num_dense_layers=int(c["num_dense_layers"]),
        num_attention_heads=int(c["num_attention_heads"]),
        num_key_value_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]),
        sliding_window=int(c["sliding_window"]),
        rope_theta=float(c["rope_theta"]),
        rms_norm_eps=float(c["rms_norm_eps"]),
        vocab_size=int(c["vocab_size"]), router_width=width, held=held,
        lo=0, num_experts_per_tok=int(c["num_experts_per_tok"]),
        num_shared_experts=int(c.get("num_shared_experts", 0)),
        route_scale=float(c.get("route_scale", 1.0)),
        route_norm=bool(c.get("route_norm", True)),
        embed_scale=math.sqrt(d) if c.get("mup_enabled") else 1.0)


class RMSNorm(nn.Module):
    eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        return (y * scale).astype(self.dtype)


def rotary(x, theta: float):
    """Rotate-half rotary positions over the whole head: x [B, L, H, D],
    position = the row's index."""
    L, D = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[None, :, None]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :D // 2], x32[..., D // 2:]
    return (x32 * cos + jnp.concatenate([-x2, x1], axis=-1) * sin
            ).astype(x.dtype)


class DecoderAttention(nn.Module):
    sizes: DecoderSizes
    kind: str
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u):
        s = self.sizes
        B, L, d = u.shape
        H, Hkv, D = s.num_attention_heads, s.num_key_value_heads, s.head_dim
        init = nn.initializers.lecun_normal()

        def proj(name, n_out):
            w = self.param(name, init, (d, n_out), jnp.float32)
            return jnp.dot(u, w.astype(self.dtype))
        q = proj("q_proj", H * D).reshape(B, L, H, D)
        k = proj("k_proj", Hkv * D).reshape(B, L, Hkv, D)
        v = proj("v_proj", Hkv * D).reshape(B, L, Hkv, D)
        gate = proj("gate_proj", H * D)
        q = RMSNorm(s.rms_norm_eps, self.dtype, name="q_norm")(q)
        k = RMSNorm(s.rms_norm_eps, self.dtype, name="k_norm")(k)
        window = None
        if self.kind == SLIDING:
            q, k = rotary(q, s.rope_theta), rotary(k, s.rope_theta)
            window = s.sliding_window
        heads_first = lambda x: x.transpose(0, 2, 1, 3)     # noqa: E731
        with jax.named_scope("fdt/attention"):
            ctx = banded_attention(heads_first(q), heads_first(k),
                                   heads_first(v), window)
        ctx = heads_first(ctx).reshape(B, L, H * D)
        ctx = ctx * jax.nn.sigmoid(gate.astype(jnp.float32)
                                   ).astype(self.dtype)
        out = self.param("o_proj", init, (H * D, d), jnp.float32)
        return jnp.dot(ctx, out.astype(self.dtype))


class DecoderBlock(nn.Module):
    sizes: DecoderSizes
    kind: str                    # sliding_attention | full_attention
    dense: bool                  # a leading dense layer, else experts
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        s = self.sizes
        norm = lambda name: RMSNorm(s.rms_norm_eps, self.dtype,  # noqa: E731
                                    name=name)
        a = DecoderAttention(s, self.kind, self.dtype, name="attn")(
            norm("input_norm")(x))
        x = x + norm("post_attn_norm")(a)
        u = norm("pre_mlp_norm")(x)
        if self.dense:
            m = SwiGLU(s.intermediate_size, self.dtype, name="mlp")(u)
        else:
            m = ExpertLayer(
                router_width=s.router_width, held=s.held, lo=s.lo,
                top_k=s.num_experts_per_tok, width=s.moe_intermediate_size,
                n_shared=s.num_shared_experts, route_scale=s.route_scale,
                route_norm=s.route_norm, dtype=self.dtype, name="moe")(u)
        return x + norm("post_mlp_norm")(m)


# What ``--remat`` keeps of a block: its input and these named values.
RematBlock = nn.remat(
    DecoderBlock, policy=jax.checkpoint_policies.save_only_these_names(
        *BANDED_RESIDUALS, *ROUTING_RESIDUALS))


class Decoder(nn.Module):
    """Token ids [B, L] -> logits [B, L, vocab].  Takes the call
    ``train/steps.py`` makes of a token model; ``token_types`` and ``mask``
    are accepted and unused (packed rows carry neither), and there is no
    dropout, so ``train`` only says whether the counters are written."""
    sizes: DecoderSizes
    dtype: Any = jnp.float32
    remat: bool = False

    @nn.compact
    def __call__(self, tokens, token_types=None, mask=None,
                 train: bool = False):
        s = self.sizes
        if mask is not None:
            raise ValueError("the decoder takes packed rows: no mask")
        table = self.param("embed_tokens",
                           nn.initializers.normal(1.0 / s.embed_scale),
                           (s.vocab_size, s.hidden_size), jnp.float32)
        x = (table[tokens] * s.embed_scale).astype(self.dtype)
        block = RematBlock if self.remat else DecoderBlock
        for i, kind in enumerate(s.layer_types):
            x = block(s, kind, i < s.num_dense_layers, self.dtype,
                      name=f"layer_{i}")(x)
        x = RMSNorm(s.rms_norm_eps, self.dtype, name="final_norm")(x)
        head = self.param("lm_head", nn.initializers.lecun_normal(),
                          (s.hidden_size, s.vocab_size), jnp.float32)
        return jnp.dot(x, head.astype(self.dtype))
