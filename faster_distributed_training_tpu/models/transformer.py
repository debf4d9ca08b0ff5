"""Transformer encoder for text classification, TPU-native.

Re-design of the reference's transformer.py with the same architecture:
6-layer pre-LN encoder, h=8, d_model=512, d_ff=1024 (GELU), maxlen=512,
BERT-style 3-way embeddings (token+position+segment, transformer.py:150-156)
*plus* an additive sinusoidal encoding (the reference adds both,
transformer.py:61-64: ``x = embeddings + dropout(embeddings + pe)`` — a
quirk we preserve), CLS pooler (transformer.py:94-101), sentence-embedding
mixup inside forward (transformer.py:71-84), FusedMLP classifier
(transformer.py:278-289), Xavier-uniform init for every >1-dim param
(transformer.py:86-91).

Deliberate fixes over the reference (SURVEY.md §7 "bugs to fix"):
  * mixup only runs when ``train=True`` — the reference mixes at eval
    too and its eval path then mis-unpacks the tuple
    (transformer_test.py:321);
  * the attention mask fills with a genuinely large negative number —
    the reference's ``-1e-9`` (transformer.py:189) is ~0 and masks
    nothing;
  * the token-embedding fp32 island (transformer.py:154-155) is kept:
    embedding tables live and are summed in fp32, then cast to the
    compute dtype;
  * attention can route through a Pallas flash-attention kernel
    (``attention_impl='flash'``) instead of the O(L^2) dense softmax.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from faster_distributed_training_tpu.ops.dropout import FastDropout
from faster_distributed_training_tpu.ops.fused_mlp import (fused_mlp,
                                                           fused_mlp_pallas,
                                                           mlp_reference)
from faster_distributed_training_tpu.ops.quant import QuantDense
from faster_distributed_training_tpu.parallel.mesh import (seq_parallel_axis,
                                                           tp_size)
from faster_distributed_training_tpu.parallel.sharding import (
    mesh_data_axes, shard_activation)

Dtype = Any
NEG_INF = -1e9  # proper masking constant (reference bug: -1e-9)


def xavier_uniform(key, shape, dtype=jnp.float32):
    return nn.initializers.xavier_uniform()(key, shape, dtype)


def qkv_xavier(key, shape, dtype=jnp.float32):
    """Xavier bound for the fused (d_model, 3, h, d_k) QKV kernel computed
    per projection: the fused kernel is three (d_model, d_model) Xavier
    matrices laid side by side, so the bound is sqrt(6/(2*d_model)) — the
    same number the reference's per-matrix init produces
    (transformer.py:86-91), not the smaller bound flax's variance_scaling
    would derive from the 4-d shape."""
    d_model = shape[0]
    bound = math.sqrt(3.0 / d_model)
    return jax.random.uniform(key, shape, dtype, -bound, bound)


class TorchLayerNorm(nn.Module):
    """The reference's hand-rolled LayerNorm (transformer.py:230-242):
    (x - mean) / (std + eps) with *unbiased* std and eps added to std."""
    eps: float = 1e-6
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        from faster_distributed_training_tpu.ops.layernorm import (
            torch_layernorm)

        d = x.shape[-1]
        a = self.param("scale", nn.initializers.ones, (d,), self.param_dtype)
        b = self.param("bias", nn.initializers.zeros, (d,), self.param_dtype)
        # fp32 core shared with the fused FFN kernel (ops/layernorm.py):
        # unbiased std (torch x.std default), eps added to std not var.
        # torch_layernorm is the saved-(mean, rstd) custom_vjp form — the
        # backward rebuilds x-hat from the input instead of storing the
        # centered/normalized intermediates (the r5-measured ~7.5 ms of
        # LN HBM round-trips across the 13 sites; FDT_LN_SAVED_STATS=0
        # restores default autodiff for probes).
        y = torch_layernorm(x.astype(jnp.float32),
                            a.astype(jnp.float32),
                            b.astype(jnp.float32), self.eps)
        return y.astype(self.dtype)


def sinusoidal_table(max_len: int, d_model: int) -> np.ndarray:
    """transformer.py:116-121 — static sin/cos table, built host-side once."""
    pe = np.zeros((max_len, d_model), dtype=np.float32)
    position = np.arange(max_len)[:, None]
    scale = np.exp(np.arange(0, d_model, 2) * -(math.log(10000.0) / d_model))
    pe[:, 0::2] = np.sin(position * scale)
    pe[:, 1::2] = np.cos(position * scale)
    return pe


class Embeddings(nn.Module):
    """token + learned-position + segment embeddings, scaled by sqrt(d_model)
    (transformer.py:132-156). Tables and the sum stay fp32 (the reference's
    autocast-disabled island), cast to compute dtype by the caller.
    Returns (embeddings, token_table) — the raw token table feeds the
    tied LM head (Transformer.tie_lm_head: logits = h @ E^T) without
    moving the param out of its checkpointed location."""
    d_model: int
    vocab: int
    maxlen: int
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, token_types: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
        tok = self.param("token_embedding", xavier_uniform,
                         (self.vocab, self.d_model), self.param_dtype)
        pos = self.param("pos_embedding", xavier_uniform,
                         (self.maxlen, self.d_model), self.param_dtype)
        seg = self.param("segment_embedding", xavier_uniform,
                         (3, self.d_model), self.param_dtype)
        L = x.shape[1]
        tokens = jnp.take(tok, x, axis=0).astype(jnp.float32)
        positions = pos[None, :L, :].astype(jnp.float32)
        segments = jnp.take(seg, token_types[:, :L], axis=0).astype(jnp.float32)
        return (tokens + positions + segments) * math.sqrt(self.d_model), tok


def dense_attention(q, k, v, mask, dropout_rate, deterministic, dropout_rng):
    """ScaledDotProduct (transformer.py:180-193) with a fixed mask constant."""
    d_k = q.shape[-1]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d_k)
    if mask is not None:
        scores = jnp.where(mask == 0, jnp.asarray(NEG_INF, scores.dtype), scores)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_rate > 0.0 and not deterministic:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


class MultiheadAttention(nn.Module):
    """transformer.py:196-227 — QKV projection + output proj.

    The reference runs Q, K, V as three separate full-width nn.Linear
    calls; here they are ONE fused (d_model → 3·d_model) matmul
    (`qkv` DenseGeneral): one MXU dispatch and one HBM read of the
    activations instead of three, with identical math and parameter
    count.  The kernel is laid out (d_model, 3, h, d_k) so tensor
    parallelism can shard the head axis (parallel/sharding._TP_RULES).

    attention_impl selects the context computation:
      dense — O(L²) ScaledDotProduct with prob dropout (the reference);
      flash — Pallas TPU kernel / blockwise fallback (ops/flash_attention);
      ring  — sequence-parallel ring attention over `sp_axis` of `mesh`
              (ops/ring_attention);
      ulysses — sequence-parallel all-to-all head/sequence swap over
              `sp_axis` (ops/ulysses_attention; needs h % sp == 0).
    EVERY impl applies attention-prob dropout in training
    (transformer.py:190-192): flash/ring/ulysses use the stateless
    index-hash dropout (ops.attention.dropout_keep) computed inside the
    kernel/scan, so the probability tensor never touches HBM; dense
    follows `dropout_impl` — hash (the default engine,
    dense_attention_reference's in-place hash keep on the materialized
    probs) or the reference's jax.random.bernoulli threefry mask when
    dropout_impl != "hash" (the bag-of-tricks OFF arm sets
    dropout_impl="xla" precisely to keep that reference-naive cost in
    the ablation baseline).
    """
    h: int
    d_model: int
    dropout: float = 0.1
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    attention_impl: str = "dense"     # dense | flash | ring | ulysses
    mesh: Optional[Any] = None        # required for ring
    sp_axis: str = "sp"
    fused_qkv: bool = True            # ONE (d_model -> 3·d_model) matmul;
                                      # False = the reference's three
                                      # separate Linears (transformer.py:
                                      # 196-227) — the bag-of-tricks
                                      # ablation's unfused arm (different
                                      # param layout, ablation-only)
    dropout_impl: str = "hash"        # prob-dropout engine for dense
    flash_save_stats: bool = True     # False inside rematted regions:
                                      # out/lse residuals would force the
                                      # flash forward to re-run in the
                                      # remat replay (flash_attention
                                      # docstring)
    quant: Optional[Any] = None       # train.amp.QuantPolicy: int8/fp8
                                      # forward GEMMs for qkv + out with
                                      # delayed per-tensor scaling
                                      # (ops/quant.py); None = bf16/fp32
    pp_ctx: Optional[Any] = None      # parallel.pipeline.PipelineTickCtx
                                      # on a pp>1 mesh (r23): per-site
                                      # stable dropout seeds + global
                                      # (b,h) stream offsets so the
                                      # microbatched attention dropout
                                      # equals pp=1's mask slice, and
                                      # the QuantDense amax cadence.
                                      # None (pp=1) leaves every trace
                                      # byte-identical

    @nn.compact
    def __call__(self, x: jax.Array, mask: Optional[jax.Array],
                 train: bool) -> jax.Array:
        B, L, _ = x.shape
        d_k = self.d_model // self.h
        # quantized projections share nn.Dense's exact param tree
        # ("kernel"/"bias" under the same module names), so checkpoints
        # interchange between --quant modes; only the GEMM math and the
        # batch_stats-resident amax state differ (ops/quant.QuantDense)
        quant_kw = (dict(fmt=self.quant.fmt,
                         amax_history_len=self.quant.amax_history_len,
                         margin=self.quant.margin,
                         use_pallas=self.quant.use_pallas,
                         frozen_scales=getattr(self.quant,
                                               "frozen_scales", False),
                         grad_fmt=getattr(self.quant, "grad_fmt", None),
                         mesh=self.mesh,
                         amax_cadence=self.pp_ctx,
                         dtype=self.dtype, param_dtype=self.param_dtype)
                    if self.quant is not None else None)
        # projection-boundary annotations for a (data, model) mesh
        # (SNIPPETS [3]): heads over tp through the dense attention
        # math, the out-proj input sharded on its contiguous-head
        # d_model grouping so the tp-sharded `out` kernel contracts
        # locally and XLA inserts exactly one psum.  flash on a
        # serviceable tp mesh (r19, heads divide tp) keeps the same
        # head-over-tp layout — the annotations line up with the
        # shard_map boundary of kernel_shard.flash_attention_sharded so
        # no resharding happens at entry/exit; ring/ulysses re-shard
        # inside their own shard_map and stay un-annotated.
        from faster_distributed_training_tpu.parallel import kernel_shard
        dat = mesh_data_axes(self.mesh)
        # the SAME predicate the flash dispatch below uses (incl. the
        # FDT_KERNEL_SHARD kill switch): annotating head-over-tp while
        # dispatching the unsharded kernel would make XLA all-gather
        # q/k/v around the custom call — the exact failure r19 closes
        head_tp = (tp_size(self.mesh) > 1
                   and (self.attention_impl == "dense"
                        or (self.attention_impl == "flash"
                            and kernel_shard.flash_serviceable(
                                self.mesh, self.h))))
        if self.fused_qkv:
            if quant_kw is not None:
                # tp_dim names the Megatron role of each site's kernel
                # for the r19 shard_map quant layer (parallel/
                # kernel_shard.py): qkv shards the head axis (column-
                # parallel), q/k/v their output features, `out` its
                # input rows (row-parallel, one psum)
                qkv = QuantDense((3, self.h, d_k), kernel_init=qkv_xavier,
                                 name="qkv", tp_dim=2, **quant_kw)(x)
            else:
                qkv = nn.DenseGeneral((3, self.h, d_k), axis=-1,
                                      kernel_init=qkv_xavier,
                                      dtype=self.dtype,
                                      param_dtype=self.param_dtype,
                                      name="qkv")(x)  # (B, L, 3, h, d_k)
            q = qkv[:, :, 0].transpose(0, 2, 1, 3)  # (B, h, L, d_k)
            k = qkv[:, :, 1].transpose(0, 2, 1, 3)
            v = qkv[:, :, 2].transpose(0, 2, 1, 3)
        else:
            def proj(name):
                if quant_kw is not None:
                    y = QuantDense(self.d_model, kernel_init=xavier_uniform,
                                   name=name, tp_dim=1, **quant_kw)(x)
                else:
                    y = nn.Dense(self.d_model, kernel_init=xavier_uniform,
                                 dtype=self.dtype,
                                 param_dtype=self.param_dtype,
                                 name=name)(x)
                return y.reshape(B, L, self.h, d_k).transpose(0, 2, 1, 3)
            q, k, v = proj("query"), proj("key"), proj("value")
        if head_tp:
            q = shard_activation(q, self.mesh, (dat, "tp", None, None))
            k = shard_activation(k, self.mesh, (dat, "tp", None, None))
            v = shard_activation(v, self.mesh, (dat, "tp", None, None))
        # training-path prob dropout for the never-materialized impls:
        # one fresh u32 hash seed per step from the dropout rng stream
        # dropout_impl "none" disables the attention-prob regularizer on
        # EVERY impl (it is the all-dropout-off floor switch, not just
        # the FastDropout sites' engine)
        drop_rate = (self.dropout
                     if (self.dropout > 0 and train
                         and self.dropout_impl != "none") else 0.0)
        use_hash = (self.attention_impl != "dense"
                    or self.dropout_impl == "hash")
        if drop_rate > 0 and use_hash:
            draw = lambda: jax.random.bits(     # noqa: E731
                self.make_rng("dropout"), dtype=jnp.uint32)
            if self.pp_ctx is not None:
                # r23 pipeline parity: ONE seed per site per step (the
                # first draw — make_rng fold count 0, pp=1's key), every
                # tick; the microbatch's position enters via the global
                # (b, h) stream offset below instead
                site = "/".join(str(p) for p in self.scope.path)
                drop_seed = self.pp_ctx.site_seed(site + ":attn", draw)
            else:
                drop_seed = draw()
        else:
            drop_seed = None
        if self.attention_impl == "flash":
            from faster_distributed_training_tpu.ops.flash_attention import (
                flash_attention)
            from faster_distributed_training_tpu.parallel import kernel_shard
            # flash_save_stats=True defers to the FDT_FLASH_SAVE_STATS
            # env default (None) so the A/B kill switch still works;
            # False (rematted attention) is a hard override
            save = None if self.flash_save_stats else False
            if (kernel_shard.flash_serviceable(self.mesh, self.h)
                    or kernel_shard.data_sharded(self.mesh)):
                # a Mosaic kernel only partitions inside shard_map, on
                # ANY mesh of more than one device.  Without a tp axis
                # the batch rows shard over the data axes; with one
                # (r19, heads divide tp) the flash kernel also runs PER
                # SHARD on each device's local heads (parallel/
                # kernel_shard.py) instead of falling back to the slower
                # sequence-parallel strategies.  Dropout masks address
                # GLOBAL (b, h) stream indices, so they are placement-
                # invariant vs the unsharded kernel
                ctx = kernel_shard.flash_attention_sharded(
                    q, k, v, mask, self.mesh,
                    dropout_rate=drop_rate, dropout_seed=drop_seed,
                    save_stats=save)
            else:
                ctx = flash_attention(q, k, v, mask=mask,
                                      dropout_rate=drop_rate,
                                      dropout_seed=drop_seed,
                                      save_stats=save)
        elif self.attention_impl in ("ring", "ulysses"):
            if self.mesh is None:
                raise ValueError(
                    f"attention_impl={self.attention_impl!r} needs a mesh "
                    f"with an {self.sp_axis!r} axis")
            if self.attention_impl == "ring":
                from faster_distributed_training_tpu.ops.ring_attention import (
                    ring_self_attention as sp_attention)
            else:
                from faster_distributed_training_tpu.ops.ulysses_attention import (
                    ulysses_self_attention as sp_attention)
            ctx = sp_attention(q, k, v, mask, self.mesh,
                               sp_axis=self.sp_axis,
                               dropout_rate=drop_rate,
                               dropout_seed=drop_seed)
        elif use_hash and drop_rate > 0:
            # dense with the hash engine: same softmax-then-hash-keep
            # semantics as every kernel path, no threefry mask tensor
            from faster_distributed_training_tpu.ops.attention import (
                bh_index, dense_attention_reference)
            bh = None
            if self.pp_ctx is not None:
                # address the GLOBAL (b, h) stream: this microbatch's
                # batch rows start at row0, so its (b, h) indices are
                # pp=1's shifted by row0*h — the mask equals pp=1's
                # slice for these rows (r23)
                bh = bh_index(B, self.h) + jnp.int32(
                    self.pp_ctx.row0 * self.h)
            ctx = dense_attention_reference(q, k, v, mask, drop_rate,
                                            dropout_seed=drop_seed,
                                            dropout_bh=bh)
        else:
            # dropout inactive (eval / rate 0): ONE dense path for every
            # engine, so a training-only flag cannot shift inference
            # numerics; with dropout active this is the reference-naive
            # arm (dropout_impl == "xla", e.g. --tricks off):
            # materialized threefry bernoulli mask on the probs
            rng = (self.make_rng("dropout") if drop_rate > 0 else None)
            ctx = dense_attention(q, k, v, mask, drop_rate,
                                  deterministic=not train, dropout_rng=rng)
        if head_tp:
            ctx = shard_activation(ctx, self.mesh, (dat, "tp", None, None))
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, L, self.d_model)
        if head_tp:
            # d_model here is h contiguous head groups: sharding it on
            # tp keeps the tp-row-sharded `out` kernel's contraction
            # local (one psum after, no activation gather before)
            ctx = shard_activation(ctx, self.mesh, (dat, None, "tp"))
        # Name the attention context so the "attn_out" remat policy can
        # SAVE it: backward under that policy replays the cheap layer
        # matmuls (qkv/out-proj/FFN) but never re-runs the attention
        # kernel itself (whose Pallas backward already recomputes its
        # scores in-kernel — re-running the forward too would pay
        # attention twice, VERDICT r3 #3).
        ctx = checkpoint_name(ctx, "attn_out")
        if quant_kw is not None:
            # tp_dim=0: the out-proj is the attention block's Megatron
            # ROW-parallel site — its kernel's input dim is tp-sharded
            # (the contiguous-head d_model grouping annotated above), so
            # the per-shard GEMM contracts locally and psums once
            return QuantDense(self.d_model, kernel_init=xavier_uniform,
                              name="out", tp_dim=0, **quant_kw)(ctx)
        return nn.Dense(self.d_model, kernel_init=xavier_uniform,
                        dtype=self.dtype, param_dtype=self.param_dtype,
                        name="out")(ctx)


class PositionalWiseFFN(nn.Module):
    """transformer.py:159-177 — Linear → GELU → dropout → Linear.

    On a (data, model) mesh the [B, L, d_ff] hidden is annotated sharded
    on tp right at the first-matmul boundary, matching the tp-sharded
    kernels (_TP_RULES: dense_0 column- / dense_1 row-sharded) so XLA
    never gathers the full hidden activation — GELU + dropout run on
    1/tp of it per device and the single psum lands after dense_1."""
    d_model: int
    d_ff: int
    dropout: float = 0.1
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    dropout_impl: str = "hash"
    mesh: Optional[Any] = None
    quant: Optional[Any] = None   # QuantPolicy: int8/fp8 FFN GEMMs
    pp_ctx: Optional[Any] = None  # PipelineTickCtx on pp>1 (r23): stable
                                  # per-site dropout seed + microbatch
                                  # stream offset, QuantDense amax
                                  # cadence; None = unchanged trace

    @nn.compact
    def __call__(self, x: jax.Array, train: bool) -> jax.Array:
        kw = dict(kernel_init=xavier_uniform, dtype=self.dtype,
                  param_dtype=self.param_dtype)
        if self.quant is not None:
            # quantized twins of the two Dense layers, explicitly named
            # Dense_0/Dense_1 so the param tree (and therefore
            # checkpoints, TP rules and _FFNParamMirror) is byte-
            # identical to the flax composition's auto-naming
            qkw = dict(fmt=self.quant.fmt,
                       amax_history_len=self.quant.amax_history_len,
                       margin=self.quant.margin,
                       use_pallas=self.quant.use_pallas,
                       frozen_scales=getattr(self.quant,
                                             "frozen_scales", False),
                       grad_fmt=getattr(self.quant, "grad_fmt", None),
                       mesh=self.mesh, amax_cadence=self.pp_ctx, **kw)
            # Megatron roles for the r19 shard_map quant layer: Dense_0
            # column-parallel (d_ff out), Dense_1 row-parallel (d_ff in,
            # one psum) — the _TP_RULES layout
            dense_0 = QuantDense(self.d_ff, name="Dense_0", tp_dim=1, **qkw)
            dense_1 = QuantDense(self.d_model, name="Dense_1", tp_dim=0,
                                 **qkw)
        else:
            dense_0 = nn.Dense(self.d_ff, **kw)
            dense_1 = nn.Dense(self.d_model, **kw)
        h = dense_0(x)
        if tp_size(self.mesh) > 1:
            h = shard_activation(h, self.mesh,
                                 (mesh_data_axes(self.mesh), None, "tp"))
        h = nn.gelu(h, approximate=False)
        h = FastDropout(self.dropout, self.dropout_impl,
                        pp_ctx=self.pp_ctx)(h, deterministic=not train)
        return dense_1(h)


# Remat policies for --remat (VERDICT r3 #3).  "layer" checkpoints the
# whole EncoderLayer — maximum memory savings, but it re-runs flash
# attention's forward in the backward replay even though the flash
# BACKWARD already recomputes its own scores in-kernel
# (ops/flash_attention.py): attention ends up computed twice per
# backward.  "ffn" checkpoints ONLY the FFN sublayer (the two big
# matmul activations, [B,L,d_ff] gelu in/out — the bulk of the per-layer
# residual footprint) and leaves attention alone.  "attn_out"
# checkpoints the whole layer under save_only_these_names("attn_out"):
# the attention context is SAVED (the kernel never re-runs) while every
# other residual — qkv, FFN hidden, LN stats — is replayed from cheap
# matmuls; the best memory/throughput trade measured.  "dots" applies
# XLA's dots_with_no_batch_dims_saveable policy to the whole layer:
# matmul outputs are saved, elementwise chains recomputed.
REMAT_POLICIES = ("layer", "ffn", "attn_out", "dots")


class _QuantDenseMirror(nn.Module):
    """QuantDense's exact param + batch_stats trees (kernel/bias under
    the module name, amax_history_x/amax_history_w in batch_stats)
    WITHOUT its compute — the quantized fused-FFN path reads the leaves
    and runs the math in the generalized kernel, so checkpoints (params
    AND scale state) interchange with the Flax QuantDense composition."""
    features: int
    amax_history_len: int = 16
    kernel_init: object = xavier_uniform
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, probe: jax.Array):
        from faster_distributed_training_tpu.ops.quant import (
            fresh_amax_history)

        kernel = self.param("kernel", self.kernel_init,
                            (probe.shape[-1], self.features),
                            self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros,
                          (self.features,), self.param_dtype)
        hx = self.variable("batch_stats", "amax_history_x",
                           fresh_amax_history, self.amax_history_len)
        hw = self.variable("batch_stats", "amax_history_w",
                           fresh_amax_history, self.amax_history_len)
        return kernel, bias, hx, hw


class _FFNParamMirror(nn.Module):
    """Declares PositionalWiseFFN's exact param tree (Dense_0 -> d_ff,
    Dense_1 -> d_model, same auto-naming order) WITHOUT its compute —
    the fused-FFN kernel path (`ffn_impl="pallas"`) reads the leaves and
    runs the math in `ops.fused_ffn`, keeping checkpoints interchangeable
    between the Flax and kernel implementations.  The probe call is
    (1, d_model) — parameter creation only, negligible compute.

    With ``quant`` set (a QuantPolicy) the mirror declares QuantDense's
    tree instead — same params plus the four amax histories in
    batch_stats — and returns them after the weights, so the quantized
    fused kernel (r19) rolls the exact state the Flax quantized
    composition would."""
    d_model: int
    d_ff: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    quant: Optional[Any] = None

    @nn.compact
    def __call__(self, probe: jax.Array):
        if self.quant is not None:
            qm = dict(amax_history_len=self.quant.amax_history_len,
                      kernel_init=xavier_uniform,
                      param_dtype=self.param_dtype)
            w1, b1, hx1, hw1 = _QuantDenseMirror(
                self.d_ff, name="Dense_0", **qm)(probe)
            w2, b2, hx2, hw2 = _QuantDenseMirror(
                self.d_model, name="Dense_1", **qm)(
                    jnp.zeros(probe.shape[:-1] + (self.d_ff,),
                              probe.dtype))
            return w1, b1, w2, b2, (hx1, hw1, hx2, hw2)
        kw = dict(kernel_init=xavier_uniform, dtype=self.dtype,
                  param_dtype=self.param_dtype)
        d0 = nn.Dense(self.d_ff, **kw)
        d1 = nn.Dense(self.d_model, **kw)
        d1(d0(probe))
        return (d0.variables["params"]["kernel"],
                d0.variables["params"]["bias"],
                d1.variables["params"]["kernel"],
                d1.variables["params"]["bias"], None)


class EncoderLayer(nn.Module):
    """One pre-LN attention sublayer + one pre-LN FFN sublayer
    (transformer.py:245-275).  Factored into its own module so
    ``Transformer.remat`` can wrap it in ``nn.remat`` — backward then
    recomputes the layer's activations instead of keeping them in HBM,
    the capacity lever long sequences need."""
    h: int
    d_model: int
    d_ff: int
    dropout_connection_attention: float = 0.1
    dropout_connection_ffn: float = 0.1
    dropout_attention: float = 0.1
    dropout_ffn: float = 0.1
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    attention_impl: str = "dense"
    mesh: Optional[Any] = None
    sp_axis: str = "sp"
    dropout_impl: str = "hash"
    remat_ffn: bool = False   # checkpoint the FFN sublayer only ("ffn")
    fused_qkv: bool = True
    ffn_impl: str = "flax"    # flax | pallas (ops/fused_ffn.py mega-kernel)
    flash_save_stats: bool = True   # False under attention-wrapping remat
    quant: Optional[Any] = None     # QuantPolicy threaded to attention +
                                    # FFN projections; with ffn_impl
                                    # "pallas" the generalized fused
                                    # kernel runs its two GEMMs on the
                                    # quantized operands in-kernel (r19
                                    # — the bf16-only caveat is gone)
    pp_ctx: Optional[Any] = None    # parallel.pipeline.PipelineTickCtx
                                    # on a pp>1 mesh (r23), threaded to
                                    # every dropout site (stable seeds +
                                    # microbatch stream offsets) and
                                    # every QuantDense (one amax roll
                                    # per step).  None on pp=1: all
                                    # traces byte-identical to r22

    @nn.compact
    def __call__(self, h: jax.Array, mask: Optional[jax.Array],
                 train: bool) -> jax.Array:
        ln = lambda name: TorchLayerNorm(   # noqa: E731
            dtype=self.dtype, param_dtype=self.param_dtype, name=name)
        # sequence-parallel LN/dropout regions (Korthikanti et al.;
        # ops/sequence_parallel.py owns the kernel-side analog): between
        # the parallel blocks the residual stream is annotated sharded
        # on the model axis ALONG THE SEQUENCE — LayerNorm (per-token
        # over D) and the connection dropouts (position-hashed) run on
        # L/ax tokens per device and the per-device activation residing
        # between TP regions shrinks by 1/ax.  XLA inserts the gather
        # exactly at the qkv/FFN entry (or hands the already-sequence-
        # sharded tensor straight to ring/ulysses' shard_map).  Identity
        # on 1D meshes (shard_activation filters absent axes).
        seq_ax, _ = seq_parallel_axis(self.mesh)
        dat = mesh_data_axes(self.mesh)
        seq_shard = (
            (lambda x: shard_activation(x, self.mesh, (dat, seq_ax, None)))
            if seq_ax is not None else (lambda x: x))
        h = seq_shard(h)
        a = ln("ln_attn")(h)
        a = MultiheadAttention(self.h, self.d_model, self.dropout_attention,
                               self.dtype, self.param_dtype,
                               self.attention_impl, self.mesh,
                               self.sp_axis, self.fused_qkv,
                               dropout_impl=self.dropout_impl,
                               flash_save_stats=self.flash_save_stats,
                               quant=self.quant, pp_ctx=self.pp_ctx,
                               name="attn")(a, mask, train)
        a = FastDropout(self.dropout_connection_attention,
                        self.dropout_impl,
                        pp_ctx=self.pp_ctx)(seq_shard(a),
                                            deterministic=not train)
        h = seq_shard(h + a)
        # ADVICE r5 (medium): the kernel's in-VMEM dropout IS the hash
        # engine — it must follow dropout_impl like every other site.
        # "none" (the all-dropout-off floor switch) runs the kernel with
        # rates 0; "xla" (the --tricks off reference-naive arm) needs the
        # threefry nn.Dropout masks, which only the Flax composition can
        # apply, so active-dropout + non-hash engines fall back to it.
        ffn_dropout_active = (train and self.dropout_impl != "none"
                              and (self.dropout_ffn > 0
                                   or self.dropout_connection_ffn > 0))
        if (self.ffn_impl == "pallas"
                and (not ffn_dropout_active
                     or self.dropout_impl == "hash")):
            # fused sublayer (ops/fused_ffn.py): LN + FFN + both dropout
            # sites + residual in one Pallas kernel, recompute backward —
            # zero FFN-shaped residuals (a capacity lever; see PARITY for
            # the measured time trade).  Param trees mirror the Flax path
            # exactly.  On sharded meshes the kernel runs PER SHARD via
            # fused_ffn_sublayer_sharded (shard_map over the data axes;
            # each shard addresses the GLOBAL dropout index space, so
            # masks are placement-invariant); tp meshes run the Megatron
            # column-then-row decomposition through the r19 shard_map
            # kernel layer (parallel/kernel_shard.py — w1/w2 consumed as
            # their tp shards in place, ONE psum per sublayer) when
            # d_ff/seq divide, with the Flax composition as the
            # registered warned fallback (build_model).  --quant rides
            # the same kernels (the generalized core quantizes the GEMMs
            # in-kernel at the delayed scales and emits the step amaxes).
            from faster_distributed_training_tpu.ops.fused_ffn import (
                ffn_core_generalized, fused_ffn_sublayer,
                fused_ffn_sublayer_sharded)
            from faster_distributed_training_tpu.parallel import kernel_shard
            lnf = ln("ln_ffn")
            lnf(h[..., :1, :])      # param creation only (probe row)
            ln_scale = lnf.variables["params"]["scale"]
            ln_bias = lnf.variables["params"]["bias"]
            w1, b1, w2, b2, qstate = _FFNParamMirror(
                self.d_model, self.d_ff, self.dtype, self.param_dtype,
                quant=self.quant, name="ffn")(h[..., :1, :])
            if ffn_dropout_active:
                draw = lambda: jax.random.bits(     # noqa: E731
                    self.make_rng("dropout"), (2,), dtype=jnp.uint32)
                if self.pp_ctx is not None:
                    # stable per-step seeds (first draw) — NOTE this is
                    # determinism only, not pp=1 parity: the fused
                    # kernel's masks address per-invocation row indices,
                    # so build_pipeline_spec keeps the warning for
                    # pallas FFN + dropout under pp
                    site = "/".join(str(p) for p in self.scope.path)
                    seeds = self.pp_ctx.site_seed(site + ":ffn", draw)
                else:
                    seeds = draw()
                hid_seed, out_seed = seeds[0], seeds[1]
                r_h, r_c = self.dropout_ffn, self.dropout_connection_ffn
            else:
                hid_seed = out_seed = jnp.uint32(0)
                r_h = r_c = 0.0
            fmt = None
            if self.quant is not None:
                from faster_distributed_training_tpu.ops.quant import (
                    quant_enabled, scale_from_history, tensor_amax,
                    update_amax_history)
                hx1, hw1, hx2, hw2 = qstate
                # FDT_QUANT=0 keeps the state tree allocated but runs
                # the plain bf16/fp32 kernel (the QuantDense contract)
                fmt = self.quant.fmt if quant_enabled() else None
            w1c, b1c = w1.astype(self.dtype), b1.astype(self.dtype)
            w2c, b2c = w2.astype(self.dtype), b2.astype(self.dtype)
            kernel_args = (h, ln_scale, ln_bias, w1c, b1c, w2c, b2c,
                           hid_seed, out_seed)
            gfmt = (getattr(self.quant, "grad_fmt", None)
                    if fmt is not None else None)
            if fmt is not None:
                mg = self.quant.margin
                if self.pp_ctx is not None:
                    # pipeline amax cadence (r23): every tick quantizes
                    # at the PRE-step scales (what pp=1 uses all step)
                    qsite = "/".join(str(p) for p in self.scope.path)
                    hists = (
                        self.pp_ctx.amax_pre(qsite + ":hx1", hx1.value),
                        self.pp_ctx.amax_pre(qsite + ":hw1", hw1.value),
                        self.pp_ctx.amax_pre(qsite + ":hx2", hx2.value),
                        self.pp_ctx.amax_pre(qsite + ":hw2", hw2.value))
                else:
                    hists = (hx1.value, hw1.value, hx2.value, hw2.value)
                scales = tuple(scale_from_history(hh, fmt, mg)
                               for hh in hists)
            else:
                scales = None
            if tp_size(self.mesh) > 1:
                res = kernel_shard.fused_ffn_sublayer_tp(
                    *kernel_args, mesh=self.mesh,
                    rate_hidden=r_h, rate_conn=r_c,
                    quant_fmt=fmt, quant_scales=scales, grad_fmt=gfmt)
            elif self.mesh is not None and any(
                    self.mesh.shape[ax] > 1 for ax in self.mesh.axis_names):
                # SPMD: per-shard kernels over the data axes, masks
                # addressed in the GLOBAL index space (ops/fused_ffn.py)
                res = fused_ffn_sublayer_sharded(
                    *kernel_args, mesh=self.mesh,
                    rate_hidden=r_h, rate_conn=r_c,
                    quant_fmt=fmt, quant_scales=scales, grad_fmt=gfmt)
            elif fmt is not None:
                res = ffn_core_generalized(
                    h, ln_scale, ln_bias, w1c, b1c, w2c, b2c,
                    hid_seed, out_seed, 0, 0, 0, r_h, r_c, 1e-6, 1, 1,
                    dff_glob=self.d_ff, quant_fmt=fmt,
                    quant_scales=scales, grad_fmt=gfmt)
            else:
                return fused_ffn_sublayer(*kernel_args, r_h, r_c)
            if fmt is None:
                return res
            out, amax2 = res
            # roll the delayed-scaling histories exactly as QuantDense
            # would: x-side amaxes from the kernel (LN output / post-
            # dropout activation), w-side from the cast weights
            if (not getattr(self.quant, "frozen_scales", False)
                    and self.is_mutable_collection("batch_stats")):
                if self.pp_ctx is not None:
                    # one roll per optimizer step: first real push
                    # rolls, later ticks max-reduce into slot 0,
                    # bubble ticks skipped (PipelineTickCtx.amax_push)
                    cad, qs = self.pp_ctx, qsite
                    hx1.value = cad.amax_push(qs + ":hx1", hx1.value,
                                              amax2[0])
                    hx2.value = cad.amax_push(qs + ":hx2", hx2.value,
                                              amax2[1])
                    hw1.value = cad.amax_push(qs + ":hw1", hw1.value,
                                              tensor_amax(w1c))
                    hw2.value = cad.amax_push(qs + ":hw2", hw2.value,
                                              tensor_amax(w2c))
                else:
                    hx1.value = update_amax_history(hx1.value, amax2[0])
                    hx2.value = update_amax_history(hx2.value, amax2[1])
                    hw1.value = update_amax_history(hw1.value,
                                                    tensor_amax(w1c))
                    hw2.value = update_amax_history(hw2.value,
                                                    tensor_amax(w2c))
            return out
        f = ln("ln_ffn")(h)
        ffn_cls = (nn.remat(PositionalWiseFFN, static_argnums=(2,))
                   if self.remat_ffn else PositionalWiseFFN)
        f = ffn_cls(self.d_model, self.d_ff, self.dropout_ffn,
                    self.dtype, self.param_dtype,
                    self.dropout_impl, self.mesh, self.quant,
                    self.pp_ctx, name="ffn")(f, train)
        f = FastDropout(self.dropout_connection_ffn,
                        self.dropout_impl,
                        pp_ctx=self.pp_ctx)(seq_shard(f),
                                            deterministic=not train)
        return seq_shard(h + f)


class Transformer(nn.Module):
    """transformer.py:12-91 — returns (logits, perm_index, lam) in train mode
    (mixup on the pooled sentence embedding), plain logits in eval mode."""
    n_class: int
    vocab: int = 30522            # bert-base-uncased vocab size
    n_layers: int = 6
    h: int = 8
    d_model: int = 512
    d_ff: int = 1024
    d_hidden: int = 1024
    maxlen: int = 512
    dropout_encodings: float = 0.1
    dropout_connection_attention: float = 0.1
    dropout_connection_ffn: float = 0.1
    dropout_attention: float = 0.1
    dropout_ffn: float = 0.1
    alpha: float = 0.99           # in-forward mixup Beta parameter
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    attention_impl: str = "dense"  # dense | flash | ring | ulysses
    mlp_impl: str = "fused"        # fused (custom_vjp) | pallas
    mesh: Optional[Any] = None     # required for ring/ulysses
    sp_axis: str = "sp"
    remat: bool = False
    remat_policy: str = "attn_out"  # layer | ffn | attn_out | dots
                                   # (see REMAT_POLICIES)
    dropout_impl: str = "hash"     # hash | xla | none (ops/dropout.py)
    ffn_impl: str = "flax"         # flax | pallas (fused FFN sublayer)
    fused_qkv: bool = True         # False = reference's 3 separate QKV
                                   # Linears (bag-of-tricks ablation arm)
    quant: Optional[Any] = None    # train.amp.QuantPolicy: int8/fp8
                                   # forward GEMMs for the attention
                                   # projections + FFN with delayed
                                   # per-tensor scaling; scale state
                                   # rides the batch_stats collection
    lm_head: bool = False          # --task lm (r18): per-position vocab
                                   # logits for next-token prediction
                                   # instead of the CLS pooler/classifier
                                   # — the streamed LM workload's head.
                                   # No mixup: sentence-embedding mixup
                                   # is a classification regularizer with
                                   # no analog on a dense token objective
    tie_lm_head: bool = False      # r19 (ROADMAP r18 follow-on (c)):
                                   # logits = h @ token_embedding^T — no
                                   # separate lm_head projection
                                   # (~vocab*d_model fewer params), and
                                   # the token_embedding vocab-sharding
                                   # TP rule serves the head for free.
                                   # False = the r18 untied nn.Dense
                                   # head (checkpoint-compatible via the
                                   # train/checkpoint.py compat shim)
    causal: bool = False           # --lm_causal (r22): apply the causal
                                   # mask at TRAINING time so the
                                   # trained conditional matches the
                                   # mask decode imposes at serving
                                   # (closes the r21 train/decode
                                   # mismatch).  Combined with any
                                   # padding mask below; routed to the
                                   # dense impl by resolve_attention —
                                   # flash only accepts key-padding
                                   # masks (ops/flash_attention.py) and
                                   # ring/ulysses shard L.

    @nn.compact
    def __call__(self, x: jax.Array, token_types: Optional[jax.Array] = None,
                 mask: Optional[jax.Array] = None, train: bool = True,
                 pp_spec: Optional[Any] = None):
        B, L = x.shape
        if token_types is None:
            token_types = jnp.zeros_like(x)
        embeddings, tok_table = Embeddings(self.d_model, self.vocab,
                                           self.maxlen,
                                           self.param_dtype)(x, token_types)
        # x = embeddings + dropout(embeddings + pe): the reference feeds the
        # PositionalEncoding module the embeddings and then ADDS its output to
        # the embeddings again (transformer.py:61-64) — preserved verbatim.
        pe = jnp.asarray(sinusoidal_table(self.maxlen, self.d_model))
        encodings = FastDropout(self.dropout_encodings, self.dropout_impl)(
            embeddings + pe[None, :L, :], deterministic=not train)
        h = (embeddings + encodings).astype(self.dtype)

        if mask is not None and mask.ndim == 2:   # (B, L) padding mask
            mask = mask[:, None, None, :]          # broadcast over heads+query
        if self.causal:
            # causal (next-token) mask, combined with any padding mask:
            # (1,1,L,L) alone broadcasts over batch+heads; against a
            # (B,1,1,L) padding mask the product is the (B,1,L,L) joint
            # mask every query row honors
            cm = jnp.tril(jnp.ones((L, L), jnp.int32))[None, None, :, :]
            mask = cm if mask is None else mask * cm

        # Each encoder layer is one EncoderLayer module; with remat=True the
        # selected policy (remat_policy) decides WHAT backward recomputes:
        #   layer — nn.remat the whole layer (max memory savings; pays
        #           flash attention's forward twice in backward, VERDICT
        #           r3 #3);
        #   ffn   — checkpoint only the FFN sublayer (the [B,L,d_ff]
        #           activations, the bulk of the residual footprint,
        #           while attention — whose Pallas backward already
        #           recomputes in-kernel — is left alone;
        #   dots  — whole-layer remat under XLA's
        #           dots_with_no_batch_dims_saveable (matmul outputs
        #           saved, elementwise chains recomputed).
        layer_cls = EncoderLayer
        remat_ffn = False
        if self.remat:
            if self.remat_policy == "ffn":
                remat_ffn = True
            elif self.remat_policy == "attn_out":
                layer_cls = nn.remat(
                    EncoderLayer, static_argnums=(3,),
                    policy=jax.checkpoint_policies.save_only_these_names(
                        "attn_out"))
            elif self.remat_policy == "dots":
                layer_cls = nn.remat(
                    EncoderLayer, static_argnums=(3,),
                    policy=jax.checkpoint_policies
                    .dots_with_no_batch_dims_saveable)
            else:   # "layer" (round-3 behavior)
                layer_cls = nn.remat(EncoderLayer, static_argnums=(3,))
        # remat policies that wrap ATTENTION ("layer"/"attn_out"/"dots")
        # recompute custom_vjp residuals in the backward replay: flash
        # must keep its residuals input-only there, or the saved
        # (out, lse) would force the forward kernel to re-run in the
        # replay (flash_attention docstring).  "ffn" checkpoints only
        # the FFN sublayer, so attention keeps the saved-stats backward.
        flash_save_stats = not (self.remat and self.remat_policy != "ffn")
        if pp_spec is None:
            for i in range(self.n_layers):
                h = layer_cls(self.h, self.d_model, self.d_ff,
                              self.dropout_connection_attention,
                              self.dropout_connection_ffn,
                              self.dropout_attention, self.dropout_ffn,
                              self.dtype, self.param_dtype,
                              self.attention_impl, self.mesh, self.sp_axis,
                              self.dropout_impl, remat_ffn, self.fused_qkv,
                              self.ffn_impl, flash_save_stats, self.quant,
                              name=f"layer_{i}")(h, mask, train)
        else:
            # Pipelined encoder (parallel/pipeline.py — the module
            # docstring there is the spec).  Selected by python
            # branching on pp_spec BEFORE trace, so pp=1 programs (the
            # branch above) stay byte-identical to r21.  Same modules,
            # same names, same param tree: only the execution order of
            # the layer applications changes — the batch runs as M
            # microbatches through V rotating virtual-stage slots, and
            # jax.grad through the rotation yields the reversed (1F1B)
            # backward pipeline.  PipelineTickCtx (r23) restores pp ≡
            # pp=1 with dropout LIVE on the hash engine (stable
            # per-site seeds + global microbatch stream offsets) and
            # with --quant (one amax roll per optimizer step) —
            # build_pipeline_spec still warns for the non-parity
            # engine combos (pipeline.py docstring).
            from faster_distributed_training_tpu.parallel.pipeline import (
                PipelineTickCtx, constrain_stage_buffer, virtual_chunks)
            spec = pp_spec
            # the tick loop runs the depth-ordered VIRTUAL chunks, not
            # a stage's concatenated layer list: slot j applies chunk j
            # (chunks ordered by first layer, pipeline.virtual_chunks),
            # so a microbatch traverses layer 0..L-1 in order under
            # EVERY schedule — 1f1b (V == S, one chunk per stage) and
            # v=2 interleaved (V == 2S, stage j % S hosts slot j) alike.
            chunks = virtual_chunks(spec)
            M, V = spec.n_microbatches, len(chunks)
            if B % M:
                raise ValueError(f"batch {B} not divisible by "
                                 f"{M} pipeline microbatches")
            # ONE mutable trace-time context shared by every layer: the
            # tick loop below sets (microbatch, bubble) before each slot
            # invocation and the dropout/quant sites read them at trace
            # time (the loop is python-unrolled, so each invocation
            # bakes its own values into the jaxpr).  Under --remat each
            # tick's layer call is its OWN checkpoint trace, so the
            # ctx's cross-tick stashes (seeds, amax histories) would
            # leak tracers between traces — no ctx there (r22 per-tick
            # behavior; build_pipeline_spec warns/refuses accordingly)
            ctx = None if self.remat else PipelineTickCtx(M, B // M)
            layers = [layer_cls(self.h, self.d_model, self.d_ff,
                                self.dropout_connection_attention,
                                self.dropout_connection_ffn,
                                self.dropout_attention, self.dropout_ffn,
                                self.dtype, self.param_dtype,
                                self.attention_impl, self.mesh,
                                self.sp_axis, self.dropout_impl,
                                remat_ffn, self.fused_qkv, self.ffn_impl,
                                flash_save_stats, self.quant,
                                pp_ctx=ctx,
                                name=f"layer_{i}")
                      for i in range(self.n_layers)]
            hs = h.reshape((M, B // M) + h.shape[1:])
            # per-microbatch view of a batch-carrying mask; a batch-free
            # causal mask (1,1,L,L) broadcasts into every slot as-is
            bmask = (mask.reshape((M, B // M) + mask.shape[1:])
                     if mask is not None and mask.shape[0] == B else None)
            # fill/drain slots recycle real microbatch data rather than
            # zeros: their outputs are never selected into the loss
            # (zero cotangents either way), but an all-zero constant
            # block lets XLA:CPU constant-fold the slot's attention
            # backward into 0*inf NaN constants at x64 — recycled data
            # keeps every slot on the generic (finite) compute path.
            buf = jnp.broadcast_to(hs[0], (V,) + hs.shape[1:])
            outs = []
            for t in range(spec.n_ticks):
                # rotate: slot j consumes what slot j-1 emitted last
                # tick (slot 0 takes the next microbatch; drain ticks
                # recycle microbatch t % M — discarded, see above).
                # Under GSPMD the pp-sharded slot-dim shift is the
                # stage-boundary collective-permute — the DCN hop.
                inp = hs[t % M]
                buf = jnp.concatenate([inp[None], buf[:-1]], axis=0)
                buf = constrain_stage_buffer(buf, spec)
                slots = []
                for j in range(V):
                    z = buf[j]
                    m_ = mask
                    if bmask is not None:
                        # the mask of the microbatch in this slot
                        # (clamped for bubble slots — their output is
                        # discarded, any finite mask will do)
                        m_ = bmask[min(max(t - j, 0), M - 1)]
                    # which microbatch this slot is processing (same
                    # clamp as the mask) and whether it's a fill/drain
                    # bubble — read at trace time by the r23 dropout
                    # offsets and the quant amax cadence
                    if ctx is not None:
                        ctx.microbatch = min(max(t - j, 0), M - 1)
                        ctx.bubble = not (0 <= t - j < M)
                    for i in chunks[j]:
                        z = layers[i](z, m_, train)
                    slots.append(z)
                buf = jnp.stack(slots)
                buf = constrain_stage_buffer(buf, spec)
                if t >= V - 1:
                    # positive static index: the negative-index gather's
                    # transpose emits a mixed s64/s32 dynamic_update_slice
                    # under x64 that the SPMD partitioner rejects
                    outs.append(buf[V - 1])
            h = jnp.stack(outs).reshape((B,) + h.shape[1:])

        ln = lambda name: TorchLayerNorm(   # noqa: E731
            dtype=self.dtype, param_dtype=self.param_dtype, name=name)

        # Final LayerNorm before the pooler.  The reference carries this
        # layer as dead code — both its definition and its application
        # are commented out (transformer.py:45,68):
        # without it, six pre-LN residual blocks leave h unnormalized,
        # the pooler's tanh pre-activation reaches |x|~3.6 at d_model=512
        # (measured), tanh saturates, and gradients into the entire
        # encoder attenuate ~300x — the d512/6L model cannot learn even
        # on an overfit batch.  Applying the norm is the standard pre-LN
        # closing step and a deliberate, documented fix (same category
        # as the eval-mixup and -1e-9 mask fixes above).
        h = ln("ln_final")(h)

        if self.lm_head:
            # next-token LM head: fp32 logits over the vocab at every
            # position (the loss shifts targets, train/steps.py).  Same
            # return shape train and eval — the mixup triplet below is
            # classification-only.
            if self.tie_lm_head:
                # tied head: logits = h @ E^T on the RAW (unscaled)
                # token table, no bias — the table stays fp32 (the
                # embedding island) and contracts against the compute-
                # dtype h with fp32 accumulation
                logits = jnp.dot(h.astype(self.dtype),
                                 tok_table.astype(self.dtype).T,
                                 preferred_element_type=jnp.float32)
            else:
                logits = nn.Dense(self.vocab, kernel_init=xavier_uniform,
                                  dtype=self.dtype,
                                  param_dtype=self.param_dtype,
                                  name="lm_head")(h)
            return logits.astype(jnp.float32)

        # Pooler: tanh(dense(CLS)) (transformer.py:94-101)
        pooled = nn.tanh(nn.Dense(self.d_model, kernel_init=xavier_uniform,
                                  dtype=self.dtype,
                                  param_dtype=self.param_dtype,
                                  name="pooler")(h[:, 0, :]))
        pooled = FastDropout(0.1, self.dropout_impl)(
            pooled, deterministic=not train)

        # FusedMLP classifier (transformer.py:278-289): d_model→d_hidden→n_class
        w1 = self.param("cls_w1", xavier_uniform,
                        (self.d_hidden, self.d_model), self.param_dtype)
        b1 = self.param("cls_b1", nn.initializers.zeros,
                        (1, self.d_hidden), self.param_dtype)
        w2 = self.param("cls_w2", xavier_uniform,
                        (self.n_class, self.d_hidden), self.param_dtype)
        b2 = self.param("cls_b2", nn.initializers.zeros,
                        (1, self.n_class), self.param_dtype)

        # pallas = VMEM-resident kernel; fused = custom_vjp recompute
        # backward; naive = plain ops under default AD (stores the hidden
        # activations — the bag-of-tricks ablation arm, matching the
        # reference's un-fused MLPScratch semantics)
        mlp_fn = {"pallas": fused_mlp_pallas,
                  "naive": lambda *a: mlp_reference(*a[:5])}.get(
            self.mlp_impl, fused_mlp)
        if (self.mlp_impl == "pallas" and self.mesh is not None
                and self.mesh.size > 1):
            # the kernel only partitions inside shard_map: per-shard
            # rows over the data axes (parallel/kernel_shard.py)
            from faster_distributed_training_tpu.parallel import (
                kernel_shard)
            mlp_fn = functools.partial(kernel_shard.fused_mlp_sharded,
                                       mesh=self.mesh)

        def classify(z):
            logits = mlp_fn(z.astype(self.dtype), w1.astype(self.dtype),
                            b1.astype(self.dtype), w2.astype(self.dtype),
                            b2.astype(self.dtype))
            return logits.astype(jnp.float32)

        if not train:
            return classify(pooled)

        # in-forward sentence-embedding mixup (transformer.py:71-84),
        # gated on train — fixing the reference's always-on mixup at eval.
        key = self.make_rng("mixup")
        k_lam, k_perm = jax.random.split(key)
        if self.alpha > 0:
            lam = jax.random.beta(k_lam, self.alpha, self.alpha)
        else:
            lam = jnp.asarray(self.alpha, jnp.float32)
        index = jax.random.permutation(k_perm, B)
        mixed = (lam.astype(pooled.dtype) * pooled
                 + (1 - lam).astype(pooled.dtype) * pooled[index])
        return classify(mixed), index, lam
