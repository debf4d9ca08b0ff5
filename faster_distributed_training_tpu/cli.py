"""CLI wiring shared by every entry point.

The reference ships two monolithic scripts (resnet50_test.py,
transformer_test.py) whose __main__ blocks duplicate device probing,
data prep, model build, optimizer selection and the DDP/FSDP launch
(resnet50_test.py:693-740, transformer_test.py:364-424).  Here all of
that is ONE code path parameterized by TrainConfig; the root-level
entry scripts are thin defaults-providers.

Launch model: one process per host, all local chips visible
(`--distributed` triggers jax.distributed.initialize) — replacing
torchrun's process-per-GPU + NCCL rendezvous (run_distributed.sh:2-3).
"""

from __future__ import annotations

import os
from typing import Callable, Optional, Tuple

import numpy as np

from faster_distributed_training_tpu.config import (TrainConfig,
                                                    build_parser,
                                                    config_from_args,
                                                    is_token_model)


def _configured_platform() -> str:
    """The platform jax WILL use, read without initializing the backend
    (jax.default_backend() would pin the platform before setup_platform's
    --device override runs)."""
    import jax

    p = (getattr(jax.config, "jax_platforms", None)
         or os.environ.get("JAX_PLATFORMS", ""))
    return p.split(",")[0] if p else ""


def quiet_cpu_aot_flags() -> None:
    """Cap the XLA:CPU target ISA at AVX2 (x86 only, before first backend
    use).  Measured root cause of the `cpu_aot_loader` warnings the
    8-virtual-device dry runs logged: targeting AVX-512 makes XLA bake the
    PSEUDO-features ``+prefer-no-scatter``/``+prefer-no-gather`` into CPU
    AOT executables, and the loader's replay check compares them against
    the host's /proc/cpuinfo features — where pseudo-features never
    appear — so EVERY persistent-cache replay warns, even same-host
    same-jaxlib (reproduced+measured: write/replay with default flags =
    6 warnings, with ``--xla_cpu_max_isa=AVX2`` = 0).  The CPU backend
    here is the test/gate simulator, never the perf path, so the ISA cap
    costs nothing that matters."""
    import platform

    if platform.machine() not in ("x86_64", "AMD64"):
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_cpu_max_isa" not in flags:
        os.environ["XLA_FLAGS"] = (flags + " --xla_cpu_max_isa=AVX2").strip()


# The persistent compile cache lives at ONE fixed path inside the
# checkout (git-ignored), the same for TPU and CPU programs: the path is
# part of nothing's key and never moves, so a second run finds the first
# run's programs.  Whoever wants it elsewhere (the chip tool, a hermetic
# child process) sets JAX_COMPILATION_CACHE_DIR, which JAX reads itself.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def enable_compilation_cache() -> None:
    """Persistent XLA compilation cache — whole-train-step compiles take
    a minute or two cold; cached reloads take seconds.  When
    ``JAX_COMPILATION_CACHE_DIR`` is set the program sets no directory
    in code at all (JAX reads the variable); otherwise it is
    ``CACHE_DIR``.  A directory that cannot be set is an error, not a
    silent cold run."""
    import jax

    if _configured_platform() != "tpu":
        # single chokepoint for every non-TPU path (INCLUDING --device
        # auto on a CPU-only host): cap the CPU target ISA before the
        # first compile so cached AOT executables never carry the
        # warn-on-every-replay AVX-512 pseudo-features.  XLA parses
        # XLA_FLAGS when the first module's debug options are built, so
        # setting the env here — before any jit — is in time.
        quiet_cpu_aot_flags()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)


def setup_platform(cfg: TrainConfig) -> str:
    """Select the JAX platform before first backend use and return one
    line naming what the run got (platform, device kind, count) — the
    first line every run logs.  ``--device tpu`` RAISES when no TPU is
    attached; `auto` keeps whatever the environment provides and says
    which that was.  On cpu, a mesh larger than the physical device
    count gets virtual devices (the multi-chip simulation used by
    tests, SURVEY.md §4)."""
    import jax

    if cfg.device != "auto":
        want = "tpu" if cfg.device == "tpu" else "cpu"
        if want == "cpu":
            quiet_cpu_aot_flags()
        jax.config.update("jax_platforms", want)
        need = int(np.prod(cfg.mesh_shape)) if cfg.mesh_shape else 1
        if want == "cpu" and need > 1:
            try:
                jax.config.update("jax_num_cpu_devices", need)
            except Exception:
                pass  # backend already initialized; make_mesh will report

    # AFTER the platform override: the CPU ISA cap reads the configured
    # platform
    enable_compilation_cache()
    devs = jax.devices()   # raises when the requested platform is absent
    if cfg.device == "tpu" and devs[0].platform != "tpu":
        raise RuntimeError(
            f"--device tpu: no TPU attached (JAX offers "
            f"{devs[0].platform!r} devices); refusing to train on a "
            f"different platform under that flag")
    return (f"[platform] --device {cfg.device}: {devs[0].platform} "
            f"({devs[0].device_kind}) x{len(devs)}")


def load_dataset(cfg: TrainConfig, train: bool):
    """Returns a BatchLoader-compatible dataset for cfg.dataset.

    CIFAR-10 falls back to synthetic data when the archive is absent and
    cannot be downloaded (zero-egress environments) — the pipeline code
    paths are identical (data/synthetic.py)."""
    from faster_distributed_training_tpu.data import (load_cifar10,
                                                      synthetic_agnews,
                                                      synthetic_cifar)

    # difficulty overrides for the synthetic fallback (accuracy-evidence
    # convergence runs lower the signal so the curve has a real shape)
    synth_kw = {}
    if os.environ.get("FDT_SYNTH_SIGNAL"):
        synth_kw["signal"] = float(os.environ["FDT_SYNTH_SIGNAL"])
    if os.environ.get("FDT_SYNTH_NOISE"):
        synth_kw["noise_std"] = float(os.environ["FDT_SYNTH_NOISE"])

    if cfg.dataset == "stream":
        # sharded on-disk dataset (data/stream/): the text flavor IS the
        # reader (it speaks encode_batch, so host/resident paths serve
        # it too — the cross-path bitwise tests depend on that); the
        # image flavor returns the (image, label) mmap pair
        from faster_distributed_training_tpu.data.stream import (
            open_stream_split)
        if not cfg.stream_dir:
            raise ValueError("--dataset stream requires --stream_dir "
                             "(scripts/shard_dataset.py writes one)")
        return open_stream_split(cfg.stream_dir, train=train)
    if cfg.dataset == "cifar10":
        try:
            x, y = load_cifar10(cfg.data_dir, train=train)
        except Exception as e:  # download impossible / corrupt archive
            print(f"[data] CIFAR-10 unavailable ({e!r}); using synthetic")
            x, y = synthetic_cifar(n=50000 if train else 10000,
                                   seed=0 if train else 1, **synth_kw)
    elif cfg.dataset == "agnews":
        from faster_distributed_training_tpu.data.agnews import AGNewsDataset
        try:
            return AGNewsDataset(cfg.data_dir, train=train,
                                 buckets=cfg.seq_buckets)
        except Exception as e:
            print(f"[data] AG News unavailable ({e!r}); using synthetic")
            return synthetic_agnews(n=12000 if train else 2000,
                                    seed=0 if train else 1,
                                    max_len=cfg.seq_len)
    elif cfg.dataset == "synthetic":
        if cfg.model == "decoder":
            # packed rows drawn from the vocabulary the model's file holds
            from faster_distributed_training_tpu.data.synthetic import (
                synthetic_packed_lm)
            from faster_distributed_training_tpu.models.decoder import (
                load_sizes)
            vocab = load_sizes(cfg.decoder_config).vocab_size
            return synthetic_packed_lm(n=512 if train else 128,
                                       seed=0 if train else 1, vocab=vocab,
                                       seq_len=cfg.seq_len)
        if is_token_model(cfg):
            return synthetic_agnews(n=4096 if train else 1024,
                                    seed=0 if train else 1,
                                    max_len=cfg.seq_len)
        x, y = synthetic_cifar(n=4096 if train else 1024,
                               seed=0 if train else 1, **synth_kw)
    else:
        raise ValueError(f"unknown dataset {cfg.dataset!r}")
    return (x, y)


def apply_subset(ds, stride: int):
    """1/N-stride subset of either dataset kind — applied to BOTH splits,
    matching the reference tuning harness (tuning/resnet50_tuning.py:328,346
    subsets train and test alike)."""
    if stride <= 1:
        return ds
    if isinstance(ds, tuple):
        x, y = ds
        return (x[::stride], y[::stride])

    class _Subset:
        def __init__(self, base):
            self._base = base
            self._idx = np.arange(0, len(base), stride)

        def __len__(self):
            return len(self._idx)

        def num_classes(self):
            return self._base.num_classes()

        def vocab_size(self):
            return self._base.vocab_size()

        def encode_batch(self, indices, max_len=512):
            return self._base.encode_batch(self._idx[np.asarray(indices)],
                                           max_len)

    return _Subset(ds)


# The dense path's backward holds ~3 score-shaped fp32 tensors at peak
# (saved probs residual + ds/dp transients — same accounting as flash's
# _DENSE_BWD_BUDGET_BYTES; an r5 chip reading of +1.6 GB at
# bs256/seq256 ≈ 3 x 537 MB agreed, not measured since).  The routing
# budget below caps that footprint so auto-routing can never walk a
# big-batch config into HBM exhaustion: the materialized probs scale
# with B·L².  Override via FDT_DENSE_ATTN_BUDGET_MB (0 forces flash
# everywhere).
_DENSE_ATTN_BUDGET_MB = 4096

# Sequence length from which a tp model axis routes attention
# sequence-parallel instead of single-chip dense/flash; below it the
# axis serves tensor parallelism and the dense/flash rule applies
# (dense/flash are tp-compatible: dense head-shards, flash is rerouted
# by build_model's capability fallback).  This boundary and the
# dense/flash crossover in resolve_attention come from r5/r6 chip
# readings older than most of this code, not re-measured since;
# ROADMAP S8 decides them in the encoder's cells.
_SEQ_PARALLEL_MIN_LEN = 2048


def _dense_attn_fits(bs: int, seq: int, n_heads: int) -> bool:
    """Memory-headroom term of the routing surface: 3 score-shaped fp32
    tensors at the dense backward's peak must fit the routing budget."""
    mb = os.environ.get("FDT_DENSE_ATTN_BUDGET_MB")
    try:
        budget_mb = int(mb) if mb is not None else _DENSE_ATTN_BUDGET_MB
    except ValueError:
        import warnings
        warnings.warn(f"ignoring malformed FDT_DENSE_ATTN_BUDGET_MB={mb!r} "
                      f"(want an integer MB count); using the default "
                      f"{_DENSE_ATTN_BUDGET_MB}", stacklevel=2)
        budget_mb = _DENSE_ATTN_BUDGET_MB
    return 3 * 4 * bs * n_heads * seq * seq <= budget_mb << 20


def _route_model_axis(cfg: TrainConfig, ax_size: int) -> Optional[str]:
    """The sequence-parallel impl a model axis of `ax_size` can serve
    for this shape, or None when it can't: BOTH strategies shard the
    sequence over the axis (shard_map divisibility), so a seq_len the
    axis doesn't divide routes back to the single-chip surface instead
    of an impl that would fail at trace time.  Among the eligible:
    ulysses when the axis also divides the heads (lower interconnect
    volume — O(B·H·L·D/sp) per tensor, collective-free inner kernel;
    the documented trade in ops/ulysses_attention.py), ring otherwise
    (any head count).  The preference is not measured on the chip
    (ROADMAP S8)."""
    if cfg.seq_len % ax_size:
        return None
    return "ulysses" if cfg.n_heads % ax_size == 0 else "ring"


def resolve_attention(cfg: TrainConfig, mesh=None) -> str:
    """'' auto-resolves among {dense, flash, ring, ulysses}; an explicit
    --attention always wins.

    A causal LM routes dense (the only impl that takes a full
    query-by-key mask).  A sequence-capable model axis routes
    sequence-parallel (_route_model_axis: ulysses when the axis divides
    seq and heads, ring when it divides seq only) from
    _SEQ_PARALLEL_MIN_LEN up, or at any length on a dedicated sp axis;
    a seq_len the axis can't divide falls through to the single-chip
    rule.  Off TPU that rule is dense; on TPU dense while seq_len <= 256
    and three fp32 [B,H,L,L] score tensors fit the budget
    (_dense_attn_fits), else flash.

    The crossover comes from r5/r6 chip readings older than most of
    this code, not re-measured since; ROADMAP S8 decides it in the
    encoder's cells."""
    if cfg.attention:
        return cfg.attention
    if (getattr(cfg, "task", "cls") == "lm"
            and getattr(cfg, "lm_causal", False)):
        # --lm_causal (r22): the model combines a causal [1,1,L,L] (or
        # joint [B,1,L,L]) mask into attention at TRAINING time, and
        # dense is the only impl whose mask path takes a full
        # query-by-key mask — flash accepts key-padding masks only
        # (ops/flash_attention.py flash mask contract) and ring/ulysses
        # shard L.  Routed here so every auto-resolved causal config
        # lands on a mask-capable impl; an explicit --attention above
        # still wins and build_model's capability fallback reroutes it
        # with a warning.
        return "dense"
    from faster_distributed_training_tpu.parallel.mesh import (
        seq_parallel_axis)
    # route against the axis the model will EXECUTE over
    # (seq_parallel_axis prefers a dedicated sp axis over tp — the same
    # policy build_model hands the model as sp_axis), never against a
    # different axis than the one shard_map will shard L on
    ax, ax_size = seq_parallel_axis(mesh)
    if ax is not None and (ax == "sp"
                           or cfg.seq_len >= _SEQ_PARALLEL_MIN_LEN):
        impl = _route_model_axis(cfg, ax_size)
        if impl:
            return impl
        # seq doesn't divide the executing axis: fall through to the
        # single-chip surface rather than crash inside shard_map
    from faster_distributed_training_tpu.ops import pallas_target
    if not pallas_target.on_tpu():
        return "dense"
    return ("dense" if cfg.seq_len <= 256
            and _dense_attn_fits(cfg.batch_size, cfg.seq_len, cfg.n_heads)
            else "flash")


def build_model(cfg: TrainConfig, vocab_size: Optional[int] = None,
                mesh=None, serving: bool = False):
    """``serving=True`` builds the INFERENCE twin of the training model:
    byte-identical param tree (checkpoints interchange), but the r13
    quant scale state is FROZEN — QuantPolicy.frozen_scales makes every
    QuantDense quantize at the scales the restored amax history implies
    and never roll it, so serving is state-free and two identical
    requests return bitwise-identical logits (serve/engine.py)."""
    import jax.numpy as jnp

    from faster_distributed_training_tpu.models import get_model
    from faster_distributed_training_tpu.ops import pallas_target

    on_tpu = pallas_target.on_tpu()
    dtype = jnp.bfloat16 if cfg.precision == "bf16" else jnp.float32
    tricks_off = cfg.tricks == "off"
    if cfg.model == "transformer":
        from faster_distributed_training_tpu.parallel.mesh import (
            seq_parallel_axis, tp_size)
        impl = resolve_attention(cfg, mesh)
        tp = tp_size(mesh)
        sp_axis, sp_ax_size = seq_parallel_axis(mesh)
        causal = (getattr(cfg, "task", "cls") == "lm"
                  and getattr(cfg, "lm_causal", False))
        if causal and impl != "dense":
            # REGISTERED warned fallback: an explicit --attention that
            # can't take the full causal mask (flash = key-padding only;
            # ring/ulysses shard L) reroutes to dense — same policy as
            # the shard_map capability fallbacks below
            import warnings
            warnings.warn(
                f"--lm_causal needs a full [B,1,L,L] attention mask; "
                f"impl {impl!r} only takes key-padding masks — using "
                f"'dense' attention", stacklevel=2)
            impl = "dense"
        from faster_distributed_training_tpu.parallel import kernel_shard
        if impl == "flash" and tp > 1 \
                and not kernel_shard.flash_serviceable(mesh, cfg.n_heads):
            # REGISTERED warned fallback (scripts/check_kernel_routing):
            # the r19 shard_map layer runs the flash kernel per-shard on
            # each device's local heads, so a serviceable tp mesh (heads
            # divide tp, FDT_KERNEL_SHARD armed) keeps flash.  Only the
            # non-dividing / killed cases reroute to the shard_map
            # sequence-parallel strategies (explicit --attention flash
            # included) — validated against the axis the model will
            # execute over (sp_ax_size — seq_parallel_axis prefers sp)
            fallback = _route_model_axis(cfg, sp_ax_size) or "dense"
            import warnings
            warnings.warn(
                f"attention 'flash' cannot run head-sharded on this "
                f"{dict(mesh.shape)} mesh "
                + (f"(n_heads={cfg.n_heads} does not divide tp={tp})"
                   if kernel_shard.enabled() else
                   "(FDT_KERNEL_SHARD=0 disables the shard_map kernel "
                   "layer)")
                + f"; using '{fallback}' "
                + ("sequence-parallel attention over tp"
                   if fallback != "dense" else
                   "attention (seq_len doesn't divide the tp axis, so "
                   "the sequence-parallel strategies can't serve it "
                   "either)"), stacklevel=2)
            impl = fallback
        mlp_impl = cfg.mlp_impl or (
            "pallas" if on_tpu else "fused")
        if mlp_impl == "pallas" and not on_tpu:
            import warnings
            warnings.warn(
                "--mlp_impl pallas off-TPU runs the kernel in Pallas "
                "INTERPRET mode (orders of magnitude slower) — test-only; "
                "use --mlp_impl fused for real off-TPU runs", stacklevel=2)
        ffn_impl = cfg.ffn_impl
        if ffn_impl == "pallas":
            from faster_distributed_training_tpu.ops.fused_ffn import (
                ffn_kernel_fits_vmem)
            if not ffn_kernel_fits_vmem(cfg.d_model, cfg.d_ff,
                                        jnp.dtype(dtype).itemsize):
                # ADVICE r5 (low): a user-configured large --d_model/
                # --d_ff would die with an opaque Mosaic scoped-VMEM
                # compile error; mirror the tp-mesh fallback instead.
                import warnings
                warnings.warn(
                    f"--ffn_impl pallas: weights+hidden for d_model="
                    f"{cfg.d_model}, d_ff={cfg.d_ff} exceed the kernel's "
                    f"VMEM budget (ops/fused_ffn.py ffn_kernel_fits_vmem)"
                    f"; falling back to the flax FFN composition",
                    stacklevel=2)
                ffn_impl = "flax"
        if ffn_impl == "pallas":
            # sharded meshes run the kernel per-shard via shard_map over
            # the data axes (fused_ffn_sublayer_sharded); tp meshes run
            # the Megatron column-then-row decomposition through the r19
            # shard_map layer (kernel_shard.fused_ffn_sublayer_tp — the
            # tp weight shards are consumed in place, no per-step
            # gather).  The flax composition survives only as the
            # REGISTERED warned fallback: FDT_KERNEL_SHARD=0 or shapes
            # tp doesn't divide.
            if tp > 1 and not kernel_shard.ffn_tp_serviceable(
                    mesh, cfg.d_ff, cfg.seq_len):
                import warnings
                warnings.warn(
                    "--ffn_impl pallas cannot run the Megatron column/"
                    f"row-sharded kernel on this {dict(mesh.shape)} mesh "
                    + (f"(d_ff={cfg.d_ff} or seq_len={cfg.seq_len} does "
                       f"not divide the tp/sp axes)"
                       if kernel_shard.enabled() else
                       "(FDT_KERNEL_SHARD=0 disables the shard_map "
                       "kernel layer)")
                    + "; falling back to the flax FFN composition",
                    stacklevel=2)
                ffn_impl = "flax"
            elif not on_tpu:
                import warnings
                warnings.warn(
                    "--ffn_impl pallas off-TPU runs the kernel in Pallas "
                    "INTERPRET mode (orders of magnitude slower) — "
                    "test-only; use the default flax FFN for real "
                    "off-TPU runs", stacklevel=2)
        # --quant int8/fp8 (r13): the QuantPolicy handed to the model,
        # with the kernel routing decided HERE where the mesh/backend
        # are known (train.amp.resolve_quant_policy owns the cfg->fmt
        # mapping; ops/quant.py owns the math/kernels).
        quant = None
        from faster_distributed_training_tpu.train.amp import (
            resolve_quant_policy)
        policy = resolve_quant_policy(cfg)
        if policy is not None:
            import warnings

            from faster_distributed_training_tpu.ops.quant import (
                quant_enabled)
            if not quant_enabled():
                # the kill switch leaves the param/state TREE intact
                # (QuantDense computes the plain matmul) so a killed
                # run's checkpoints interchange with quantized ones
                warnings.warn(
                    f"--quant {cfg.quant} requested but FDT_QUANT=0 is "
                    f"set: every quantized site computes the plain "
                    f"full-precision matmul this run (scale state is "
                    f"still allocated, so checkpoints interchange)",
                    stacklevel=2)
            use_pallas = None
            if tp > 1:
                # r19: serviceable sites (their sharded kernel dim
                # divides tp) run the quant kernel PER SHARD on the
                # Megatron column/row tiles through the shard_map layer
                # (QuantDense mesh/tp_dim routing); anything else takes
                # the REGISTERED warned fallback — the XLA reference
                # path is a plain dot_general on int8/fp8 operands,
                # which partitions like any other dot, so quantization
                # itself stays on either way.
                div = (cfg.n_heads % tp == 0 and cfg.d_ff % tp == 0
                       and cfg.d_model % tp == 0)
                if not (kernel_shard.enabled() and div):
                    warnings.warn(
                        f"--quant {cfg.quant}: the quant matmul kernel "
                        f"cannot run column/row-sharded on this "
                        f"{dict(mesh.shape)} mesh "
                        + (f"(n_heads={cfg.n_heads}/d_ff={cfg.d_ff}/"
                           f"d_model={cfg.d_model} must all divide "
                           f"tp={tp})" if kernel_shard.enabled() else
                           "(FDT_KERNEL_SHARD=0 disables the shard_map "
                           "kernel layer)")
                        + "; using the XLA reference quantized GEMMs "
                        "(quantization stays on)", stacklevel=2)
                    use_pallas = False
            elif not on_tpu:
                # the designed off-TPU path (tests/CPU convergence
                # harness): reference GEMMs, same math, no interpret-
                # mode Pallas on the hot path
                use_pallas = False
            # --ffn_impl pallas composes with --quant since r19: the
            # generalized fused-FFN kernel runs its two GEMMs on the
            # quantized operands in-kernel (models/transformer.py)
            quant = policy._replace(use_pallas=use_pallas,
                                    frozen_scales=bool(serving))
        # the model sees the mesh whenever it has work to do with it:
        # sequence-parallel attention, the sharded fused-FFN kernel, a
        # model axis to annotate activations over (tp/sp activation
        # constraints, models/transformer.py), or — on any mesh of more
        # than one device — a Pallas kernel on the path: a Mosaic kernel
        # only partitions inside shard_map (parallel/kernel_shard.py),
        # the data axes included.  Kernel-free pure-dp meshes and every
        # one-device mesh pass None, so those programs stay
        # byte-identical.
        kernel_on_path = (impl == "flash" or mlp_impl == "pallas"
                          or (quant is not None
                              and quant.use_pallas is not False))
        model_mesh = (mesh if (impl in ("ring", "ulysses")
                               or ffn_impl == "pallas"
                               or tp > 1 or sp_ax_size > 1
                               or (kernel_on_path and mesh is not None
                                   and mesh.size > 1)) else None)
        return get_model("transformer", cfg.num_classes,
                         vocab=vocab_size or 30522, maxlen=cfg.seq_len,
                         n_layers=cfg.n_layers, d_model=cfg.d_model,
                         d_ff=cfg.d_ff, h=cfg.n_heads,
                         attention_impl=impl, mlp_impl=mlp_impl,
                         mesh=model_mesh,
                         sp_axis=sp_axis or "sp",
                         alpha=cfg.alpha if cfg.alpha > 0 else 0.99,
                         dtype=dtype, remat=cfg.remat,
                         remat_policy=cfg.remat_policy,
                         dropout_impl=cfg.dropout_impl, ffn_impl=ffn_impl,
                         fused_qkv=not tricks_off, quant=quant,
                         lm_head=getattr(cfg, "task", "cls") == "lm",
                         tie_lm_head=(getattr(cfg, "task", "cls") == "lm"
                                      and getattr(cfg, "tie_lm_head",
                                                  True)),
                         causal=causal)
    if cfg.model == "decoder":
        return _build_decoder(cfg, vocab_size, mesh, dtype)
    if (getattr(cfg, "quant", "none") or "none") != "none":
        import warnings
        warnings.warn(
            f"--quant {cfg.quant} is only wired for the transformer's "
            f"GEMMs (attention projections + FFN); {cfg.model} runs "
            f"full-precision", stacklevel=2)
    return get_model(cfg.model, cfg.num_classes, dtype=dtype,
                     remat=cfg.remat, conv_remat=not tricks_off)


def _build_decoder(cfg: TrainConfig, vocab_size: Optional[int], mesh,
                   dtype):
    """``--model decoder``: every size from the one file ``--decoder_config``
    names (models/decoder.py).  Attention is the causal-band kernel at
    every length (ops/flash_attention.banded_attention; off-TPU its XLA
    blockwise twin), so none of the encoder's routing applies.  One chip
    or a data axis of one: the kernels are not wrapped for a larger mesh
    yet (ROADMAP R3)."""
    from faster_distributed_training_tpu.models import get_model
    from faster_distributed_training_tpu.models.decoder import load_sizes
    if not cfg.decoder_config:
        raise ValueError("--model decoder needs --decoder_config <file> "
                         "(the model's sizes; models/decoder.py)")
    if mesh is not None and mesh.size > 1:
        raise ValueError(f"--model decoder runs on one device; mesh "
                         f"{dict(mesh.shape)} (its kernels are not "
                         f"wrapped for a mesh yet)")
    sizes = load_sizes(cfg.decoder_config)
    if vocab_size is not None and vocab_size > sizes.vocab_size:
        raise ValueError(f"the data's vocabulary ({vocab_size}) is larger "
                         f"than {cfg.decoder_config}'s vocab_size "
                         f"{sizes.vocab_size}")
    return get_model("decoder", 0, sizes=sizes, dtype=dtype,
                     remat=cfg.remat)


def make_loaders(cfg: TrainConfig, train_ds, eval_ds, dp: int = 1
                 ) -> Tuple[Callable, Callable, int]:
    """(train_loader(epoch), eval_loader(epoch), steps_per_epoch).

    cfg.batch_size is the GLOBAL batch: each host loads batch_size /
    process_count samples and make_array_from_process_local_data
    assembles the global array (DistributedSampler semantics,
    resnet50_test.py:331)."""
    import jax

    from faster_distributed_training_tpu.data import (BatchLoader,
                                                      PrefetchIterator)
    from faster_distributed_training_tpu.data.loader import (
        ParallelBatchIterator, dataset_len)

    pc = jax.process_count()
    if cfg.batch_size % pc:
        raise ValueError(f"global batch {cfg.batch_size} not divisible by "
                         f"{pc} processes")
    if dp > 1 and cfg.batch_size % dp:
        raise ValueError(f"global batch {cfg.batch_size} not divisible by "
                         f"the data-parallel world size {dp}")
    local_bs = cfg.batch_size // pc

    if cfg.debug:
        # multi-host data contract: local partition algebra + cross-host
        # agreement on the actual sharding inputs (collective)
        from faster_distributed_training_tpu.data import (
            verify_host_shards, verify_host_shards_global)
        n_train = dataset_len(train_ds)
        verify_host_shards(n_train, epoch=0, seed=cfg.seed)
        verify_host_shards_global(n_train, epoch=0, seed=cfg.seed)

    # --workers N > 1: a thread pool materializes batches concurrently
    # (tokenize/gather run in the GIL-releasing C++ core), the reference's
    # DataLoader worker model (resnet50_test.py:52,321-352); otherwise one
    # background prefetch thread.
    def _wrap(loader):
        if cfg.prefetch_depth <= 0:
            # genuinely synchronous iteration (the bag-of-tricks OFF arm):
            # no background thread at all — queue.Queue(maxsize=0) would
            # mean an UNBOUNDED prefetch queue, the opposite of the intent
            return loader
        if cfg.workers > 1:
            return ParallelBatchIterator(loader, cfg.workers,
                                         depth=max(cfg.prefetch_depth,
                                                   cfg.workers))
        return PrefetchIterator(loader, depth=cfg.prefetch_depth)

    def train_loader(epoch: int):
        return _wrap(
            BatchLoader(train_ds, local_bs, epoch=epoch, seed=cfg.seed,
                        shuffle=True, max_len=cfg.seq_len))

    # eval pads the final partial batch with valid=0 samples (BatchLoader
    # pad_last) so the whole split counts toward test accuracy at any
    # --bs — matching the reference's full-split eval
    # (resnet50_test.py:631-659); the padded batch keeps the train batch
    # shape, so dp-sharding constraints are unchanged and eval can never
    # be starved by a small (e.g. subset-strided) split
    def eval_loader(epoch: int):
        return _wrap(
            BatchLoader(eval_ds, local_bs, epoch=0, seed=cfg.seed,
                        shuffle=False, max_len=cfg.seq_len, pad_last=True))

    steps = len(BatchLoader(train_ds, local_bs))
    return train_loader, eval_loader, max(steps, 1)


def _warm_spare_park(trainer, state, res, train_loader, eval_loader,
                     telemetry, log) -> Optional[dict]:
    """Warm-spare pre-admission (r17): warm the steady-state programs
    through the observatory (and its executable cache, when armed) and
    park on the coordinator until a failed slice's seat is claimable.
    Each new COMMIT is restored once as a PROBE — proving the newest
    checkpoint restorable and keeping the storage medium warm before
    the swap depends on it — but the restored tree is deliberately NOT
    retained: holding a second full state resident would double the
    spare's HBM footprint for the whole park, and the post-claim
    attempt path re-restores through the slice-scoped barrier anyway
    (restore is the cheap half of MTTR; the programs are the warm
    part).  Returns the claim dict after ``Resilience.adopt_seat``
    re-keys the bundle (the caller then runs the normal supervised
    attempt path: the coordinator is already rejoining under the
    adopted identity), or None when the pod completed incident-free."""
    from faster_distributed_training_tpu.telemetry import spans

    coord = res.coordinator
    log(f"[spare] warm spare {coord.spare_index} pre-admitting: warming "
        f"programs + restoring to the last COMMIT")
    with spans.span("spare_warm"):
        warmed = trainer.warm_programs(state, train_loader, eval_loader)
    log(f"[spare] {warmed} program(s) warm; parking for incidents "
        f"(claim = first CLAIM marker writer wins)")
    if telemetry is not None:
        telemetry.recorder.record_event("spare", event="parked",
                                        spare=int(coord.spare_index))
    warm = {"step": -1}

    def refresh():
        if res.manager is None:
            return
        newest = res.manager.latest_valid()
        if newest is None or newest[0] <= warm["step"]:
            return
        got = res.manager.peek_latest(state)
        if got is not None:
            _st, meta = got      # restorability probe only — dropped
            warm["step"] = int(meta.get("step", newest[0]))
            log(f"[spare] COMMIT step {warm['step']} verified restorable")

    claim = coord.spare_wait(refresh_fn=refresh)
    if claim is None:
        if telemetry is not None:
            telemetry.recorder.record_event(
                "spare", event="stood_down", spare=int(coord.spare_index))
        return None
    res.adopt_seat(claim["seat"])
    if telemetry is not None:
        fields = {"event": "claimed", "spare": int(coord.spare_index),
                  "seat": int(claim["seat"]),
                  "generation": int(claim["generation"]),
                  "step": int(warm["step"])}
        fields["slice"] = int(claim["slice"])
        telemetry.recorder.record_event("spare", **fields)
    return claim


def run_training(cfg: TrainConfig,
                 log: Callable[[str], None] = print) -> dict:
    """Full training run; returns {'state','history','best_acc','cfg'}."""
    log(setup_platform(cfg))

    import jax
    import jax.numpy as jnp

    from faster_distributed_training_tpu.data.augment import augment_batch
    from faster_distributed_training_tpu.optim import build_optimizer
    from faster_distributed_training_tpu.parallel import (
        initialize_distributed, make_mesh)
    from faster_distributed_training_tpu.parallel.placement import (
        dp_size, make_put_batch, shard_train_state, train_state_shardings)
    from faster_distributed_training_tpu.train import (Trainer,
                                                       create_train_state,
                                                       init_attn_lambda,
                                                       init_meta_lambda)
    from faster_distributed_training_tpu.train.steps import resolve_mixup_mode
    from faster_distributed_training_tpu.utils.plotting import draw_graph
    from faster_distributed_training_tpu.utils.profiling import trace_profile

    if cfg.distributed:
        initialize_distributed()

    mesh = make_mesh(cfg.mesh_axes, cfg.mesh_shape)
    is_text = is_token_model(cfg)

    if cfg.data_path == "stream":
        if cfg.dataset != "stream":
            raise ValueError(
                f"--data_path stream reads the sharded on-disk format; "
                f"use --dataset stream --stream_dir <dir> (got dataset="
                f"{cfg.dataset!r}; scripts/shard_dataset.py shards a "
                f"corpus/split into that format)")
        if cfg.subset_stride > 1:
            raise ValueError("--subset_stride is not supported with "
                             "--data_path stream (the window refill "
                             "addresses the full on-disk index space); "
                             "shard a smaller dataset instead")
    if cfg.dataset == "stream":
        # chaos arm FDT_FAULT_CORRUPT_SHARD (resilience/faults.py): flip
        # bytes inside one committed shard file BEFORE the reader opens
        # its mmaps — the manifest sizes still match, so only the CRC
        # screen (data/stream/reader.py) can catch it, which is the point
        from faster_distributed_training_tpu.resilience.faults import (
            apply_corrupt_shard_fault)
        apply_corrupt_shard_fault(cfg.stream_dir, log=log)
    train_ds = apply_subset(load_dataset(cfg, train=True), cfg.subset_stride)
    eval_ds = apply_subset(load_dataset(cfg, train=False), cfg.subset_stride)
    if cfg.dataset == "stream" and is_text:
        if (train_ds.manifest.get("content") == "lm"
                and getattr(cfg, "task", "cls") != "lm"):
            # the packed LM rows carry NO labels — the reader fabricates
            # zero labels purely as shape placeholders, so a cls run
            # would "learn" constant class 0 to 100% accuracy silently
            raise ValueError(
                f"{cfg.stream_dir} is an LM-content corpus (packed token "
                f"rows, no labels) but --task is {cfg.task!r} — train it "
                f"with --task lm")
        # pre-tokenized packed rows have ONE width; the model's maxlen
        # and every bucket decision must agree with it
        sl = int(getattr(train_ds, "seq_len", 0) or 0)
        if sl and sl != cfg.seq_len:
            log(f"[data] stream dataset rows are seq_len={sl}; "
                f"overriding --seq_len {cfg.seq_len}")
            cfg = cfg.replace(seq_len=sl)
    vocab = train_ds.vocab_size() if is_text else None
    model = build_model(cfg, vocab_size=vocab, mesh=mesh)

    # pp>1 (r22): the third parallelism axis — encoder layers staged
    # over pp, microbatched 1F1B inside the K-dispatch scan.  Every
    # routing decision (stage assignment, microbatch count, collective
    # placement) is made HERE, once, in parallel/pipeline.py and dumped
    # as one rule table into manifest.json beside the compile table.
    # None on every pp=1 mesh — those programs stay byte-identical.
    from faster_distributed_training_tpu.parallel.pipeline import (
        build_pipeline_spec, pipeline_rules, stage_idle_ticks)
    pipeline = build_pipeline_spec(
        cfg, mesh,
        attention_impl=getattr(model, "attention_impl", None))
    if pipeline is not None:
        log(f"[pipeline] pp={pipeline.n_stages} stages x "
            f"{pipeline.n_microbatches} microbatches "
            f"({pipeline.schedule}): layers "
            f"{[list(s) for s in pipeline.stage_layers]}, "
            f"bubble {pipeline.bubble_pct:.1f}% "
            f"({pipeline.n_ticks} ticks/step; stage boundary = "
            f"collective-permute over pp, the DCN hop)")

    train_loader, eval_loader, steps_per_epoch = make_loaders(
        cfg, train_ds, eval_ds, dp=dp_size(mesh))

    # xN LR scaling: actual DP world size, not the reference's hard-coded
    # x4 (resnet50_test.py:482-483).
    tx, _ = build_optimizer(cfg, steps_per_epoch,
                            lr_scale=float(dp_size(mesh)))

    rng = jax.random.PRNGKey(cfg.seed)
    if is_text:
        sample = jnp.zeros((cfg.batch_size, cfg.seq_len), jnp.int32)
        extra = None
    else:
        sample = jnp.zeros((cfg.batch_size, 32, 32, 3), jnp.float32)
        # learnable-lambda modes own a trainable leaf beside the model:
        # meta = per-sample scalar, attn = per-pixel NHWC map
        # (resnet50_test.py:388-401, 404-424)
        mode = resolve_mixup_mode(cfg)
        if mode == "meta":
            extra = {"mixup_lambda": init_meta_lambda(rng, cfg.batch_size)}
        elif mode == "attn":
            extra = {"mixup_lambda": init_attn_lambda(rng, cfg.batch_size,
                                                      32, 32, 3)}
        else:
            extra = None
    state = create_train_state(model, tx, sample, rng,
                               init_kwargs={"train": True},
                               extra_params=extra)
    # the explicit sharding tree is needed beyond --host_offload on any
    # mesh with a model axis: the train step pins its OUTPUT state to it
    # (steps.make_train_step) so XLA's partitioner can neither drift the
    # updated tp-sharded params toward replication nor scatter
    # replicated params onto the sp axis between donated steps
    # (measured: an sp mesh without the pin re-sharded pos_embedding
    # over sp after step 1 and the donated recall mismatched)
    from faster_distributed_training_tpu.parallel.mesh import (pp_size,
                                                               sp_size,
                                                               tp_size)
    shardings = (train_state_shardings(state, mesh, cfg,
                                       pipeline=pipeline)
                 if cfg.host_offload or cfg.offload_opt_state
                 or cfg.overlap_grad_reduce or tp_size(mesh) > 1
                 or sp_size(mesh) > 1 or pp_size(mesh) > 1 else None)
    state = shard_train_state(state, mesh, cfg, shardings=shardings)

    # TRAIN augmentation lives inside the train step now (steps.py):
    # uint8 batches are crop/flip/normalized on device with the key
    # derived from the CHECKPOINTED step counter — fold_in(PRNGKey(seed+1),
    # state.step) — so a resumed run's augmentation stream is bitwise-
    # identical to an uninterrupted one (the r7 ROADMAP gap: the old
    # host-side aug_counter restarted at 0 on resume) and the K-step
    # fused dispatch advances it with zero host involvement.  Train
    # staging therefore uploads RAW uint8 (4x less H2D than the old
    # augment-at-put float32); eval still normalizes at staging (no RNG).
    aug_key = jax.random.PRNGKey(cfg.seed + 1)
    aug = jax.jit(augment_batch, static_argnames=("train",))

    def eval_augment(batch):
        if is_text or "image" not in batch:
            return batch
        return {**batch, "image": aug(aug_key, batch["image"], train=False)}

    put_train = make_put_batch(mesh)
    put_stacked = make_put_batch(mesh, stacked=True)
    put_eval = make_put_batch(mesh, eval_augment)

    # --data_path resident: the train split uploads once — replicated on
    # one host, per-host ROW-SHARDED on pods (each process's HBM holds
    # only its ~n/process_count shard; one jitted re-shard per epoch
    # builds the batch-major view the dispatch indexes locally)
    from faster_distributed_training_tpu.data.device_resident import (
        build_device_resident)
    # --data_path stream (r18): the split stays on disk; a fixed device
    # window (2 buffers x stream_window batches) is refilled by a
    # background double-buffered H2D thread (data/stream/window.py)
    from faster_distributed_training_tpu.data.stream import build_stream
    # the text flavor's train_ds IS the open reader — reuse its mmaps
    stream = build_stream(cfg, mesh=mesh, dataset=train_ds)
    if stream is not None:
        log(f"[data] streaming train split from disk: {stream.n} samples "
            f"({stream.dataset.nbytes_on_disk / 1e6:.0f} MB on disk, "
            f"{len(stream.dataset.manifest['shards'])} shard(s)), device "
            f"window 2x{stream.window} batches "
            f"(peak ~{stream.nbytes / 1e6:.1f} MB/host), "
            f"{stream.steps_per_epoch} steps/epoch"
            + (f", seq_len={stream.seq_len}" if stream.is_text else ""))
    resident = build_device_resident(cfg, train_ds, mesh=mesh)
    if resident is not None:
        layout = ("sharded" if getattr(resident, "batch_major", False)
                  else "replicated")
        log(f"[data] device-resident train split ({layout}): "
            f"{resident.n} samples, {resident.nbytes / 1e6:.0f} MB "
            f"{'per-host shard' if layout == 'sharded' else 'in HBM'}, "
            f"{resident.steps_per_epoch} steps/epoch"
            + (f", seq_len={resident.seq_len}" if resident.is_text else ""))

    from faster_distributed_training_tpu.resilience import (Preempted,
                                                            Supervisor,
                                                            build_resilience)
    from faster_distributed_training_tpu.train.metrics import attach_goodput

    if cfg.supervise and not (cfg.checkpoint_every
                              or cfg.checkpoint_every_secs):
        # a supervisor without restore points can only replay from scratch;
        # default to one step-cadence save per epoch
        cfg = cfg.replace(checkpoint_every=steps_per_epoch)
        log(f"[resilience] --supervise without a checkpoint cadence: "
            f"defaulting --checkpoint_every to {steps_per_epoch} "
            f"(one save per epoch)")
    # K-step fused dispatch: the checkpoint/preemption cadence only
    # polls at dispatch boundaries, so the save cadence must quantize to
    # a multiple of K (rounded UP — never save more often than asked)
    k = max(int(cfg.steps_per_dispatch or 1), 1)
    if k > 1 and cfg.checkpoint_every and cfg.checkpoint_every % k:
        rounded = -(-cfg.checkpoint_every // k) * k
        import warnings
        warnings.warn(
            f"--checkpoint_every {cfg.checkpoint_every} is not a multiple "
            f"of --steps_per_dispatch {k}; rounding up to {rounded} "
            f"(checkpoints land on dispatch boundaries)", stacklevel=2)
        log(f"[ckpt] checkpoint_every rounded {cfg.checkpoint_every} -> "
            f"{rounded} (multiple of steps_per_dispatch={k})")
        cfg = cfg.replace(checkpoint_every=rounded)
    res = build_resilience(cfg, log=log)
    # stream-shard CRC quarantine events land in the sentinel's durable
    # ledger + goodput counters (goodput-only when the sentinel is off —
    # the reader warns + remaps regardless, see data/stream/reader.py)
    if res is not None:
        reader = (stream.dataset if stream is not None
                  else train_ds if hasattr(train_ds, "on_quarantine")
                  else None)
        if reader is not None:
            if res.sentinel is not None:
                reader.on_quarantine = res.sentinel.quarantine_shard
            else:
                reader.on_quarantine = (
                    lambda s, p: res.goodput.count("quarantined_shards"))
    if (resident is not None
            and getattr(resident, "upload_checksums", None)
            and getattr(cfg, "sentinel", "none") == "full"):
        # end-to-end upload integrity (--sentinel full): re-read the
        # device-resident split and compare against the host-side
        # checksums taken at encode time — once, before training, off
        # the hot path (raises on mismatch; a corrupt upload must not
        # train silently)
        resident.verify_upload()
        log("[sentinel] device-resident upload verified: post-upload "
            "readback matches the host-side encode checksums")
    # -- telemetry (r12): every run emits a structured record of itself
    # — per-dispatch JSONL + manifest + span breakdown + (pods) the
    # epoch straggler fold.  build_telemetry returns None under
    # --no_telemetry and the hot loop gets zero new work.
    from faster_distributed_training_tpu.telemetry import (
        build_telemetry, flight, programs, resolve_telemetry_dir, spans,
        write_manifest)
    from faster_distributed_training_tpu.utils.profiling import (
        StepWindowProfiler, parse_profile_steps)

    ckpt_name = (cfg.model if is_text else "resnet")
    telemetry = build_telemetry(cfg, log=log)
    prev_span_recorder = None
    prev_observatory = None
    prev_flight = None
    if telemetry is not None:
        prev_span_recorder = spans.set_recorder(telemetry.recorder)
        # the compile observatory doubles as a process-global (the span
        # idiom) so seams outside the Trainer — the device-resident
        # epoch re-shard — observe their compiles through it too
        prev_observatory = programs.set_observatory(telemetry.observatory)
        # crash flight recorder: failure seams (supervisor, watchdog,
        # the unhandled-exception escape below) dump the in-memory ring
        # + open spans + program table durably — through the r14
        # storage backend when resilience has one, so a dead slice
        # leaves forensics where the pod can read them
        prev_flight = flight.configure(
            telemetry.directory,
            backend=res.backend if res is not None else None,
            goodput=res.goodput if res is not None else None, log=log)
        if telemetry.pi == 0:
            write_manifest(telemetry.directory, cfg, mesh,
                           extra={"steps_per_epoch": steps_per_epoch,
                                  "workload": ckpt_name,
                                  # the pp routing/stage rule table —
                                  # one inspectable record of every
                                  # pipeline decision, beside the
                                  # compile table telemetry.close merges
                                  "pipeline": pipeline_rules(pipeline,
                                                             cfg)})
        if pipeline is not None:
            # schedule accounting into the telemetry stream: the
            # analytic bubble (the executed program pays exactly this —
            # fill/drain ticks compute on discarded microbatches) and the
            # per-stage idle/active tick split (idle ms = idle ticks x
            # a measured tick time; not measured on the chip)
            telemetry.recorder.record_event(
                "pp_bubble", n_stages=pipeline.n_stages,
                n_microbatches=pipeline.n_microbatches,
                n_ticks=pipeline.n_ticks, schedule=pipeline.schedule,
                bubble_pct=round(pipeline.bubble_pct, 3))
            for s, idle in enumerate(stage_idle_ticks(pipeline)):
                telemetry.recorder.record_event(
                    "pp_stage", stage=s,
                    layers=[f"layer_{i}"
                            for i in pipeline.stage_layers[s]],
                    idle_ticks=idle,
                    # slot-tick units, matching idle_ticks: M per slot
                    # x V/S slots per stage (== M for 1f1b)
                    active_ticks=pipeline.n_microbatches
                    * (pipeline.n_virtual // pipeline.n_stages))
        if res is not None:
            # restart/preemption/peer-failure counters land in the
            # stream as they happen (goodput.set_event_sink)
            res.goodput.set_event_sink(telemetry.recorder
                                       .goodput_event_sink)
        log(f"[telemetry] recording to {telemetry.directory} "
            f"(host {telemetry.pi}/{telemetry.pc}; disable with "
            f"--no_telemetry)")
    if telemetry is not None and telemetry.observatory is not None:
        # r17 instant restart: the persistent executable cache rides the
        # compile observatory (lookup-before-compile / store-after-
        # compile — a restarted process deserializes its programs,
        # cache_source=deserialized in the manifest compile table), and
        # the observatory feeds program-acquisition seconds to goodput
        # so restart MTTR splits into compile vs restore components
        from faster_distributed_training_tpu.resilience.executable_cache \
            import build_executable_cache
        telemetry.observatory.executable_cache = build_executable_cache(
            cfg, backend=res.backend if res is not None else None,
            mesh=mesh, log=log)
        if res is not None:
            telemetry.observatory.goodput = res.goodput
    profiler = None
    window = parse_profile_steps(cfg.profile_steps)
    if window is not None:
        trace_dir = os.path.join(resolve_telemetry_dir(cfg),
                                 f"trace_steps_{window[0]}_{window[1]}")
        profiler = StepWindowProfiler(trace_dir, *window, log=log)
        log(f"[profile] windowed capture armed: global steps "
            f"{window[0]}..{window[1]} -> {trace_dir}")

    preempted = False
    with mesh:
        trainer = Trainer(cfg, put_batch=put_train,
                          put_eval_batch=put_eval, log=log,
                          state_shardings=shardings, resilience=res,
                          put_stacked=put_stacked, resident=resident,
                          telemetry=telemetry, profiler=profiler,
                          stream=stream, pipeline=pipeline)

        # restored states (host numpy) must land back on the run's
        # sharding policy — placement.place_on_shardings, shared with
        # the loop's auto-recover rollback
        from faster_distributed_training_tpu.parallel.placement import (
            place_on_shardings)

        state, start_epoch = trainer.maybe_resume(state, ckpt_name)
        state = place_on_shardings(state, shardings)

        def attempt(restart_index: int):
            """One training attempt: resume from the newest VALID
            step-cadence checkpoint when one exists (crash recovery AND
            process-restart recovery share this path), else from the
            epoch-checkpoint/fresh state.

            Deliberately NOT gated on --resume: after a preemption the
            platform re-runs the same command, and that re-launch must
            pick up the emergency checkpoint unaided (the standard
            production-manager semantic).  Corollary, documented in the
            README: a checkpoint_dir with step checkpoints in it always
            resumes — re-running a COMPLETED run's command is an
            (intentional) idempotent no-op; point --checkpoint_dir at a
            fresh directory for a fresh run."""
            st, ep, sie, restored_step = state, start_epoch, 0, 0
            rejoining = (res is not None and res.coordinator is not None
                         and res.coordinator.rejoining)
            if res is not None and res.manager is not None:
                prev_step = trainer.global_step
                got = res.manager.restore_latest(st)
                if got is not None:
                    st, meta = got
                    st = place_on_shardings(st, shardings)
                    ep = int(meta.get("epoch", 0))
                    sie = int(meta.get("step_in_epoch", 0))
                    trainer.best_acc = float(meta.get("best_acc",
                                                      trainer.best_acc))
                    restored_step = step = int(meta.get("step", 0))
                    log(f"[resume] restored step-cadence checkpoint: "
                        f"step {step} (epoch {ep}, batch {sie})")
                    if restart_index > 0 and prev_step > step:
                        # rollback badput: steps re-run because the newest
                        # checkpoint predates the crash, costed at the
                        # run's observed productive step time
                        s = res.goodput.summary()
                        if s["steps"]:
                            res.goodput.add(
                                "rollback_lost_s",
                                (prev_step - step)
                                * s["productive_s"] / s["steps"])
                elif cfg.supervise and restart_index == 0 and not rejoining:
                    # seed a step-0 restore point so a crash before the
                    # first cadence save is still recoverable (the donated
                    # live state can't serve as one).  Never while
                    # REJOINING: the parked survivors are not taking this
                    # tick, so its commit barrier could only time out.
                    res.manager.save(st, 0, epoch=ep, step_in_epoch=0,
                                     best_acc=trainer.best_acc)
            if rejoining:
                # rejoining slice (r14): agree the catch-up target with
                # the parked survivors now — when the restored step
                # already IS the target, the readiness handshake
                # completes here, before the dispatch loop re-enters
                res.coordinator.rejoin_sync(restored_step)
            return trainer.fit(st, train_loader, eval_loader,
                               ckpt_name=ckpt_name, start_epoch=ep,
                               start_step_in_epoch=sie)

        with trace_profile("./profile" if cfg.profile else None):
            try:
                spare_stood_down = False
                if (res is not None and res.coordinator is not None
                        and res.coordinator.spare_index is not None):
                    # r17 warm spare: pre-admit (programs warmed through
                    # the executable cache, params restored to the last
                    # COMMIT + refreshed) and park until a failed seat
                    # is claimable; on a claim the coordinator is
                    # already in rejoin mode under the adopted identity
                    # and the NORMAL supervised attempt path below runs
                    # the swap (restore through the slice barrier, catch
                    # up, RJREADY, release, then train to completion)
                    claim = _warm_spare_park(trainer, state, res,
                                             train_loader, eval_loader,
                                             telemetry, log)
                    spare_stood_down = claim is None
                if spare_stood_down:
                    log("[spare] pod completed without an incident; "
                        "spare stands down (state untouched)")
                elif res is not None and cfg.supervise:
                    # coordinator (pods / --step_timeout_s): every attempt
                    # enters the shared-fs generation rendezvous and every
                    # failure is published as a FAIL marker BEFORE the
                    # backoff, so all hosts of the pod restart together
                    # (resilience/coordinator.py)
                    sup = Supervisor(max_restarts=cfg.max_restarts,
                                     goodput=res.goodput, log=log,
                                     coordinator=res.coordinator)
                    state = sup.run(attempt,
                                    progress=lambda: trainer.global_step)
                else:
                    state = attempt(0)
            except Preempted as p:
                preempted = True
                if p.state is not None:
                    state = p.state
                log(f"[preempt] training stopped cleanly at step {p.step}; "
                    f"re-launch with the same --checkpoint_dir to resume")
            except BaseException as e:
                # the run is dying for good (supervisor budget exhausted,
                # deterministic crash, an unsupervised fault): leave the
                # flight dump behind before the exception escapes.  The
                # dump is per-exception-deduplicated, so an incident the
                # supervisor already dumped doesn't land twice.
                flight.emergency_dump("unhandled_exception", exc=e,
                                      step=trainer.global_step)
                raise
            finally:
                # even when training dies for good (supervisor budget
                # exhausted, deterministic crash re-raise): drain the
                # in-flight async save and give the SIGTERM/SIGINT
                # handlers back — a long-lived caller must not inherit a
                # swallowed Ctrl-C or a thread still writing checkpoints
                if res is not None:
                    res.close()
                if profiler is not None:
                    profiler.close()   # an open window is still captured
                if telemetry is not None:
                    # flush the tail, refresh pod_summary.json, merge the
                    # program table into the manifest, and give the
                    # process-global sinks back (a crashed run's
                    # telemetry is exactly the telemetry worth keeping)
                    telemetry.close()
                    spans.set_recorder(prev_span_recorder)
                    programs.set_observatory(prev_observatory)
                    flight.restore(prev_flight)

    if cfg.plot and jax.process_index() == 0 and trainer.history["test_acc"]:
        prefix = ckpt_name
        draw_graph(trainer.history["test_acc"], "test accuracy",
                   f"{prefix} test accuracy", f"{prefix}_accuracy.png")
        draw_graph(trainer.history["epoch_time"], "seconds",
                   f"{prefix} epoch time", f"{prefix}_time.png")
    out = {"state": state, "history": trainer.history,
           "best_acc": trainer.best_acc, "cfg": cfg}
    if stream is not None and trainer.stream_stall_pct is not None:
        # the streamed input path's headline: steady-state % of step
        # time blocked on the window refill (<1% target; not measured
        # on the chip: no cell streams, PERF.md 7 row 2)
        out["stream_stall_pct"] = round(trainer.stream_stall_pct, 3)
        log(f"[stream] steady-state stall: {out['stream_stall_pct']}% of "
            f"step time blocked on the data window (target <1%)")
    if telemetry is not None:
        out["telemetry_dir"] = telemetry.directory
    if res is not None:
        out["preempted"] = preempted
        attach_goodput(out, res.goodput)
    return out


def synth_requests(n: int, vocab: int, buckets, seed: int = 0,
                   min_len: int = 4):
    """Ragged synthetic serving request mix: ``n`` token arrays with
    lengths uniform over [min_len, max bucket] — every configured
    bucket gets traffic and partial batches occur naturally.  The
    CLI serve smoke's built-in load; scripts/serve_smoke.py builds a
    nastier mix (spill lengths, over-long truncation) on top."""
    rng = np.random.default_rng(seed)
    top = max(buckets)
    out = []
    for _ in range(int(n)):
        length = int(rng.integers(min_len, top + 1))
        out.append(rng.integers(1, max(int(vocab), 2),
                                size=length).astype(np.int32))
    return out


def run_serving(cfg: TrainConfig, requests=None,
                log: Callable[[str], None] = print) -> dict:
    """The serving entrypoint (the ROADMAP's "millions of users" half):
    load the trained artifact from ``cfg.checkpoint_dir`` through the
    configured StorageBackend, stand up the serve/ stack — AOT-warmed
    per-bucket predict programs, continuous-batching queue, N replicas
    with heartbeat liveness — push ``requests`` (ragged int32 token
    arrays; a synthetic mix of ``cfg.serve_requests`` when None)
    through it, and return results + latency/throughput summary.

    Replica layout (SNIPPETS [3] — 1D partitioning "is essentially
    always faster for inference/decoding"): REPLICATED-per-chip, one
    replica per local device, unless the mesh names a model axis —
    models that needed tp/sp to train don't fit one chip, so that case
    serves ONE model-sharded replica group over the mesh."""
    log(setup_platform(cfg))

    import jax

    from faster_distributed_training_tpu.parallel import make_mesh
    from faster_distributed_training_tpu.parallel.mesh import (sp_size,
                                                               tp_size)
    from faster_distributed_training_tpu.serve import (BatchScheduler,
                                                       InferenceEngine,
                                                       Replica, ReplicaSet,
                                                       RequestQueue,
                                                       load_serving_state)
    from faster_distributed_training_tpu.telemetry import (
        TelemetryRecorder, resolve_telemetry_dir, spans, update_manifest)

    mesh = make_mesh(cfg.mesh_axes, cfg.mesh_shape)
    sharded = tp_size(mesh) > 1 or sp_size(mesh) > 1
    recorder = None
    prev_rec = None
    obs = None
    prev_obs = None
    if cfg.telemetry:
        import dataclasses
        import time as time_mod

        tdir = resolve_telemetry_dir(cfg)
        recorder = TelemetryRecorder(tdir, log=log)
        # MERGE a serve section into the manifest — the documented flow
        # serves from the TRAINING checkpoint dir, whose manifest.json
        # carries the r15 compile/program table; write_manifest would
        # atomically replace it and wipe that evidence
        update_manifest(tdir, {"serve": {
            "unix_time": round(time_mod.time(), 3),
            "config": dataclasses.asdict(cfg)}})
        prev_rec = spans.set_recorder(recorder)
        # r17: serving gets its own compile observatory (run_training's
        # never existed in this process), so the engines' AOT warmups
        # observe through it — and through the persistent executable
        # cache when armed, a restarted serving replica deserializes
        # its serve:predict:L<bucket> programs instead of recompiling
        from faster_distributed_training_tpu.telemetry import (
            ProgramObservatory, programs)
        if programs.observatory_enabled():
            obs = ProgramObservatory(recorder=recorder, log=log)
            from faster_distributed_training_tpu.resilience \
                .executable_cache import build_executable_cache
            from faster_distributed_training_tpu.resilience.storage \
                import build_backend
            # the cache rides the SAME configured backend serving's
            # checkpoint loads do — a posix default here would strand
            # the entries on the local disk while the deployment's
            # durable medium (the one a replica restarted on another
            # machine can reach) is an object store
            obs.executable_cache = build_executable_cache(
                cfg,
                backend=build_backend(
                    getattr(cfg, "storage_backend", "posix"),
                    cfg.checkpoint_dir, log=log),
                mesh=mesh if sharded else None, log=log)
            prev_obs = programs.set_observatory(obs)
        log(f"[serve] telemetry recording to {tdir}")
    try:
        model, sstate, meta = load_serving_state(
            cfg, mesh=mesh if sharded else None, log=log)
        # the queue owns the eligible-bucket set (data.loader
        # .eligible_buckets — one rule); the engines warm exactly it
        q = RequestQueue(cfg.seq_buckets, max_len=cfg.seq_len)
        buckets = q.buckets
        if sharded:
            log(f"[serve] mesh {dict(mesh.shape)} has a model axis: the "
                f"model did not fit one chip — serving ONE model-sharded "
                f"replica group (SNIPPETS [3]: replicate per chip "
                f"whenever it fits; it doesn't here)")
            engines = [InferenceEngine(model.apply, sstate,
                                       cfg.serve_batch_size, buckets,
                                       mesh=mesh, name="replica0",
                                       log=log)]
            chips_serving = mesh.size
        else:
            devs = jax.local_devices()
            n_rep = int(cfg.serve_replicas) or len(devs)
            engines = [InferenceEngine(model.apply, sstate,
                                       cfg.serve_batch_size, buckets,
                                       device=devs[i % len(devs)],
                                       name=f"replica{i}", log=log)
                       for i in range(n_rep)]
            # replicas round-robin over local devices; fewer replicas
            # than chips occupy only min(n, devices) of them — the
            # per-chip headline divides by chips actually SERVING, not
            # the host's total (a 2-replica run on an 8-chip host
            # would otherwise understate qps/chip 4x)
            chips_serving = min(n_rep, len(devs))
        with spans.span("serve_warmup"):
            warm_s = sum(e.warmup() for e in engines)
        log(f"[serve] {len(engines)} replica(s) x {len(buckets)} bucket "
            f"programs AOT-warmed in {warm_s:.1f}s "
            f"(buckets {list(buckets)}, batch {cfg.serve_batch_size})")
        replicas = [Replica(e.name, e, log=log) for e in engines]
        rset = ReplicaSet(
            replicas, heartbeat_timeout_s=cfg.serve_heartbeat_timeout_s,
            readmit_after_s=cfg.serve_readmit_s, log=log)
        sched = BatchScheduler(q, rset, batch_size=cfg.serve_batch_size,
                               max_delay_ms=cfg.serve_max_delay_ms,
                               recorder=recorder, log=log)
        sched.start()
        try:
            if requests is None:
                requests = synth_requests(cfg.serve_requests,
                                          meta.get("vocab") or 30522,
                                          buckets, seed=cfg.seed)
            handles = [q.submit(t) for t in requests]
            results = [h.wait(timeout=300.0) for h in handles]
        finally:
            sched.close()
        summary = sched.summary()
        out = {"results": results, "meta": meta, "cfg": cfg,
               "state": sstate, "replicas": rset.stats(), **summary,
               "chips_serving": chips_serving,
               "qps_per_chip": round(summary["qps"]
                                     / max(chips_serving, 1), 2)}
        log(f"[serve] served {summary['requests']} requests in "
            f"{summary['batches']} batches ({summary['padded_rows']} pad "
            f"rows): p50 {summary['p50_ms']} ms, p99 {summary['p99_ms']} "
            f"ms, {summary['qps']} qps ({out['qps_per_chip']}/chip)")
        return out
    finally:
        if recorder is not None:
            if obs is not None:
                from faster_distributed_training_tpu.telemetry import (
                    programs, update_manifest as _upd)
                programs.set_observatory(prev_obs)
                # the serve compile story under its OWN manifest key —
                # merging into "compile" would clobber the training
                # run's program table (the r16 lesson, kept)
                _upd(recorder.directory,
                     {"serve_compile": obs.summary()})
            spans.set_recorder(prev_rec)
            recorder.close()


def run_decode_serving(cfg: TrainConfig, prompts=None,
                       log: Callable[[str], None] = print) -> dict:
    """The AUTOREGRESSIVE serving entrypoint (ROADMAP item #1's online
    half): load the trained LM artifact from ``cfg.checkpoint_dir``,
    stand up the serve/decode stack — paged KV cache, AOT prefill +
    decode-step program families, token-granular continuous batching —
    push ``prompts`` (ragged int32 token arrays; a synthetic mix of
    ``cfg.decode_requests`` when None) through it with a
    ``cfg.decode_max_new_tokens`` budget each, and return the generated
    token arrays + TTFT/throughput summary.

    Replica layout is run_serving's SNIPPETS [3] decision verbatim:
    REPLICATED per chip (one DecodeEngine + DecodeScheduler per local
    device, all draining ONE queue) unless the mesh names a model axis,
    in which case ONE model-sharded replica serves over the mesh.  The
    multi-PROCESS front door (serve/decode/frontend.FrontDoor) stacks
    on top of this entrypoint — each worker process runs exactly this
    single-replica wiring."""
    log(setup_platform(cfg))

    import jax

    from faster_distributed_training_tpu.models.decode import SamplingCfg
    from faster_distributed_training_tpu.parallel import make_mesh
    from faster_distributed_training_tpu.parallel.mesh import (sp_size,
                                                               tp_size)
    from faster_distributed_training_tpu.serve import (RequestQueue,
                                                       load_serving_state)
    from faster_distributed_training_tpu.serve.decode import (
        DecodeEngine, DecodeScheduler)
    from faster_distributed_training_tpu.telemetry import (
        TelemetryRecorder, resolve_telemetry_dir, spans, update_manifest)
    from faster_distributed_training_tpu.train.metrics import percentiles

    mesh = make_mesh(cfg.mesh_axes, cfg.mesh_shape)
    sharded = tp_size(mesh) > 1 or sp_size(mesh) > 1
    recorder = None
    prev_rec = None
    obs = None
    prev_obs = None
    if cfg.telemetry:
        import dataclasses
        import time as time_mod

        tdir = resolve_telemetry_dir(cfg)
        recorder = TelemetryRecorder(tdir, log=log)
        # MERGE (never write_manifest): the training checkpoint dir's
        # manifest carries the r15 program table this run must not wipe
        update_manifest(tdir, {"decode_serve": {
            "unix_time": round(time_mod.time(), 3),
            "config": dataclasses.asdict(cfg)}})
        prev_rec = spans.set_recorder(recorder)
        from faster_distributed_training_tpu.telemetry import (
            ProgramObservatory, programs)
        if programs.observatory_enabled():
            obs = ProgramObservatory(recorder=recorder, log=log)
            from faster_distributed_training_tpu.resilience \
                .executable_cache import build_executable_cache
            from faster_distributed_training_tpu.resilience.storage \
                import build_backend
            # same durable backend as the checkpoint loads — a restarted
            # decode replica on another machine must reach the cached
            # executables too
            obs.executable_cache = build_executable_cache(
                cfg,
                backend=build_backend(
                    getattr(cfg, "storage_backend", "posix"),
                    cfg.checkpoint_dir, log=log),
                mesh=mesh if sharded else None, log=log)
            prev_obs = programs.set_observatory(obs)
        log(f"[decode] telemetry recording to {tdir}")
    try:
        model, sstate, meta = load_serving_state(
            cfg, mesh=mesh if sharded else None, log=log)
        q = RequestQueue(cfg.seq_buckets, max_len=cfg.seq_len)
        buckets = q.buckets
        sampling = SamplingCfg(method=cfg.decode_sample,
                               temperature=cfg.decode_temperature,
                               top_k=cfg.decode_top_k, seed=cfg.seed)
        if sharded:
            log(f"[decode] mesh {dict(mesh.shape)} has a model axis: the "
                f"model did not fit one chip — serving ONE model-sharded "
                f"decode replica (SNIPPETS [3]: replicate per chip "
                f"whenever it fits; it doesn't here)")
            engines = [DecodeEngine(model, sstate, buckets,
                                    batch_size=cfg.decode_batch_size,
                                    page=cfg.decode_page,
                                    max_pages=cfg.decode_max_pages,
                                    sampling=sampling, mesh=mesh,
                                    name="decode0", log=log)]
            chips_serving = mesh.size
        else:
            devs = jax.local_devices()
            n_rep = int(cfg.decode_replicas) or len(devs)
            engines = [DecodeEngine(model, sstate, buckets,
                                    batch_size=cfg.decode_batch_size,
                                    page=cfg.decode_page,
                                    max_pages=cfg.decode_max_pages,
                                    sampling=sampling,
                                    device=devs[i % len(devs)],
                                    name=f"decode{i}", log=log)
                       for i in range(n_rep)]
            chips_serving = min(n_rep, len(devs))
        with spans.span("decode_warmup"):
            warm_s = sum(e.warmup() for e in engines)
        log(f"[decode] {len(engines)} replica(s) x ({len(buckets)} "
            f"prefill + {engines[0].max_pages} decode-step) programs "
            f"AOT-warmed in {warm_s:.1f}s (buckets {list(buckets)}, "
            f"page {cfg.decode_page}, {cfg.decode_batch_size} slots)")
        scheds = [DecodeScheduler(q, e,
                                  max_delay_ms=cfg.serve_max_delay_ms,
                                  max_new_tokens=cfg.decode_max_new_tokens,
                                  recorder=recorder, name=e.name, log=log)
                  for e in engines]
        for s in scheds:
            s.start()
        try:
            if prompts is None:
                prompts = synth_requests(cfg.decode_requests,
                                         meta.get("vocab") or 30522,
                                         buckets, seed=cfg.seed)
            handles = [q.submit(t,
                                max_new_tokens=cfg.decode_max_new_tokens)
                       for t in prompts]
            results = [h.wait(timeout=300.0) for h in handles]
        finally:
            q.close()
            for s in scheds:
                s.close()
        # aggregate across schedulers: one summary over the union of
        # their per-request samples (percentiles are over the combined
        # population, not an average of per-replica percentiles)
        ttft, total = [], []
        n_req = toks = steps = prefills = 0
        t_first, t_last = None, None
        for s in scheds:
            ttft += [t for t in s.ttft_ms if t is not None]
            total += [t for t in s.total_ms if t is not None]
            n_req += s.completed_requests
            toks += s.generated_tokens
            steps += s.engine.steps
            prefills += s.engine.prefills
            if s._t_first is not None:
                t_first = s._t_first if t_first is None \
                    else min(t_first, s._t_first)
            if s._t_last is not None:
                t_last = s._t_last if t_last is None \
                    else max(t_last, s._t_last)
        wall = ((t_last - t_first)
                if (t_first is not None and t_last is not None
                    and t_last > t_first) else 0.0)
        pt = percentiles(ttft, qs=(50, 99))
        pl = percentiles(total, qs=(50, 99))
        tps = round(toks / wall, 2) if wall else 0.0
        out = {"results": results, "meta": meta, "cfg": cfg,
               "state": sstate,
               "requests": n_req, "tokens": toks, "steps": steps,
               "prefills": prefills,
               "ttft_p50_ms": pt.get(50, 0.0),
               "ttft_p99_ms": pt.get(99, 0.0),
               "latency_p50_ms": pl.get(50, 0.0),
               "latency_p99_ms": pl.get(99, 0.0),
               "tokens_per_sec": tps,
               "chips_serving": chips_serving,
               "tokens_per_sec_per_chip": round(
                   tps / max(chips_serving, 1), 2)}
        log(f"[decode] generated {toks} tokens for {n_req} requests in "
            f"{steps} steps ({prefills} prefills): TTFT p50 "
            f"{out['ttft_p50_ms']} ms / p99 {out['ttft_p99_ms']} ms, "
            f"{tps} tok/s ({out['tokens_per_sec_per_chip']}/chip)")
        return out
    finally:
        if recorder is not None:
            if obs is not None:
                from faster_distributed_training_tpu.telemetry import (
                    programs, update_manifest as _upd)
                programs.set_observatory(prev_obs)
                # decode's compile story under its OWN manifest key —
                # "serve_compile" belongs to the classifier tier
                _upd(recorder.directory,
                     {"decode_compile": obs.summary()})
            spans.set_recorder(prev_rec)
            recorder.close()


def main(argv=None, defaults: Optional[TrainConfig] = None,
         prog: str = "fdt") -> dict:
    parser = build_parser(prog=prog, defaults=defaults)
    args = parser.parse_args(argv)
    cfg = config_from_args(args, defaults=defaults)
    return run_training(cfg)


def main_serve(argv=None, defaults: Optional[TrainConfig] = None,
               prog: str = "fdt-serve") -> dict:
    """The ``serve`` CLI twin of :func:`main`: same flag surface, but
    the checkpoint_dir is READ (never written) and the run pushes a
    synthetic ragged request mix through the serving stack instead of
    training.  ``python -m faster_distributed_training_tpu.serve.run``
    / scripts/serve_smoke.py are the script-level entries."""
    parser = build_parser(prog=prog, defaults=defaults)
    args = parser.parse_args(argv)
    cfg = config_from_args(args, defaults=defaults)
    out = run_serving(cfg)
    # CLI use: the numbers, not the tensors — drop the logits, the live
    # param bundle and the config object (meta/summary/replica stats
    # are plain scalars)
    for heavy in ("results", "state", "cfg"):
        out.pop(heavy, None)
    return out


def main_decode(argv=None, defaults: Optional[TrainConfig] = None,
                prog: str = "fdt-decode") -> dict:
    """The ``decode`` CLI twin of :func:`main_serve`: same flag surface,
    checkpoint_dir READ only, a synthetic ragged prompt mix generated
    to ``cfg.decode_max_new_tokens`` each.  scripts/decode_smoke.py is
    the script-level entry (with the multi-process front door on top)."""
    parser = build_parser(prog=prog, defaults=defaults)
    args = parser.parse_args(argv)
    cfg = config_from_args(args, defaults=defaults)
    out = run_decode_serving(cfg)
    for heavy in ("results", "state", "cfg"):
        out.pop(heavy, None)
    return out
