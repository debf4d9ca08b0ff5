"""Benchmark: every BASELINE.md tracked metric in ONE JSON line.

  {"metric": "resnet50_cifar10_train_images_per_sec_per_chip_bs1024",
   "value": N, "unit": "images/sec/chip", "vs_baseline": N,
   "ngd_overhead_pct": N,
   "transformer_agnews_ex_per_sec_bs256_seq256": N,
   "transformer_agnews_ex_per_sec_bs64_seq512": N, ...}

The primary metric stays the flagship ResNet-50/CIFAR-10 NGD+mixup
throughput @ bs=1024 (resnet50_test.py's headline workload); the same
line now always carries the other tracked numbers (VERDICT r1 weak #3):
NGD's step-time overhead vs SGD and both reference transformer configs
(transformer_test.py:355-361: bs=256/seq=256 and bs=64/seq=512).

Round-3 additions (VERDICT r2 #1/#2/#8): each transformer config also
emits its ROOFLINE fields — analytic model FLOPs/step, achieved
TFLOP/s, MFU vs the chip's bf16 peak (device_peak_tflops, overridable
via FDT_PEAK_TFLOPS), compiled peak memory, and XLA's own
bytes-accessed estimate — plus a bs=256/seq=512 capacity pair with and
without --remat (the layer-checkpoint lever), and the long-context
attention ladder (attn_fwdbwd_ms_L{2048,4096,8192,16384}, fwd+bwd flash
kernels, token count held at 16k) so the driver records the kernel
envelope round-over-round instead of trusting hand-run PARITY notes
(default-on since round 4, VERDICT r3 #4; FDT_BENCH_ATTN=0 disables).

Round-5 additions (VERDICT r4 #1/#2/#7): the GEMM-chain ceiling probe
(transformer_gemm_ceiling_* — the step's actual matmul shapes as a bare
jitted chain under grad, the measured MXU ceiling its MFU is judged
against), absolute per-step times beside the NGD-overhead % (the % alone
is ambiguous across denominator re-bases), explicit raw-step vs
full-pipeline tricks-speedup keys, a `baseline_note`, and the
`regressions` field: every tracked numeric metric is compared against
the previous round's BENCH_r*.json and >5% moves in the harmful
direction are flagged in-record.

Round-6 additions (VERDICT r5 #1/#2/#5/#7): the EVIDENCE CHAIN — the
full record is persisted to the committed BENCH_LATEST.json every run
and a compact <=1.5 KB essentials line prints LAST so the driver's 2 KB
stdout tail always parses (the r5 record was lost to tail truncation);
`_prev_bench_record` now skips unparseable driver wrappers and prefers
the newest parseable record.  The flagged bs64/seq512 and
tricks-transformer metrics are measured N>=5 times INTERLEAVED with
medians published plus *_noise_band_pct fields that feed the guard's
thresholds.  New arms: the 2D dense/flash crossover cells
(ATTN_ROUTE_BENCH_CELLS -> attn_route_*_step_ms), eval throughput
through the real pad-and-mask eval step (resnet_eval_img_per_sec_*,
transformer_eval_ex_per_sec_*), per-arm transformer_*_step_ms, and the
tentpole A/B attribution arms (transformer_bs256_seq256_ln_autodiff_
step_ms, transformer_bs64_seq512_flash_recompute_step_ms).

Round-7 addition (resilience PR): the checkpoint-overhead arms —
the ResNet NGD step under the resilience manager's save cadence,
per-step fenced, async vs blocking vs no checkpointing.  Two overhead
definitions per arm: ckpt_*_overhead_pct compares MEDIANS (steady-state
non-save step; the tracked <1% claim for async) and
ckpt_*_amortized_overhead_pct compares MEANS (save ticks included — the
honest total cost; a median alone would exclude every save-bearing step
and read 0% even for a fully blocking saver).  Opt out with
FDT_BENCH_CKPT=0.

Round-8 additions (host-free inner loop PR): the fused-dispatch ladder —
transformer_bs256_seq256_k{1,4,16}_step_ms and resnet_bs512_k{1,4,16}_
step_ms, the full train program on DEVICE-RESIDENT synthetic data with
K steps per dispatch (steps.make_fused_train_step), K=1 being the
dispatch-per-step floor on the same path — plus the input-pipeline A/B
data_path_{host,resident}_step_ms (BatchLoader+prefetch+H2D vs resident
in-graph gather, both at K=1, the only arms that INCLUDE steady-state
data work).  All measured N-interleaved with *_noise_band_pct per the
r6 protocol.  Opt out with FDT_BENCH_KDIS=0.

Round-19 additions (shard_map kernel layer): the tp-mesh kernel A/B —
transformer_tp2_{flash,ffn,quant}_{kernel,fallback}_step_ms, the
bs256/seq256 NGD step on a dp x tp=2 mesh per recovered kernel,
kernel-via-shard_map (parallel/kernel_shard.py) vs the forced pre-r19
fallback (FDT_KERNEL_SHARD=0), N>=3 interleaved (FDT_BENCH_TPK=0 opts
out; the ffn cell is TPU-only — interpret mode would measure the
interpreter) — and transformer_bs256_seq256_fp8_e5m2_grad_step_ms, the
FP8-LM completion (fp8 forward + E5M2 JIT-scaled gradient quantization
+ quantized dW/dx), interleaved with the r13 quant set so its A/B twin
is the plain fp8 arm.

Round-18 additions (streaming data plane): data_path_stream_step_ms
joins the input-pipeline A/B — the same ResNet NGD program fed from a
DISK-sharded split through the double-buffered device window
(data/stream/) — and stream_stall_pct records the steady-state fraction
of step time blocked on the window refill (<1% target, absolute-pp
guard like telemetry_overhead_pct).  Same FDT_BENCH_KDIS=0 opt-out.

Round-9 additions (pod-scale hot path PR): the ckpt_async_sharded arm —
the per-host shard-streaming checkpoint path (addressable-shard
snapshot + background shard write + two-phase COMMIT) forced on over
the same ResNet NGD program, tracked as ckpt_async_sharded_overhead_pct
beside the r7 async/sync arms — and the live-record guard: `*_step_ms`
A/B comparisons only run when the baseline is a live bench record
(_is_live_record), never against the r5 record_note reconstruction,
with a warning naming the PARITY flip procedure otherwise.

Round-12 addition (observability PR): the telemetry-overhead arm —
telem_{on,off}_median_step_ms, the ResNet NGD step with a live
per-dispatch TelemetryRecorder vs none (the FDT_TELEMETRY=0 path),
N>=5 interleaved, tracked as telemetry_overhead_pct with a <1%
absolute guard (_ABS_PP_WORSE_IF_UP) — the run-scoped telemetry
subsystem can never silently tax the hot path.  Opt out with
FDT_BENCH_TELEM=0.

Baseline: the reference publishes no absolute throughput (BASELINE.md).
`vs_baseline` is value / FDT_BENCH_BASELINE (img/s/chip) when that env
var is set; otherwise the constant 1.0 with "baseline_configured": false
— the absolute `value` is the tracked metric.  Synthetic device-resident
data, so the numbers measure the compiled train step, not disk IO.

Process model: ONE PROCESS PER CHIP.  A chip belongs to the process
that touched JAX, so the parent never imports jax: it only spawns timed
runs as children (FDT_BENCH_CHILD), one at a time, and collects their
JSON — the primary ResNet NGD run included.  Every child's JSON carries
the device it ran on (``device``: platform, kind, count).  A child that
dies makes the parent exit non-zero after the remaining arms have run.
Set FDT_BENCH_FAST=1 to emit only the primary metric.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

# Reference proxy: 4xA100 aggregate throughput for ResNet-50/CIFAR-10 @
# bs=1024 with AMP+fusion is not published (BASELINE.md); the driver tracks
# our absolute number round-over-round. Overridable bookkeeping constant:
BASELINE_REF_IPS = float(os.environ.get("FDT_BENCH_BASELINE", "0") or 0)

# 2D dense/flash crossover arms (VERDICT r5 #5): (bs, seq, impls) cells
# measured per round as attn_route_bs{bs}_seq{seq}_{impl}_step_ms.
# cli._ATTN_ROUTE_SURFACE cites these cells per routed region;
# tests/test_substrate.py pins the correspondence.  bs1024/seq256 runs
# flash only — its dense arm is excluded by the routing memory bound
# (see the note emitted beside it).
ATTN_ROUTE_BENCH_CELLS = ((512, 128, ("dense", "flash")),
                          (1024, 128, ("dense", "flash")),
                          (512, 256, ("dense", "flash")),
                          (1024, 256, ("flash",)),
                          (256, 384, ("dense", "flash")))

# r11 sequence-parallel route cells: full NGD train steps at the long-
# context cells the 4-impl surface serves — flash on the 1D mesh (the
# single-chip-replicated alternative) vs ring/ulysses over a
# (dp=1, sp=all-chips) mesh.  Measured N>=5 interleaved with
# *_noise_band_pct (FDT_BENCH_ATTN2D gate in main()); the matching
# kernel-level ladder arms are attn_fwdbwd_ms_L*_{ring,ulysses}.
ATTN_ROUTE_SP_BENCH_CELLS = ((8, 2048, ("flash", "ring", "ulysses")),
                             (4, 4096, ("flash", "ring", "ulysses")))


def _fence(metrics) -> None:
    import jax
    jax.block_until_ready(metrics)


def _resnet_train_program(use_ngd: bool, bs: int, steps: int,
                          sentinel: str = "none"):
    """Build + AOT-compile + warm ONE donating ResNet train program (the
    Trainer's exact configuration, honoring FDT_BENCH_REMAT /
    FDT_BENCH_TRICKS).  Shared by timed_resnet and the ckpt_* overhead
    arms so both measure the SAME program.  Returns
    (mesh, compiled, state, batch, compiled_peak_mem_bytes_or_None) with
    the 12-step warmup already run (past NGD's always-update phase — the
    Fisher refresh runs EVERY step while t < 10, then every 4th —
    optim/ngd.py NUM_INITIAL_ITERS) so the caller times steady state."""
    import jax
    import jax.numpy as jnp

    from faster_distributed_training_tpu.cli import (build_model,
                                                     enable_compilation_cache)
    from faster_distributed_training_tpu.config import (TrainConfig,
                                                        resolve_tricks)
    from faster_distributed_training_tpu.optim import build_optimizer
    from faster_distributed_training_tpu.parallel import make_mesh
    from faster_distributed_training_tpu.parallel.placement import (
        make_put_batch, shard_train_state)
    from faster_distributed_training_tpu.train import (create_train_state,
                                                       make_train_step)
    from faster_distributed_training_tpu.utils.profiling import (
        compiled_memory_bytes)

    enable_compilation_cache()
    mesh = make_mesh(("dp",))  # batch sharded over every visible chip
    remat = os.environ.get("FDT_BENCH_REMAT") == "1"
    cfg = resolve_tricks(TrainConfig(
        model="resnet50", batch_size=bs, alpha=0.2, use_ngd=use_ngd,
        optimizer="ngd" if use_ngd else "sgd",
        precision="bf16", epochs=1, remat=remat, sentinel=sentinel,
        tricks=os.environ.get("FDT_BENCH_TRICKS", "") or "on"))
    # build_model so dtype/conv_remat follow cfg (the CLI's real path)
    model = build_model(cfg)
    rng = jax.random.PRNGKey(cfg.seed)
    sample = jnp.zeros((bs, 32, 32, 3), jnp.float32)
    tx, _ = build_optimizer(cfg, steps_per_epoch=steps)
    state = create_train_state(model, tx, sample, rng,
                               init_kwargs={"train": True})
    with mesh:
        state = shard_train_state(state, mesh, cfg)
        put = make_put_batch(mesh)
        rr = np.random.default_rng(0)
        batch = put({
            "image": rr.normal(size=(bs, 32, 32, 3)).astype(np.float32),
            "label": rr.integers(0, 10, size=(bs,)).astype(np.int32),
        })
        # AOT-compile so the executable's memory analysis is available,
        # then run the compiled object directly.
        step = jax.jit(make_train_step(cfg), donate_argnums=0)
        compiled = step.lower(state, batch).compile()
        mem = compiled_memory_bytes(compiled)
        for _ in range(12):
            state, metrics = compiled(state, batch)
        _fence(metrics)
    return mesh, compiled, state, batch, mem


def timed_resnet(use_ngd: bool, bs: int, steps: int):
    """Time `steps` executions of the shared ResNet train program.
    Returns (elapsed_seconds, compiled_peak_mem_bytes_or_None,
    state_bytes_table) — the table's ``opt_state_bytes_per_chip`` /
    ``params_bytes_per_chip`` are the committed HBM-attribution baseline
    ROADMAP's ZeRO item sizes its win against (today the optimizer state
    is replicated across any model axis; ZeRO should drop it ~tp×)."""
    from faster_distributed_training_tpu.telemetry.programs import (
        state_bytes_table)

    mesh, compiled, state, batch, mem = _resnet_train_program(
        use_ngd, bs, steps)
    with mesh:
        state_bytes = state_bytes_table(state)
        t0 = time.monotonic()
        for _ in range(steps):
            state, metrics = compiled(state, batch)
        _fence(metrics)
        return time.monotonic() - t0, mem, state_bytes


def transformer_model_flops(bs: int, seq: int, n_layers: int = 6,
                            d: int = 512, dff: int = 1024,
                            d_hidden: int = 1024, n_class: int = 4) -> float:
    """Analytic matmul FLOPs for one train step (fwd + bwd ≈ 3× fwd), the
    standard MFU numerator.  Per token per layer fwd: QKV 2·d·3d, out
    proj 2·d², FFN 2·2·d·dff, attention 2·2·L·d (QKᵀ + PV); per sentence:
    pooler 2·d² + classifier 2·d·dh + 2·dh·ncls.  Embedding gathers do
    no matmul FLOPs and are excluded (convention)."""
    per_tok = n_layers * (6 * d * d + 2 * d * d + 4 * d * dff
                          + 4 * seq * d)
    per_sent = 2 * d * d + 2 * d * d_hidden + 2 * d_hidden * n_class
    return 3.0 * (bs * seq * per_tok + bs * per_sent)


def device_peak_tflops() -> tuple:
    """(peak bf16 TFLOP/s for MFU, source). FDT_PEAK_TFLOPS overrides; else
    a device_kind table.  A device that is not in the table is an error,
    never a default: an MFU over a guessed peak is not a measurement."""
    import jax
    env = os.environ.get("FDT_PEAK_TFLOPS")
    if env:
        return float(env), "env"
    kind = jax.devices()[0].device_kind.lower()
    for pat, peak in (("v6e", 918.0), ("v6 lite", 918.0), ("v5p", 459.0),
                      ("v5e", 197.0), ("v5 lite", 197.0), ("v4", 275.0),
                      ("v3", 123.0)):
        if pat in kind:
            return peak, kind
    raise ValueError(
        f"no published bf16 peak for device_kind={kind!r}; add it to the "
        f"table in bench.device_peak_tflops (with its source) or set "
        f"FDT_PEAK_TFLOPS")


def timed_transformer(bs: int, seq: int, steps: int,
                      remat: bool = False) -> dict:
    """One donating transformer train program (reference architecture:
    6L d512 h8 ff1024, bert vocab — transformer.py:12-35) on synthetic
    tokens; NGD like the flagship AG News run.  Returns a dict with
    elapsed seconds plus the roofline fields: compiled peak memory and
    XLA's own cost analysis (flops / bytes accessed) when exposed."""
    import jax
    import jax.numpy as jnp

    from faster_distributed_training_tpu.cli import (build_model,
                                                     enable_compilation_cache)
    from faster_distributed_training_tpu.config import TrainConfig
    from faster_distributed_training_tpu.optim import build_optimizer
    from faster_distributed_training_tpu.parallel import make_mesh
    from faster_distributed_training_tpu.parallel.placement import (
        make_put_batch, shard_train_state)
    from faster_distributed_training_tpu.train import (create_train_state,
                                                       make_train_step)
    from faster_distributed_training_tpu.utils.profiling import (
        compiled_memory_bytes)

    enable_compilation_cache()
    mesh_spec = os.environ.get("FDT_BENCH_TF_MESH", "")
    if mesh_spec:
        # 2D arms (route2d_* children): e.g. "dp=1,sp=8" for the
        # sequence-parallel route cells — axis aliases canonicalized
        from faster_distributed_training_tpu.config import parse_mesh
        maxes, mshape = parse_mesh(mesh_spec)
        mesh = make_mesh(maxes, mshape)
    else:
        mesh = make_mesh(("dp",))
    opt = os.environ.get("FDT_BENCH_TF_OPT", "ngd")
    from faster_distributed_training_tpu.config import resolve_tricks
    cfg = resolve_tricks(TrainConfig(
        model="transformer", dataset="agnews", num_classes=4,
        batch_size=bs, seq_len=seq, use_ngd=(opt == "ngd"),
        optimizer=opt, precision="bf16", epochs=1,
        quant=os.environ.get("FDT_BENCH_TF_QUANT", "") or "none",
        quant_grad=os.environ.get("FDT_BENCH_TF_QUANT_GRAD", "") or "none",
        remat=remat,
        remat_policy=os.environ.get("FDT_BENCH_TF_REMAT_POLICY",
                                    "") or "attn_out",
        attention=os.environ.get("FDT_BENCH_TF_ATTN", ""),
        mlp_impl=os.environ.get("FDT_BENCH_TF_MLP", ""),
        ffn_impl=os.environ.get("FDT_BENCH_TF_FFN", "") or "flax",
        dropout_impl=os.environ.get("FDT_BENCH_TF_DROPOUT", "") or "hash",
        tricks=os.environ.get("FDT_BENCH_TRICKS", "") or "on"))
    model = build_model(cfg, vocab_size=30522, mesh=mesh)
    rng = jax.random.PRNGKey(cfg.seed)
    sample = jnp.zeros((bs, seq), jnp.int32)
    tx, _ = build_optimizer(cfg, steps_per_epoch=steps)
    state = create_train_state(model, tx, sample, rng,
                               init_kwargs={"train": True})
    # model-axis meshes (the 2D route arms) pin the step's output state
    # to the placement policy, mirroring run_training — without it XLA
    # drifts params across the model axis between donated steps
    from faster_distributed_training_tpu.parallel.mesh import (sp_size,
                                                               tp_size)
    from faster_distributed_training_tpu.parallel.placement import (
        train_state_shardings)
    shardings = (train_state_shardings(state, mesh, cfg)
                 if tp_size(mesh) > 1 or sp_size(mesh) > 1 else None)
    with mesh:
        state = shard_train_state(state, mesh, cfg, shardings=shardings)
        put = make_put_batch(mesh)
        rr = np.random.default_rng(1)
        lens = rr.integers(seq // 2, seq + 1, size=(bs,))
        batch = put({
            "tokens": rr.integers(0, 30522, size=(bs, seq)).astype(np.int32),
            "token_types": np.zeros((bs, seq), np.int32),
            "mask": (np.arange(seq)[None, :] < lens[:, None]).astype(np.int32),
            "label": rr.integers(0, 4, size=(bs,)).astype(np.int32),
        })
        step = jax.jit(make_train_step(cfg, shardings), donate_argnums=0)
        compiled = step.lower(state, batch).compile()
        out = {"bs": bs, "seq": seq, "remat": remat}
        if remat:
            out["remat_policy"] = cfg.remat_policy
        mem = compiled_memory_bytes(compiled)
        if mem:
            out["compiled_peak_mem_bytes"] = int(mem)
        try:
            ca = compiled.cost_analysis()
            ca = ca[0] if isinstance(ca, (list, tuple)) else ca
            if ca:
                if ca.get("flops"):
                    out["xla_flops_per_step"] = float(ca["flops"])
                ba = ca.get("bytes accessed") or ca.get("bytes_accessed")
                if ba:
                    out["xla_bytes_accessed_per_step"] = float(ba)
        except Exception:
            pass
        for _ in range(12):
            state, metrics = compiled(state, batch)
        _fence(metrics)
        t0 = time.monotonic()
        for _ in range(steps):
            state, metrics = compiled(state, batch)
        _fence(metrics)
        out["elapsed"] = time.monotonic() - t0
        return out


def timed_gemm_ceiling(bs: int, seq: int, steps: int = 30) -> dict:
    """Bare GEMM-chain ceiling probe (VERDICT r4 #1).

    Runs the transformer train step's ACTUAL matmul shapes — fused QKV
    (B·L,512)×(512,1536), the batched attention matmuls QKᵀ and PV at
    (B·H,L,64), out-proj (B·L,512)×(512,512), FFN
    (B·L,512)×(512,1024)×(1024,512), pooler + classifier — as one
    jitted chain under jax.grad (so the backward's dW/dx GEMMs run too,
    FLOPs = 3× forward exactly like the analytic MFU numerator), with
    NOTHING else: no softmax, LN, dropout, residuals, embedding, or
    optimizer.  The achieved TFLOP/s of this chain IS the measured MXU
    ceiling for the step's GEMM structure at these shapes; the train
    step's MFU divided by this ceiling separates "structure-bound"
    (d_model=512 tiles) from recoverable overhead."""
    import jax
    import jax.numpy as jnp

    d, dff, H, n_layers, dh, ncls = 512, 1024, 8, 6, 1024, 4
    dk = d // H
    rr = np.random.default_rng(0)

    def mk(*s):
        return jnp.asarray(rr.normal(size=s) * 0.02, jnp.bfloat16)

    params = [{"qkv": mk(d, 3 * d), "out": mk(d, d),
               "f1": mk(d, dff), "f2": mk(dff, d)} for _ in range(n_layers)]
    head = {"pool": mk(d, d), "w1": mk(d, dh), "w2": mk(dh, ncls)}
    x0 = mk(bs * seq, d)

    def chain(x, params, head):
        for p in params:
            qkv = x @ p["qkv"]
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(bs, seq, H, dk).transpose(0, 2, 1, 3)
            k = k.reshape(bs, seq, H, dk).transpose(0, 2, 1, 3)
            v = v.reshape(bs, seq, H, dk).transpose(0, 2, 1, 3)
            s = q @ k.transpose(0, 1, 3, 2)          # scores GEMM
            c = s @ v                                # context GEMM
            c = c.transpose(0, 2, 1, 3).reshape(bs * seq, d)
            x = c @ p["out"]
            h = x @ p["f1"]
            x = h @ p["f2"]
        cls = x.reshape(bs, seq, d)[:, 0]
        return (cls @ head["pool"]) @ head["w1"] @ head["w2"]

    def loss(x, params, head):
        return jnp.sum(chain(x, params, head).astype(jnp.float32) ** 2)

    fence = jax.block_until_ready

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    g = step(x0, params, head)
    fence(g)
    t0 = time.monotonic()
    for _ in range(steps):
        g = step(x0, params, head)
    fence(g)
    elapsed = time.monotonic() - t0
    mf = transformer_model_flops(bs, seq)
    return {"bs": bs, "seq": seq, "elapsed": elapsed,
            "gemm_ceiling_tflops": round(mf * steps / elapsed / 1e12, 1)}


def timed_attention_ladder(steps: int = 30, impl: str = "flash") -> dict:
    """Long-context ladder (VERDICT r2 #8: promoted from PARITY prose
    into the bench JSON).  fwd+bwd attention, bf16, D=64, H=8, token
    count held at 16k (B·L = 16384), padding mask — the exact hand-run
    configuration behind PARITY.md's envelope row.

    impl "flash" (default) is the single-chip kernel; "ring"/"ulysses"
    (r11) run the sequence-parallel strategies over a (dp=1, sp=all-
    chips) mesh at the SAME global shapes — the multi-chip side of the
    4-impl routing surface.  Returns {"attn_fwdbwd_ms_L{L}": ms, ...}
    (suffix "_ring"/"_ulysses" for the sp variants); cells the chip
    count cannot serve (L or H not divisible) are omitted, not faked."""
    import jax
    import jax.numpy as jnp

    from faster_distributed_training_tpu.ops.flash_attention import (
        flash_attention)

    H, D, tokens = 8, 64, 16384
    sp_fn, mesh, n = None, None, 1
    if impl != "flash":
        from faster_distributed_training_tpu.ops.ring_attention import (
            ring_self_attention)
        from faster_distributed_training_tpu.ops.ulysses_attention import (
            ulysses_self_attention)
        from faster_distributed_training_tpu.parallel import make_mesh
        n = jax.device_count()
        if n < 2:
            return {}
        mesh = make_mesh(("dp", "sp"), (1, n))
        sp_fn = (ring_self_attention if impl == "ring"
                 else ulysses_self_attention)
    out = {}
    suffix = "" if impl == "flash" else f"_{impl}"
    for L in (2048, 4096, 8192, 16384):
        if impl != "flash" and (L % n or (impl == "ulysses" and H % n)):
            continue
        B = max(tokens // L, 1)
        rr = np.random.default_rng(L)
        q, k, v = (jnp.asarray(rr.normal(size=(B, H, L, D)), jnp.bfloat16)
                   for _ in range(3))
        lens = rr.integers(L // 2, L + 1, size=(B,))
        mask = jnp.asarray(
            (np.arange(L)[None, :] < lens[:, None]).astype(np.int32))

        if impl == "flash":
            def loss(q_, k_, v_):
                return jnp.sum(
                    flash_attention(q_, k_, v_,
                                    mask=mask).astype(jnp.float32) ** 2)
        else:
            def loss(q_, k_, v_):
                return jnp.sum(
                    sp_fn(q_, k_, v_, mask, mesh).astype(jnp.float32) ** 2)

        step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        g = step(q, k, v)
        jax.block_until_ready(g)
        t0 = time.monotonic()
        for _ in range(steps):
            g = step(q, k, v)
        jax.block_until_ready(g)
        out[f"attn_fwdbwd_ms_L{L}{suffix}"] = round(
            (time.monotonic() - t0) / steps * 1e3, 2)
    return out


def timed_checkpoint_overhead(mode: str, bs: int, steps: int) -> dict:
    """Checkpoint-save overhead per train step (r7 resilience arm): the
    ResNet-50 NGD train program stepped `steps` times with the resilience
    manager saving every FDT_BENCH_CKPT_EVERY (default 10) steps, each
    step individually fenced and timed.  mode: "off" = no checkpointing
    (the floor), "async" = off-critical-path manager (snapshot on the
    step thread, serialize+commit in the background), "sync" = blocking
    saves, "async_sharded" = the pod-scale per-host shard-streaming
    path forced on (addressable-shard snapshot + background shard write
    + two-phase commit — what a multi-host run takes per host).  The
    tracked claim (ISSUE r7 acceptance): async median step time within
    1% of off — the save cost leaves the critical path; r9 extends the
    same claim to the sharded path (ckpt_async_sharded_overhead_pct).
    The mean (save ticks included) is published beside it as the
    amortized total cost; see the record-building note in main()."""
    import shutil
    import tempfile

    from faster_distributed_training_tpu.resilience import (
        AsyncCheckpointManager, GoodputTracker)

    mesh, compiled, state, batch, _mem = _resnet_train_program(
        True, bs, steps)
    every = int(os.environ.get("FDT_BENCH_CKPT_EVERY", "10"))
    goodput = GoodputTracker()
    manager, ckpt_dir = None, None
    if mode != "off":
        ckpt_dir = tempfile.mkdtemp(prefix="fdt_bench_ckpt_")
        manager = AsyncCheckpointManager(
            ckpt_dir, every_steps=every, keep=2,
            async_save=mode in ("async", "async_sharded"),
            force_sharded=(mode == "async_sharded"),
            goodput=goodput, log=lambda *_: None)
    try:
        with mesh:
            per_step = []
            for i in range(1, steps + 1):
                t0 = time.monotonic()
                state, metrics = compiled(state, batch)
                _fence(metrics)   # per-step fence: each step timed alone
                if manager is not None:
                    manager.maybe_save(state, i)
                per_step.append(time.monotonic() - t0)
            if manager is not None:
                manager.close()
    finally:
        if ckpt_dir is not None:
            # keep=2 full ResNet+NGD states — do not let repeated bench
            # runs accumulate gigabytes under /tmp
            shutil.rmtree(ckpt_dir, ignore_errors=True)
    per_step.sort()
    g = goodput.summary()
    # median = the steady-state (non-save-tick) step; mean = AMORTIZED
    # cost including the save ticks — with saves on 10% of steps the
    # median alone would exclude every save-bearing step and report a
    # vacuous 0% for even a fully blocking saver, so both are tracked.
    out = {"mode": mode, "bs": bs, "steps": steps, "save_every": every,
           "median_step_ms": round(per_step[len(per_step) // 2] * 1e3, 3),
           "mean_step_ms": round(sum(per_step) / len(per_step) * 1e3, 3),
           "max_step_ms": round(per_step[-1] * 1e3, 3),
           "saves": int(g.get("saves", 0))}
    if g.get("saves"):
        out["blocking_ms_per_save"] = round(
            g["checkpoint_blocking_s"] * 1e3 / g["saves"], 2)
    return out


def timed_telemetry_overhead(mode: str, bs: int, steps: int) -> dict:
    """telemetry_overhead_pct arm (r12 observability tentpole): the
    ResNet-50 NGD train program stepped `steps` times with a live
    TelemetryRecorder taking one per-dispatch record ("on") vs no
    recorder at all ("off" — the FDT_TELEMETRY=0 kill-switch path),
    each step individually fenced so the recorder's hot-path cost (a
    few clock reads + dict build + lock-guarded append; JSON/IO on the
    background thread) lands inside the timed region.  Tracked claim:
    on-vs-off median step delta <1% — observability must never silently
    tax the hot path, and the regression guard
    (_ABS_PP_WORSE_IF_UP['telemetry_overhead_pct']) holds it there
    round over round."""
    import shutil
    import tempfile

    from faster_distributed_training_tpu.telemetry import TelemetryRecorder

    mesh, compiled, state, batch, _mem = _resnet_train_program(
        True, bs, steps)
    rec, tdir = None, None
    if mode == "on":
        tdir = tempfile.mkdtemp(prefix="fdt_bench_telem_")
        rec = TelemetryRecorder(tdir, process_index=0, process_count=1,
                                log=lambda *_: None)
    try:
        with mesh:
            per_step = []
            for i in range(1, steps + 1):
                t0 = time.monotonic()
                state, metrics = compiled(state, batch)
                _fence(metrics)   # per-step fence: each step timed alone
                if rec is not None:
                    t1 = time.monotonic()
                    rec.record_step(i, 0, i, 1, (t1 - t0) * 1e3,
                                    (t1 - t0) * 1e3, bs)
                per_step.append(time.monotonic() - t0)
            if rec is not None:
                rec.close()
    finally:
        if tdir is not None:
            shutil.rmtree(tdir, ignore_errors=True)
    per_step.sort()
    return {"mode": mode, "bs": bs, "steps": steps,
            "median_step_ms": round(per_step[len(per_step) // 2] * 1e3, 3),
            "mean_step_ms": round(sum(per_step) / len(per_step) * 1e3, 3)}


def timed_sentinel_overhead(mode: str, bs: int, steps: int) -> dict:
    """sentinel_overhead_pct arm (r24 robustness tentpole): the
    ResNet-50 NGD train program stepped `steps` times with the in-graph
    bad-step guard compiled in plus a live host-side SpikeDetector
    observing every fenced loss ("on" — exactly what --sentinel full
    buys per dispatch) vs the stock program ("off" — --sentinel none,
    byte-identical HLO to pre-sentinel, pinned by
    tests/test_sentinel.py).  BOTH arms fence every step through
    float(metrics["loss"]) — the sentinel's documented per-dispatch
    sync IS that readback, which the bench already pays — so the delta
    isolates the guard's in-graph cost (one fused finiteness reduction
    riding the grad-norm pass + a select on the update) plus the
    detector's host arithmetic.  Tracked claim: <1% median step delta,
    held by _ABS_PP_WORSE_IF_UP['sentinel_overhead_pct']."""
    from faster_distributed_training_tpu.resilience.sentinel import (
        SpikeDetector)

    mesh, compiled, state, batch, _mem = _resnet_train_program(
        True, bs, steps, sentinel="guard" if mode == "on" else "none")
    det = SpikeDetector() if mode == "on" else None
    with mesh:
        per_step = []
        for _ in range(steps):
            t0 = time.monotonic()
            state, metrics = compiled(state, batch)
            loss = float(metrics["loss"])   # fence: BOTH arms pay this
            if det is not None:
                det.observe(loss)
            per_step.append(time.monotonic() - t0)
    per_step.sort()
    return {"mode": mode, "bs": bs, "steps": steps,
            "median_step_ms": round(per_step[len(per_step) // 2] * 1e3, 3),
            "mean_step_ms": round(sum(per_step) / len(per_step) * 1e3, 3)}


# inline child for the relaunch-MTTR arms: one tiny supervised-config
# training run against a shared checkpoint dir; the crash phase dies on
# an injected fault AFTER a committed cadence save, the relaunch phase
# auto-resumes and prints its recovery decomposition (restore seconds
# from goodput, program-acquisition seconds from the observatory feed).
_RELAUNCH_CHILD = r"""
import json, os, sys
sys.path.insert(0, os.environ["FDT_BENCH_REPO"])
from faster_distributed_training_tpu.config import TrainConfig
from faster_distributed_training_tpu.cli import run_training
cfg = TrainConfig(model="transformer", dataset="synthetic", num_classes=4,
                  batch_size=8, seq_len=16, n_layers=1, d_model=16, d_ff=32,
                  n_heads=2, epochs=2, subset_stride=64, optimizer="sgd",
                  precision="fp32", plot=False, workers=0, log_every=0,
                  donate=False, checkpoint_dir=os.environ["FDT_BENCH_DIR"],
                  checkpoint_every=4,
                  executable_cache=os.environ.get("FDT_BENCH_EXEC_CACHE", ""))
out = run_training(cfg, log=lambda *a: print(*a, file=sys.stderr))
print(json.dumps({"step": int(out["state"].step),
                  "restore_s": float(out.get("goodput_restore_s", 0.0)),
                  "compile_s": float(out.get("goodput_compile_s", 0.0)),
                  "restores": int(out.get("goodput_restores", 0))}))
"""


def timed_restart_mttr(cache: bool = False) -> dict:
    """Restart-MTTR arm, r17 definition: the recovery cost of a
    RELAUNCHED process — crash phase (injected fault after a committed
    cadence save) then a fresh process that auto-resumes — which is the
    scenario a restarted/rejoining slice actually pays.  MTTR = the
    relaunch's checkpoint-restore seconds + its program-acquisition
    seconds (every compile in a relaunch is recovery recompile; with
    ``cache`` the executable tier deserializes instead —
    restart_cached_mttr_s vs restart_mttr_s is the tentpole A/B).
    detect/backoff are 0 by scenario: a platform relaunch's detection
    is platform-side and the r17 supervisor's first restart is
    immediate.  Pre-r17 this arm measured the IN-process supervised
    cycle, which keeps its compiled programs alive and therefore could
    never see the compile-dominated half of real-hardware MTTR — the
    old number survives in goodput's restart_mttr_s for supervised
    runs.  Both phases run against a HERMETIC XLA compilation-cache
    dir: a developer's warm ~/.cache would otherwise serve the crash
    phase's compiles and (XLA:CPU) cache-served executables don't
    serialize round-trippably, making the arm measure the machine's
    history instead of the cache tier."""
    import shutil
    import subprocess as sp
    import tempfile

    d = tempfile.mkdtemp(prefix="fdt_bench_mttr_")
    die_at = int(os.environ.get("FDT_BENCH_MTTR_DIE_AT", "13"))
    repo = os.path.dirname(os.path.abspath(__file__))
    xla_dirs = []

    def phase(extra, expect_fail=False):
        # one hermetic XLA cache dir PER PHASE: the persistent dir is
        # machine-local and a relaunched slice on a fresh machine only
        # keeps the (durable, StorageBackend-backed) executable cache —
        # the tier this arm A/Bs
        xla_dirs.append(tempfile.mkdtemp(prefix="fdt_bench_mttr_xla_"))
        env = dict(os.environ, FDT_BENCH_DIR=d, FDT_BENCH_REPO=repo,
                   JAX_COMPILATION_CACHE_DIR=xla_dirs[-1],
                   FDT_BENCH_EXEC_CACHE="on" if cache else "0", **extra)
        env.pop("FDT_BENCH_CHILD", None)
        p = sp.run([sys.executable, "-c", _RELAUNCH_CHILD], env=env,
                   capture_output=True, text=True, timeout=900)
        if expect_fail:
            if p.returncode == 0:
                # a disarmed fault would silently turn the "relaunch"
                # into resume-from-a-completed-run and commit bogus
                # MTTR numbers — fail the arm loudly instead
                raise RuntimeError(
                    "crash phase was expected to die on the injected "
                    "fault but exited cleanly (fault not armed?)")
            return None
        if p.returncode != 0:
            raise RuntimeError(f"relaunch child rc={p.returncode}: "
                               f"{p.stderr[-1500:]}")
        return json.loads(p.stdout.strip().splitlines()[-1])

    try:
        phase({"FDT_FAULT_DIE_AT_STEP": str(die_at)}, expect_fail=True)
        out = phase({})
        sources = {}
        try:
            with open(os.path.join(d, "telemetry", "manifest.json")) as f:
                man = json.load(f)
            for prog in man.get("compile", {}).get("programs", []):
                sources[prog["name"]] = [v.get("cache_source", "?")
                                         for v in prog["variants"]]
        except (OSError, ValueError, KeyError):
            pass
    finally:
        shutil.rmtree(d, ignore_errors=True)
        for x in xla_dirs:
            shutil.rmtree(x, ignore_errors=True)
    restore = round(out["restore_s"], 3)
    compile_ = round(out["compile_s"], 3)
    return {"mttr_s": round(restore + compile_, 3),
            "restore_s": restore, "compile_s": compile_,
            "detect_s": 0.0, "backoff_s": 0.0,
            "restores": int(out["restores"]), "die_at": die_at,
            "cache": bool(cache), "cache_sources": sources}


def timed_warm_spare() -> dict:
    """Warm-spare swap arm (r17 tentpole): a simulated 2-slice pod (one
    host thread per slice) plus ONE parked spare thread whose step
    program is already built — slice 1 is killed for good (no restart
    budget), the survivor holds, the spare claims the seat, restores,
    catches up, and finishes the run in slice 1's place.  Reports
    warm_spare_swap_s (claim -> release, published by the spare's
    goodput summary beside the badput segments)
    and warm_spare_hold_s (the survivor's parked time) — the numbers
    the cold-rejoin twin pays a process relaunch + full recompile for.
    Training is tiny by design: the arm measures the swap machinery."""
    import shutil
    import tempfile
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as _np

    from faster_distributed_training_tpu.config import TrainConfig
    from faster_distributed_training_tpu.models import Transformer
    from faster_distributed_training_tpu.optim import build_optimizer
    from faster_distributed_training_tpu.resilience import (
        AsyncCheckpointManager, FaultPlan, GoodputTracker, PodCoordinator,
        Supervisor)
    from faster_distributed_training_tpu.train import (create_train_state,
                                                       make_train_step)

    cfg = TrainConfig(model="transformer", dataset="agnews", num_classes=4,
                      batch_size=4, seq_len=8, optimizer="sgd",
                      precision="fp32", epochs=1, donate=False)
    model = Transformer(n_class=4, vocab=32, n_layers=1, h=2, d_model=16,
                        d_ff=32, d_hidden=16, maxlen=8)
    tx, _ = build_optimizer(cfg, steps_per_epoch=2)
    state0 = create_train_state(model, tx, jnp.zeros((4, 8), jnp.int32),
                                jax.random.PRNGKey(0),
                                init_kwargs={"train": True})
    batch = {"tokens": _np.random.default_rng(0).integers(
                 0, 32, size=(4, 8)).astype(_np.int32),
             "label": _np.arange(4, dtype=_np.int32) % 4}
    step_fn = jax.jit(make_train_step(cfg))
    step_fn(state0, batch)          # the spare's programs are warm
    total, every = 12, 4
    die_at = int(os.environ.get("FDT_BENCH_SPARE_DIE_AT", "6"))
    d = tempfile.mkdtemp(prefix="fdt_bench_spare_")
    goodputs = [GoodputTracker().start() for _ in range(3)]
    # loose lockstep between the two MEMBERS until the kill (the r14
    # harness idiom): without it a scheduling hiccup lets the survivor
    # run ahead into a cadence save whose commit barrier can only wait
    # out the dead peer — the hold would measure the commit timeout,
    # not the swap
    barrier = threading.Barrier(2)

    def member(pi, faults, budget):
        coord = PodCoordinator(
            os.path.join(d, "_pod"), process_index=pi, process_count=2,
            sync_every=1, peer_timeout_s=30.0, slice_index=pi,
            slice_count=2, readmit_timeout_s=60.0,
            goodput=goodputs[pi], log=lambda *_: None)
        mgr = AsyncCheckpointManager(
            d, every_steps=every, process_index=pi, process_count=2,
            shard_owner=((lambda sh: sh.replica_id == 0) if pi == 0
                         else (lambda sh: False)),
            commit_timeout_s=15.0,
            step_gather_fn=coord.gather_restored_step,
            goodput=goodputs[pi], log=lambda *_: None)
        coord.drain_fn = mgr.wait
        sup = Supervisor(max_restarts=budget, backoff_base=0.01,
                         goodput=goodputs[pi], log=lambda *_: None,
                         coordinator=coord)
        progress = {"step": 0}

        def attempt(_i):
            try:
                st, start = state0, 0
                got = mgr.restore_latest(st)
                if got is not None:
                    st, meta = got
                    start = int(meta["step"])
                progress["step"] = start
                if coord.rejoining:
                    coord.rejoin_sync(start)
                with coord.watch_steps():
                    for i in range(start + 1, total + 1):
                        try:
                            barrier.wait(timeout=30.0)
                        except threading.BrokenBarrierError:
                            time.sleep(0.01)   # pace the free run
                        st, _m = step_fn(st, batch)
                        progress["step"] = i
                        if faults is not None:
                            faults.on_step(i)
                        coord.check(i)
                        align = coord.consume_cadence_align()
                        if align is not None:
                            mgr.align_cadence(align)
                        if not coord.saves_suspended:
                            mgr.maybe_save(st, i)
                mgr.wait()
                return st
            except BaseException:
                barrier.abort()
                raise
        try:
            # the supervisor records completion on the coordinator
            return sup.run(attempt, lambda: progress["step"])
        finally:
            barrier.abort()      # a finished member frees the other side
            mgr.close()
            coord.close()

    def spare():
        coord = PodCoordinator(
            os.path.join(d, "_pod"), process_index=0, process_count=2,
            sync_every=1, peer_timeout_s=30.0, slice_count=2,
            readmit_timeout_s=60.0, spare_index=0,
            goodput=goodputs[2], log=lambda *_: None)
        claim = coord.spare_wait(poll_s=0.02)
        if claim is None:
            coord.close()
            return None
        mgr = AsyncCheckpointManager(
            d, every_steps=every, process_index=coord.pi, process_count=2,
            shard_owner=(lambda sh: False), commit_timeout_s=15.0,
            step_gather_fn=coord.gather_restored_step,
            goodput=goodputs[2], log=lambda *_: None)
        coord.drain_fn = mgr.wait
        try:
            st, start = state0, 0
            got = mgr.restore_latest(st)
            if got is not None:
                st, meta = got
                start = int(meta["step"])
            coord.rejoin_sync(start)
            with coord.watch_steps():
                for i in range(start + 1, total + 1):
                    st, _m = step_fn(st, batch)
                    coord.check(i)
                    align = coord.consume_cadence_align()
                    if align is not None:
                        mgr.align_cadence(align)
                    if not coord.saves_suspended:
                        mgr.maybe_save(st, i)
            mgr.wait()
            coord.record_completion(step=total)
            return st
        finally:
            mgr.close()
            coord.close()

    errors = {}

    def body(label, fn, *a):
        try:
            fn(*a)
        except BaseException as e:          # pragma: no cover - reported
            if label != "victim":
                errors[label] = repr(e)

    threads = [
        threading.Thread(target=body, args=("survivor", member, 0, None, 3),
                         daemon=True),
        threading.Thread(target=body,
                         args=("victim", member, 1,
                               FaultPlan(die_at=die_at), 0),
                         daemon=True),
        threading.Thread(target=body, args=("spare", spare), daemon=True)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    shutil.rmtree(d, ignore_errors=True)
    s0, s2 = goodputs[0].summary(), goodputs[2].summary()
    return {"warm_spare_swap_s": round(
                float(s2.get("warm_spare_swap_s", 0.0)), 3),
            "warm_spare_hold_s": round(
                float(s0.get("readmission_hold_s", 0.0)), 3),
            "claims": int(s2.get("warm_spare_claims", 0)),
            "swaps": int(s2.get("warm_spare_swaps", 0)),
            "survivor_restarts": int(s0.get("restarts", 0)),
            "errors": errors, "die_at": die_at}


def timed_restart_slice_mttr() -> dict:
    """Slice-recovery MTTR arm (r14 elastic-recovery PR): a simulated
    2-slice pod (two host threads, one slice each, shared directory —
    the tier-1 simulation seam), slice 1 killed by a deterministic
    injected crash.  The survivor HOLDS at its dispatch boundary
    (await_readmission) instead of restarting; the killed slice
    restarts, rejoins the same generation, restores, catches up and is
    re-admitted.  Reports restart_slice_mttr_s = (detect + hold +
    restore) / readmissions with the components beside it — the
    slice-granular sibling of restart_mttr_s (whose backoff+rollback
    the surviving slice no longer pays).  Training is tiny by design:
    the arm measures the recovery machinery, not the workload."""
    import shutil
    import tempfile
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as _np

    from faster_distributed_training_tpu.config import TrainConfig
    from faster_distributed_training_tpu.models import Transformer
    from faster_distributed_training_tpu.optim import build_optimizer
    from faster_distributed_training_tpu.resilience import (
        AsyncCheckpointManager, FaultPlan, GoodputTracker, PodCoordinator,
        Supervisor)
    from faster_distributed_training_tpu.train import (create_train_state,
                                                       make_train_step)

    cfg = TrainConfig(model="transformer", dataset="agnews", num_classes=4,
                      batch_size=4, seq_len=8, optimizer="sgd",
                      precision="fp32", epochs=1, donate=False)
    model = Transformer(n_class=4, vocab=32, n_layers=1, h=2, d_model=16,
                        d_ff=32, d_hidden=16, maxlen=8)
    tx, _ = build_optimizer(cfg, steps_per_epoch=2)
    state0 = create_train_state(model, tx, jnp.zeros((4, 8), jnp.int32),
                                jax.random.PRNGKey(0),
                                init_kwargs={"train": True})
    batch = {"tokens": _np.random.default_rng(0).integers(
                 0, 32, size=(4, 8)).astype(_np.int32),
             "label": _np.arange(4, dtype=_np.int32) % 4}
    step_fn = jax.jit(make_train_step(cfg))
    total, every = 12, 4
    die_at = int(os.environ.get("FDT_BENCH_SLICE_MTTR_DIE_AT", "6"))
    d = tempfile.mkdtemp(prefix="fdt_bench_slice_mttr_")
    goodputs = [GoodputTracker().start() for _ in range(2)]
    # loose lockstep until the kill (then the barrier is aborted and
    # both sides run free), plus a small per-step pace so the
    # survivor's FAIL-marker observation is deterministic-ish
    barrier = threading.Barrier(2)

    def host(pi, faults):
        coord = PodCoordinator(
            os.path.join(d, "_pod"), process_index=pi, process_count=2,
            sync_every=1, peer_timeout_s=30.0, slice_index=pi,
            slice_count=2, readmit_timeout_s=60.0,
            goodput=goodputs[pi], log=lambda *_: None)
        mgr = AsyncCheckpointManager(
            d, every_steps=every, process_index=pi, process_count=2,
            shard_owner=((lambda sh: sh.replica_id == 0) if pi == 0
                         else (lambda sh: False)),
            commit_timeout_s=15.0,
            step_gather_fn=coord.gather_restored_step,
            goodput=goodputs[pi], log=lambda *_: None)
        coord.drain_fn = mgr.wait
        sup = Supervisor(max_restarts=3, backoff_base=0.01,
                         goodput=goodputs[pi], log=lambda *_: None,
                         coordinator=coord)
        progress = {"step": 0}

        def attempt(_i):
            try:
                st, start = state0, 0
                got = mgr.restore_latest(st)
                if got is not None:
                    st, meta = got
                    start = int(meta["step"])
                progress["step"] = start
                if coord.rejoining:
                    coord.rejoin_sync(start)
                with coord.watch_steps():
                    for i in range(start + 1, total + 1):
                        try:
                            barrier.wait(timeout=30.0)
                        except threading.BrokenBarrierError:
                            pass
                        st, _m = step_fn(st, batch)
                        time.sleep(0.01)
                        progress["step"] = i
                        if faults is not None:
                            faults.on_step(i)
                        coord.check(i)
                        align = coord.consume_cadence_align()
                        if align is not None:
                            mgr.align_cadence(align)
                        if not coord.saves_suspended:
                            mgr.maybe_save(st, i)
                mgr.wait()
                return st
            except BaseException:
                barrier.abort()
                raise
        try:
            return sup.run(attempt, lambda: progress["step"])
        finally:
            mgr.close()
            coord.close()

    errors = {}

    def body(pi, faults):
        try:
            host(pi, faults)
        except BaseException as e:          # pragma: no cover - reported
            errors[pi] = repr(e)

    threads = [
        threading.Thread(target=body, args=(0, None), daemon=True),
        threading.Thread(target=body, args=(1, FaultPlan(die_at=die_at)),
                         daemon=True)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    shutil.rmtree(d, ignore_errors=True)
    s0, s1 = goodputs[0].summary(), goodputs[1].summary()
    readmits = int(s0.get("slice_readmissions", 0))
    detect = float(s0.get("detect_s", 0.0))
    hold = float(s0.get("readmission_hold_s", 0.0))
    restore = float(s1.get("restore_s", 0.0))
    return {"restart_slice_mttr_s": round(
                (detect + hold + restore) / max(readmits, 1), 3),
            "detect_s": round(detect, 3), "hold_s": round(hold, 3),
            "restore_s": round(restore, 3),
            "readmissions": readmits,
            "fallbacks": (int(s0.get("pod_fallback_restarts", 0))
                          + int(s1.get("pod_fallback_restarts", 0))),
            "errors": errors, "die_at": die_at}


def timed_pp_pipeline(pp: int) -> dict:
    """Pipeline weak-scaling rung (r22 pp tentpole): a simulated pod of
    ``pp`` slices (virtual host devices — the same tier-1 simulation
    seam as timed_restart_slice_mttr), pp = one pipeline stage per
    slice, model DEPTH grown with the slice count (weak scaling: fixed
    work per slice).  Ideal pipelining holds step time ~flat as depth
    scales; the executed rotation schedule genuinely pays the
    (S-1)/(M+S-1) fill/drain bubble, so the rung reports the schedule
    it actually ran (n_ticks, bubble share, per-stage idle ticks)
    beside the measured step time.  pp=1 is the unstaged baseline rung
    through the SAME child path.  Tiny by design: the arm measures the
    pipeline machinery; real-DCN numbers are a ROADMAP carryover."""
    import jax
    import jax.numpy as jnp
    import optax

    from faster_distributed_training_tpu.config import TrainConfig
    from faster_distributed_training_tpu.parallel.mesh import make_mesh
    from faster_distributed_training_tpu.parallel.pipeline import (
        build_pipeline_spec)
    from faster_distributed_training_tpu.cli import build_model
    from faster_distributed_training_tpu.train.state import (
        create_train_state)
    from faster_distributed_training_tpu.train.steps import make_train_step

    devices = jax.devices()
    if len(devices) < pp:
        return {"skipped": f"pp={pp} rung needs {pp} devices, host "
                           f"exposes {len(devices)}"}
    steps = int(os.environ.get("FDT_BENCH_PP_STEPS", "10"))
    cfg = TrainConfig(model="transformer", dataset="synthetic", task="lm",
                      batch_size=16, seq_len=32, n_layers=2 * pp,
                      d_model=64, d_ff=128, n_heads=4,
                      dropout_impl="none", optimizer="sgd",
                      precision="fp32", donate=False, num_classes=4)
    mesh = make_mesh(("dp", "pp"), (1, pp), devices[:pp])
    spec = build_pipeline_spec(cfg, mesh)   # None at pp=1 (baseline rung)
    model = build_model(cfg, vocab_size=256, mesh=None)
    sample = jnp.zeros((cfg.batch_size, cfg.seq_len), jnp.int32)
    state = create_train_state(model, optax.sgd(0.01), sample,
                               jax.random.PRNGKey(0),
                               init_kwargs={"train": True})
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (cfg.batch_size, cfg.seq_len), 0, 256)}
    step_fn = jax.jit(make_train_step(cfg, pipeline=spec), donate_argnums=0)
    with mesh:
        for _ in range(3):
            state, m = step_fn(state, batch)
        jax.block_until_ready(m)
        t0 = time.monotonic()
        for _ in range(steps):
            state, m = step_fn(state, batch)
        jax.block_until_ready(m)
    out = {"elapsed": time.monotonic() - t0, "steps_timed": steps,
           "n_stages": 1 if spec is None else spec.n_stages,
           "n_layers": cfg.n_layers}
    if spec is not None:
        out.update(n_microbatches=spec.n_microbatches,
                   n_ticks=spec.n_ticks,
                   bubble_pct=round(spec.bubble_pct, 2),
                   stage_idle_ticks=spec.n_stages - 1)
    return out


# Serving-latency mixes (r16 serve/ tentpole): one tiny checkpoint,
# three batch/length request mixes through the REAL serve stack —
# continuous-batching queue, AOT-warmed per-bucket programs, 2
# replicas.  "ragged" (full bucket spread, partial batches occur
# naturally) is the headline mix published as serve_p50_ms /
# serve_p99_ms / serve_qps_per_chip; the short/long mixes bound the
# surface (smallest-bucket latency floor vs top-bucket compute).
SERVE_BENCH_MIXES = (
    ("short", 4, 8),       # lengths U[4, 8]: smallest bucket only
    ("ragged", 4, 32),     # lengths U[4, 32]: every bucket + spill
    ("long", 24, 32),      # lengths U[24, 32]: top bucket only
)


def timed_serve(mix: str) -> dict:
    """Serving arm (r16): train a tiny transformer checkpoint, stand up
    the serve/ stack on it (cli.run_serving: AOT-warmed bucket
    programs, continuous batching, 2 replicas) and push one request
    mix through the queue.  Reports nearest-rank p50/p99 request
    latency and sustained qps/chip — the serving tier's headline
    numbers feeding the regression guard.  The model is tiny by
    design: the arm measures the queue/batching/dispatch machinery
    (and the predict program's fixed cost), not the workload."""
    import shutil
    import tempfile

    import numpy as _np

    from faster_distributed_training_tpu.cli import (run_serving,
                                                     run_training)
    from faster_distributed_training_tpu.config import TrainConfig

    lo, hi = next((l, h) for m, l, h in SERVE_BENCH_MIXES if m == mix)
    n_req = int(os.environ.get("FDT_BENCH_SERVE_REQUESTS", "128"))
    d = tempfile.mkdtemp(prefix="fdt_bench_serve_")
    cfg = TrainConfig(model="transformer", dataset="synthetic",
                      num_classes=4, batch_size=8, seq_len=32,
                      seq_buckets=(8, 16, 32), n_layers=1, d_model=16,
                      d_ff=32, n_heads=2, epochs=1, subset_stride=64,
                      optimizer="sgd", precision="fp32", plot=False,
                      workers=0, log_every=0, donate=False,
                      checkpoint_dir=d, checkpoint_every=8,
                      serve_batch_size=8, serve_replicas=2,
                      serve_max_delay_ms=5.0)
    try:
        run_training(cfg, log=lambda *_: None)
        rng = _np.random.default_rng(0)
        reqs = [rng.integers(1, 1000,
                             size=int(rng.integers(lo, hi + 1))
                             ).astype(_np.int32) for _ in range(n_req)]
        out = run_serving(cfg, requests=reqs, log=lambda *_: None)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return {"mix": mix, "requests": out["requests"],
            "batches": out["batches"], "padded_rows": out["padded_rows"],
            "p50_ms": out["p50_ms"], "p99_ms": out["p99_ms"],
            "qps": out["qps"], "qps_per_chip": out["qps_per_chip"]}


# Decode-serving arms (r21 serve/decode tentpole): one tiny LM
# checkpoint per child, the REAL autoregressive stack on it — paged KV
# cache, AOT prefill + per-page-count decode-step program families,
# token-granular continuous batching.  Two arms: decode_gen (closed
# loop — submit everything, measure TTFT percentiles + sustained decode
# throughput per chip) and decode_sustained (open loop — submissions
# PACED at a target QPS so queueing delay surfaces as SLO violations;
# a closed loop self-throttles and can never show an under-provisioned
# decode tier failing).
DECODE_BENCH_SEQ = 16


def _decode_bench_cfg(d):
    """The decode arms' tiny-LM config: stream-corpus next-token
    training at seq 16 with (8, 16) buckets, then single-replica greedy
    decoding at 4 slots over 4-token pages.  Tiny by design — the arms
    measure the prefill/step/admission machinery's fixed cost, not the
    model."""
    from faster_distributed_training_tpu.config import TrainConfig
    return TrainConfig(model="transformer", dataset="stream", task="lm",
                       data_path="stream",
                       stream_dir=os.path.join(d, "stream"),
                       batch_size=8, seq_len=DECODE_BENCH_SEQ,
                       n_layers=1, d_model=16, d_ff=32, n_heads=2,
                       epochs=1, steps_per_dispatch=2, stream_window=4,
                       optimizer="sgd", precision="fp32", plot=False,
                       workers=0, log_every=0, donate=False,
                       checkpoint_dir=os.path.join(d, "ckpt"),
                       seq_buckets=(8, 16), decode_batch_size=4,
                       decode_page=4, decode_replicas=1,
                       decode_max_new_tokens=8, telemetry=False)


def _decode_train_ckpt(cfg):
    from faster_distributed_training_tpu.cli import run_training
    from faster_distributed_training_tpu.data.stream import (
        synthetic_corpus, write_lm_corpus)
    texts = synthetic_corpus(40, seed=3, words_per_doc=(25, 50))
    write_lm_corpus(cfg.stream_dir, texts, seq_len=DECODE_BENCH_SEQ,
                    rows_per_shard=16, val_fraction=0.15)
    run_training(cfg, log=lambda *_: None)


def timed_decode_gen() -> dict:
    """Closed-loop decode arm: train the tiny LM, push
    FDT_BENCH_DECODE_REQUESTS ragged prompts through
    cli.run_decode_serving, report TTFT percentiles + generated tokens
    per second per chip — the decode tier's headline throughput."""
    import shutil
    import tempfile

    from faster_distributed_training_tpu.cli import run_decode_serving

    n_req = int(os.environ.get("FDT_BENCH_DECODE_REQUESTS", "24"))
    d = tempfile.mkdtemp(prefix="fdt_bench_decode_")
    try:
        cfg = _decode_bench_cfg(d)
        _decode_train_ckpt(cfg)
        out = run_decode_serving(cfg.replace(decode_requests=n_req),
                                 log=lambda *_: None)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return {"requests": out["requests"], "tokens": out["tokens"],
            "steps": out["steps"], "prefills": out["prefills"],
            "ttft_p50_ms": out["ttft_p50_ms"],
            "ttft_p99_ms": out["ttft_p99_ms"],
            "tokens_per_sec_per_chip": out["tokens_per_sec_per_chip"]}


def timed_decode_sustained() -> dict:
    """Open-loop decode arm: same tiny LM, single decode replica, but
    submissions arrive PACED at FDT_BENCH_DECODE_QPS regardless of
    completions — arrival-time load, not completion-time load.  A
    request whose total latency exceeds FDT_BENCH_DECODE_SLO_MS counts
    as an SLO violation; the violation percentage is the metric an
    under-provisioned decode tier actually fails on."""
    import shutil
    import tempfile
    import time as _time

    import numpy as _np

    from faster_distributed_training_tpu.models.decode import SamplingCfg
    from faster_distributed_training_tpu.serve import (RequestQueue,
                                                       load_serving_state)
    from faster_distributed_training_tpu.serve.decode import (
        DecodeEngine, DecodeScheduler)

    n_req = int(os.environ.get("FDT_BENCH_DECODE_REQUESTS", "24"))
    qps = float(os.environ.get("FDT_BENCH_DECODE_QPS", "8"))
    slo_ms = float(os.environ.get("FDT_BENCH_DECODE_SLO_MS", "2000"))
    d = tempfile.mkdtemp(prefix="fdt_bench_decode_")
    try:
        cfg = _decode_bench_cfg(d)
        _decode_train_ckpt(cfg)
        model, sstate, meta = load_serving_state(cfg, log=lambda *_: None)
        q = RequestQueue(cfg.seq_buckets, max_len=cfg.seq_len)
        eng = DecodeEngine(model, sstate, q.buckets,
                           batch_size=cfg.decode_batch_size,
                           page=cfg.decode_page,
                           sampling=SamplingCfg(seed=cfg.seed),
                           name="decode0", log=lambda *_: None)
        eng.warmup()
        sched = DecodeScheduler(q, eng,
                                max_new_tokens=cfg.decode_max_new_tokens,
                                name="decode0", log=lambda *_: None)
        sched.start()
        rng = _np.random.default_rng(0)
        vocab = int(meta.get("vocab") or 256)
        prompts = [rng.integers(1, vocab, size=int(rng.integers(3, 13))
                                ).astype(_np.int32) for _ in range(n_req)]
        handles = []
        t0 = _time.monotonic()
        for i, p in enumerate(prompts):
            # open loop: the i-th arrival is scheduled at t0 + i/qps no
            # matter how far behind the decoder is running
            lag = t0 + i / qps - _time.monotonic()
            if lag > 0:
                _time.sleep(lag)
            handles.append(
                q.submit(p, max_new_tokens=cfg.decode_max_new_tokens))
        for h in handles:
            h.wait(timeout=300.0)
        q.close()
        sched.close()
    finally:
        shutil.rmtree(d, ignore_errors=True)
    lat = [h.latency_ms() for h in handles]
    viol = sum(1 for t in lat if t is None or t > slo_ms)
    return {"requests": len(handles), "target_qps": qps,
            "slo_ms": slo_ms,
            "slo_violation_pct": round(
                100.0 * viol / max(len(lat), 1), 1)}


def zero_opt_state_bytes(zero: bool) -> dict:
    """Per-chip state bytes of the ResNet-50/NGD train state on a
    dp x tp=2 mesh with the ZeRO opt-state overlay on or off — the
    post-ZeRO twin of the r15 replicated baseline the tentpole is
    measured against (no stepping: placement is what's being sized)."""
    import jax
    import jax.numpy as jnp

    from faster_distributed_training_tpu.cli import build_model
    from faster_distributed_training_tpu.config import TrainConfig
    from faster_distributed_training_tpu.optim import build_optimizer
    from faster_distributed_training_tpu.parallel import make_mesh
    from faster_distributed_training_tpu.parallel.placement import (
        shard_train_state, train_state_shardings)
    from faster_distributed_training_tpu.telemetry.programs import (
        state_bytes_table)
    from faster_distributed_training_tpu.train import create_train_state

    n_dev = jax.device_count()
    if n_dev < 2:
        return {"skipped": f"tp=2 sizing needs >=2 chips, host exposes "
                           f"{n_dev}"}
    cfg = TrainConfig(model="resnet50", dataset="synthetic",
                      batch_size=64, use_ngd=True, optimizer="ngd",
                      precision="bf16", mesh_axes=("dp", "tp"),
                      mesh_shape=(n_dev // 2, 2), zero_opt=zero)
    mesh = make_mesh(cfg.mesh_axes, cfg.mesh_shape)
    model = build_model(cfg)
    tx, _ = build_optimizer(cfg, steps_per_epoch=10)
    sample = jnp.zeros((8, 32, 32, 3), jnp.float32)
    state = create_train_state(model, tx, sample, jax.random.PRNGKey(0),
                               init_kwargs={"train": True})
    with mesh:
        sh = train_state_shardings(state, mesh, cfg)
        state = shard_train_state(state, mesh, cfg, shardings=sh)
        table = state_bytes_table(state)
    return {"zero_opt": bool(zero),
            "params_bytes_per_chip": int(table["params_bytes_per_chip"]),
            "opt_state_bytes_per_chip": int(
                table["opt_state_bytes_per_chip"]),
            "opt_state_tiers": table.get("opt_state_tiers") or {}}


def pp_residency_bytes(staged: bool) -> dict:
    """Per-chip param + opt-state bytes of a layer-dominated transformer
    train state on a dp x pp=2 mesh with per-stage residency on
    (``staged``) vs the r22 replicated-over-pp layout (``--no_pp_
    residency``) — the zero_opt_state_bytes idiom applied to the r23
    tentpole.  No stepping: placement is what's being sized.  The model
    is sized so the per-layer stack dominates the shared embedding
    tables (the stage-owned fraction is what residency divides by S, so
    a tiny embeddings-heavy config would understate the ratio real
    models see).  zero_opt is OFF in both twins so the pair isolates
    the residency reduction alone; the ZeRO-over-pp composition is
    pinned functionally by tests/test_pp_residency.py."""
    import jax
    import jax.numpy as jnp

    from faster_distributed_training_tpu.cli import build_model
    from faster_distributed_training_tpu.config import TrainConfig
    from faster_distributed_training_tpu.optim import build_optimizer
    from faster_distributed_training_tpu.parallel import make_mesh
    from faster_distributed_training_tpu.parallel.pipeline import (
        build_pipeline_spec)
    from faster_distributed_training_tpu.parallel.placement import (
        shard_train_state, train_state_shardings)
    from faster_distributed_training_tpu.telemetry.programs import (
        state_bytes_table)
    from faster_distributed_training_tpu.train import create_train_state

    n_dev = jax.device_count()
    if n_dev < 4:
        return {"skipped": f"dp x pp=2 sizing needs >=4 chips, host "
                           f"exposes {n_dev}"}
    cfg = TrainConfig(model="transformer", dataset="synthetic", task="lm",
                      batch_size=8, seq_len=64, n_layers=8, d_model=128,
                      d_ff=512, n_heads=4, dropout_impl="none",
                      optimizer="adamw", precision="fp32",
                      mesh_axes=("dp", "pp"), mesh_shape=(2, 2),
                      zero_opt=False, pp_residency=staged)
    mesh = make_mesh(cfg.mesh_axes, cfg.mesh_shape, jax.devices()[:4])
    model = build_model(cfg, vocab_size=256, mesh=None)
    tx, _ = build_optimizer(cfg, steps_per_epoch=10)
    sample = jnp.zeros((8, cfg.seq_len), jnp.int32)
    state = create_train_state(model, tx, sample, jax.random.PRNGKey(0),
                               init_kwargs={"train": True})
    pipeline = build_pipeline_spec(cfg, mesh)
    with mesh:
        sh = train_state_shardings(state, mesh, cfg, pipeline=pipeline)
        state = shard_train_state(state, mesh, cfg, shardings=sh)
        table = state_bytes_table(state)
    return {"pp_residency": bool(staged),
            "params_bytes_per_chip": int(table["params_bytes_per_chip"]),
            "opt_state_bytes_per_chip": int(
                table["opt_state_bytes_per_chip"]),
            "pp_residency_table": table.get("pp_residency") or {}}


def timed_fused(model: str, k: int, bs: int, seq: int, steps: int,
                overlap=None, offload: bool = False) -> dict:
    """K-step fused dispatch arm (r8 tentpole): the full train program on
    DEVICE-RESIDENT synthetic data, K steps per dispatch
    (steps.make_fused_train_step over data/device_resident.py) — the
    configuration whose per-step time the transformer_bs256_seq256_k{K}_
    step_ms / resnet_bs512_k{K}_step_ms arms track.  The K=1 cell is the
    dispatch-per-step floor on the SAME resident path, so the K ladder
    isolates dispatch amortization from data-path effects; uint8 images
    are augmented in-step (the real pipeline), tokens run as-is.

    overlap (ISSUE 16): None = the legacy ladder program.  True/False =
    the overlap A/B pair — BOTH arms route through train_state_shardings
    (the program shape with the ZeRO overlay), differing only in
    cfg.overlap_grad_reduce, so the pair isolates the bucketed
    reduce-scatter reshard.  offload=True adds --offload_opt_state (on a
    backend without pinned_host the step degrades it to off; the arm
    then measures the same program — read the pair on TPU)."""
    import jax
    import jax.numpy as jnp

    from faster_distributed_training_tpu.cli import (build_model,
                                                     enable_compilation_cache)
    from faster_distributed_training_tpu.config import (TrainConfig,
                                                        resolve_tricks)
    from faster_distributed_training_tpu.data import (DeviceResidentData,
                                                      synthetic_agnews,
                                                      synthetic_cifar)
    from faster_distributed_training_tpu.optim import build_optimizer
    from faster_distributed_training_tpu.parallel import make_mesh
    from faster_distributed_training_tpu.parallel.placement import (
        shard_train_state)
    from faster_distributed_training_tpu.train import (
        create_train_state, make_fused_train_step)

    enable_compilation_cache()
    mesh = make_mesh(("dp",))
    is_text = model == "transformer"
    cfg = resolve_tricks(TrainConfig(
        model=model, dataset="synthetic", num_classes=4 if is_text else 10,
        batch_size=bs, seq_len=seq or 512, use_ngd=True, optimizer="ngd",
        precision="bf16", epochs=1, steps_per_dispatch=k,
        data_path="resident", tricks="on",
        overlap_grad_reduce=bool(overlap), offload_opt_state=offload))
    sharded_state = overlap is not None or offload
    # enough resident steps/epoch to cover ONE K-dispatch in-bounds
    # (dynamic_slice would silently CLAMP an out-of-range start to the
    # last window, re-training the final batch instead of wrapping);
    # successive dispatches wrap the order via `span` below
    n = bs * max(8, k)
    if is_text:
        ds = synthetic_agnews(n, max_len=seq)
        resident = DeviceResidentData(ds, bs, seed=cfg.seed, max_len=seq,
                                      mesh=mesh)
        model_obj = build_model(cfg, vocab_size=ds.vocab_size(), mesh=mesh)
        sample = jnp.zeros((bs, resident.seq_len), jnp.int32)
    else:
        ds = synthetic_cifar(n)
        resident = DeviceResidentData(ds, bs, seed=cfg.seed, mesh=mesh)
        model_obj = build_model(cfg)
        sample = jnp.zeros((bs, 32, 32, 3), jnp.float32)
    rng = jax.random.PRNGKey(cfg.seed)
    tx, _ = build_optimizer(cfg, steps_per_epoch=resident.steps_per_epoch)
    state = create_train_state(model_obj, tx, sample, rng,
                               init_kwargs={"train": True})
    with mesh:
        sh = None
        if sharded_state:
            from faster_distributed_training_tpu.parallel.placement import (
                train_state_shardings)
            sh = train_state_shardings(state, mesh, cfg)
            state = shard_train_state(state, mesh, cfg, shardings=sh)
        else:
            state = shard_train_state(state, mesh, cfg)
        fused = jax.jit(make_fused_train_step(cfg, k, state_shardings=sh,
                                              resident=resident,
                                              mesh=mesh), donate_argnums=0)
        order = resident.epoch_order(0)
        span = max(resident.steps_per_epoch - k + 1, 1)
        n_dispatch = max(-(-steps // k), 1)
        # warm past NGD's always-update phase (the Fisher refresh runs
        # EVERY step while t < 10 — same policy as timed_resnet) and the
        # compile, so the timed window is steady state
        for w in range(max(2, -(-12 // k))):
            state, metrics = fused(state, resident.arrays, order,
                                   jnp.asarray(w % span, jnp.int32))
        _fence(metrics)
        t0 = time.monotonic()
        for d in range(n_dispatch):
            state, metrics = fused(state, resident.arrays, order,
                                   jnp.asarray((d * k) % span, jnp.int32))
        _fence(metrics)
        return {"model": model, "k": k, "bs": bs, "seq": seq,
                "elapsed": time.monotonic() - t0,
                "steps_timed": n_dispatch * k}


def timed_data_path(path: str, bs: int, steps: int) -> dict:
    """data_path_{host,resident,stream} A/B arm (r8 tentpole; stream
    r18): the SAME ResNet NGD train program fed by (a) the host
    pipeline — BatchLoader + PrefetchIterator + device_prefetch staging,
    per-batch H2D — or (b) the device-resident path (split uploaded
    once, batches gathered in-graph), or (c) the streamed path (split
    sharded to DISK in the stream format, trained through the
    double-buffered device window — data/stream/), all at
    steps_per_dispatch=1 so the delta is purely the input path, not
    dispatch fusion.  Includes ALL steady-state data work, which the
    synthetic-device-array arms above deliberately exclude.  The stream
    run additionally returns ``stall_s`` — time the consumer blocked on
    the window refill during the timed span — from which main()
    publishes ``stream_stall_pct`` (<1% steady-state target, the input
    pipeline's ``ckpt_async_overhead_pct`` sibling)."""
    import jax
    import jax.numpy as jnp

    from faster_distributed_training_tpu.cli import (build_model,
                                                     enable_compilation_cache)
    from faster_distributed_training_tpu.config import (TrainConfig,
                                                        resolve_tricks)
    from faster_distributed_training_tpu.data import (BatchLoader,
                                                      DeviceResidentData,
                                                      PrefetchIterator,
                                                      synthetic_cifar)
    from faster_distributed_training_tpu.data.loader import device_prefetch
    from faster_distributed_training_tpu.optim import build_optimizer
    from faster_distributed_training_tpu.parallel import make_mesh
    from faster_distributed_training_tpu.parallel.placement import (
        make_put_batch, shard_train_state)
    from faster_distributed_training_tpu.train import (
        create_train_state, make_fused_train_step, make_train_step)

    enable_compilation_cache()
    mesh = make_mesh(("dp",))
    cfg = resolve_tricks(TrainConfig(
        model="resnet50", batch_size=bs, use_ngd=True, optimizer="ngd",
        precision="bf16", epochs=1, data_path=path, tricks="on"))
    # the stream arm wants warmup+timed to fit ONE epoch (so the timed
    # span sees steady double-buffered refills, not epoch-boundary
    # window restarts) — sized from the requested step count so
    # FDT_BENCH_K_STEPS can't run the window off the end of the epoch;
    # host/resident cycle an 8-step split like r8
    data = synthetic_cifar(bs * (12 + steps + 8 if path == "stream" else 8))
    rng = jax.random.PRNGKey(cfg.seed)
    sample = jnp.zeros((bs, 32, 32, 3), jnp.float32)
    tx, _ = build_optimizer(cfg, steps_per_epoch=8)
    model_obj = build_model(cfg)
    state = create_train_state(model_obj, tx, sample, rng,
                               init_kwargs={"train": True})
    with mesh:
        state = shard_train_state(state, mesh, cfg)
        if path == "stream":
            import tempfile

            from faster_distributed_training_tpu.data.stream import (
                DiskStreamSource, ShardedStreamDataset, write_array_dataset)
            import shutil

            sdir = tempfile.mkdtemp(prefix="fdt_bench_stream_")
            win = None
            try:
                x, y = data
                write_array_dataset(sdir, {"image": x, "label": y},
                                    rows_per_shard=bs * 4)
                src = DiskStreamSource(ShardedStreamDataset(sdir), bs,
                                       seed=cfg.seed, mesh=mesh,
                                       window_batches=8)
                fused = jax.jit(make_fused_train_step(cfg, 1, resident=src,
                                                      mesh=mesh),
                                donate_argnums=0)
                win = src.epoch_window(0)

                def run_span(n0, count):
                    nonlocal state
                    m = None
                    for i in range(n0, n0 + count):
                        base, _hi, dev = win.buffer_for(i)
                        state, m = fused(state, dev, src.dummy_order,
                                         jnp.asarray(i - base, jnp.int32))
                    return m

                _fence(run_span(0, 12))      # past NGD's always-update phase
                stall0 = win.stall_s
                t0 = time.monotonic()
                _fence(run_span(12, steps))
                elapsed = time.monotonic() - t0
                stall = win.stall_s - stall0
            finally:
                if win is not None:     # refill thread never outlives
                    win.close()         # the arm, even on a mid-span crash
                # ~75 MB of shards per rep otherwise accumulates in /tmp
                shutil.rmtree(sdir, ignore_errors=True)
            return {"path": path, "bs": bs, "elapsed": elapsed,
                    "steps_timed": steps, "stall_s": stall}
        if path == "resident":
            resident = DeviceResidentData(data, bs, seed=cfg.seed,
                                          mesh=mesh)
            fused = jax.jit(make_fused_train_step(cfg, 1, resident=resident,
                                                  mesh=mesh),
                            donate_argnums=0)
            order = resident.epoch_order(0)
            for w in range(12):      # past NGD's always-update phase
                state, metrics = fused(state, resident.arrays, order,
                                       jnp.asarray(w % 8, jnp.int32))
            _fence(metrics)
            t0 = time.monotonic()
            for i in range(steps):
                state, metrics = fused(state, resident.arrays, order,
                                       jnp.asarray(i % 8, jnp.int32))
            _fence(metrics)
            elapsed = time.monotonic() - t0
        else:
            put = make_put_batch(mesh)
            step = jax.jit(make_train_step(cfg), donate_argnums=0)

            def stream():
                epoch = 0
                while True:
                    loader = PrefetchIterator(
                        BatchLoader(data, bs, epoch=epoch, seed=cfg.seed),
                        depth=cfg.prefetch_depth)
                    yield from device_prefetch(loader, put,
                                               depth=cfg.prefetch_depth)
                    epoch += 1

            it = stream()
            for _ in range(12):
                state, metrics = step(state, next(it))
            _fence(metrics)
            t0 = time.monotonic()
            for _ in range(steps):
                state, metrics = step(state, next(it))
            _fence(metrics)
            elapsed = time.monotonic() - t0
    return {"path": path, "bs": bs, "elapsed": elapsed,
            "steps_timed": steps}


BENCH_LATEST = "BENCH_LATEST.json"


def _bench_dir() -> str:
    return os.path.dirname(os.path.abspath(__file__))


def _load_bench_record(path):
    """One bench artifact -> metric record, or None.  Handles the
    committed full record (BENCH_LATEST.json), the driver wrapper
    {n, cmd, rc, tail, parsed} — using `parsed` when it is a dict, else
    scanning the captured tail for a parseable JSON line — and a bare
    record.  A wrapper whose tail is a truncated mid-record fragment
    (the r5 failure mode, VERDICT r5 #1) yields None instead of the
    metric-less wrapper itself."""
    try:
        with open(path) as fh:
            rec = json.load(fh)
    except Exception:
        return None
    if not isinstance(rec, dict):
        return None
    if "tail" in rec or "parsed" in rec:          # driver wrapper
        parsed = rec.get("parsed")
        if isinstance(parsed, dict):
            return parsed
        for line in reversed(str(rec.get("tail", "")).splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    cand = json.loads(line)
                except Exception:
                    continue
                if isinstance(cand, dict) and ("value" in cand
                                               or "essentials" in cand):
                    return cand
        return None
    if "value" in rec or "metric" in rec or "essentials" in rec:
        return rec
    return None


def _is_live_record(rec) -> bool:
    """True iff `rec` is a LIVE bench-produced full record — not the r5
    `record_note` reconstruction (re-emitted prose/partial numbers, no
    `bench_unix_time`).  The r6/r7 standing note: A/B `*_step_ms` pairs
    drive the PARITY lever-flip procedure, so the guard must never
    compare them against a reconstructed baseline (a fabricated delta
    could flip a default on zero evidence)."""
    return (isinstance(rec, dict)
            and "record_note" not in rec
            and bool(rec.get("bench_unix_time")))


def _prev_bench_record():
    """(record, filename) for the round-over-round regression guard
    (VERDICT r4 #2c, repaired per VERDICT r5 #1): the NEWEST parseable
    record among the driver-captured BENCH_r*.json wrappers and the
    committed BENCH_LATEST.json (written by bench itself every run so a
    truncated driver tail can never orphan a round again).  Unparseable
    wrappers (r5's `parsed: null` mid-record fragment) are skipped, not
    returned.  Newness = (bench_unix_time, full-record-over-essentials,
    round number); when the newest driver tail carries only the compact
    essentials line of the same run, BENCH_LATEST's full record wins the
    tie on bench_unix_time."""
    import glob
    import re as _re

    here = _bench_dir()
    candidates = []   # (time, is_full, round_rank, rec, name)
    for f in glob.glob(os.path.join(here, "BENCH_r*.json")):
        m = _re.search(r"BENCH_r(\d+)\.json$", f)
        if not m:
            continue
        rec = _load_bench_record(f)
        if rec is None:
            continue
        candidates.append((float(rec.get("bench_unix_time", 0) or 0),
                           0 if rec.get("essentials") else 1,
                           int(m.group(1)), rec, os.path.basename(f)))
    latest = _load_bench_record(os.path.join(here, BENCH_LATEST))
    if latest is not None:
        candidates.append((float(latest.get("bench_unix_time", 0) or 0),
                           0 if latest.get("essentials") else 1,
                           1 << 30, latest, BENCH_LATEST))
    if not candidates:
        return None, None
    _, _, _, rec, name = max(candidates, key=lambda c: c[:3])
    return rec, name


# tracked-metric direction rules for the regression guard: a move the
# WRONG way past the metric's noise threshold vs the previous round's
# BENCH_r*.json is flagged in-record.  Thresholds are per-metric-class,
# set ABOVE each metric's documented run-to-run noise so the permanent
# record doesn't accumulate false alarms (PARITY.md: the ladder shows
# >10% variance on the attention ladder and ±1 percentage point on the
# NGD-overhead ratio; throughputs are stable to well under 5%).
_HIGHER_IS_BETTER = ("value", "tricks_speedup", "ex_per_sec",
                     "img_per_sec", "achieved_tflops", "mfu_pct",
                     "gemm_ceiling", "qps_per_chip",
                     "tokens_per_sec_per_chip")
_LOWER_IS_BETTER = ("attn_fwdbwd_ms", "peak_mem_bytes", "step_ms",
                    "bytes_per_chip", "p50_ms", "p99_ms")
_REL_THRESHOLD = {"attn_fwdbwd_ms": 0.25,   # ladder: >10% run-to-run variance
                  "step_ms": 0.10,          # per-step times: modest noise
                  "p50_ms": 0.50,           # serve latency percentiles on
                  "p99_ms": 0.60,           # a shared CPU host: scheduler
                  #                           sleeps + thread timing noise
                  #                           dominate; the qps arm is the
                  #                           tighter serving signal
                  "qps_per_chip": 0.35,
                  # decode throughput shares the serving class: thread
                  # scheduling + per-step dispatch noise on a shared CPU
                  # host, tightened further by its measured noise band
                  "tokens_per_sec_per_chip": 0.35,
                  "peak_mem_bytes": 0.02,   # compiled memory: deterministic
                  "bytes_per_chip": 0.02}   # state-byte attribution:
#                                             deterministic (a move means
#                                             the state tree itself moved)
_DEFAULT_REL_THRESHOLD = 0.05
# percentage-POINT metrics get an absolute tolerance instead (a relative
# threshold on a small ratio amplifies noise: 5.2% -> 6.0% is +15%
# "relative" but within the documented ±1 pp run-to-run noise)
_ABS_PP_WORSE_IF_UP = {"ngd_overhead_pct": 1.5,
                       # r12 observability claim: the per-dispatch
                       # recorder costs <1% of median step — a round
                       # that moves the measured overhead up by a full
                       # percentage point has put real work on the hot
                       # path and gets flagged
                       "telemetry_overhead_pct": 1.0,
                       # r18 streaming claim: <1% of streamed step time
                       # blocked on the window refill at steady state —
                       # a +1pp move means the double-buffered H2D
                       # stopped hiding under compute
                       "stream_stall_pct": 1.0,
                       # r21 decode tier: open-loop sustained load at
                       # the target QPS must stay inside the SLO; a
                       # +5pp move in the violation rate means the
                       # decode loop lost real headroom (the wide
                       # tolerance absorbs CPU-host scheduler jitter
                       # on a ~24-request sample: one request = ~4pp)
                       "decode_slo_violation_pct": 5.0,
                       # r22 pp tentpole: the executed schedule's
                       # fill/drain bubble share, (S-1)/(M+S-1), at the
                       # headline rung — analytic from the schedule the
                       # program actually ran, so a move means the
                       # stage/microbatch resolution itself changed
                       # (e.g. auto-microbatching picked a smaller M);
                       # 5pp absorbs one step of the M ladder
                       "pipeline_bubble_pct": 5.0,
                       # r24 robustness claim: the anomaly sentinel's
                       # in-graph guard + host spike detector cost <1%
                       # of median step — a +1pp move means the guard
                       # stopped fusing into the grad-norm pass (or the
                       # detector grew real host work)
                       "sentinel_overhead_pct": 1.0}
# -- guard-drift registry (r13 satellite; scripts/check_bench_arms.py) --
# Every record key a bench arm can emit, as fnmatch patterns.  The lint
# cross-checks this registry against (a) the *_step_ms string literals
# actually present in this file's source (AST scan — a new arm whose key
# matches no pattern fails the lint, so arms can't silently fall out of
# the regression gate) and (b) _EXPECTED_MOVES/_ABS_PP_WORSE_IF_UP
# (every guard-named metric must be producible).  *_step_ms patterns
# additionally must either appear in NOISE_BANDED_STEP_MS (the r6
# N-interleaved protocol publishes a *_noise_band_pct beside them) or be
# consciously allowlisted in SINGLE_RUN_STEP_MS with the reason class
# documented here: single-run arms predate the noise protocol and their
# guard threshold is the 10% step_ms class default instead of a measured
# band.
PRODUCED_METRIC_PATTERNS = (
    "value", "vs_baseline", "ngd_overhead_pct",
    "resnet_ngd_step_ms", "resnet_sgd_step_ms",
    "compiled_peak_mem_bytes",
    # r15 HBM attribution (the ZeRO-item baseline): per-chip bytes of
    # the primary program's train state, params vs optimizer state
    "params_bytes_per_chip", "opt_state_bytes_per_chip",
    # ISSUE 16 ZeRO tentpole: the dp x tp=2 sizing twins (post-ZeRO vs
    # forced-replicated opt state; the "resnet_bs512_k*_step_ms" pattern
    # above also covers the resnet_bs512_k{1,4}_overlap_{on,off}_step_ms
    # A/B pair), plus the single-run host-offload attribution probe
    "opt_state_bytes_per_chip_tp2_*", "params_bytes_per_chip_tp2",
    "opt_state_zero_reduction_x", "opt_offload_step_ms",
    "transformer_agnews_ex_per_sec_*", "transformer_ex_per_sec_*",
    # per-config train arms: EXACT keys, not a transformer_bs*_seq*
    # wildcard — a wildcard here would swallow every future
    # transformer_*_step_ms arm at lint rule 1 and the single-run
    # allowlist below, making the noise-protocol check vacuous
    "transformer_bs256_seq256_step_ms",
    "transformer_bs64_seq512_step_ms",
    "transformer_bs256_seq512_step_ms",
    "transformer_bs256_seq512_remat_step_ms",
    "transformer_bs*_seq*_model_tflops_per_step",
    "transformer_bs*_seq*_achieved_tflops_per_chip",
    "transformer_bs*_seq*_mfu_pct",
    "transformer_bs*_seq*_peak_mem_bytes",
    "transformer_bs*_seq*_xla_gb_per_step",
    "transformer_bs*_seq*_policy",
    "transformer_gemm_ceiling_*",
    "tricks_speedup_*",
    "attn_route_bs512_seq*_*_step_ms",         # 1D route cells (1 run)
    "attn_route_bs1024_seq*_*_step_ms",
    "attn_route_bs256_seq384_*_step_ms",
    "attn_route_bs8_seq2048_*_step_ms",        # route2d (interleaved)
    "attn_route_bs4_seq4096_*_step_ms",
    "attn_fwdbwd_ms_L*",
    "transformer_bs256_seq256_ln_autodiff_step_ms",
    "transformer_bs64_seq512_flash_recompute_step_ms",
    "ckpt_*_median_step_ms", "ckpt_*_mean_step_ms",
    "ckpt_*_blocking_ms_per_save", "ckpt_*_overhead_pct",
    "restart_mttr_s", "restart_mttr_*_s",
    "restart_slice_mttr_s", "restart_slice_mttr_*_s",
    # r17 instant restart: cached-relaunch twin + warm-spare swap
    "restart_cached_mttr_s", "restart_cached_mttr_*_s",
    "restart_cached_deserialized_programs",
    "warm_spare_swap_s", "warm_spare_hold_s",
    "telem_on_median_step_ms", "telem_off_median_step_ms",
    "telemetry_overhead_pct",
    # r24 robustness arm: in-graph bad-step guard + host spike detector
    # on vs off (interleaved), overhead held <1% by the guard above
    "sentinel_on_median_step_ms", "sentinel_off_median_step_ms",
    "sentinel_overhead_pct",
    "transformer_bs256_seq256_quant_off_step_ms",   # r13 quant A/B
    "transformer_bs256_seq256_int8_step_ms",
    "transformer_bs256_seq256_fp8_step_ms",
    # r19 FP8-LM completion: fp8 forward + E5M2 JIT-scaled gradient
    # quantization (its A/B twin is the fp8 arm above)
    "transformer_bs256_seq256_fp8_e5m2_grad_step_ms",
    # r19 shard_map kernel layer: per recovered kernel on a dp x tp=2
    # mesh, kernel-via-shard_map vs forced fallback (FDT_KERNEL_SHARD=0)
    "transformer_tp2_*_step_ms",
    "quant_peak_tflops_assumed",
    "transformer_bs256_seq256_k*_step_ms",     # r8 K ladder
    "resnet_bs512_k*_step_ms",
    "data_path_host_step_ms", "data_path_resident_step_ms",
    # r18 streaming tier: the disk-windowed input path's step time +
    # steady-state stall fraction (<1% target, guard below)
    "data_path_stream_step_ms", "stream_stall_pct",
    "resnet_eval_img_per_sec_*", "transformer_eval_ex_per_sec_*",
    # r16 serving arms (serve/ tentpole): nearest-rank request-latency
    # percentiles + sustained throughput per mix, ragged = headline
    "serve_*_p50_ms", "serve_*_p99_ms", "serve_*_qps_per_chip",
    "serve_p50_ms", "serve_p99_ms", "serve_qps_per_chip",
    # r21 decode arms (serve/decode tentpole): closed-loop generation
    # throughput + TTFT percentiles, and the open-loop sustained arm's
    # SLO-violation rate at the target QPS (guard above)
    "decode_tokens_per_sec_per_chip",
    "decode_ttft_p50_ms", "decode_ttft_p99_ms",
    "decode_slo_violation_pct",
    # r22 pipeline arms (pp tentpole): weak-scaling ladder over
    # simulated pods of {1,2,4} slices (pp = one stage per slice, depth
    # grown with the slice count) + the executed schedule's bubble
    # share and per-stage idle time from the headline (largest) rung.
    # EXACT rung keys, not a weak_scaling_* wildcard — same reasoning
    # as the per-config transformer arms above.
    "weak_scaling_slice1_step_ms",
    "weak_scaling_slice2_step_ms",
    "weak_scaling_slice4_step_ms",
    "pipeline_bubble_pct", "pp_stage_idle_ms",
    # r23 per-stage residency (ISSUE 19 tentpole): dp x pp=2 sizing
    # twins — per-chip param/opt-state bytes with stage-owned leaves
    # sharded over pp vs the r22 replicated-over-pp layout, plus the
    # reduction ratios the headline quotes (~S x at pp=S for the
    # layer-dominated fraction)
    "pp_param_bytes_per_chip_pp2_*",
    "pp_opt_state_bytes_per_chip_pp2_*",
    "pp_param_residency_reduction_x",
    "pp_opt_state_residency_reduction_x",
)
# *_step_ms arms measured N-interleaved with a published noise band:
NOISE_BANDED_STEP_MS = (
    "telem_on_median_step_ms", "telem_off_median_step_ms",
    "sentinel_on_median_step_ms", "sentinel_off_median_step_ms",
    "transformer_bs256_seq256_quant_off_step_ms",
    "transformer_bs256_seq256_int8_step_ms",
    "transformer_bs256_seq256_fp8_step_ms",
    "transformer_bs256_seq256_fp8_e5m2_grad_step_ms",
    "transformer_tp2_*_step_ms",
    "transformer_bs256_seq256_k*_step_ms",
    "resnet_bs512_k*_step_ms",
    "data_path_host_step_ms", "data_path_resident_step_ms",
    "data_path_stream_step_ms",
    "attn_route_bs8_seq2048_*_step_ms",        # route2d (interleaved)
    "attn_route_bs4_seq4096_*_step_ms",
)
# single-run *_step_ms arms, consciously exempt from the band protocol
# (pre-r6 arms and one-shot attribution probes; class threshold 10%):
SINGLE_RUN_STEP_MS = (
    "resnet_ngd_step_ms", "resnet_sgd_step_ms",
    # the per-config train arms — exact keys (see the PRODUCED note)
    "transformer_bs256_seq256_step_ms",
    "transformer_bs64_seq512_step_ms",
    "transformer_bs256_seq512_step_ms",
    "transformer_bs256_seq512_remat_step_ms",
    "attn_route_bs512_seq*_*_step_ms",         # 1D route cells (1 run)
    "attn_route_bs1024_seq*_*_step_ms",
    "attn_route_bs256_seq384_*_step_ms",
    "transformer_bs256_seq256_ln_autodiff_step_ms",
    "transformer_bs64_seq512_flash_recompute_step_ms",
    "ckpt_*_median_step_ms", "ckpt_*_mean_step_ms",
    # ISSUE 16 offload probe: one-shot attribution arm; its baseline is
    # resnet_bs512_k1_step_ms published beside it (banding the pair
    # would re-measure the ladder cell a third time for no information)
    "opt_offload_step_ms",
    # r22 weak-scaling rungs: single-run simulated-pod arms (like
    # restart_slice_mttr — each rung spins up a virtual multi-slice
    # pod; interleaving the ladder N times would triple a machinery
    # measurement whose real-DCN twin is a ROADMAP carryover anyway)
    "weak_scaling_slice1_step_ms",
    "weak_scaling_slice2_step_ms",
    "weak_scaling_slice4_step_ms",
)

# documented intentional trades: still FLAGGED (honesty first) but
# annotated so a flagged record self-explains instead of reading as an
# unexplained regression
_EXPECTED_MOVES = {
    "transformer_bs256_seq256_peak_mem_bytes": (
        "intentional r5 trade: auto-routed dense attention materializes "
        "the [B,H,L,L] probs (~+1.6 GB) for +13-15% throughput at this "
        "config (PARITY.md, resolve_attention)"),
    "transformer_bs64_seq512_peak_mem_bytes": (
        "intentional r6 trade: the monolithic flash forward now emits "
        "the row lse as a backward residual (saved-stats backward skips "
        "the in-kernel softmax recompute, ops/flash_attention.py); the "
        "128-lane lse buffer costs ~130 MB transient at this shape — "
        "FDT_FLASH_SAVE_STATS=0 restores the recompute backward"),
    "ngd_overhead_pct": (
        "noise-sensitive ratio; diagnose with the absolute "
        "resnet_{ngd,sgd}_step_ms arms published beside it"),
}


def _find_regressions(record: dict, prev: dict, check_missing: bool = True,
                      compare_step_ms: bool = True):
    """[{metric, prev, now, change_pct}] for tracked numeric metrics that
    moved past their noise threshold in the harmful direction since the
    previous round.  A tracked metric PRESENT last round but MISSING now
    (e.g. its _run_child subprocess died) is flagged too — a silently
    vanished metric must not read as a clean round.  check_missing=False
    suppresses that (an INTENTIONAL opt-out like FDT_BENCH_FAST=1 must
    not flood the record with missing:true noise); the primary `value`/
    memory comparison is skipped when the two records' `metric` names
    differ (e.g. a different FDT_BENCH_BS configuration).
    compare_step_ms=False excludes every `*_step_ms` key — main() passes
    it when the baseline is not a live record (_is_live_record), because
    the A/B step-ms pairs feed the PARITY lever-flip procedure and must
    only ever be judged against measured numbers."""
    out = []
    tracked = (_HIGHER_IS_BETTER + _LOWER_IS_BETTER
               + tuple(_ABS_PP_WORSE_IF_UP))
    if check_missing:
        for key, was in prev.items():
            if (isinstance(was, (int, float)) and not isinstance(was, bool)
                    and key not in record
                    and not key.endswith("_noise_band_pct")
                    and (compare_step_ms or "step_ms" not in key)
                    and any(p in key for p in tracked)):
                out.append({"metric": key, "prev": was, "now": None,
                            "missing": True})
    same_config = record.get("metric") == prev.get("metric")
    for key, now in record.items():
        if key in ("value", "compiled_peak_mem_bytes") and not same_config:
            continue
        if key.endswith("_noise_band_pct"):   # metadata, not a metric
            continue
        if not compare_step_ms and "step_ms" in key:
            continue
        if not isinstance(now, (int, float)) or isinstance(now, bool):
            continue
        was = prev.get(key)
        if not isinstance(was, (int, float)):
            continue
        if key in _ABS_PP_WORSE_IF_UP:
            if now - was > _ABS_PP_WORSE_IF_UP[key]:
                out.append(_regression_entry(
                    key, was, now, round(now - was, 1),
                    f"+{_ABS_PP_WORSE_IF_UP[key]}pp"))
            continue
        if was == 0:
            continue
        worse_if_down = any(p in key for p in _HIGHER_IS_BETTER)
        worse_if_up = any(p in key for p in _LOWER_IS_BETTER)
        if worse_if_down == worse_if_up:   # untracked or ambiguous key
            continue
        thr = next((t for p, t in _REL_THRESHOLD.items() if p in key),
                   _DEFAULT_REL_THRESHOLD)
        # VERDICT r5 #2: metrics with a MEASURED noise band (N interleaved
        # re-runs, *_noise_band_pct published beside them) set their
        # threshold from the data — the larger of the class threshold and
        # either round's observed band
        band = max(float(prev.get(f"{key}_noise_band_pct") or 0.0),
                   float(record.get(f"{key}_noise_band_pct") or 0.0)) / 100.0
        thr = max(thr, band)
        change = (now - was) / abs(was)
        if (worse_if_down and change < -thr) or (worse_if_up and change > thr):
            out.append(_regression_entry(key, was, now,
                                         round(change * 100.0, 1),
                                         f"{thr:.0%}",
                                         band_pct=round(band * 100.0, 1)
                                         if band else None))
    return out


def _regression_entry(key, prev, now, change_pct, threshold, band_pct=None):
    entry = {"metric": key, "prev": prev, "now": now,
             "change_pct": change_pct, "threshold": threshold}
    notes = []
    if band_pct:
        notes.append(f"threshold includes the measured interleaved-re-run "
                     f"noise band ({band_pct}% of median) — the move is "
                     f"outside it")
    if key in _EXPECTED_MOVES:
        notes.append(_EXPECTED_MOVES[key])
    if notes:
        entry["note"] = "; ".join(notes)
    return entry


def timed_eval(kind: str, bs: int, seq: int, steps: int) -> dict:
    """Eval throughput through the REAL pad-and-mask eval path (VERDICT
    r5 #7): make_eval_step's masked reduction with a padded final batch
    (`valid` carrying zeros exactly as BatchLoader pad_last emits), so a
    routing change at eval shapes — this round makes several — cannot
    regress inference invisibly.  Tracked fields:
    resnet_eval_img_per_sec_bs* and transformer_eval_ex_per_sec_*."""
    import jax
    import jax.numpy as jnp

    from faster_distributed_training_tpu.cli import (build_model,
                                                     enable_compilation_cache)
    from faster_distributed_training_tpu.config import (TrainConfig,
                                                        resolve_tricks)
    from faster_distributed_training_tpu.optim import build_optimizer
    from faster_distributed_training_tpu.parallel import make_mesh
    from faster_distributed_training_tpu.parallel.placement import (
        make_put_batch, shard_train_state)
    from faster_distributed_training_tpu.train import create_train_state
    from faster_distributed_training_tpu.train.steps import make_eval_step

    enable_compilation_cache()
    mesh = make_mesh(("dp",))
    rr = np.random.default_rng(2)
    if kind == "transformer":
        cfg = resolve_tricks(TrainConfig(
            model="transformer", dataset="agnews", num_classes=4,
            batch_size=bs, seq_len=seq, optimizer="sgd", precision="bf16",
            epochs=1, attention=os.environ.get("FDT_BENCH_TF_ATTN", ""),
            tricks="on"))
        model = build_model(cfg, vocab_size=30522, mesh=mesh)
        sample = jnp.zeros((bs, seq), jnp.int32)
        lens = rr.integers(seq // 2, seq + 1, size=(bs,))
        batch_np = {
            "tokens": rr.integers(0, 30522, size=(bs, seq)).astype(np.int32),
            "token_types": np.zeros((bs, seq), np.int32),
            "mask": (np.arange(seq)[None, :] < lens[:, None]
                     ).astype(np.int32),
            "label": rr.integers(0, 4, size=(bs,)).astype(np.int32),
        }
    else:
        cfg = resolve_tricks(TrainConfig(
            model="resnet50", batch_size=bs, precision="bf16", epochs=1,
            tricks="on"))
        model = build_model(cfg)
        sample = jnp.zeros((bs, 32, 32, 3), jnp.float32)
        batch_np = {
            "image": rr.normal(size=(bs, 32, 32, 3)).astype(np.float32),
            "label": rr.integers(0, 10, size=(bs,)).astype(np.int32),
        }
    # the padded-final-batch contract: valid=0 rows count toward nothing
    valid = np.ones((bs,), np.float32)
    valid[-max(bs // 8, 1):] = 0.0
    batch_np["valid"] = valid
    tx, _ = build_optimizer(cfg, steps_per_epoch=1)
    state = create_train_state(model, tx, sample, jax.random.PRNGKey(0),
                               init_kwargs={"train": True})
    with mesh:
        state = shard_train_state(state, mesh, cfg)
        batch = make_put_batch(mesh)(batch_np)
        step = jax.jit(make_eval_step(cfg))
        compiled = step.lower(state, batch).compile()
        for _ in range(5):
            m = compiled(state, batch)
        _fence(m)
        t0 = time.monotonic()
        for _ in range(steps):
            m = compiled(state, batch)
        _fence(m)
        return {"bs": bs, "seq": seq, "elapsed": time.monotonic() - t0}


_FAILED_CHILDREN: list = []


def _run_child(mode: str, timeout: int = 1800):
    """Run one timed workload in a subprocess; returns its parsed JSON
    (last stdout line) or None on failure.  A broken secondary metric
    does not stop the remaining arms, but it is remembered: main() exits
    non-zero when any child died."""
    env = dict(os.environ, FDT_BENCH_CHILD=mode)
    try:
        out = subprocess.run([sys.executable, os.path.abspath(__file__)],
                             env=env, capture_output=True, text=True,
                             timeout=timeout)
        if out.returncode != 0:
            raise RuntimeError(f"exit code {out.returncode}: "
                               f"{out.stderr.strip()[-400:]}")
        return json.loads(out.stdout.strip().splitlines()[-1])
    except Exception as e:
        print(f"[bench] child {mode} failed: {e!r}", file=sys.stderr)
        _FAILED_CHILDREN.append(mode)
        return None


def _emit(result: dict) -> None:
    """A child's one JSON line, with the device it ran on."""
    import jax
    dev = jax.devices()[0]
    print(json.dumps({**result, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))


def _refuse_virtual_pod_on_tpu() -> bool:
    """The pp_*/ppbytes_* children simulate a pod on 8 virtual HOST
    devices; on a chip run they would print CPU numbers under device-
    metric names, so they are refused there (ROADMAP S1/D2 decide their
    fate)."""
    import jax
    if jax.default_backend() != "tpu":
        return False
    _emit({"skipped": "simulated-pod arm (virtual host devices) refused "
                      "on a tpu run"})
    return True


def main() -> None:
    _main()
    if _FAILED_CHILDREN:
        print(f"[bench] {len(_FAILED_CHILDREN)} child run(s) failed: "
              f"{_FAILED_CHILDREN}", file=sys.stderr)
        sys.exit(1)


def _main() -> None:
    bs = int(os.environ.get("FDT_BENCH_BS", "1024"))
    steps = int(os.environ.get("FDT_BENCH_STEPS", "20"))
    tf_steps = int(os.environ.get("FDT_BENCH_TF_STEPS", "20"))

    child = os.environ.get("FDT_BENCH_CHILD", "")
    if child == "resnet_ngd":
        # the primary metric's run — a child like every other, so the
        # parent never holds the chip
        elapsed, mem, state_bytes = timed_resnet(True, bs, steps)
        _emit({"elapsed": elapsed, "mem": mem, "state_bytes": state_bytes})
        return
    if child == "resnet_sgd":
        _emit({"elapsed": timed_resnet(False, bs, steps)[0]})
        return
    if child == "tricks_resnet":
        # bag-of-tricks OFF arm: same workload/optimizer, every speed
        # lever disabled (fp32, autodiff conv+BN, no fusion)
        os.environ["FDT_BENCH_TRICKS"] = "off"
        _emit({"elapsed": timed_resnet(True, bs, steps)[0]})
        return
    if child == "tricks_tf":
        # the reference's figures/time.png workload is maxlen=512 at 64
        # per device (global 256 over 4 GPUs); bs=64 also FITS the OFF
        # arm's O(L^2) fp32 dense-attention memory on one 16 GB chip
        os.environ["FDT_BENCH_TRICKS"] = "off"
        _emit(timed_transformer(64, 512, tf_steps))
        return
    if child.startswith(("tf_", "tfr_")):
        tag, cbs, cseq = child.split("_")
        _emit(timed_transformer(int(cbs), int(cseq), tf_steps,
                                           remat=(tag == "tfr")))
        return
    if child == "attn_ladder":
        _emit(timed_attention_ladder())
        return
    if child.startswith("attn_ladder_"):
        # r11: sequence-parallel ladder variant (ring | ulysses)
        _emit(timed_attention_ladder(
            impl=child[len("attn_ladder_"):]))
        return
    if child.startswith("route2d_"):
        # r11 sequence-parallel route cell: one impl at one long-context
        # cell; ring/ulysses run over a (dp=1, sp=all-chips) mesh, the
        # flash baseline over a dp mesh capped so the small batch still
        # divides it.  Cells this host's chip count cannot serve (seq or
        # heads not divisible — same guards as the ladder) report
        # {"skipped": ...} instead of crashing the child.
        import math as _math

        import jax as _jax
        _, cbs, cseq, impl = child.split("_")
        cbs, cseq = int(cbs), int(cseq)
        n_dev = _jax.device_count()
        os.environ["FDT_BENCH_TF_ATTN"] = impl
        if impl in ("ring", "ulysses"):
            if (n_dev < 2 or cseq % n_dev
                    or (impl == "ulysses" and 8 % n_dev)):
                _emit(
                    {"skipped": f"{impl} at bs{cbs}/seq{cseq}: "
                                f"{n_dev} chips can't serve the cell "
                                f"(seq/heads divisibility)"})
                return
            os.environ["FDT_BENCH_TF_MESH"] = f"dp=1,sp={n_dev}"
        else:
            os.environ["FDT_BENCH_TF_MESH"] = f"dp={_math.gcd(cbs, n_dev)}"
        rsteps = int(os.environ.get("FDT_BENCH_ROUTE_STEPS", "10"))
        _emit(timed_transformer(cbs, cseq, rsteps))
        return
    if child.startswith("gemm_"):
        _, cbs, cseq = child.split("_")
        _emit(timed_gemm_ceiling(int(cbs), int(cseq)))
        return
    if child.startswith("route_"):
        # 2D dense/flash crossover arm: one explicit impl at one cell
        _, cbs, cseq, impl = child.split("_")
        os.environ["FDT_BENCH_TF_ATTN"] = impl
        rsteps = int(os.environ.get("FDT_BENCH_ROUTE_STEPS", "10"))
        _emit(timed_transformer(int(cbs), int(cseq), rsteps))
        return
    if child.startswith("ckpt_"):
        # resilience arm: checkpoint-save overhead per step, one mode
        # (off|async|sync) per child process
        cbs = int(os.environ.get("FDT_BENCH_CKPT_BS", "256"))
        csteps = int(os.environ.get("FDT_BENCH_CKPT_STEPS", "40"))
        _emit(timed_checkpoint_overhead(
            child[len("ckpt_"):], cbs, csteps))
        return
    if child == "restart_mttr":
        # r17 resilience arm: crash + COLD process relaunch — the
        # restore + full-recompile recovery a restarted slice pays
        _emit(timed_restart_mttr(cache=False))
        return
    if child == "restart_cached_mttr":
        # r17 tentpole A/B twin: the same relaunch with the persistent
        # executable cache armed — programs deserialize, not recompile
        _emit(timed_restart_mttr(cache=True))
        return
    if child == "warm_spare":
        # r17 tentpole arm: parked spare claims a killed slice's seat
        _emit(timed_warm_spare())
        return
    if child == "restart_slice_mttr":
        # r14 elastic-recovery arm: simulated 2-slice pod, one slice
        # killed and re-admitted; detect + hold + restore decomposition
        _emit(timed_restart_slice_mttr())
        return
    if child.startswith("pp_"):
        # r22 pipeline weak-scaling rung: simulated pod of N slices,
        # pp = one stage per slice, depth grown with the slice count.
        # The parent cannot widen its own device view, so each rung's
        # child forces virtual host devices BEFORE the backend
        # initializes (harmless off-CPU: the flag only shapes the host
        # platform; a real multi-chip backend serves the rung as-is).
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8")
        if _refuse_virtual_pod_on_tpu():
            return
        _emit(timed_pp_pipeline(int(child[len("pp_"):])))
        return
    if child.startswith("serve_"):
        # r16 serving arm: one batch/length request mix through the
        # serve/ stack (continuous batching + 2 AOT-warmed replicas)
        _emit(timed_serve(child[len("serve_"):]))
        return
    if child == "decode_gen":
        # r21 decode arm: closed-loop generation through the paged-KV
        # decode stack (TTFT percentiles + tokens/sec/chip)
        _emit(timed_decode_gen())
        return
    if child == "decode_sustained":
        # r21 decode arm: open-loop sustained load at a target QPS —
        # SLO-violation percentage under arrival-time pacing
        _emit(timed_decode_sustained())
        return
    if child.startswith("telem_"):
        # r12 observability arm: per-dispatch recorder on vs off, one
        # mode per child process (interleaved by the parent)
        tbs = int(os.environ.get("FDT_BENCH_TELEM_BS", "256"))
        tsteps = int(os.environ.get("FDT_BENCH_TELEM_STEPS", "40"))
        _emit(timed_telemetry_overhead(
            child[len("telem_"):], tbs, tsteps))
        return
    if child.startswith("sentinel_"):
        # r24 robustness arm: in-graph bad-step guard + host spike
        # detector on vs off, one mode per child process (interleaved
        # by the parent)
        sbs = int(os.environ.get("FDT_BENCH_SENTINEL_BS", "256"))
        ssteps = int(os.environ.get("FDT_BENCH_SENTINEL_STEPS", "40"))
        _emit(timed_sentinel_overhead(
            child[len("sentinel_"):], sbs, ssteps))
        return
    if child.startswith("kdis_"):
        # r8 fused-dispatch ladder: one (model, K) cell per child
        _, m, kk = child.split("_")
        ksteps = int(os.environ.get("FDT_BENCH_K_STEPS", "32"))
        if m == "tf":
            _emit(timed_fused("transformer", int(kk), 256, 256,
                                         ksteps))
        else:
            _emit(timed_fused("resnet50", int(kk), 512, 0,
                                         ksteps))
        return
    if child.startswith("datapath_"):
        dsteps = int(os.environ.get("FDT_BENCH_K_STEPS", "32"))
        _emit(timed_data_path(child[len("datapath_"):], 512,
                                         dsteps))
        return
    if child.startswith("kov_"):
        # ISSUE 16 overlap A/B: resnet K-dispatch with the bucketed
        # gradient reduce-scatter reshard on|off, one (mode, K) cell per
        # child — both arms run the state_shardings program, only
        # cfg.overlap_grad_reduce differs
        _, mode, kk = child.split("_")
        ksteps = int(os.environ.get("FDT_BENCH_K_STEPS", "32"))
        _emit(timed_fused("resnet50", int(kk), 512, 0, ksteps,
                                     overlap=(mode == "on")))
        return
    if child == "optoffload":
        # ISSUE 16 host-offload arm: the K=1 resnet program with
        # --offload_opt_state (pinned_host tiers engage on TPU; on a
        # host-only backend the step degrades the flag to off and the
        # arm measures the undegraded twin of resnet_bs512_k1_step_ms)
        ksteps = int(os.environ.get("FDT_BENCH_K_STEPS", "32"))
        _emit(timed_fused("resnet50", 1, 512, 0, ksteps,
                                     overlap=False, offload=True))
        return
    if child.startswith("zerobytes_"):
        # ISSUE 16 sizing twins: per-chip opt-state bytes on dp x tp=2
        # with the ZeRO overlay on ("zero") vs forced replicated ("repl")
        _emit(zero_opt_state_bytes(child.endswith("_zero")))
        return
    if child.startswith("ppbytes_"):
        # r23 residency sizing twins: per-chip param/opt-state bytes on
        # dp x pp=2 with per-stage residency on ("staged") vs the r22
        # replicated-over-pp layout ("repl").  Same virtual-device seam
        # as the pp_ rungs: the sizing needs a 4-chip mesh.
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8")
        if _refuse_virtual_pod_on_tpu():
            return
        _emit(pp_residency_bytes(child.endswith("_staged")))
        return
    if child == "eval_tf":
        _emit(timed_eval("transformer", 256, 256, tf_steps))
        return
    if child == "eval_resnet":
        _emit(timed_eval("resnet", bs, 0, steps))
        return
    if child.startswith("quant_"):
        # r13 quantized-training A/B arm: one precision (off|int8|fp8)
        # at one cell per child process, interleaved by the parent.
        # "off" is the bf16 baseline measured through the SAME child
        # path so the pair shares every other variable.
        _, fmt, cbs, cseq = child.split("_")
        if fmt == "e5m2grad":
            # r19 FP8-LM completion arm: fp8-E4M3 forward + fp8-E5M2
            # JIT-scaled gradient quantization with the quantized
            # dW/dx GEMMs — the A/B twin of the plain fp8 arm
            os.environ["FDT_BENCH_TF_QUANT"] = "fp8"
            os.environ["FDT_BENCH_TF_QUANT_GRAD"] = "fp8_e5m2"
        elif fmt != "off":
            os.environ["FDT_BENCH_TF_QUANT"] = fmt
        _emit(timed_transformer(int(cbs), int(cseq), tf_steps))
        return
    if child.startswith("tpk_"):
        # r19 shard_map kernel-layer A/B: one (kernel, mode) cell per
        # child on a dp x tp=2 mesh — mode "kernel" runs the recovered
        # per-shard kernel through parallel/kernel_shard.py, mode
        # "fallback" forces the pre-r19 warned reroute with
        # FDT_KERNEL_SHARD=0 (the layer's kill switch IS the A/B arm).
        import warnings as _w

        import jax as _jax
        _, kern, mode = child.split("_")
        n_dev = _jax.device_count()
        if n_dev < 2:
            _emit({"skipped": f"tp=2 arm needs >=2 chips, "
                                         f"host exposes {n_dev}"})
            return
        if kern == "ffn" and _jax.default_backend() != "tpu":
            # off-TPU the fused-FFN kernel runs in Pallas INTERPRET mode
            # (orders of magnitude slower) — the cell would measure the
            # interpreter, not the kernel; read this pair on TPU
            _emit({"skipped": "ffn kernel cell is TPU-only "
                                         "(interpret mode off-TPU)"})
            return
        dp = max(1, min(n_dev // 2, 256))
        while 256 % dp:
            dp -= 1
        os.environ["FDT_BENCH_TF_MESH"] = f"dp={dp},tp=2"
        if mode == "fallback":
            os.environ["FDT_KERNEL_SHARD"] = "0"
        if kern == "flash":
            os.environ["FDT_BENCH_TF_ATTN"] = "flash"
        elif kern == "ffn":
            os.environ["FDT_BENCH_TF_FFN"] = "pallas"
        elif kern == "quant":
            os.environ["FDT_BENCH_TF_QUANT"] = "int8"
        rsteps = int(os.environ.get("FDT_BENCH_ROUTE_STEPS", "10"))
        with _w.catch_warnings():
            _w.simplefilter("ignore")   # the fallback arm warns by design
            _emit(timed_transformer(256, 256, rsteps))
        return
    if child == "ab_ln_256_256":
        # tentpole A/B arm: LayerNorm saved-stats VJP OFF (r5 behavior)
        os.environ["FDT_LN_SAVED_STATS"] = "0"
        _emit(timed_transformer(256, 256, tf_steps))
        return
    if child == "ab_flashstats_64_512":
        # tentpole A/B arm: flash saved-(out,lse) backward OFF (r5
        # in-kernel-recompute backward)
        os.environ["FDT_FLASH_SAVE_STATS"] = "0"
        _emit(timed_transformer(64, 512, tf_steps))
        return

    if child:
        raise SystemExit(f"unknown FDT_BENCH_CHILD mode {child!r}")
    primary = _run_child("resnet_ngd")
    if primary is None:
        # nothing to report without the primary metric
        return
    n_chips = max(primary["device"]["count"], 1)
    elapsed, mem, state_bytes = (primary["elapsed"], primary["mem"],
                                 primary["state_bytes"])
    ips_per_chip = bs * steps / elapsed / n_chips
    # vs_baseline: ratio against FDT_BENCH_BASELINE (img/s/chip) when set;
    # 1.0 otherwise = "no external baseline configured" — the absolute value
    # is the tracked metric (the reference publishes no absolute throughput).
    vs = (ips_per_chip / BASELINE_REF_IPS) if BASELINE_REF_IPS else 1.0
    record = {
        "metric": "resnet50_cifar10_train_images_per_sec_per_chip_bs%d" % bs,
        "value": round(ips_per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(vs, 3),
        "baseline_configured": bool(BASELINE_REF_IPS),
        # VERDICT r4 #7: make the missing denominator self-explanatory
        # where readers look, instead of leaving `false` as an apparent
        # omission.
        "baseline_note": (
            "the reference publishes no absolute throughput (its README "
            "reports unitless relative-time figures only, "
            "/root/reference/README.md:56-73); set FDT_BENCH_BASELINE "
            "(img/s/chip) to wire an external denominator in — until "
            "then the absolute value above is the tracked metric and "
            "the `regressions` field guards it round-over-round"),
    }
    if mem:
        record["compiled_peak_mem_bytes"] = int(mem)
    # HBM attribution of the primary program's train state (ISSUE 11
    # satellite seeding ROADMAP's ZeRO item): opt_state_bytes_per_chip is
    # the number the optimizer-state sharding win will be measured
    # against — today's record IS the replicated baseline (the TP overlay
    # covers params only, so opt state holds full size on every chip of a
    # model axis).  params_bytes_per_chip beside it gives the ratio.
    record["params_bytes_per_chip"] = int(
        state_bytes["params_bytes_per_chip"])
    record["opt_state_bytes_per_chip"] = int(
        state_bytes["opt_state_bytes_per_chip"])
    record["bench_unix_time"] = round(time.time(), 1)

    if os.environ.get("FDT_BENCH_FAST") != "1":
        # VERDICT r4 #2a: the % alone is ambiguous across rounds
        # (re-basing the denominator moves it) — always publish the
        # absolute per-step times of BOTH arms beside it.  The NGD arm's
        # time is local; it must not vanish if the SGD child dies.
        record["resnet_ngd_step_ms"] = round(elapsed / steps * 1e3, 2)
        sgd = _run_child("resnet_sgd")
        if sgd:
            record["resnet_sgd_step_ms"] = round(
                sgd["elapsed"] / steps * 1e3, 2)
            record["ngd_overhead_pct"] = round(
                (elapsed - sgd["elapsed"]) / sgd["elapsed"] * 100.0, 1)
        peak, peak_src = device_peak_tflops()
        record["peak_tflops_assumed"] = peak
        record["peak_tflops_source"] = peak_src
        # Roofline fields (VERDICT r2 #1): model FLOPs per step (analytic
        # matmul count), achieved TFLOP/s, MFU vs the chip's bf16 peak,
        # plus XLA's own cost analysis and the compiled peak memory.
        # tfr_256_512 is the remat capacity point (VERDICT r2 #2): the
        # same config with layer checkpointing, showing the memory delta.
        # VERDICT r5 #2: the four flagged bs64/seq512 + tricks-transformer
        # moves get resolved by MEASUREMENT, not prose — N interleaved
        # re-runs of both arms on the same chip (alternating children so
        # drift decorrelates), median published as the tracked value, the
        # observed range published beside it as *_noise_band_pct, and the
        # guard threshold for these metrics derived from that band
        # (_find_regressions).  FDT_BENCH_REPEATS overrides N.
        def _median_run(runs):
            runs = sorted(runs, key=lambda r: r["elapsed"])
            return runs[len(runs) // 2]

        def _band_pct(runs):
            es = sorted(r["elapsed"] for r in runs)
            med = es[len(es) // 2]
            if len(es) < 2 or not med:
                return 0.0
            return round((es[-1] - es[0]) / med * 100.0, 1)

        reps = max(1, int(os.environ.get("FDT_BENCH_REPEATS", "5")))
        tf64_runs, tricks_tf_runs = [], []
        for _ in range(reps):
            r = _run_child("tf_64_512")
            if r:
                tf64_runs.append(r)
            t = _run_child("tricks_tf")
            if t:
                tricks_tf_runs.append(t)

        tf64_elapsed = None
        for tag, cbs, cseq in (("tf", 256, 256), ("tf", 64, 512),
                               ("tf", 256, 512), ("tfr", 256, 512)):
            if (tag, cbs, cseq) == ("tf", 64, 512):
                if not tf64_runs:
                    continue
                res = _median_run(tf64_runs)
                tf64_elapsed = res["elapsed"]
            else:
                res = _run_child(f"{tag}_{cbs}_{cseq}")
                if not res:
                    continue
            name = f"bs{cbs}_seq{cseq}" + ("_remat" if tag == "tfr" else "")
            exs = cbs * tf_steps / res["elapsed"] / n_chips
            if tag == "tf" and (cbs, cseq) in ((256, 256), (64, 512)):
                # round-over-round tracked keys, unchanged names
                record[f"transformer_agnews_ex_per_sec_{name}"] = round(exs, 1)
            else:
                record[f"transformer_ex_per_sec_{name}"] = round(exs, 1)
            mf = transformer_model_flops(cbs, cseq)
            step_s = res["elapsed"] / tf_steps
            record[f"transformer_{name}_step_ms"] = round(step_s * 1e3, 2)
            # per-chip: the step is sharded over all visible chips, so
            # achieved TFLOP/s and MFU are divided by the chip count to
            # compare against ONE chip's peak
            tflops = mf / step_s / 1e12 / n_chips
            record[f"transformer_{name}_model_tflops_per_step"] = round(
                mf / 1e12, 3)
            record[f"transformer_{name}_achieved_tflops_per_chip"] = round(
                tflops, 1)
            record[f"transformer_{name}_mfu_pct"] = round(
                100.0 * tflops / peak, 1)
            if "compiled_peak_mem_bytes" in res:
                record[f"transformer_{name}_peak_mem_bytes"] = (
                    res["compiled_peak_mem_bytes"])
            if "xla_bytes_accessed_per_step" in res:
                record[f"transformer_{name}_xla_gb_per_step"] = round(
                    res["xla_bytes_accessed_per_step"] / 1e9, 2)
            if "remat_policy" in res:
                record[f"transformer_{name}_policy"] = res["remat_policy"]
            if (tag, cbs, cseq) == ("tf", 64, 512) and len(tf64_runs) > 1:
                band64 = _band_pct(tf64_runs)
                record["transformer_bs64_seq512_repeats"] = len(tf64_runs)
                for kk in (f"transformer_agnews_ex_per_sec_{name}",
                           f"transformer_{name}_achieved_tflops_per_chip",
                           f"transformer_{name}_mfu_pct"):
                    record[kk + "_noise_band_pct"] = band64
        # GEMM-chain ceiling (VERDICT r4 #1): the step's matmul shapes as
        # a bare jitted chain — the measured MXU ceiling the step MFU is
        # judged against (see timed_gemm_ceiling).
        for cbs, cseq in ((256, 256), (64, 512)):
            res = _run_child(f"gemm_{cbs}_{cseq}")
            if res:
                # single-chip by construction (no mesh — the chain runs
                # on device 0), so NOT divided by n_chips
                ceiling = res["gemm_ceiling_tflops"]
                record[f"transformer_gemm_ceiling_tflops_bs{cbs}_seq{cseq}"] \
                    = round(ceiling, 1)
                record[f"transformer_gemm_ceiling_mfu_pct_bs{cbs}_seq{cseq}"] \
                    = round(100.0 * ceiling / peak, 1)
        # Bag-of-tricks end-to-end ablation (VERDICT r3 #1/#2): the same
        # train step with EVERY speed lever disabled (resolve_tricks:
        # fp32, dense attention, naive MLP, unfused QKV, autodiff
        # conv+BN, threefry nn.Dropout) vs the default stack — the
        # analog of the reference's headline ~2.5x figure
        # (/root/reference/README.md:63, figures/time.png).
        off_r = _run_child("tricks_resnet")
        if off_r:
            record["tricks_speedup_resnet50"] = round(
                off_r["elapsed"] / elapsed, 2)
        if tricks_tf_runs and tf64_elapsed:
            # both arms already measured N times interleaved above; the
            # ratio uses the medians, and the published band is the sum
            # of both arms' observed ranges (conservative)
            off_med = _median_run(tricks_tf_runs)["elapsed"]
            record["tricks_speedup_transformer"] = round(
                off_med / tf64_elapsed, 2)
            # the headline analog: the reference's time.png measures the
            # transformer workload at maxlen 512, 64 examples per device
            record["tricks_speedup_x"] = record["tricks_speedup_transformer"]
            if len(tricks_tf_runs) > 1 and len(tf64_runs) > 1:
                band = round(_band_pct(tricks_tf_runs)
                             + _band_pct(tf64_runs), 1)
                record["tricks_speedup_transformer_noise_band_pct"] = band
                record["tricks_speedup_x_noise_band_pct"] = band
        # VERDICT r4 #2b: two DEFINITIONS circulate — the bench keys above
        # are RAW COMPILED STEP ratios (loader/H2D excluded); the
        # figures/tricks_times.json epoch runs are FULL PIPELINE.  Say so
        # in-record, and surface the full-pipeline numbers beside them.
        record["tricks_speedup_definition"] = (
            "tricks_speedup_{resnet50,transformer,x}: raw compiled "
            "train-step time ratio (synthetic device-resident data); "
            "*_fullpipeline: steady-state epoch-time ratio incl. loader/"
            "augmentation/H2D (scripts/bag_of_tricks.py, "
            "figures/tricks_times.json)")
        try:
            with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                   "figures", "tricks_times.json")) as fh:
                tt = json.load(fh)
            for arm in ("resnet50", "transformer"):
                on = tt.get(f"{arm}_on", [])[1:]
                off = tt.get(f"{arm}_off", [])[1:]
                if on and off:
                    record[f"tricks_speedup_{arm}_fullpipeline"] = round(
                        (sum(off) / len(off)) / (sum(on) / len(on)), 2)
        except Exception:
            pass
        # 2D dense/flash crossover arms (VERDICT r5 #5): both impls at
        # every cell the routing surface newly serves, as full NGD train
        # steps — resolve_attention's surface comment cites these fields
        # per cell (cli._ATTN_ROUTE_SURFACE).  bs1024/seq256's dense arm
        # is deliberately NOT run: the materialized probs (6.4 GB) exceed
        # the routing memory budget, which is exactly why that cell
        # routes flash.  Opt out with FDT_BENCH_ROUTE=0.
        if os.environ.get("FDT_BENCH_ROUTE", "1") != "0":
            rsteps = int(os.environ.get("FDT_BENCH_ROUTE_STEPS", "10"))
            for cbs, cseq, impls in ATTN_ROUTE_BENCH_CELLS:
                for impl in impls:
                    res = _run_child(f"route_{cbs}_{cseq}_{impl}")
                    if res:
                        record[f"attn_route_bs{cbs}_seq{cseq}_{impl}"
                               f"_step_ms"] = round(
                            res["elapsed"] / rsteps * 1e3, 2)
            record["attn_route_bs1024_seq256_dense_note"] = (
                "dense arm deliberately not run: 3*4*B*H*L^2 = 6.4 GB of "
                "materialized probs exceeds the routing memory budget "
                "(cli._dense_attn_fits, default FDT_DENSE_ATTN_BUDGET_MB="
                "4096) — the cell routes flash by the headroom bound")
        # Tentpole attribution arms (VERDICT r5 #3/#4): the same train
        # program with ONE lever restored to its r5 behavior, so the
        # committed record carries each change's measured step-time
        # delta in-record (the per-arm transformer_*_step_ms fields
        # above are the ON side of each pair):
        #   ln_autodiff — LayerNorm under default XLA autodiff instead
        #     of the saved-(mean, rstd) custom_vjp (FDT_LN_SAVED_STATS=0)
        #     at bs256/seq256, the 13-site LN-cost shape;
        #   flash_recompute — the r5 in-kernel-recompute flash backward
        #     instead of the saved-stats pair (FDT_FLASH_SAVE_STATS=0)
        #     at bs64/seq512, the flash-routed shape.
        ab = _run_child("ab_ln_256_256")
        if ab:
            record["transformer_bs256_seq256_ln_autodiff_step_ms"] = round(
                ab["elapsed"] / tf_steps * 1e3, 2)
        ab = _run_child("ab_flashstats_64_512")
        if ab:
            record["transformer_bs64_seq512_flash_recompute_step_ms"] = \
                round(ab["elapsed"] / tf_steps * 1e3, 2)
        # Checkpoint-save overhead (r7 resilience arm): the async manager
        # must leave the step critical path — tracked claim: async median
        # step time within 1% of checkpointing-off (the sync arm shows
        # what the background write saves).  Opt out: FDT_BENCH_CKPT=0.
        if os.environ.get("FDT_BENCH_CKPT", "1") != "0":
            ck = {m: _run_child(f"ckpt_{m}") for m in ("off", "async",
                                                       "sync",
                                                       "async_sharded")}
            for m, r in ck.items():
                if r:
                    record[f"ckpt_{m}_median_step_ms"] = r["median_step_ms"]
                    record[f"ckpt_{m}_mean_step_ms"] = r["mean_step_ms"]
                    if "blocking_ms_per_save" in r:
                        record[f"ckpt_{m}_blocking_ms_per_save"] = (
                            r["blocking_ms_per_save"])
            # overhead published under BOTH definitions: *_overhead_pct
            # compares medians (steady-state step; the ISSUE's tracked
            # <1% claim) and *_amortized_overhead_pct compares means
            # (includes the save ticks — the honest total-cost number;
            # the sync arm's amortized value shows what the background
            # write saves)
            # ckpt_async_sharded_overhead_pct (r9 tentpole arm): the
            # per-host shard-streaming save — the path every host of a
            # pod takes now that the sync-collective fallback is gone —
            # must leave the critical path like the single-host async
            # one; its blocking part is the addressable-shard fetch.
            for m in ("async", "sync", "async_sharded"):
                if ck.get("off") and ck.get(m):
                    record[f"ckpt_{m}_overhead_pct"] = round(
                        (ck[m]["median_step_ms"]
                         - ck["off"]["median_step_ms"])
                        / ck["off"]["median_step_ms"] * 100.0, 2)
                    record[f"ckpt_{m}_amortized_overhead_pct"] = round(
                        (ck[m]["mean_step_ms"] - ck["off"]["mean_step_ms"])
                        / ck["off"]["mean_step_ms"] * 100.0, 2)
            # Restart MTTR (redefined r17 — see timed_restart_mttr):
            # crash + COLD process relaunch, MTTR = restore + full
            # program recompile, split into its components.  The old
            # in-process supervised number (which keeps compiled
            # programs alive and, post backoff-fix, reduces to
            # restore_s) lives on in every supervised run's goodput
            # summary; detect/backoff publish 0.0 here by scenario
            # (platform relaunch + immediate first restart).
            mt = _run_child("restart_mttr")
            if mt and mt.get("restores"):
                record["restart_mttr_s"] = mt["mttr_s"]
                record["restart_mttr_restore_s"] = mt["restore_s"]
                record["restart_mttr_compile_s"] = mt["compile_s"]
                record["restart_mttr_backoff_s"] = mt["backoff_s"]
                record["restart_mttr_detect_s"] = mt["detect_s"]
            # ...and the executable-cache twin (r17 tentpole A/B): the
            # SAME relaunch with --executable_cache on — programs
            # deserialize (cache_source=deserialized) instead of
            # recompiling; restart_cached_mttr_s < restart_mttr_s is
            # the committed win.
            cmt = _run_child("restart_cached_mttr")
            if cmt and cmt.get("restores"):
                record["restart_cached_mttr_s"] = cmt["mttr_s"]
                record["restart_cached_mttr_restore_s"] = cmt["restore_s"]
                record["restart_cached_mttr_compile_s"] = cmt["compile_s"]
                srcs = [s for v in cmt.get("cache_sources", {}).values()
                        for s in v]
                record["restart_cached_deserialized_programs"] = sum(
                    1 for s in srcs if s == "deserialized")
            # Warm-spare swap (r17 tentpole arm): a parked spare claims
            # a killed slice's seat — swap wall time (claim->release)
            # and the survivor's hold; the headline awaits real TPU
            # hardware, but the arm commits the machinery's number.
            ws = _run_child("warm_spare")
            if ws and ws.get("swaps"):
                record["warm_spare_swap_s"] = ws["warm_spare_swap_s"]
                record["warm_spare_hold_s"] = ws["warm_spare_hold_s"]
            # Slice-recovery MTTR (r14 elastic-recovery arm): one
            # slice killed and RE-ADMITTED while the other holds —
            # detect + hold + restore per readmission (see
            # timed_restart_slice_mttr); the whole-pod backoff and the
            # survivor's rollback replay are exactly the costs this
            # path removes, so the two headlines are directly
            # comparable.
            smt = _run_child("restart_slice_mttr")
            if smt and smt.get("readmissions"):
                record["restart_slice_mttr_s"] = smt["restart_slice_mttr_s"]
                record["restart_slice_mttr_detect_s"] = smt["detect_s"]
                record["restart_slice_mttr_hold_s"] = smt["hold_s"]
                record["restart_slice_mttr_restore_s"] = smt["restore_s"]
        # Serving arm family (r16 serve/ tentpole): p50/p99 request
        # latency + sustained qps/chip through the REAL serving stack
        # (continuous-batching queue, AOT-warmed per-bucket programs, 2
        # replicas) at three batch/length mixes; the ragged mix is the
        # headline (serve_p50_ms / serve_p99_ms / serve_qps_per_chip in
        # essentials).  CPU-container numbers measure the batching/
        # dispatch machinery — real-TPU latency lands when the driver's
        # TPU bench does.  Opt out: FDT_BENCH_SERVE=0.
        if os.environ.get("FDT_BENCH_SERVE", "1") != "0":
            for mix, _lo, _hi in SERVE_BENCH_MIXES:
                r = _run_child(f"serve_{mix}")
                if r and r.get("requests"):
                    record[f"serve_{mix}_p50_ms"] = r["p50_ms"]
                    record[f"serve_{mix}_p99_ms"] = r["p99_ms"]
                    record[f"serve_{mix}_qps_per_chip"] = r["qps_per_chip"]
            if "serve_ragged_p50_ms" in record:
                record["serve_p50_ms"] = record["serve_ragged_p50_ms"]
                record["serve_p99_ms"] = record["serve_ragged_p99_ms"]
                record["serve_qps_per_chip"] = \
                    record["serve_ragged_qps_per_chip"]
        # Decode-serving arm family (r21 serve/decode tentpole):
        # autoregressive generation through the REAL decode stack —
        # paged KV cache, AOT prefill + decode-step program families,
        # token-granular continuous batching.  The closed-loop child
        # publishes TTFT percentiles + decode_tokens_per_sec_per_chip,
        # measured N INTERLEAVED with the open-loop sustained child (r6
        # noise protocol: alternating children so drift decorrelates)
        # so the throughput headline carries a measured band; the
        # sustained child paces submissions at FDT_BENCH_DECODE_QPS and
        # publishes decode_slo_violation_pct — a closed loop
        # self-throttles, so queueing failure only ever shows open
        # loop.  Opt out: FDT_BENCH_DECODE=0.
        if os.environ.get("FDT_BENCH_DECODE", "1") != "0":
            dreps = max(1, int(os.environ.get("FDT_BENCH_DECODE_REPEATS",
                                              "3")))
            dg_runs, ds_runs = [], []
            for _ in range(dreps):
                r = _run_child("decode_gen")
                if r and r.get("requests"):
                    dg_runs.append(r)
                r = _run_child("decode_sustained")
                if r and r.get("requests"):
                    ds_runs.append(r)

            def _decode_med(key, rs):
                vs = sorted(r[key] for r in rs if key in r)
                return vs[len(vs) // 2] if vs else None

            if dg_runs:
                tps = sorted(r["tokens_per_sec_per_chip"]
                             for r in dg_runs)
                med = tps[len(tps) // 2]
                record["decode_tokens_per_sec_per_chip"] = med
                if len(tps) > 1 and med:
                    record["decode_tokens_per_sec_per_chip"
                           "_noise_band_pct"] = round(
                        (tps[-1] - tps[0]) / med * 100.0, 1)
                record["decode_ttft_p50_ms"] = _decode_med("ttft_p50_ms",
                                                           dg_runs)
                record["decode_ttft_p99_ms"] = _decode_med("ttft_p99_ms",
                                                           dg_runs)
            if ds_runs:
                record["decode_slo_violation_pct"] = _decode_med(
                    "slo_violation_pct", ds_runs)
                record["decode_target_qps"] = ds_runs[0]["target_qps"]
                record["decode_slo_ms"] = ds_runs[0]["slo_ms"]
        # Telemetry-overhead arm (r12 observability tentpole): the
        # per-dispatch recorder must be free — on-vs-off measured N>=5
        # times INTERLEAVED (the r6 noise protocol: alternating children
        # so drift decorrelates), medians published with their observed
        # noise bands, and telemetry_overhead_pct held <1% by the guard
        # (_ABS_PP_WORSE_IF_UP).  The off arm is exactly what
        # FDT_TELEMETRY=0 / --no_telemetry buys.  Opt out:
        # FDT_BENCH_TELEM=0.
        if os.environ.get("FDT_BENCH_TELEM", "1") != "0":
            treps = max(1, int(os.environ.get("FDT_BENCH_TELEM_REPEATS",
                                              "5")))
            t_runs = {"on": [], "off": []}
            for _ in range(treps):
                for m in ("on", "off"):
                    r = _run_child(f"telem_{m}")
                    if r:
                        t_runs[m].append(r)

            def _telem_med_band(name, rs):
                if not rs:
                    return None
                ms = sorted(r["median_step_ms"] for r in rs)
                med = ms[len(ms) // 2]
                record[name] = med
                if len(ms) > 1 and med:
                    record[name + "_noise_band_pct"] = round(
                        (ms[-1] - ms[0]) / med * 100.0, 1)
                return med

            t_on = _telem_med_band("telem_on_median_step_ms",
                                   t_runs["on"])
            t_off = _telem_med_band("telem_off_median_step_ms",
                                    t_runs["off"])
            if t_on and t_off:
                record["telemetry_overhead_pct"] = round(
                    (t_on - t_off) / t_off * 100.0, 2)
        # Sentinel-overhead arm (r24 robustness tentpole): the in-graph
        # bad-step guard + host spike detector must be near-free — on
        # (--sentinel full's per-dispatch cost: fused finiteness
        # reduction + update select in-graph, median/MAD arithmetic on
        # host) vs off (--sentinel none, byte-identical HLO to
        # pre-sentinel) measured N>=5 times INTERLEAVED per the r6
        # noise protocol, sentinel_overhead_pct held <1% by the guard
        # (_ABS_PP_WORSE_IF_UP).  Opt out: FDT_BENCH_SENTINEL=0.
        if os.environ.get("FDT_BENCH_SENTINEL", "1") != "0":
            sreps = max(1, int(os.environ.get(
                "FDT_BENCH_SENTINEL_REPEATS", "5")))
            s_runs = {"on": [], "off": []}
            for _ in range(sreps):
                for m in ("on", "off"):
                    r = _run_child(f"sentinel_{m}")
                    if r:
                        s_runs[m].append(r)

            def _sent_med_band(name, rs):
                if not rs:
                    return None
                ms = sorted(r["median_step_ms"] for r in rs)
                med = ms[len(ms) // 2]
                record[name] = med
                if len(ms) > 1 and med:
                    record[name + "_noise_band_pct"] = round(
                        (ms[-1] - ms[0]) / med * 100.0, 1)
                return med

            s_on = _sent_med_band("sentinel_on_median_step_ms",
                                  s_runs["on"])
            s_off = _sent_med_band("sentinel_off_median_step_ms",
                                   s_runs["off"])
            if s_on and s_off:
                record["sentinel_overhead_pct"] = round(
                    (s_on - s_off) / s_off * 100.0, 2)
        # Quantized-training A/B arms (r13 tentpole): the bs256/seq256
        # NGD train step with the attention-projection + FFN forward
        # GEMMs at int8 / fp8-E4M3 delayed scaling vs the bf16 baseline
        # measured through the SAME child path, N>=5 INTERLEAVED per
        # the r6 noise protocol (medians + *_noise_band_pct feeding the
        # guard thresholds).  Roofline variants judge the quantized
        # arms against the LOW-PRECISION MXU peak (~2x bf16 on TPU;
        # FDT_QUANT_PEAK_TFLOPS overrides) — the ceiling the ROADMAP
        # MFU item says quantization raises.  Opt out: FDT_BENCH_QUANT=0.
        if os.environ.get("FDT_BENCH_QUANT", "1") != "0":
            qreps = max(1, int(os.environ.get("FDT_BENCH_QUANT_REPEATS",
                                              "5")))
            # e5m2grad (r19): the fp8 arm + --quant_grad fp8_e5m2 — its
            # A/B twin is the plain fp8 arm in the same interleaved set
            q_runs = {m: [] for m in ("off", "int8", "fp8", "e5m2grad")}
            for _ in range(qreps):
                for m in q_runs:
                    r = _run_child(f"quant_{m}_256_256")
                    if r:
                        q_runs[m].append(r)
            qpeak = float(os.environ.get("FDT_QUANT_PEAK_TFLOPS", "0")
                          or 0) or 2.0 * peak
            record["quant_peak_tflops_assumed"] = round(qpeak, 1)
            mf_q = transformer_model_flops(256, 256)
            for m, rs in q_runs.items():
                if not rs:
                    continue
                ms = sorted(r["elapsed"] / tf_steps * 1e3 for r in rs)
                med = ms[len(ms) // 2]
                tag = {"off": "quant_off",
                       "e5m2grad": "fp8_e5m2_grad"}.get(m, m)
                key = f"transformer_bs256_seq256_{tag}_step_ms"
                record[key] = round(med, 2)
                if len(ms) > 1 and med:
                    record[key + "_noise_band_pct"] = round(
                        (ms[-1] - ms[0]) / med * 100.0, 1)
                if m in ("int8", "fp8"):
                    # quantized roofline: achieved TFLOP/s at the SAME
                    # analytic FLOP count, MFU vs the low-precision peak
                    # (the e5m2grad arm reads against its fp8 twin's
                    # step_ms instead — same forward, quantized backward)
                    tflops = mf_q / (med / 1e3) / 1e12 / n_chips
                    record[f"transformer_bs256_seq256_{m}"
                           f"_achieved_tflops_per_chip"] = round(tflops, 1)
                    record[f"transformer_bs256_seq256_{m}_mfu_pct"] = \
                        round(100.0 * tflops / qpeak, 1)
        # tp-mesh kernel A/B arms (r19 tentpole): the bs256/seq256 NGD
        # train step on a dp x tp=2 mesh, each recovered kernel measured
        # kernel-via-shard_map vs forced fallback (FDT_KERNEL_SHARD=0 —
        # the layer's kill switch IS the off arm), N>=3 INTERLEAVED per
        # the r6 noise protocol.  On this CPU container the pairs
        # measure the routing/collective machinery (flash runs its
        # blockwise twin per shard, quant the reference GEMMs); the
        # kernel-side wins land with the first live TPU bench — the ffn
        # cell is TPU-only (interpret mode would measure the
        # interpreter).  Opt out: FDT_BENCH_TPK=0.
        if os.environ.get("FDT_BENCH_TPK", "1") != "0":
            treps = max(1, int(os.environ.get("FDT_BENCH_TPK_REPEATS",
                                              "3")))
            rsteps = int(os.environ.get("FDT_BENCH_ROUTE_STEPS", "10"))
            tpk_runs = {(kern, mode): []
                        for kern in ("flash", "ffn", "quant")
                        for mode in ("kernel", "fallback")}
            for _ in range(treps):
                for (kern, mode) in tpk_runs:
                    r = _run_child(f"tpk_{kern}_{mode}")
                    if r and "elapsed" in r:
                        tpk_runs[(kern, mode)].append(r)
            for (kern, mode), rs in tpk_runs.items():
                if not rs:
                    continue
                ms = sorted(r["elapsed"] / rsteps * 1e3 for r in rs)
                med = ms[len(ms) // 2]
                key = f"transformer_tp2_{kern}_{mode}_step_ms"
                record[key] = round(med, 2)
                if len(ms) > 1 and med:
                    record[key + "_noise_band_pct"] = round(
                        (ms[-1] - ms[0]) / med * 100.0, 1)
        # K-step fused dispatch ladder + data-path A/B (r8 tentpole):
        # per-step time at K in {1, 4, 16} on the device-resident path
        # for both workloads, and the host-vs-resident input-pipeline
        # A/B at K=1.  Measured N times INTERLEAVED (r6 noise protocol):
        # medians published, observed range beside them as
        # *_noise_band_pct feeding the regression guard's thresholds.
        # Opt out with FDT_BENCH_KDIS=0.
        if os.environ.get("FDT_BENCH_KDIS", "1") != "0":
            def _k_name(m, kk):
                return (f"transformer_bs256_seq256_k{kk}_step_ms"
                        if m == "tf" else f"resnet_bs512_k{kk}_step_ms")

            reps = max(1, int(os.environ.get("FDT_BENCH_K_REPEATS", "3")))
            arms = [("tf", kk) for kk in (1, 4, 16)] \
                + [("rn", kk) for kk in (1, 4, 16)]
            k_runs = {a: [] for a in arms}
            dp_runs = {p: [] for p in ("host", "resident", "stream")}
            for _ in range(reps):
                for m, kk in arms:
                    r = _run_child(f"kdis_{m}_{kk}")
                    if r:
                        k_runs[(m, kk)].append(r)
                for p in dp_runs:
                    r = _run_child(f"datapath_{p}")
                    if r:
                        dp_runs[p].append(r)

            def _publish(name, rs):
                if not rs:
                    return
                ms = sorted(r["elapsed"] / r["steps_timed"] * 1e3
                            for r in rs)
                med = ms[len(ms) // 2]
                record[name] = round(med, 3)
                if len(ms) > 1 and med:
                    record[name + "_noise_band_pct"] = round(
                        (ms[-1] - ms[0]) / med * 100.0, 1)

            for (m, kk), rs in k_runs.items():
                _publish(_k_name(m, kk), rs)
            for p, rs in dp_runs.items():
                _publish(f"data_path_{p}_step_ms", rs)
            # r18 streaming tier: steady-state stall fraction (median
            # over the interleaved reps) — the <1% acceptance number
            pcts = sorted(100.0 * r["stall_s"] / r["elapsed"]
                          for r in dp_runs["stream"]
                          if r.get("elapsed") and "stall_s" in r)
            if pcts:
                record["stream_stall_pct"] = round(pcts[len(pcts) // 2], 2)
            # ISSUE 16 ZeRO arms (opt out: FDT_BENCH_ZERO=0) — three
            # pieces: (a) dp x tp=2 sizing twins for the tentpole's
            # headline (post-ZeRO opt_state_bytes_per_chip vs the forced-
            # replicated twin, guard class bytes_per_chip); (b) the
            # overlap reduce-scatter A/B at K in {1,4}, N interleaved
            # with noise bands like every other *_step_ms pair; (c) the
            # single-run --offload_opt_state attribution probe.
            if os.environ.get("FDT_BENCH_ZERO", "1") != "0":
                zb = {m: _run_child(f"zerobytes_{m}")
                      for m in ("zero", "repl")}
                z, rp = zb["zero"], zb["repl"]
                if z and "opt_state_bytes_per_chip" in z:
                    record["opt_state_bytes_per_chip_tp2_zero"] = int(
                        z["opt_state_bytes_per_chip"])
                    record["params_bytes_per_chip_tp2"] = int(
                        z["params_bytes_per_chip"])
                elif z and "skipped" in z:
                    record["zero_bytes_note"] = z["skipped"]
                if rp and "opt_state_bytes_per_chip" in rp:
                    record["opt_state_bytes_per_chip_tp2_replicated"] = \
                        int(rp["opt_state_bytes_per_chip"])
                    if z and z.get("opt_state_bytes_per_chip"):
                        record["opt_state_zero_reduction_x"] = round(
                            rp["opt_state_bytes_per_chip"]
                            / z["opt_state_bytes_per_chip"], 2)
                ov_runs = {(mode, kk): [] for mode in ("on", "off")
                           for kk in (1, 4)}
                for _ in range(reps):
                    for (mode, kk) in ov_runs:
                        r = _run_child(f"kov_{mode}_{kk}")
                        if r and "elapsed" in r:
                            ov_runs[(mode, kk)].append(r)
                for (mode, kk), rs in ov_runs.items():
                    _publish(f"resnet_bs512_k{kk}_overlap_{mode}"
                             f"_step_ms", rs)
                r = _run_child("optoffload")
                if r and "elapsed" in r:
                    record["opt_offload_step_ms"] = round(
                        r["elapsed"] / r["steps_timed"] * 1e3, 3)
        # Pipeline weak-scaling ladder (r22 pp tentpole): simulated
        # pods of {1, 2, 4} slices (virtual host devices — the same
        # tier-1 simulation seam as restart_slice_mttr), pp = one
        # stage per slice, model depth grown with the slice count.
        # Ideal pipelining holds step time ~flat across the rungs;
        # the headline (largest) rung also publishes the executed
        # schedule's fill/drain bubble share (pipeline_bubble_pct,
        # guarded above) and the per-stage idle time it implies
        # (pp_stage_idle_ms = idle ticks x measured tick time).  CPU-
        # container rungs measure the rotation/collective machinery —
        # real-DCN numbers land with the first live multi-slice bench
        # (ROADMAP carryover).  Opt out: FDT_BENCH_PP=0.
        if os.environ.get("FDT_BENCH_PP", "1") != "0":
            for npp in (1, 2, 4):
                r = _run_child(f"pp_{npp}")
                if r and "elapsed" in r:
                    pp_ms = round(r["elapsed"] / r["steps_timed"] * 1e3, 3)
                    record[f"weak_scaling_slice{npp}_step_ms"] = pp_ms
                    if r.get("n_stages", 1) > 1:
                        record["pipeline_bubble_pct"] = r["bubble_pct"]
                        record["pp_n_stages"] = r["n_stages"]
                        record["pp_n_microbatches"] = r["n_microbatches"]
                        record["pp_stage_idle_ms"] = round(
                            pp_ms / r["n_ticks"] * r["stage_idle_ticks"],
                            3)
                elif r and r.get("skipped"):
                    # no silent caps: an unservable rung is recorded
                    record[f"pp_slice{npp}_note"] = r["skipped"]
            # r23 per-stage residency sizing twins (ISSUE 19 tentpole
            # headline): per-chip param + opt-state bytes on dp x pp=2
            # with stage-owned leaves sharded over pp vs the r22
            # replicated-over-pp layout — the zerobytes_ twin pattern.
            # Guard class bytes_per_chip (lower is better, 2% band).
            pb = {m: _run_child(f"ppbytes_{m}")
                  for m in ("staged", "repl")}
            st, rp = pb["staged"], pb["repl"]
            if st and "params_bytes_per_chip" in st:
                record["pp_param_bytes_per_chip_pp2_staged"] = int(
                    st["params_bytes_per_chip"])
                record["pp_opt_state_bytes_per_chip_pp2_staged"] = int(
                    st["opt_state_bytes_per_chip"])
            elif st and "skipped" in st:
                record["pp_residency_bytes_note"] = st["skipped"]
            if rp and "params_bytes_per_chip" in rp:
                record["pp_param_bytes_per_chip_pp2_replicated"] = int(
                    rp["params_bytes_per_chip"])
                record["pp_opt_state_bytes_per_chip_pp2_replicated"] = \
                    int(rp["opt_state_bytes_per_chip"])
                if st and st.get("params_bytes_per_chip"):
                    record["pp_param_residency_reduction_x"] = round(
                        rp["params_bytes_per_chip"]
                        / st["params_bytes_per_chip"], 2)
                if st and st.get("opt_state_bytes_per_chip"):
                    record["pp_opt_state_residency_reduction_x"] = round(
                        rp["opt_state_bytes_per_chip"]
                        / st["opt_state_bytes_per_chip"], 2)
        # Eval throughput under the guard (VERDICT r5 #7): the real
        # pad-and-mask eval step at each workload's headline shape.
        ev = _run_child("eval_resnet")
        if ev:
            record[f"resnet_eval_img_per_sec_bs{bs}"] = round(
                bs * steps / ev["elapsed"] / n_chips, 1)
        ev = _run_child("eval_tf")
        if ev:
            record["transformer_eval_ex_per_sec_bs256_seq256"] = round(
                256 * tf_steps / ev["elapsed"] / n_chips, 1)
        # Long-context attention ladder: DEFAULT-ON (VERDICT r3 #4 — the
        # driver runs plain `python bench.py`, so the envelope numbers
        # must land in BENCH_r*.json without hand-running).  Opt out with
        # FDT_BENCH_ATTN=0.
        if os.environ.get("FDT_BENCH_ATTN", "1") != "0":
            ladder = _run_child("attn_ladder")
            if ladder:
                record.update(ladder)
        # r11 2D-mesh attention arms: the ring/ulysses ladder variants
        # plus the sequence-parallel route cells (flash vs ring vs
        # ulysses as full NGD train steps), N>=5 INTERLEAVED re-runs —
        # medians published, observed range beside them as
        # *_noise_band_pct feeding the guard thresholds (the r6 noise
        # protocol).  These arms are what lets `_ATTN_ROUTE_SURFACE`'s
        # sp rows claim their cells with a measurement.  Opt out with
        # FDT_BENCH_ATTN2D=0; single-device hosts skip (nothing to
        # shard over) and say so in-record.
        if os.environ.get("FDT_BENCH_ATTN2D", "1") != "0":
            if n_chips < 2:
                record["attn2d_note"] = (
                    "ring/ulysses ladder + route cells skipped: single-"
                    "device host (the sp strategies need >=2 chips)")
            else:
                reps2 = max(1, int(os.environ.get(
                    "FDT_BENCH_ATTN2D_REPEATS", "5")))
                rsteps2 = int(os.environ.get("FDT_BENCH_ROUTE_STEPS",
                                             "10"))
                lad_runs = {"ring": [], "ulysses": []}
                route2d_runs = {}
                for _ in range(reps2):
                    for impl in ("ring", "ulysses"):
                        r = _run_child(f"attn_ladder_{impl}")
                        if r:
                            lad_runs[impl].append(r)
                    for cbs, cseq, impls in ATTN_ROUTE_SP_BENCH_CELLS:
                        for impl in impls:
                            r = _run_child(f"route2d_{cbs}_{cseq}_{impl}")
                            if r and "elapsed" in r:
                                route2d_runs.setdefault(
                                    (cbs, cseq, impl), []).append(
                                    r["elapsed"] / rsteps2 * 1e3)
                            elif r and r.get("skipped"):
                                # no silent caps: an unservable cell is
                                # recorded, not just absent
                                record[f"attn_route_bs{cbs}_seq{cseq}"
                                       f"_{impl}_note"] = r["skipped"]

                def _med_band(name, ms):
                    ms = sorted(ms)
                    med = ms[len(ms) // 2]
                    record[name] = round(med, 2)
                    if len(ms) > 1 and med:
                        record[name + "_noise_band_pct"] = round(
                            (ms[-1] - ms[0]) / med * 100.0, 1)

                for impl, runs in lad_runs.items():
                    for k2 in sorted(set().union(
                            *(r.keys() for r in runs)) if runs else ()):
                        _med_band(k2, [r[k2] for r in runs if k2 in r])
                for (cbs, cseq, impl), ms in sorted(route2d_runs.items()):
                    _med_band(f"attn_route_bs{cbs}_seq{cseq}_{impl}"
                              f"_step_ms", ms)

    # Round-over-round regression guard (VERDICT r4 #2c): compare every
    # tracked numeric metric against the previous round's record and flag
    # wrong-way moves past each metric's noise threshold — no more
    # hand-diffing rounds.
    prev, prev_file = _prev_bench_record()
    if prev:
        record["regression_baseline_file"] = prev_file
        # missing-metric detection only when the full metric set ran —
        # intentional opt-outs (FDT_BENCH_FAST / FDT_BENCH_ATTN=0) must
        # not read as vanished metrics
        full_run = (os.environ.get("FDT_BENCH_FAST") != "1"
                    and os.environ.get("FDT_BENCH_ATTN", "1") != "0"
                    and os.environ.get("FDT_BENCH_ATTN2D", "1") != "0"
                    and os.environ.get("FDT_BENCH_ROUTE", "1") != "0"
                    and os.environ.get("FDT_BENCH_CKPT", "1") != "0"
                    and os.environ.get("FDT_BENCH_TELEM", "1") != "0"
                    and os.environ.get("FDT_BENCH_QUANT", "1") != "0"
                    and os.environ.get("FDT_BENCH_KDIS", "1") != "0"
                    and os.environ.get("FDT_BENCH_SERVE", "1") != "0"
                    and os.environ.get("FDT_BENCH_DECODE", "1") != "0"
                    and os.environ.get("FDT_BENCH_PP", "1") != "0")
        # r6/r7 standing-note follow-through: the A/B `*_step_ms` pairs
        # are only comparable against a LIVE record — the committed
        # baseline may still be the r5 `record_note` reconstruction,
        # which carries NO measured step-ms pairs worth judging against.
        live = _is_live_record(prev)
        if not live:
            msg = (f"[bench] baseline {prev_file} is the r5 record_note "
                   f"reconstruction, not a live record: *_step_ms A/B "
                   f"guard comparisons skipped — when a live TPU record "
                   f"lands, apply PARITY.md 'r6 A/B follow-up decision' "
                   f"(steps a-d: LN/flash-stats kill switches, route-"
                   f"cell flips, ckpt overhead) to its measured pairs")
            print(msg, file=sys.stderr)
            record["regression_baseline_note"] = msg[len("[bench] "):]
        record["regressions"] = _find_regressions(record, prev,
                                                  check_missing=full_run,
                                                  compare_step_ms=live)
    # Evidence chain (VERDICT r5 #1): persist the FULL record to a
    # committed file beside this script — the driver's 2 KB stdout tail
    # can never orphan a round's numbers again — and print a compact
    # essentials line LAST so that tail always carries the headline even
    # as the record grows.  FDT_BENCH_FAST smoke runs must NOT clobber
    # the committed full record (a near-empty fast record would become
    # the newest baseline and the guard would silently compare nothing).
    if os.environ.get("FDT_BENCH_FAST") != "1":
        try:
            with open(os.path.join(_bench_dir(), BENCH_LATEST), "w") as fh:
                json.dump(record, fh, indent=1)
                fh.write("\n")
        except OSError as e:
            print(f"[bench] could not write {BENCH_LATEST}: {e!r}",
                  file=sys.stderr)
    print(json.dumps(record))
    print(json.dumps(_essentials(record)))


def _essentials(record: dict) -> dict:
    """<=1.5 KB headline subset printed as the LAST stdout line: the
    driver's tail capture parses this even when the full record outgrows
    it; bench_unix_time ties it back to the full BENCH_LATEST.json."""
    keys = ("metric", "value", "unit", "ngd_overhead_pct",
            "transformer_agnews_ex_per_sec_bs256_seq256",
            "transformer_bs256_seq256_mfu_pct",
            "transformer_agnews_ex_per_sec_bs64_seq512",
            "transformer_bs64_seq512_mfu_pct",
            "transformer_bs64_seq512_mfu_pct_noise_band_pct",
            "transformer_eval_ex_per_sec_bs256_seq256",
            "params_bytes_per_chip", "opt_state_bytes_per_chip",
            "tricks_speedup_x", "ckpt_async_overhead_pct",
            "ckpt_async_amortized_overhead_pct",
            "ckpt_async_sharded_overhead_pct", "restart_mttr_s",
            "restart_mttr_compile_s", "restart_mttr_restore_s",
            "restart_cached_mttr_s", "restart_slice_mttr_s",
            "warm_spare_swap_s",
            "serve_p50_ms", "serve_p99_ms", "serve_qps_per_chip",
            "decode_tokens_per_sec_per_chip", "decode_ttft_p50_ms",
            "decode_ttft_p99_ms", "decode_slo_violation_pct",
            "telemetry_overhead_pct",
            "transformer_bs256_seq256_quant_off_step_ms",
            "transformer_bs256_seq256_int8_step_ms",
            "transformer_bs256_seq256_int8_step_ms_noise_band_pct",
            "transformer_bs256_seq256_fp8_step_ms",
            "transformer_bs256_seq256_int8_mfu_pct",
            "transformer_bs256_seq256_fp8_mfu_pct",
            "transformer_bs256_seq256_k1_step_ms",
            "transformer_bs256_seq256_k4_step_ms",
            "transformer_bs256_seq256_k16_step_ms",
            "transformer_bs256_seq256_k4_step_ms_noise_band_pct",
            "resnet_bs512_k1_step_ms", "resnet_bs512_k4_step_ms",
            "resnet_bs512_k16_step_ms",
            "opt_state_bytes_per_chip_tp2_zero",
            "opt_state_bytes_per_chip_tp2_replicated",
            "opt_state_zero_reduction_x",
            "resnet_bs512_k4_overlap_on_step_ms",
            "resnet_bs512_k4_overlap_off_step_ms",
            "opt_offload_step_ms",
            "data_path_host_step_ms", "data_path_resident_step_ms",
            "data_path_stream_step_ms", "stream_stall_pct",
            "weak_scaling_slice1_step_ms", "weak_scaling_slice2_step_ms",
            "weak_scaling_slice4_step_ms",
            "pipeline_bubble_pct", "pp_stage_idle_ms",
            "pp_param_bytes_per_chip_pp2_staged",
            "pp_param_bytes_per_chip_pp2_replicated",
            "pp_opt_state_bytes_per_chip_pp2_staged",
            "pp_opt_state_bytes_per_chip_pp2_replicated",
            "pp_param_residency_reduction_x",
            "pp_opt_state_residency_reduction_x",
            "bench_unix_time", "regression_baseline_file")
    ess = {"essentials": True, "full_record": BENCH_LATEST}
    for k in keys:
        if k in record:
            ess[k] = record[k]
    for k in record:
        if k.startswith("resnet_eval_img_per_sec"):
            ess[k] = record[k]
    regs = record.get("regressions")
    if regs is not None:
        ess["regressions_count"] = len(regs)
        ess["regressed_metrics"] = [r["metric"] for r in regs][:8]
    return ess


if __name__ == "__main__":
    main()
