#!/usr/bin/env python
"""Transformer single-chip roofline exploration (VERDICT r2 #1).

Measures the reference transformer configs plus diagnostic variants to
attribute the step time: optimizer (NGD vs SGD), batch scaling, remat,
and the fp32 embedding island.  One process per chip (bench.py's
process model): invoked without arguments the parent stays off JAX and
runs each variant in ITS OWN child process, one at a time; with
FDT_ROOFLINE_CHILD set it runs exactly one variant and prints one JSON
line that names the device it ran on.  A variant that dies makes the
parent exit non-zero.

Run on a QUIET chip:
    python scripts/transformer_roofline.py
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

VARIANTS = {
    # name: (bs, seq, opt, remat[, attention, mlp_impl, dropout_impl,
    #        mode]) — mode: "" | "noln" (identity LayerNorm probe)
    #        | "ffn_pallas" (fused FFN-sublayer kernel arm)
    #        | "ln_autodiff" (saved-stats LN VJP disabled, r6)
    #        | "flash_recompute" (flash saved-stats backward disabled, r6)
    "ngd_256_256": (256, 256, "ngd", False),
    "sgd_256_256": (256, 256, "sgd", False),
    "adamw_256_256": (256, 256, "adamw", False),
    "ngd_512_256": (512, 256, "ngd", False),
    "ngd_64_512": (64, 512, "ngd", False),
    "ngd_256_512": (256, 512, "ngd", False),
    "ngd_256_512_remat": (256, 512, "ngd", True),
    # impl attribution: XLA dense attention / XLA fused MLP vs the
    # Pallas defaults at the short reference lengths
    "sgd_256_256_dense": (256, 256, "sgd", False, "dense", ""),
    "sgd_256_256_xla_mlp": (256, 256, "sgd", False, "", "fused"),
    "sgd_256_256_dense_xla_mlp": (256, 256, "sgd", False, "dense", "fused"),
    "sgd_64_512_dense": (64, 512, "sgd", False, "dense", ""),
    # dropout-impl attribution (r4): the hash default vs the xla
    # nn.Dropout path vs the no-dropout floor — the r3 roofline found
    # mask generation+traffic was the dominant non-matmul term
    "ngd_256_256_drop_hash": (256, 256, "ngd", False, "", "", "hash"),
    "ngd_256_256_drop_xla": (256, 256, "ngd", False, "", "", "xla"),
    "ngd_256_256_drop_none": (256, 256, "ngd", False, "", "", "none"),
    # LayerNorm attribution (r5): TorchLayerNorm as identity (params
    # still registered so state shapes match) — the delta vs the
    # baseline is the 13 LN sites' end-to-end cost.  Measured on a
    # quiet chip: 112.3 -> 104.8 ms/step @ bs256/seq256, i.e. LN is
    # ~7.5 ms = ~6.7% of the step (pure HBM round-trips: 13 sites x
    # read+write in fwd and bwd ~ 4-5 GB/step at ~800 GB/s).
    "ngd_256_256_noln": (256, 256, "ngd", False, "", "", "hash", "noln"),
    # Saved-stats LN VJP attribution (r6, ops/layernorm.py): the same
    # step with the custom_vjp disabled (default XLA autodiff at all 13
    # LN sites) — baseline-vs-this is the measured recovery of the ~7.5
    # ms the noln probe attributed; the remaining noln delta is the LN
    # forward's irreducible cost.  bench.py tracks the same pair as
    # transformer_bs256_seq256_step_ms vs _ln_autodiff_step_ms.
    "ngd_256_256_ln_autodiff": (256, 256, "ngd", False, "", "", "hash",
                                "ln_autodiff"),
    # Flash saved-(out,lse) backward attribution (r6,
    # ops/flash_attention.py) at the flash-routed shape: the same step
    # with FDT_FLASH_SAVE_STATS=0 (r5 in-kernel-recompute backward);
    # bench.py tracks the pair as transformer_bs64_seq512_step_ms vs
    # _flash_recompute_step_ms.
    "ngd_64_512_flash_recompute": (64, 512, "ngd", False, "flash", "",
                                   "hash", "flash_recompute"),
    # Fused FFN-sublayer kernel (r5, ops/fused_ffn.py): the capacity-
    # lever arm beside the flax default — measured 244 ms @ 10.7 GB vs
    # flax 225 @ 12.0 at bs256/seq512 (PARITY).
    "ngd_256_512_ffn_pallas": (256, 512, "ngd", False, "", "", "hash",
                               "ffn_pallas"),
}


def run_variant(name: str) -> dict:
    bs, seq, opt, remat = VARIANTS[name][:4]
    extra = VARIANTS[name][4:]
    os.environ["FDT_BENCH_TF_OPT"] = opt
    if extra:
        os.environ["FDT_BENCH_TF_ATTN"] = extra[0]
        os.environ["FDT_BENCH_TF_MLP"] = extra[1]
    if len(extra) > 2:
        os.environ["FDT_BENCH_TF_DROPOUT"] = extra[2]
    mode = extra[3] if len(extra) > 3 else ""
    if mode == "noln":
        from faster_distributed_training_tpu.models import transformer as T
        _orig_ln = T.TorchLayerNorm.__call__

        def _ident_ln(self, x):
            _orig_ln(self, x)   # register scale/bias params, drop result
            return x

        T.TorchLayerNorm.__call__ = _ident_ln
    elif mode == "ffn_pallas":
        os.environ["FDT_BENCH_TF_FFN"] = "pallas"
    elif mode == "ln_autodiff":
        os.environ["FDT_LN_SAVED_STATS"] = "0"
    elif mode == "flash_recompute":
        os.environ["FDT_FLASH_SAVE_STATS"] = "0"
    import bench
    res = bench.timed_transformer(bs, seq, steps=20, remat=remat)
    import jax
    dev = jax.devices()[0]
    res["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": jax.device_count()}
    res["variant"] = name
    res["ex_per_sec"] = round(bs * 20 / res["elapsed"], 1)
    mf = bench.transformer_model_flops(bs, seq)
    res["mfu_pct"] = round(
        100.0 * mf / (res["elapsed"] / 20) / 1e12
        / bench.device_peak_tflops()[0], 1)
    return res


def main() -> None:
    child = os.environ.get("FDT_ROOFLINE_CHILD")
    if child:
        print(json.dumps(run_variant(child)))
        return
    failed = []
    for name in VARIANTS:
        env = dict(os.environ, FDT_ROOFLINE_CHILD=name)
        out = subprocess.run([sys.executable, os.path.abspath(__file__)],
                             env=env, capture_output=True, text=True,
                             timeout=2400)
        if out.returncode != 0 or not out.stdout.strip():
            failed.append(name)
            print(f"[roofline] variant {name} failed:\n"
                  f"{out.stderr[-2000:]}", file=sys.stderr)
            print(json.dumps({"variant": name, "error": True}), flush=True)
            continue
        print(out.stdout.strip().splitlines()[-1], flush=True)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
