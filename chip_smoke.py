#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that training still starts on the chip.

    python chip_smoke.py            # one TPU chip: both paper workloads
    python chip_smoke.py --chips 4  # one four-chip host: the mesh phase only

One process, no children, through ``cli.main`` exactly as
``transformer_test.py`` / ``resnet50_test.py`` call it (``--device tpu
--dataset synthetic``, bf16, default tricks, default routing, telemetry
on), at the full paper widths (transformer 6L/d512/h8/ff1024/vocab
30522; ResNet-50); only the number of steps is cut, and the weights are
random from ``--seed``.  It FAILS — it does not fall back — when JAX
finds no TPU, and it exits non-zero if any phase failed.  What it
prints per run (device kind, compile seconds, step ms, peak bytes,
cache hit/miss) are observations, not a benchmark.  The last stdout
line is the one-object JSON verdict.

One chip (default): transformer NGD bs64/seq512 (flash fwd+bwd kernels
and the Pallas MLP head in the step) for 8 steps + eval, then ResNet-50
NGD+mixup bs1024 32x32 for 4 steps + eval.

``--chips 4`` runs ONLY: (i) the transformer config on the default mesh
(all four chips on dp), (ii) bs256/seq256 on ``--mesh dp=2,tp=2``, and
(iii) what (i) is compared with: the same config and seed on one of the
four chips (``--mesh dp=1``).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import shutil
import statistics
import sys
import tempfile
import time

# -- what a CPU rehearsal (tests/test_chip_smoke.py) overrides ----------------
PLATFORM = "tpu"            # the platform every run must execute on
OUT_DIR = os.path.join("chiprun_out", "chip_smoke")

COMMON = ["--dataset", "synthetic", "--epoch", "1", "--log_every", "1",
          "--no_plot"]
# full paper width comes from the entry scripts' own defaults (n_layers 6,
# d_model 512, n_heads 8, d_ff 1024, synthetic vocab 30522)
TRANSFORMER = ["--ngd", "--bs", "64", "--seq_len", "512",
               "--subset_stride", "8"]          # 4096/8/64 = 8 steps
TRANSFORMER_TP = ["--ngd", "--bs", "256", "--seq_len", "256",
                  "--subset_stride", "2"]       # 4096/2/256 = 8 steps
RESNET = ["--ngd", "--bs", "1024"]              # 4096/1024 = 4 steps, mixup
MIN_STEPS = {"transformer": 8, "resnet": 4}
# tpu_custom_call sites the lowered train step must hold.  bs64/seq512:
# flash forward + backward per layer (2 x 6) + the Pallas MLP head;
# bs256/seq256 routes dense attention, so only the MLP head; ResNet has
# no kernel.  Keyed by the argv list's name.
MIN_KERNELS = {"TRANSFORMER": 13, "TRANSFORMER_TP": 1, "RESNET": 0}
# (i) on dp=4 vs (iii) on one chip: same seed, same global batch, same
# effective LR.  bf16 compute with different reduction orders (per-chip
# partial sums + all-reduce): the per-step losses (~1.4, 4 classes) must
# agree to this absolute tolerance on every step.
LOSS_TOL = 0.05

_LOSS_LINE = re.compile(r"\] step (\d+): loss=([-+0-9.eE]+|nan|inf)")


class _Tee:
    """stdout pass-through that keeps (time, line) of every line."""

    def __init__(self, stream):
        self.stream, self.lines, self._buf = stream, [], ""

    def write(self, s):
        self.stream.write(s)
        self._buf += s
        while "\n" in self._buf:
            line, self._buf = self._buf.split("\n", 1)
            self.lines.append((time.monotonic(), line))
        return len(s)

    def flush(self):
        self.stream.flush()


def _check(ok: bool, what: str, failures: list) -> None:
    print(f"[smoke]   {'ok  ' if ok else 'FAIL'} {what}")
    if not ok:
        failures.append(what)


def run_training(tag: str, kind: str, size: str, failures: list,
                 extra: tuple = ()) -> dict:
    """One ``cli.main`` run of the module-level argv list named ``size``
    (+ ``extra``) and the checks every run must pass.  Returns what
    later comparisons need (losses, cfg, state)."""
    import jax

    from faster_distributed_training_tpu.cli import main
    if kind == "transformer":
        from transformer_test import DEFAULTS
    else:
        from resnet50_test import DEFAULTS

    # the epoch checkpoint is part of the main path but is hundreds of
    # MB at full width: it goes to a temp dir that is removed, only the
    # telemetry (manifest + JSONL) stays under OUT_DIR
    ckpt_dir = tempfile.mkdtemp(prefix="chip_smoke_")
    argv = (["--device", PLATFORM] + COMMON + globals()[size] + list(extra)
            + ["--checkpoint_dir", ckpt_dir, "--telemetry_dir",
               os.path.join(OUT_DIR, tag, "telemetry")])
    print(f"[smoke] {tag}: cli.main({' '.join(argv)})")
    tee = _Tee(sys.stdout)
    t0 = time.monotonic()
    try:
        with contextlib.redirect_stdout(tee):
            out = main(argv, defaults=DEFAULTS, prog=f"{kind}_test")
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    wall = time.monotonic() - t0

    # -- losses: one fenced read-back per step (--log_every 1) -------------
    steps = [(t, int(m.group(1)), float(m.group(2)))
             for t, line in tee.lines
             if (m := _LOSS_LINE.search(line))]
    losses = [l for _, _, l in steps]
    _check(len(losses) >= MIN_STEPS[kind],
           f"{tag}: {len(losses)} optimizer steps logged "
           f"(need >= {MIN_STEPS[kind]})", failures)
    _check(bool(losses) and all(l == l and abs(l) != float("inf")
                                for l in losses),
           f"{tag}: loss finite on every step {losses}", failures)
    _check(len(losses) > 1 and losses[-1] != losses[0],
           f"{tag}: last loss differs from first", failures)
    hist = out["history"]
    _check(bool(hist["test_loss"]) and all(
        l == l and abs(l) != float("inf") for l in hist["test_loss"]),
        f"{tag}: one eval pass, finite eval loss {hist['test_loss']}",
        failures)

    # -- it ran on the chip -------------------------------------------------
    leaves = jax.tree.leaves(out["state"].params)
    plats = {d.platform for leaf in leaves for d in leaf.devices()}
    _check(plats == {PLATFORM},
           f"{tag}: state lives on platform {sorted(plats)}", failures)

    # -- the compiled programs (manifest compile table) ----------------------
    with open(os.path.join(out["telemetry_dir"], "manifest.json")) as f:
        manifest = json.load(f)
    programs = manifest["compile"]["programs"]
    train = [v for p in programs if p["name"].startswith("train")
             for v in p["variants"]]
    _check(bool(train), f"{tag}: a train program was compiled", failures)
    ops: dict = {}
    for v in train:
        for k, n in (v.get("hlo_ops") or {}).items():
            ops[k] = max(ops.get(k, 0), n)
    _check(ops.get("tpu_custom_call", 0) >= MIN_KERNELS[size],
           f"{tag}: train step holds {ops.get('tpu_custom_call', 0)} "
           f"tpu_custom_call(s) (need >= {MIN_KERNELS[size]})", failures)
    for p in programs:
        for v in p["variants"]:
            print(f"[smoke]   program {p['name']}: compile "
                  f"{v['compile_ms'] / 1e3:.2f} s, persistent cache "
                  f"{v['cache']} ({v['cache_method']}), hlo_ops "
                  f"{v.get('hlo_ops')}")

    # -- observations (not a benchmark) --------------------------------------
    # step time: gaps between consecutive fenced per-step loss reads,
    # i.e. from the first step's read-back on (its compile lies before)
    gaps = [1e3 * (b[0] - a[0]) for a, b in zip(steps, steps[1:])]
    dev = jax.devices()[0]
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use"))
    obs = {"run": tag, "device_kind": dev.device_kind,
           "devices": len(jax.devices()),
           "wall_s": round(wall, 2),
           "compile_s": round(manifest["compile"]["total_compile_ms"] / 1e3,
                              2),
           "train_compile_s": round(sum(v["compile_ms"] for v in train)
                                    / 1e3, 2),
           "train_cache": [v["cache"] for v in train],
           "step_ms_median": (round(statistics.median(gaps), 3)
                              if gaps else None),
           "step_ms_all": [round(g, 2) for g in gaps],
           "peak_bytes_in_use_process_so_far": peaks,
           "losses": losses, "eval_loss": hist["test_loss"],
           "hlo_ops": ops}
    print(f"[smoke] {tag} observations (not a benchmark): "
          + json.dumps(obs))
    return {"out": out, "losses": losses, "ops": ops, "obs": obs}


def one_chip(failures: list) -> None:
    run_training("transformer_bs64_seq512", "transformer", "TRANSFORMER",
                 failures)
    run_training("resnet50_bs1024", "resnet", "RESNET", failures)


def _every_device_holds(tag: str, res: dict, failures: list) -> None:
    """(i)/(ii): nothing silently on device 0 — every device of the mesh
    holds shards of the train state, takes a shard of the batch under the
    run's own placement rule, and reports memory in use."""
    import jax
    import numpy as np

    from faster_distributed_training_tpu.parallel import make_mesh
    from faster_distributed_training_tpu.parallel.mesh import (
        _ici_device_mesh)
    from faster_distributed_training_tpu.parallel.placement import (
        make_put_batch)

    cfg, state = res["out"]["cfg"], res["out"]["state"]
    mesh = make_mesh(cfg.mesh_axes, cfg.mesh_shape)
    all_ids = {d.id for d in mesh.devices.flat}
    held = set()
    for leaf in jax.tree.leaves((state.params, state.opt_state)):
        held |= {s.device.id for s in leaf.addressable_shards}
    _check(held == all_ids,
           f"{tag}: params/optimizer state have shards on devices "
           f"{sorted(held)}", failures)
    shape = tuple(mesh.shape[a] for a in mesh.axis_names)
    if PLATFORM == "tpu":
        ici = _ici_device_mesh(shape, tuple(mesh.axis_names))
        _check(ici is not None and bool(
            (np.vectorize(lambda d: d.id)(ici)
             == np.vectorize(lambda d: d.id)(mesh.devices)).all()),
            f"{tag}: _ici_device_mesh served the {dict(mesh.shape)} mesh",
            failures)
    put = make_put_batch(mesh)(
        {"tokens": np.zeros((cfg.batch_size, cfg.seq_len), np.int32)})
    got = {s.device.id for s in put["tokens"].addressable_shards}
    rows = {s.data.shape[0] for s in put["tokens"].addressable_shards}
    dp = int(np.prod([mesh.shape[a] for a in ("dp", "fsdp")
                      if a in mesh.axis_names]))
    _check(got == all_ids and rows == {cfg.batch_size // dp},
           f"{tag}: batch placement puts {sorted(rows)} rows on devices "
           f"{sorted(got)}", failures)
    in_use = {d.id: (d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in mesh.devices.flat}
    # (the CPU backend of a rehearsal reports no memory stats)
    _check(PLATFORM != "tpu" or all(in_use.values()),
           f"{tag}: per-device peak_bytes_in_use {in_use}", failures)


def four_chips(failures: list) -> None:
    import jax
    from transformer_test import DEFAULTS

    # (i) default mesh: all four chips on dp — flash kernels and the MLP
    # head run per shard under the data axis
    dp4 = run_training("dp4_bs64_seq512", "transformer", "TRANSFORMER",
                       failures)
    _every_device_holds("dp4_bs64_seq512", dp4, failures)
    _check(dp4["ops"].get("all-reduce", 0) > 0,
           f"dp4_bs64_seq512: compiled step holds the data-parallel "
           f"all-reduce ({dp4['ops']})", failures)
    # (ii) dp=2,tp=2: _ici_device_mesh, the tp kernel_shard paths, ZeRO/tp
    tp = run_training("dp2_tp2_bs256_seq256", "transformer",
                      "TRANSFORMER_TP", failures,
                      extra=("--mesh", "dp=2,tp=2"))
    _every_device_holds("dp2_tp2_bs256_seq256", tp, failures)
    _check(tp["ops"].get("all-reduce", 0) > 0
           and (tp["ops"].get("all-gather", 0) > 0
                or tp["ops"].get("reduce-scatter", 0) > 0),
           f"dp2_tp2_bs256_seq256: compiled step holds tensor-parallel "
           f"collectives ({tp['ops']})", failures)
    # (iii) what (i) is compared with: same config and seed on ONE of the
    # four chips.  cli.run_training scales the LR by the data-parallel
    # world size, so the one-chip run is given (i)'s effective LR.
    one = run_training("dp1_bs64_seq512", "transformer", "TRANSFORMER",
                       failures, extra=("--mesh", "dp=1", "--lr", repr(
                           DEFAULTS.lr * len(jax.devices()))))
    a, b = dp4["losses"], one["losses"]
    diffs = [abs(x - y) for x, y in zip(a, b)]
    _check(len(a) == len(b) and bool(diffs) and max(diffs) <= LOSS_TOL,
           f"dp=4 vs one chip: per-step |loss diff| {diffs} <= {LOSS_TOL}",
           failures)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4 = run ONLY the four-chip mesh phase")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != PLATFORM:
        print(f"[smoke] FAIL: need a {PLATFORM} device, JAX offers "
              f"{devs[0].platform!r} — not falling back", file=sys.stderr)
        return 2
    if len(devs) != args.chips:
        print(f"[smoke] FAIL: --chips {args.chips} but JAX sees "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2

    from faster_distributed_training_tpu.runtime import native_lib
    print(f"[smoke] device: {devs[0].platform} ({devs[0].device_kind}) "
          f"x{len(devs)}; native library: {native_lib.status()}; "
          f"JAX_COMPILATION_CACHE_DIR="
          f"{os.environ.get('JAX_COMPILATION_CACHE_DIR', '(unset)')}")
    failures: list = []
    (four_chips if args.chips == 4 else one_chip)(failures)
    if failures:
        print("[smoke] FAILED:\n  " + "\n  ".join(failures),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
