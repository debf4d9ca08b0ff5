#!/usr/bin/env python
"""Streaming data plane smoke (r18): shard a tiny corpus to disk, train
the next-token LM workload THROUGH THE STREAMED WINDOW, kill it
mid-epoch (mid-WINDOW) with an injected fault, resume in a fresh
process, and assert the final state digest equals the uninterrupted
streamed run's — the process-level twin of
tests/test_stream.py::TestStreamTrainingE2E (which recovers in-process
under the supervisor).  Nothing survives between the killed and resumed
processes except the checkpoint dir and the on-disk shards, exactly the
production relaunch contract.

    python scripts/stream_smoke.py              # CPU, ~1-2 min
    FDT_SMOKE_DIE_AT=14 python scripts/stream_smoke.py

Also prints each run's steady-state stream_stall_pct.  NOTE: at this
toy scale (sub-ms steps) the stall fraction is meaningless — the <1%
target is for a real step on the chip, where it is not measured (no
cell of BENCHMARK.json streams).  Prints PASS/FAIL per assertion; exit
0 iff all pass."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SEQ_LEN = 32
BATCH = 8
EPOCHS = 2
K = 2                 # steps per dispatch
WINDOW = 4            # batches per stream buffer
CADENCE = 4           # checkpoint_every (a multiple of K)

_CHILD = r"""
import hashlib, json, os, sys
import numpy as np, jax
from faster_distributed_training_tpu.cli import run_training
from faster_distributed_training_tpu.config import TrainConfig

cfg = TrainConfig(model="transformer", dataset="stream", task="lm",
                  data_path="stream",
                  stream_dir=os.environ["FDT_SMOKE_STREAM_DIR"],
                  batch_size=%(batch)d, seq_len=%(seq)d, n_layers=1,
                  d_model=16, d_ff=32, n_heads=2, epochs=%(epochs)d,
                  steps_per_dispatch=%(k)d, stream_window=%(window)d,
                  optimizer="sgd", precision="fp32", plot=False, workers=0,
                  log_every=0, donate=False, device="cpu",
                  checkpoint_dir=os.environ["FDT_SMOKE_DIR"],
                  checkpoint_every=%(cadence)d)
out = run_training(cfg, log=lambda *a: print(*a, file=sys.stderr))
h = hashlib.sha256()
for tree in (out["state"].params, out["state"].opt_state,
             out["state"].batch_stats):
    for path, leaf in sorted(
            ((jax.tree_util.keystr(p), l) for p, l in
             jax.tree_util.tree_leaves_with_path(tree))):
        h.update(path.encode())
        h.update(np.ascontiguousarray(jax.device_get(leaf)).tobytes())
print(json.dumps({
    "digest": h.hexdigest(),
    "final_step": int(out["state"].step),
    "restores": int(out.get("goodput_restores", 0)),
    "stall_pct": out.get("stream_stall_pct"),
    "test_ppl": out["history"]["test_ppl"][-1:],
}))
"""


def run_phase(stream_dir: str, ckpt_dir: str, die_at: int = 0,
              expect_crash: bool = False) -> dict:
    env = dict(os.environ, FDT_SMOKE_STREAM_DIR=stream_dir,
               FDT_SMOKE_DIR=ckpt_dir, JAX_PLATFORMS="cpu")
    if die_at:
        env["FDT_FAULT_DIE_AT_STEP"] = str(die_at)
    else:
        env.pop("FDT_FAULT_DIE_AT_STEP", None)
    code = _CHILD % {"batch": BATCH, "seq": SEQ_LEN, "epochs": EPOCHS,
                     "k": K, "window": WINDOW, "cadence": CADENCE}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    if expect_crash:
        if r.returncode == 0:
            print(r.stderr[-2000:], file=sys.stderr)
            raise RuntimeError("kill phase exited 0 — the injected fault "
                               "never fired")
        return {"rc": r.returncode}
    if r.returncode != 0:
        print(r.stderr[-2000:], file=sys.stderr)
        raise RuntimeError(f"phase exited rc={r.returncode}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def main() -> int:
    die_at = int(os.environ.get("FDT_SMOKE_DIE_AT", "10"))
    work = tempfile.mkdtemp(prefix="fdt_stream_smoke_")
    try:
        return _run(work, die_at)
    finally:
        # the smoke also runs per tier-1 invocation — don't accumulate
        # shards+checkpoints in /tmp (kept on failure for post-mortem)
        if not _keep_work:
            import shutil
            shutil.rmtree(work, ignore_errors=True)
        else:
            print(f"[smoke] kept {work} for inspection")


_keep_work = True     # flipped to False only on a clean PASS — crashed
                      # or failing runs keep their dirs for post-mortem


def _run(work: str, die_at: int) -> int:
    global _keep_work
    stream_dir = os.path.join(work, "corpus")
    failures = 0

    def check(name, ok, detail=""):
        nonlocal failures
        print(f"[{'PASS' if ok else 'FAIL'}] {name}"
              + (f" ({detail})" if detail else ""))
        failures += 0 if ok else 1

    from faster_distributed_training_tpu.data.stream import (
        ShardedStreamDataset, synthetic_corpus, write_lm_corpus)

    print(f"phase 0: shard a tiny synthetic corpus -> {stream_dir}")
    write_lm_corpus(stream_dir, synthetic_corpus(64, seed=3), SEQ_LEN,
                    rows_per_shard=32, val_fraction=0.15)
    train = ShardedStreamDataset(os.path.join(stream_dir, "train"))
    steps_per_epoch = train.n // BATCH
    total = steps_per_epoch * EPOCHS
    check("corpus sharded (multi-shard, committed manifest)",
          len(train.manifest["shards"]) > 1 and train.n >= BATCH * 4,
          f"{train.n} rows x {train.seq_len}, "
          f"{len(train.manifest['shards'])} shards")
    assert CADENCE < die_at < steps_per_epoch, \
        f"pick FDT_SMOKE_DIE_AT in ({CADENCE}, {steps_per_epoch})"

    print(f"phase 1: uninterrupted streamed LM reference "
          f"({total} steps)")
    ref = run_phase(stream_dir, os.path.join(work, "ck_ref"))
    check("reference ran every step", ref["final_step"] == total,
          str(ref["final_step"]))
    check("perplexity finite", bool(ref["test_ppl"])
          and ref["test_ppl"][-1] > 0, str(ref["test_ppl"]))
    print(f"  reference stream_stall_pct={ref['stall_pct']} (toy scale — "
          f"the <1% target is for a real step on the chip)")

    ck = os.path.join(work, "ck_kill")
    print(f"phase 2: streamed run killed MID-WINDOW at step {die_at} "
          f"(window {WINDOW}, cadence {CADENCE})")
    run_phase(stream_dir, ck, die_at=die_at, expect_crash=True)
    from faster_distributed_training_tpu.resilience import (
        AsyncCheckpointManager)
    mgr = AsyncCheckpointManager(ck, prefix="transformer",
                                 log=lambda *_: None)
    committed = mgr.committed_steps()
    # the cadence save is ASYNC: at toy scale (sub-ms steps) the kill a
    # couple of steps after a save can beat that save's background
    # COMMIT, so the newest pre-kill cadence point is not guaranteed —
    # only that SOME committed checkpoint exists strictly before the
    # kill (resume replays the rest; the digest check below is the
    # bitwise contract either way)
    check("a cadence checkpoint committed before the kill",
          bool(committed) and max(committed) < die_at
          and all(s % CADENCE == 0 for s in committed), str(committed))

    print("phase 3: fresh-process resume (pure seek into the same "
          "global batch stream)")
    second = run_phase(stream_dir, ck)
    check("resumed from the cadence checkpoint", second["restores"] == 1,
          str(second["restores"]))
    check(f"reached all {total} steps", second["final_step"] == total,
          str(second["final_step"]))
    check("final state digest == uninterrupted streamed reference",
          second["digest"] == ref["digest"],
          f"{second['digest'][:12]} vs {ref['digest'][:12]}")

    print("PASS" if not failures else f"FAIL ({failures} assertion(s))")
    _keep_work = bool(failures)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
