#!/usr/bin/env python
"""Pod-restart smoke: REAL multi-process simulated pods (the
FDT_POD_INDEX/FDT_POD_COUNT seam — jax single-process per host, restart
coordination and the sharded two-phase checkpoint commit genuinely
cross-PROCESS), with injected kills.  Three scenarios:

  * default: the r10 acceptance — a 2-process pod, host 1 killed via
    FDT_FAULT_HOST, both supervisors converge on the same restart
    generation, restore the same step, and finish with state digests
    byte-identical to an uninterrupted single-process reference;
  * ``--backend fake_object_store`` (r14): the SAME kill/recover
    scenario with every resilience-critical durable write routed
    through the rename-free object-store backend (framed generation
    files under ``<dir>/_objects`` — whole-object PUT + O_EXCL create,
    no os.replace anywhere): digest equality must hold with no rename
    primitive, and the script additionally asserts that no marker/step-
    checkpoint state leaked onto the plain filesystem;
  * ``--slices 2`` (r14 elastic recovery): a 2-slice pod of 4
    processes (FDT_SLICE_COUNT=2), the whole of slice 1 killed via
    FDT_FAULT_SLICE — the surviving slice holds at a dispatch boundary
    (zero restarts, zero restores — it never exits its dispatch loop or
    rolls back), the killed slice restarts, REJOINS the same
    generation, catches up to the agreed step, and all four hosts
    finish digest-equal to the uninterrupted reference with
    ``slice_readmissions`` counted and ``pod_fallback_restarts`` == 0;
  * ``--cache`` (r17 instant restart): crash + PROCESS-relaunch twins,
    one with ``--executable_cache on`` and one cold, each against its
    own hermetic XLA compilation-cache dir — the cached relaunch must
    record ``cache_source=deserialized`` for EVERY steady-state
    program (train + eval) with zero retraces, finish bitwise-equal to
    the cold-restart twin AND the uninterrupted reference, and spend
    less on program acquisition than the cold twin (the
    ``restart_cached_mttr_s`` < ``restart_mttr_s`` story at smoke
    scale).

The default scenario additionally asserts the r15 crash flight
recorder: the killed host's injected crash must leave a durable
``telemetry/flight_<pi>_<ts>.json`` dump (written through the same
storage backend the children used) that parses and names the fault —
``scripts/telemetry_report.py --flight`` renders the same files.

    python scripts/pod_restart_smoke.py                      # CPU, ~1 min
    python scripts/pod_restart_smoke.py --backend fake_object_store
    python scripts/pod_restart_smoke.py --slices 2
    python scripts/pod_restart_smoke.py --cache
    FDT_SMOKE_DIE_AT=9 python scripts/pod_restart_smoke.py

Prints PASS/FAIL per assertion; exit code 0 iff all pass."""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# synthetic AG News, subset_stride 64 -> 64 samples @ bs 8 = 8 steps/epoch
# x 2 epochs = 16 global steps
STEPS_PER_EPOCH = 8
EPOCHS = 2
TOTAL_STEPS = STEPS_PER_EPOCH * EPOCHS
CKPT_EVERY = 2     # the cadence's commit barrier also bounds host drift:
#                    host 0's step-2k tick DRAINS its step-2(k-1) commit,
#                    which needs host 1's DONE — so unsynchronized
#                    processes can never drift a full failure past each
#                    other


def reference_cfg(workdir: str, backend: str = "posix"):
    """The uninterrupted single-process reference configuration — the
    same training math with no pod, no faults, no supervisor."""
    from faster_distributed_training_tpu.config import TrainConfig
    return TrainConfig(model="transformer", dataset="synthetic",
                       num_classes=4, batch_size=8, seq_len=16, n_layers=1,
                       d_model=16, d_ff=32, n_heads=2, epochs=EPOCHS,
                       subset_stride=64, optimizer="sgd", precision="fp32",
                       plot=False, workers=0, log_every=0, donate=False,
                       checkpoint_dir=workdir, storage_backend=backend)


def state_digest(state) -> str:
    """sha256 over every checkpointable leaf's bytes (params, BN stats,
    optimizer state, loss scale, step, RNG) — byte-identical final
    states hash equal."""
    import jax
    import numpy as np

    from faster_distributed_training_tpu.train import checkpoint as ckpt
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(ckpt._state_pytree(state)):
        h.update(np.ascontiguousarray(jax.device_get(leaf)).tobytes())
    return h.hexdigest()


_CHILD = r"""
import json, os, sys
sys.path.insert(0, os.environ["FDT_SMOKE_REPO"])
import importlib.util
spec = importlib.util.spec_from_file_location(
    "pod_restart_smoke",
    os.path.join(os.environ["FDT_SMOKE_REPO"], "scripts",
                 "pod_restart_smoke.py"))
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
from faster_distributed_training_tpu.cli import run_training

cfg = mod.reference_cfg(os.environ["FDT_SMOKE_DIR"],
                        backend=os.environ.get("FDT_SMOKE_BACKEND", "posix"))
if os.environ.get("FDT_POD_COUNT"):
    cfg = cfg.replace(supervise=True, checkpoint_every=%(every)d,
                      preempt_sync_every=1, peer_timeout_s=5.0,
                      max_restarts=3)
if os.environ.get("FDT_SMOKE_CKPT_EVERY"):
    # the --cache relaunch scenario: cadence saves without a pod
    cfg = cfg.replace(
        checkpoint_every=int(os.environ["FDT_SMOKE_CKPT_EVERY"]))
if os.environ.get("FDT_SMOKE_EXEC_CACHE"):
    cfg = cfg.replace(
        executable_cache=os.environ["FDT_SMOKE_EXEC_CACHE"])
out = run_training(cfg, log=lambda *a: print(*a, file=sys.stderr))
print(json.dumps({
    "final_step": int(out["state"].step),
    "digest": mod.state_digest(out["state"]),
    "restarts": int(out.get("goodput_restarts", 0)),
    "restores": int(out.get("goodput_restores", 0)),
    "restore_s": float(out.get("goodput_restore_s", 0.0)),
    "compile_s": float(out.get("goodput_compile_s", 0.0)),
    "peer_failures": int(out.get("goodput_peer_failures", 0)),
    "restart_generations": int(out.get("goodput_restart_generations", 0)),
    "restart_mttr_s": float(out.get("goodput_restart_mttr_s", 0.0)),
    "slice_readmissions": int(out.get("goodput_slice_readmissions", 0)),
    "pod_fallback_restarts": int(
        out.get("goodput_pod_fallback_restarts", 0)),
    "readmission_hold_s": float(
        out.get("goodput_readmission_hold_s", 0.0)),
}))
"""

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spawn(workdir: str, pod: bool, pi: int = 0, die_at: int = 0,
           backend: str = "posix", pod_count: int = 2, slices: int = 1,
           die_slice: int = -1, extra_env=None):
    env = dict(os.environ, FDT_SMOKE_DIR=workdir, FDT_SMOKE_REPO=_REPO,
               FDT_SMOKE_BACKEND=backend, JAX_PLATFORMS="cpu")
    for k in ("FDT_POD_INDEX", "FDT_POD_COUNT", "FDT_SLICE_COUNT",
              "FDT_FAULT_HOST", "FDT_FAULT_SLICE",
              "FDT_FAULT_DIE_AT_STEP", "FDT_SMOKE_CKPT_EVERY",
              "FDT_SMOKE_EXEC_CACHE", "JAX_COMPILATION_CACHE_DIR"):
        env.pop(k, None)
    if extra_env:
        env.update(extra_env)
    if pod:
        env.update(FDT_POD_INDEX=str(pi), FDT_POD_COUNT=str(pod_count))
        if slices > 1:
            env.update(FDT_SLICE_COUNT=str(slices))
        if die_at:
            # the crash is armed in EVERY process's environment; the
            # FDT_FAULT_HOST / FDT_FAULT_SLICE scope is what keeps the
            # surviving processes fault-free
            env.update(FDT_FAULT_DIE_AT_STEP=str(die_at))
            if die_slice >= 0:
                env.update(FDT_FAULT_SLICE=str(die_slice))
            else:
                env.update(FDT_FAULT_HOST="1")
    code = _CHILD % {"every": CKPT_EVERY}
    return subprocess.Popen([sys.executable, "-c", code], env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)


def _join(proc, label: str, expect_fail: bool = False) -> dict:
    out, err = proc.communicate(timeout=900)
    if expect_fail:
        if proc.returncode == 0:
            raise RuntimeError(f"{label} was expected to crash but "
                               f"exited cleanly")
        return {}
    if proc.returncode != 0:
        print(f"--- {label} stderr ---\n{err[-3000:]}", file=sys.stderr)
        raise RuntimeError(f"{label} exited rc={proc.returncode}")
    return json.loads(out.strip().splitlines()[-1])


def _reference_digest() -> str:
    print(f"phase 0: uninterrupted single-process reference "
          f"({TOTAL_STEPS} steps)")
    ref = _join(_spawn(tempfile.mkdtemp(prefix="fdt_pod_ref_"), pod=False),
                "reference")
    assert ref["final_step"] == TOTAL_STEPS, ref
    return ref["digest"]


def main(ref_digest: str = "", backend: str = "posix",
         slices: int = 1, cache: bool = False,
         cache_cold_twin: bool = True) -> int:
    die_at = int(os.environ.get("FDT_SMOKE_DIE_AT", "6"))
    failures = 0

    def check(name, ok, detail=""):
        nonlocal failures
        print(f"[{'PASS' if ok else 'FAIL'}] {name}"
              + (f" ({detail})" if detail else ""))
        failures += 0 if ok else 1

    if not ref_digest:
        ref_digest = _reference_digest()

    if cache:
        failures += _run_cache_scenario(check, ref_digest,
                                        cold_twin=cache_cold_twin)
        print("PASS" if not failures else f"FAIL ({failures} assertion(s))")
        return 1 if failures else 0

    if slices > 1:
        failures += _run_slice_scenario(check, ref_digest, backend, die_at)
        print("PASS" if not failures else f"FAIL ({failures} assertion(s))")
        return 1 if failures else 0

    workdir = tempfile.mkdtemp(prefix="fdt_pod_smoke_")
    print(f"phase 1: 2-process simulated pod ({backend}), host 1 dies at "
          f"step {die_at} (shared dir {workdir})")
    procs = [_spawn(workdir, pod=True, pi=pi, die_at=die_at,
                    backend=backend)
             for pi in (0, 1)]
    h0, h1 = (_join(p, f"host {pi}") for pi, p in enumerate(procs))

    check("both hosts finished every step",
          h0["final_step"] == h1["final_step"] == TOTAL_STEPS,
          f"{h0['final_step']}/{h1['final_step']}")
    check("host 1 restarted from its injected crash",
          h1["restarts"] >= 1, str(h1["restarts"]))
    check("host 0 observed the peer failure and restarted with it",
          h0["peer_failures"] >= 1 and h0["restarts"] >= 1,
          f"peer_failures={h0['peer_failures']} restarts={h0['restarts']}")
    check("both hosts advanced into a new shared generation",
          h0["restart_generations"] >= 1
          and h0["restart_generations"] == h1["restart_generations"],
          f"{h0['restart_generations']}/{h1['restart_generations']}")
    # the generation namespace itself records the converged protocol:
    # the incident landed in gen 0, both hosts' restore-agreement
    # markers landed in gen 1 — read through whichever medium the
    # markers actually live on
    pod_dir = os.path.join(workdir, "_pod")
    be = _inspection_backend(backend, workdir)
    gens = sorted({k[len(pod_dir) + 1:].split(os.sep)[0].split("/")[0]
                   for k in be.list_prefix(pod_dir + os.sep)})
    check("shared _pod namespace shows the restart generation",
          "gen_000001" in gens, str(gens))
    g1 = os.path.join(pod_dir, "gen_000001")
    agree = sorted(os.path.basename(k)
                   for k in be.list_prefix(g1 + os.sep)
                   if os.path.basename(k).startswith("RESTORE_"))
    check("both hosts joined the gen-1 restore agreement",
          agree == ["RESTORE_00000", "RESTORE_00001"], str(agree))
    steps = [be.read_json(os.path.join(g1, a))["step"] for a in agree]
    check("restore agreement: both hosts restored the SAME step",
          steps[0] == steps[1] and steps[0] >= 0, str(steps))
    check("host states byte-identical to each other",
          h0["digest"] == h1["digest"])
    check("...and to the uninterrupted reference",
          h0["digest"] == ref_digest,
          f"{h0['digest'][:12]} vs {ref_digest[:12]}")
    check("recovery MTTR landed in the goodput summary",
          h0["restart_mttr_s"] > 0 and h1["restart_mttr_s"] > 0,
          f"{h0['restart_mttr_s']}s/{h1['restart_mttr_s']}s")
    # r15 flight recorder: the killed host's injected crash must have
    # left a durable flight dump (through whichever storage backend the
    # children used) that parses and names the fault — the forensics a
    # real dead slice leaves behind for the pod to read
    tdir = os.path.join(workdir, "telemetry")
    dumps = sorted(k for k in be.list_prefix(tdir + os.sep)
                   if os.path.basename(k).startswith("flight_00001"))
    check("killed host left a flight dump in the telemetry dir",
          bool(dumps), str([os.path.basename(d) for d in dumps]))
    if dumps:
        fl = be.read_json(dumps[0])
        exc = (fl or {}).get("exception") or {}
        check("flight dump parses and names the injected fault",
              exc.get("type") == "InjectedFault"
              and str(die_at) in exc.get("message", ""),
              f"{exc.get('type')}: {exc.get('message', '')[:60]}")
        check("flight dump carries the in-memory record ring",
              bool((fl or {}).get("recent_records")),
              f"{len((fl or {}).get('recent_records', []))} records")
    if backend == "fake_object_store":
        # nothing resilience-critical may have leaked onto the plain
        # filesystem: markers and step checkpoints live as framed
        # objects under _objects/ (epoch-level orbax checkpoints are
        # the documented posix exception)
        leaked = [n for n in os.listdir(workdir)
                  if n == "_pod" or "_step_" in n]
        check("no rename-dependent filesystem state outside the object "
              "store", not leaked, str(leaked))
        check("object store holds the pod markers",
              any("_pod" in k for k in be.list_prefix(workdir + os.sep)))

    print("PASS" if not failures else f"FAIL ({failures} assertion(s))")
    return 1 if failures else 0


def _inspection_backend(backend: str, workdir: str):
    # the SAME construction path the children used (build_backend), so
    # the parent inspects the namespace they actually wrote through
    from faster_distributed_training_tpu.resilience import storage
    return storage.build_backend(backend, workdir, log=lambda *_: None)


def _run_cache_scenario(check, ref_digest: str,
                        cold_twin: bool = True) -> int:
    """r17 instant-restart acceptance: crash + process-relaunch twins,
    cached (--executable_cache on) vs cold, each against a hermetic XLA
    compilation-cache dir (a warm developer ~/.cache would serve the
    crash phase's compiles, and XLA:CPU cache-served executables don't
    serialize round-trippably — the scenario must measure the tier, not
    the machine's history).  The kill lands in epoch 2 (step 13, after
    the step-12 cadence save) so BOTH steady-state programs — the train
    dispatch and the epoch-end eval — exist in the cache before the
    relaunch.

    ``cold_twin=False`` (the tier-1 wrapper's budget mode) runs only
    the cached pair and checks its digest against the UNINTERRUPTED
    reference — equivalent coverage, because cold-restart ≡
    uninterrupted is already pinned bitwise by the resilience e2e
    suite (kill-at-N resume, r7) — and leaves the cold-acquisition
    A/B to the manual script run, which keeps the full twin."""
    die_at = 13
    runs = {}
    for mode in (("cold", "cached") if cold_twin else ("cached",)):
        workdir = tempfile.mkdtemp(prefix=f"fdt_cache_smoke_{mode}_")
        env = {"FDT_SMOKE_CKPT_EVERY": "4"}
        if mode == "cached":
            env["FDT_SMOKE_EXEC_CACHE"] = "on"
        print(f"phase {mode}: crash at step {die_at} + process relaunch "
              f"(dir {workdir})")
        # die_at rides extra_env: _spawn's die_at parameter is the POD
        # scenarios' (it also arms FDT_FAULT_HOST); this is a plain
        # single-process crash.  Each PHASE gets its own hermetic XLA
        # compilation-cache dir: the persistent dir is MACHINE-LOCAL
        # and a restarted slice on a fresh machine doesn't have it —
        # only the executable cache (durable, StorageBackend) survives,
        # which is exactly the tier the twins A/B.
        _join(_spawn(workdir, pod=False,
                     extra_env={**env,
                                "JAX_COMPILATION_CACHE_DIR":
                                    tempfile.mkdtemp(prefix="fdt_xla_"),
                                "FDT_FAULT_DIE_AT_STEP": str(die_at)}),
              f"{mode} crash", expect_fail=True)
        runs[mode] = _join(
            _spawn(workdir, pod=False,
                   extra_env={**env, "JAX_COMPILATION_CACHE_DIR":
                              tempfile.mkdtemp(prefix="fdt_xla_")}),
            f"{mode} relaunch")
        try:
            with open(os.path.join(workdir, "telemetry",
                                   "manifest.json")) as f:
                runs[mode]["manifest"] = json.load(f)
        except (OSError, ValueError):
            runs[mode]["manifest"] = {}
    cached = runs["cached"]
    check("cached relaunch finished every step",
          cached["final_step"] == TOTAL_STEPS, str(cached["final_step"]))
    check("cached relaunch bitwise-equal to the (cold-restart ≡ "
          "uninterrupted) reference",
          cached["digest"] == ref_digest,
          f"{cached['digest'][:12]} vs {ref_digest[:12]}")
    progs = {p["name"]: [v.get("cache_source") for v in p["variants"]]
             for p in cached["manifest"].get("compile", {})
             .get("programs", [])}
    steady = {n: s for n, s in progs.items()
              if n.startswith("train:") or n == "eval"}
    check("cached relaunch deserialized EVERY steady-state program",
          bool(steady) and all(s == "deserialized"
                               for srcs in steady.values() for s in srcs),
          str(progs))
    check("zero retraces in the cached relaunch",
          cached["manifest"].get("compile", {}).get("retraces") == [],
          str(cached["manifest"].get("compile", {}).get("retraces")))
    check("cached relaunch actually restored a checkpoint",
          cached["restores"] == 1, str(cached["restores"]))
    if cold_twin:
        cold = runs["cold"]
        check("cold relaunch finished every step",
              cold["final_step"] == TOTAL_STEPS, str(cold["final_step"]))
        check("cached relaunch bitwise-equal to the cold-restart twin",
              cached["digest"] == cold["digest"],
              f"{cached['digest'][:12]} vs {cold['digest'][:12]}")
        check("cached program acquisition cheaper than cold recompile",
              0 < cached["compile_s"] < cold["compile_s"],
              f"{cached['compile_s']:.2f}s vs {cold['compile_s']:.2f}s")
        check("cold relaunch restored a checkpoint too",
              cold["restores"] == 1, str(cold["restores"]))
    return 0


def _run_slice_scenario(check, ref_digest: str, backend: str,
                        die_at: int) -> int:
    """2-slice pod, 4 processes, slice 1 killed whole via
    FDT_FAULT_SLICE: the surviving slice must hold (never restart,
    never restore), the killed slice must rejoin the SAME generation,
    and every host must finish digest-equal to the reference."""
    workdir = tempfile.mkdtemp(prefix="fdt_pod_slice_smoke_")
    print(f"phase 1: 2-slice pod, 4 processes ({backend}), slice 1 dies "
          f"at step {die_at} (shared dir {workdir})")
    procs = [_spawn(workdir, pod=True, pi=pi, die_at=die_at,
                    backend=backend, pod_count=4, slices=2, die_slice=1)
             for pi in range(4)]
    hosts = [_join(p, f"host {pi}") for pi, p in enumerate(procs)]
    h0, h1, h2, h3 = hosts

    check("all four hosts finished every step",
          all(h["final_step"] == TOTAL_STEPS for h in hosts),
          str([h["final_step"] for h in hosts]))
    check("surviving slice NEVER restarted or rolled back",
          all(h["restarts"] == 0 and h["restores"] == 0
              for h in (h0, h1)),
          f"restarts={[h['restarts'] for h in (h0, h1)]} "
          f"restores={[h['restores'] for h in (h0, h1)]}")
    check("surviving slice held for re-admission (hold time billed)",
          all(h["slice_readmissions"] >= 1
              and h["readmission_hold_s"] > 0 for h in (h0, h1)),
          f"readmit={[h['slice_readmissions'] for h in (h0, h1)]} "
          f"hold={[h['readmission_hold_s'] for h in (h0, h1)]}")
    check("killed slice restarted and was re-admitted",
          all(h["restarts"] >= 1 and h["slice_readmissions"] >= 1
              for h in (h2, h3)),
          f"restarts={[h['restarts'] for h in (h2, h3)]} "
          f"readmit={[h['slice_readmissions'] for h in (h2, h3)]}")
    check("no whole-pod fallback was needed",
          all(h["pod_fallback_restarts"] == 0 for h in hosts),
          str([h["pod_fallback_restarts"] for h in hosts]))
    check("all four digests identical",
          len({h["digest"] for h in hosts}) == 1)
    check("...and equal to the uninterrupted reference",
          h0["digest"] == ref_digest,
          f"{h0['digest'][:12]} vs {ref_digest[:12]}")
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--backend", default="posix",
                    choices=["posix", "fake_object_store"])
    ap.add_argument("--slices", type=int, default=1, choices=[1, 2])
    ap.add_argument("--cache", action="store_true",
                    help="r17 instant-restart scenario: crash + relaunch "
                         "twins, executable cache vs cold recompile")
    args = ap.parse_args()
    sys.exit(main(backend=args.backend, slices=args.slices,
                  cache=args.cache))
