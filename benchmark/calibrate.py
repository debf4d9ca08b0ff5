"""Readings the limits of ``correct`` are set from, taken on the chip at a
cell's own size in ONE process (set-up is long):

    python benchmark/calibrate.py --workload <cell> --seeds 12 --controls 3 \\
        --out chiprun_out/calibrate_<cell>.json

For every seed: the program's first steps (through the runner's own
session, feed and ``Trainer.run_epoch``) and the plain reference's.  For the
first ``--controls`` seeds also the control (the reference at the nearest
precision below the configuration's) and the planted fault (half of the
batch left out), each put in the program's place.  Every side goes through
the harness's own comparison and verdict under the cell's limits
(``benchmark/correct.py``), printed a line each; every leaf's norms and its
distance from the reference are kept in the output file, so a change of
statistic needs no second visit to the chip.  The benchmark's own runs
never run this.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def flat(tree):
    import jax
    return [float(v) for v in jax.tree.leaves(tree)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first_seed", type=int, default=2100000001)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax

    from benchmark import configuration, correct, run
    from benchmark.reference import steps as reference_steps
    from benchmark.runners import train

    bench, cell, config, traffic = run.resolve(args.workload)
    run.check_devices(int(cell["chips"]))
    reference = importlib.import_module(
        f"benchmark.configs.{config['reference']}")
    out_dir = os.path.join(ROOT, "benchmark_out", "calibrate")
    checked = int(traffic["checked_steps"])
    results, names = {}, None
    # first the program for every seed (one session, reseeded: the compiled
    # step stays loaded), then — the program's memory freed — the reference
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    s, programs, feeds = None, {}, {}
    for seed in seeds:
        t = time.monotonic()
        if s is None:
            s = train.Session(config, traffic, seed, out_dir, reference,
                              log=print)
        else:
            s._seed_data_and_state(seed)
        program = s.first_steps(checked, checked)
        feeds[seed] = (program.pop("batches"), s.steps_per_epoch,
                       s.cfg.seed)
        s.feed.kept = []
        programs[seed] = program
        if names is None:
            names = ["/".join(str(getattr(k, "key", k)) for k in path)
                     for path, _ in jax.tree_util.tree_leaves_with_path(
                         program["grad_norm"])]
        print(f"[calibrate] seed {seed}: program {time.monotonic() - t:.1f} "
              f"s, loss {program['loss']}", flush=True)
    sizes = dict(configuration.sizes(config), batch_size=s.cfg.batch_size,
                 seq_len=s.cfg.seq_len)
    s.close()
    del s
    for i, seed in enumerate(seeds):
        batches, spe, pseed = feeds[seed]
        sides = {"program": programs[seed]}
        t = time.monotonic()
        sides["reference"] = reference_steps.first_steps(
            reference, sizes, config["training"], seed, batches, spe, pseed,
            log=print)
        t_ref = time.monotonic() - t
        if i < args.controls:
            sides["control"] = reference_steps.first_steps(
                reference, sizes, config["training"], seed, batches, spe,
                pseed, precision="fp8")
            sides["half_batch"] = reference_steps.first_steps(
                reference, sizes, config["training"], seed, batches, spe,
                pseed, fault="half_batch")
        ref = sides["reference"]
        kept = {}
        for side, r in sides.items():
            kept[side] = {k: (v if k == "loss" else flat(v))
                          for k, v in r.items()
                          if k not in ("stats", "stats_start")}
            if side == "reference":
                kept[side]["stats_moved"] = correct.diff_norms(
                    r["stats"], r["stats_start"])
                continue
            c = correct.compare(r, ref, traffic["limits"])
            print(f"[calibrate] seed {seed} {side}: correct "
                  f"{correct.verdict(c)}: "
                  + ", ".join(f"{k} {v['value']:.4g}"
                              for k, v in c.items()), flush=True)
            kept[side]["stats_diff"] = correct.diff_norms(r["stats"],
                                                          ref["stats"])
        programs[seed] = sides = None
        print(f"[calibrate] seed {seed}: reference {t_ref:.1f} s",
              flush=True)
        results[str(seed)] = kept
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "leaves": names,
                       "seeds": results}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
