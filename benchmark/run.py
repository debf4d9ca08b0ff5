"""The benchmark's one command:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process.  Everything about a cell is data found by the names in
``BENCHMARK.json``: the configuration (``benchmark/configs/<config>.json``
with its plain reference beside it), the traffic mix
(``benchmark/traffic/<traffic>.json``), the runner module the configuration
names (``benchmark/runners/<runner>.py``) and one reader a per-layer metric
(``benchmark/metrics/<metric>.py``).  No cell, configuration or metric name
appears in this file.

The last line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, in a traced run ``breakdown``, and last
``compared`` (each number compared beside its limit, also the last lines of
standard error).  Everything else goes to earlier lines and to files under
``benchmark_out/<cell>/``.
"""

from __future__ import annotations

import time

T0 = time.monotonic()          # set-up is counted from here

import argparse                # noqa: E402
import importlib               # noqa: E402
import json                    # noqa: E402
import os                      # noqa: E402
import shutil                  # noqa: E402
import sys                     # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
REQUIRED_PLATFORM = "tpu"


def log(msg: str) -> None:
    print(msg, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def resolve(workload: str, root: str = ROOT, bench_dir: str = HERE):
    """(benchmark, cell, configuration, traffic) for a cell's name: the
    entries of BENCHMARK.json and the files they name."""
    bench = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(root, entry["file"])
    traffic = load_json(bench_dir, "traffic", cell["traffic"] + ".json")
    return bench, cell, config, traffic


def cell_metrics(bench: dict, cell_name: str, kind: str) -> list:
    """The metrics of ``end_to_end`` or ``per_layer`` this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def check_devices(chips: int) -> dict:
    """The device as JAX reports it; SystemExit with the cause when it is
    not the accelerator, or not as many chips as the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform != REQUIRED_PLATFORM:
        raise SystemExit(
            f"benchmark: JAX offers {devs[0].platform!r} devices, the cell "
            f"needs {chips} {REQUIRED_PLATFORM} chip(s); there is no "
            f"fallback (a number from another platform is not a "
            f"measurement)")
    if len(devs) != chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chip(s), JAX "
                         f"sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def read_metrics(names, facts: dict) -> dict:
    """One reader a metric, found by name; a reader that finds nothing to
    read returns None and the metric is left out of the line."""
    out = {}
    for m in names:
        reader = importlib.import_module(f"benchmark.metrics.{m['name']}")
        value = reader.read(facts)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    bench, cell, config, traffic = resolve(args.workload)
    device = check_devices(int(cell["chips"]))
    out_dir = os.path.join(ROOT, "benchmark_out", cell["name"])
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)

    runner = importlib.import_module(f"benchmark.runners.{config['runner']}")
    result = runner.run(cell=cell, config=config, traffic=traffic,
                        seed=args.seed, seconds=args.seconds,
                        trace=bool(args.trace), out_dir=out_dir, t0=T0,
                        device=device, log=log)

    facts = result["facts"]
    if args.trace:
        metrics = read_metrics(cell_metrics(bench, cell["name"], "per_layer"),
                               facts)
    else:
        metrics = {m["name"]: {"value": float(result["end_to_end"][m["name"]]),
                               "unit": m["unit"]}
                   for m in cell_metrics(bench, cell["name"], "end_to_end")}
    device = dict(device, memory_peak_bytes=facts["memory_peak_bytes"])
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": metrics,
            "device": device}
    if args.trace:
        device["busy_s"] = facts["trace"]["busy_s"]
        device["window_s"] = facts["trace"]["window_s"]
        line["breakdown"] = {"device_ops": facts["trace"]["device_ops"][:10],
                             "idle_gaps": facts["trace"]["idle_gaps"][:10]}
    line["compared"] = result["compared"]
    sys.stdout.flush()
    for name, c in result["compared"].items():
        print(f"compared {name}: value {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
