"""The 0.67B rehearsal: the program's own encoder as a causal language model
with AdamW at 672M parameters (10.7 GB at 16 bytes a parameter), run from a
COPY of a tree's ``benchmark/`` with new files only, as the next
configuration will arrive.  On the chip, from the repository's root:

    chiprun --chips 1 --timeout 1800 -- python3 tests/benchmark/fixtures/lm_0p67b/rehearse.py run.py --seed 7 --seconds 20 --trace 1
    chiprun --chips 1 --timeout 1800 -- python3 tests/benchmark/fixtures/lm_0p67b/rehearse.py calibrate.py --seeds 3 --controls 1 --out chiprun_out/calibrate_lm_0p67b.json

The first word is the copy's command (``run.py`` or ``calibrate.py``), the
rest its arguments; ``--workload`` is added.  ``--harness <dir>`` takes the
``benchmark/`` of another tree (an unpacked parent commit) in place of this
one's; the program and the fixture's files are this tree's either way.  The
copy is ``benchmark_out/rehearsal/`` (git-ignored), made anew each time.
This process never touches JAX: the chip is the child's.
"""

import filecmp
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(HERE))))
CONFIG, TRAFFIC = "encoder_lm_0p67b", "lm_0p67b_tokens"
REFERENCE = os.path.join(os.path.dirname(HERE), "lm_toy",
                         "encoder_lm_toy_reference.py")
CELL = "encoder_lm_0p67b.bs8_seq512"


def make_copy(dest: str, harness: str = ROOT) -> list:
    """``harness``'s ``benchmark/`` and ``BENCHMARK.json`` copied to
    ``dest`` as they are, plus the fixture's three files and two entries;
    returns the paths (relative to ``dest``) of the files that were added."""
    shutil.rmtree(dest, ignore_errors=True)
    shutil.copytree(os.path.join(harness, "benchmark"),
                    os.path.join(dest, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "*.gz"))
    added = []
    for src, folder in ((os.path.join(HERE, CONFIG + ".json"), "configs"),
                        (REFERENCE, "configs"),
                        (os.path.join(HERE, TRAFFIC + ".json"), "traffic")):
        to = os.path.join("benchmark", folder, os.path.basename(src))
        if os.path.exists(os.path.join(dest, to)):
            # the same bytes: a tree in which the toy fixture, whose
            # reference this one shares, was entered as a configuration
            if not filecmp.cmp(src, os.path.join(dest, to), shallow=False):
                raise FileExistsError(f"{to} is a file of the harness "
                                      f"already")
            continue
        shutil.copy(src, os.path.join(dest, to))
        added.append(to)
    with open(os.path.join(harness, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(HERE, CONFIG + ".json")) as f:
        source = json.load(f)["source"]
    bench["configs"].append({
        "name": CONFIG, "source": source,
        "file": f"benchmark/configs/{CONFIG}.json", "reduced": [],
        "why": "rehearsal: 0.67B parameters under AdamW, the size the "
               "harness's comparison has to fit beside"})
    bench["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 1,
        "why": "rehearsal: 8 packed rows of 512 ids, causal, AdamW, bf16; "
               "10.7 GB of state and gradient on one chip"})
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return added


def main(argv) -> int:
    harness = ROOT
    if argv[:1] == ["--harness"]:
        harness, argv = os.path.abspath(argv[1]), argv[2:]
    if not argv or argv[0] not in ("run.py", "calibrate.py"):
        raise SystemExit(__doc__)
    if "--out" in argv:         # the child runs from the copy: keep the place
        at = argv.index("--out") + 1
        argv[at] = os.path.abspath(argv[at])
    dest = os.path.join(ROOT, "benchmark_out", "rehearsal")
    added = make_copy(dest, harness)
    print(f"[rehearse] harness {harness}; copy {dest}; added {added}",
          flush=True)
    env = dict(os.environ,
               PYTHONPATH=os.pathsep.join([dest, ROOT]))
    return subprocess.run(
        [sys.executable, os.path.join("benchmark", argv[0]),
         "--workload", CELL] + argv[1:], cwd=dest, env=env).returncode


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
