"""What the tree may not name, and which switches it has (no jax).

PR 29 retired the measurement stack that predates BENCHMARK.json: a
root benchmark script, its lint, its record files and the environment
names that steered it.  A comment that cites one of them tells the next
reader that a guard exists which does not, so no file of the program,
its scripts, its tests or its user documents may name them.
CHANGES.md, ROADMAP.md, PERF.md and SURVEY.md are history and plan and
are exempt, as is tests/benchmark/ (the benchmark's own)."""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "faster_distributed_training_tpu")

RETIRED = re.compile(
    r"(?<![\w/])bench\.py|FDT_BENCH_|BENCH_LATEST|BENCH_r0|MULTICHIP_r0"
    r"|check_bench_arms|transformer_roofline|FDT_TELEMETRY")

_TEXT = (".py", ".sh", ".md", ".json", ".jsonl", ".cc", ".h", ".txt",
         ".toml", ".cfg", ".ini")


def _walk(top, skip=()):
    for root, dirs, files in os.walk(top):
        dirs[:] = [d for d in dirs if d not in ("__pycache__", "_build")
                   and os.path.join(root, d) not in skip]
        for name in files:
            if name.endswith(_TEXT):
                yield os.path.join(root, name)


def _root(*suffixes):
    return [os.path.join(REPO, n) for n in sorted(os.listdir(REPO))
            if n.endswith(suffixes)
            and os.path.isfile(os.path.join(REPO, n))]


def _area(area):
    if area == "package":
        return list(_walk(PACKAGE))
    if area == "scripts_and_entries":
        return (list(_walk(os.path.join(REPO, "scripts")))
                + list(_walk(os.path.join(REPO, "tuning")))
                + _root(".py", ".sh"))
    if area == "tests":
        here = os.path.abspath(__file__)
        tests = os.path.join(REPO, "tests")
        return [p for p in _walk(tests,
                                 skip=(os.path.join(tests, "benchmark"),))
                if p != here]
    assert area == "docs", area
    return [os.path.join(REPO, *p.split("/")) for p in (
        "README.md", "PARITY.md", "BASELINE.md", "ACCURACY.md",
        ".claude/skills/verify/SKILL.md")]


@pytest.mark.parametrize("area", ["package", "scripts_and_entries", "tests",
                                  "docs"])
def test_nothing_names_the_retired_stack(area):
    files = _area(area)
    assert files, area
    hits = []
    for path in files:
        with open(path, encoding="utf-8") as f:
            for n, line in enumerate(f, 1):
                m = RETIRED.search(line)
                if m:
                    hits.append(f"{os.path.relpath(path, REPO)}:{n}: "
                                f"{m.group(0)}")
    assert not hits, "\n".join(hits)


# Every FDT_* name the package reads or documents.  A switch comes or
# goes only in a diff that edits this set: ROADMAP D3 keeps the verdicts.
ENV_NAMES = {
    # process / pod / slice identity (launcher contract)
    "FDT_COORDINATOR", "FDT_NUM_PROCESSES", "FDT_PROCESS_ID",
    "FDT_POD_COUNT", "FDT_POD_INDEX",
    "FDT_SLICE_COUNT", "FDT_SLICE_INDEX", "FDT_SLICE_SPARE",
    # fault injection (resilience/faults.py; FDT_FAULT and FDT_FAULT_
    # are the prefix as prose and code spell it)
    "FDT_FAULT", "FDT_FAULT_", "FDT_FAULT_CORRUPT_SHARD",
    "FDT_FAULT_DATA_AT_BATCH", "FDT_FAULT_DIE_AT_STEP",
    "FDT_FAULT_HANG_AT_STEP", "FDT_FAULT_HOST",
    "FDT_FAULT_LOSS_SPIKE_AT_STEP", "FDT_FAULT_NAN_AT_STEP",
    "FDT_FAULT_SIGTERM_AT_STEP", "FDT_FAULT_SLICE",
    # executable cache
    "FDT_EXEC_CACHE", "FDT_EXEC_CACHE_MAX_BYTES",
    "FDT_EXEC_CACHE_MAX_ENTRIES",
    # kernel and routing switches that keep a second path (ROADMAP D3)
    "FDT_KERNEL_SHARD", "FDT_QUANT", "FDT_FLASH_SAVE_STATS",
    "FDT_LN_SAVED_STATS", "FDT_DENSE_ATTN_BUDGET_MB",
    "FDT_DENSE_BWD_BUDGET_MB", "FDT_DISABLE_PALLAS_BWD",
    "FDT_FORCE_PALLAS_INTERPRET",
    # program observatory
    "FDT_PROGRAM_OBS", "FDT_HLO_FINGERPRINT",
    # synthetic data
    "FDT_SYNTH_NOISE", "FDT_SYNTH_SIGNAL",
}


def test_env_switches_are_the_listed_ones():
    found = set()
    for path in _walk(PACKAGE):
        with open(path, encoding="utf-8") as f:
            found.update(re.findall(r"FDT_[A-Z0-9_]+", f.read()))
    assert found == ENV_NAMES, (
        f"new: {sorted(found - ENV_NAMES)}; gone: "
        f"{sorted(ENV_NAMES - found)}")
