#!/usr/bin/env python
"""Kernel-routing lint (ISSUE 15 satellite; the
check_telemetry_schema.py idiom applied to Pallas dispatch).

The repo shipped THREE silent tp-capability gaps in a row (flash r11,
fused-FFN r11, quant-matmul r13): a Pallas custom call cannot partition
over the tp axis, so any call site that hands a logically-global array
to a kernel on a 2D mesh silently reroutes (or worse, mis-executes) the
paper's "faster" lever.  r19 closed them with ONE shard_map layer
(parallel/kernel_shard.py) plus registered WARNED fallbacks in
cli.build_model.  This lint makes a FOURTH gap a tier-1 failure at
commit time (tests/test_kernel_shard.py):

  1. every function that launches ``pl.pallas_call`` must live in a
     module registered in ``KERNEL_MODULES`` — a brand-new Pallas
     module cannot appear without declaring how it routes on tp meshes;
  2. every CALL to a public kernel entry point from OUTSIDE its
     defining module must be a registered (module, entry) pair in
     ``ALLOWED_CALLERS`` with the routing story documented — reaching a
     kernel from a new call site forces the author to state how that
     site behaves on a tp mesh (through the shard_map layer, or behind
     a registered warned fallback);
  3. every registered pair must actually occur (the registry cannot rot
     into fiction).

Run:  python scripts/check_kernel_routing.py   (exit 0 = clean)
"""

from __future__ import annotations

import ast
import os
import sys
from typing import Dict, List, Set, Tuple

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)

PACKAGE_DIR = os.path.join(_REPO, "faster_distributed_training_tpu")

# modules allowed to contain pl.pallas_call launches, with the committed
# one-line routing story for tp meshes.
KERNEL_MODULES: Dict[str, str] = {
    "ops/flash_attention.py":
        "head-sharded per-shard via kernel_shard.flash_attention_sharded;"
        " build_model reroutes non-dividing heads (warned)",
    "ops/fused_ffn.py":
        "Megatron column/row tiles via kernel_shard.fused_ffn_sublayer_tp"
        " (ONE psum); build_model falls back to flax (warned)",
    "ops/quant.py":
        "per-site column/row tiles via kernel_shard.quant_dense_sharded;"
        " QuantDense forces the XLA reference on unrouted tp sites",
    "ops/fused_mlp.py":
        "classifier MLP on the pooled (B, d) activations — no tensor-"
        "parallel dimension; on any mesh > 1 device it runs per shard "
        "over the data axes via kernel_shard.fused_mlp_sharded",
}

# public kernel entry points -> defining module.  Private helpers
# (_-prefixed) are module-local by convention and rule 2 need not track
# them; these are the names other layers may reach for.
ENTRY_POINTS: Dict[str, str] = {
    "flash_attention": "ops/flash_attention.py",
    "fused_ffn_sublayer": "ops/fused_ffn.py",
    "fused_ffn_sublayer_sharded": "ops/fused_ffn.py",
    "ffn_core_generalized": "ops/fused_ffn.py",
    "quant_dot": "ops/quant.py",
    "quant_dot_pallas": "ops/quant.py",
    "fused_mlp_pallas": "ops/fused_mlp.py",
}

# registered cross-module call sites: (caller module, entry point) ->
# why this site is tp-safe.  Adding a call site anywhere else fails
# rule 2 until it is registered here WITH its routing story.
ALLOWED_CALLERS: Dict[Tuple[str, str], str] = {
    ("parallel/kernel_shard.py", "flash_attention"):
        "THE shard_map layer: runs the kernel per-shard on local heads",
    ("parallel/kernel_shard.py", "ffn_core_generalized"):
        "THE shard_map layer: per-shard Megatron column/row FFN tiles",
    ("parallel/kernel_shard.py", "quant_dot"):
        "THE shard_map layer: per-shard quant GEMM on the site's tile",
    ("parallel/kernel_shard.py", "fused_mlp_pallas"):
        "THE shard_map layer: classifier head per shard over the data "
        "axes (a Mosaic kernel only partitions inside shard_map)",
    ("models/transformer.py", "flash_attention"):
        "one-device / mesh-less branch only: guarded by kernel_shard"
        ".flash_serviceable + data_sharded at the call site; build_model's"
        " registered warned fallback reroutes non-dividing tp",
    ("models/transformer.py", "fused_ffn_sublayer"):
        "unsharded-mesh branch only (tp routes through "
        "kernel_shard.fused_ffn_sublayer_tp in the same dispatch chain)",
    ("models/transformer.py", "fused_ffn_sublayer_sharded"):
        "data/sp-axes shard_map wrapper (weights replicated; tp branch "
        "routes through kernel_shard first)",
    ("models/transformer.py", "ffn_core_generalized"):
        "unsharded quantized composition (mesh is None on that branch)",
    ("models/transformer.py", "fused_mlp_pallas"):
        "one-device / mesh-less branch only; any mesh > 1 device routes "
        "through kernel_shard.fused_mlp_sharded in the same expression",
    ("ops/fused_ffn.py", "quant_dot"):
        "the pure-XLA oracle/backward (use_pallas=False reference path "
        "— partitions like any dot)",
}


def _call_name(func) -> str:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return ""


def _module_files(package_dir: str) -> List[str]:
    out = []
    for dirpath, dirs, files in os.walk(package_dir):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            if name.endswith(".py"):
                out.append(os.path.join(dirpath, name))
    return sorted(out)


def scan(package_dir: str):
    """(pallas_defs, entry_calls): modules whose functions launch
    pallas_call, and every (module, entry-point) Call pair."""
    pallas_defs: Set[str] = set()
    entry_calls: Set[Tuple[str, str]] = set()
    for path in _module_files(package_dir):
        rel = os.path.relpath(path, package_dir).replace(os.sep, "/")
        with open(path, encoding="utf-8") as fh:
            try:
                tree = ast.parse(fh.read(), filename=path)
            except SyntaxError as e:
                print(f"[kernel-routing] cannot parse {rel}: {e}")
                pallas_defs.add(rel)     # fail loudly via rule 1
                continue
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and _call_name(node.func) == "pallas_call":
                pallas_defs.add(rel)
            # any REFERENCE to an entry-point name counts as reachable
            # (the transformer passes fused_mlp_pallas as a value and
            # calls it later — a Call-only scan would miss it);
            # imports/defs don't produce Name/Attribute nodes, so
            # re-exporting a kernel name is not itself a call site
            if isinstance(node, ast.Name) and node.id in ENTRY_POINTS:
                entry_calls.add((rel, node.id))
            elif isinstance(node, ast.Attribute) \
                    and node.attr in ENTRY_POINTS:
                entry_calls.add((rel, node.attr))
    return pallas_defs, entry_calls


def check(package_dir: str = PACKAGE_DIR) -> List[str]:
    """The lint body; returns the list of violations (empty = clean)."""
    problems: List[str] = []
    pallas_defs, entry_calls = scan(package_dir)

    for rel in sorted(pallas_defs):
        if rel not in KERNEL_MODULES:
            problems.append(
                f"rule 1: {rel} launches pl.pallas_call but is not "
                f"registered in KERNEL_MODULES — declare its tp-mesh "
                f"routing story in scripts/check_kernel_routing.py")

    for rel, entry in sorted(entry_calls):
        if rel == ENTRY_POINTS[entry]:
            continue                     # the defining module itself
        if (rel, entry) not in ALLOWED_CALLERS:
            problems.append(
                f"rule 2: {rel} calls kernel entry point {entry}() but "
                f"the pair is not registered in ALLOWED_CALLERS — state "
                f"how this site routes on a tp mesh (through parallel/"
                f"kernel_shard.py, or behind a registered warned "
                f"fallback) and register it")

    for (rel, entry) in sorted(ALLOWED_CALLERS):
        if (rel, entry) not in entry_calls:
            problems.append(
                f"rule 3: ALLOWED_CALLERS registers ({rel}, {entry}) "
                f"but no such call exists — the registry rotted; remove "
                f"the entry")
    return problems


def main() -> int:
    problems = check()
    for p in problems:
        print(f"[kernel-routing] {p}")
    if problems:
        print(f"[kernel-routing] {len(problems)} violation(s)")
        return 1
    print("[kernel-routing] clean: every Pallas kernel is reachable only "
          "through parallel/kernel_shard.py or a registered call site")
    return 0


if __name__ == "__main__":
    sys.exit(main())
