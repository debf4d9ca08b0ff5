"""The held experts' grouped products' share of the chip's bf16 peak, in
percent: the FLOPs of the token-slots that really landed here (the program's
``moe_slots`` counter, a step and layer, x the expert layers x three
products of hidden x expert width, forward x 3; ``expert_slot_flops`` beside
the configuration's ``train_flops``) over the device time of the
``moe_experts`` scope group and the peak.  Compute bounds it at these shapes
(about 512 slots an expert against weights read once a row tile).  Read from
the scope's time, so it reads the same work whatever implements it; under
``--remat`` that time holds the recomputed forward and the count does not, so
the share reads lower, never higher.  None where the run was not traced, the
configuration names no such group, or the program wrote no ``moe_slots``.

THIS configuration's own metric, whatever the name suggests: ``facts``
carries no handle to the configuration's flops module, so the count is
resolved from ``trinity_mini_reference`` by name.  A second configuration
with an expert layer brings a reader of its own until ``facts`` carries that module
(PERF.md section 7, row 6)."""

from benchmark import flops
from benchmark.trace_reduce import scope_ms

REFERENCE = "benchmark.configs.trinity_mini_reference"


def read(facts):
    ms = scope_ms(facts, "moe_experts")
    slots = [r["moe_slots"] for r in facts["records"] if "moe_slots" in r]
    if not ms or not slots or not facts.get("peaks"):
        return None
    try:
        per_slot = flops.resolve(f"{REFERENCE}:expert_slot_flops")
        layers = flops.resolve(f"{REFERENCE}:moe_layers")
    except (ImportError, AttributeError):
        return None
    sizes = facts["sizes"]
    work = (sum(slots) / len(slots)) * layers(sizes) * per_slot(sizes)
    return 100.0 * work / (ms / 1e3) / facts["peaks"]["bf16_flops_per_s"]
