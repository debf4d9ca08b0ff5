"""Attention's share of the chip's bf16 peak, in percent: the model FLOPs of
a step inside attention proper (the causal band's pairs, not the square;
forward x 3; ``attention_flops`` beside the configuration's ``train_flops``)
over the device time of the ``attention`` scope group and the peak.  Compute
bounds it: at head 128 a band tile does 4 x 128 FLOPs a pair on bytes it
reads once a tile.  Read from the scope's time, so it reads the same work
whatever implements it; under ``--remat`` that time holds the recomputed
forward and the count does not, so the share reads lower, never higher.
None where the run was not traced, the configuration names no such group or
its flops module counts no attention.

THIS configuration's own metric, whatever the name suggests: ``facts``
carries no handle to the configuration's flops module, so the count is
resolved from ``trinity_mini_reference`` by name.  A second configuration
with attention brings a reader of its own until ``facts`` carries that module
(PERF.md section 7, row 6)."""

from benchmark import flops
from benchmark.trace_reduce import scope_ms


def read(facts):
    ms = scope_ms(facts, "attention")
    if not ms or not facts.get("peaks"):
        return None
    try:
        count = flops.resolve(
            "benchmark.configs.trinity_mini_reference:attention_flops")
    except (ImportError, AttributeError):
        return None
    work = count(facts["sizes"], facts["batch_size"], facts["seq_len"])
    return 100.0 * work / (ms / 1e3) / facts["peaks"]["bf16_flops_per_s"]
