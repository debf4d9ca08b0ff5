"""Device-busy milliseconds of one step spent in the held experts' grouped
products, forward and backward: the operations whose op_name matches the
``moe_experts`` group of the configuration's ``scopes`` (the program's
``fdt/moe_experts`` scope), each instant counted once, at the innermost
operation (``benchmark/trace_reduce.py``).  None where the configuration
names no such group."""

from benchmark.trace_reduce import scope_ms


def read(facts):
    return scope_ms(facts, "moe_experts")
