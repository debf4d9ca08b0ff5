"""Device-busy milliseconds of one step spent round an expert layer's
experts: routing (scores, top-k, weights), dispatch (sort, gather) and
combine (un-sort, weighted sum), forward and backward: the operations
whose op_name matches the ``moe_route`` group of the configuration's
``scopes`` (the program's ``fdt/moe_route``, ``fdt/moe_dispatch`` and
``fdt/moe_combine`` scopes), each instant counted once, at the innermost
operation (``benchmark/trace_reduce.py``).  None where the configuration
names no such group."""

from benchmark.trace_reduce import scope_ms


def read(facts):
    return scope_ms(facts, "moe_route")
