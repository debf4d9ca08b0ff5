"""Device-busy milliseconds of one step spent in attention proper (scores,
softmax, values, forward and backward; not the projections): the operations
whose op_name matches the ``attention`` group of the configuration's
``scopes`` (the program's ``fdt/attention`` scope), each instant counted
once, at the innermost operation (``benchmark/trace_reduce.py``).  None
where the configuration names no such group."""

from benchmark.trace_reduce import scope_ms


def read(facts):
    return scope_ms(facts, "attention")
