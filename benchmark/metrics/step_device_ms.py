"""Device-busy milliseconds of one step: the union of the operations'
intervals inside each whole execution of the step program in the traced
window, over their number (``benchmark/trace_reduce.py``)."""


def read(facts):
    trace = facts.get("trace")
    return trace["device_ms_per_step"] if trace and trace["steps_traced"] else None
