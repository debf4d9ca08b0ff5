"""The CPU rehearsal of the ``encoder_lm_toy`` fixture
(``tests/benchmark/fixtures/lm_toy/``), the second configuration every
parametrised test holds: its widths are a toy's already and its argv names
the CPU, so only the batch and the rows' length shrink — in the traffic's
argv AND in its data, which have to agree (``--seq_len`` is the length of a
packed row)."""

ARGV = []
SIZES = {}
SHRINK = {"--bs": "4", "--seq_len": "8"}
DATA = {"seq_len": 8}
# the reference keeps no running statistics: the bfloat16 control, put in
# the program's place for the three steps, is shown by the first
# gradient's norms (CPU readings of the fixture's traffic file: the control
# at least 1.8e-3, the limit 2e-4)
CONTROL = {"sizes": {}, "breaks": "grad_norm_gap"}
