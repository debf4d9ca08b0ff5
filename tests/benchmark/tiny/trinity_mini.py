"""The CPU rehearsal of ``trinity_mini``: every kind of layer of the
configuration's file (dense/sliding, expert/sliding, expert/full) at a toy's
widths, one share of two, float32.  The program reads its sizes from the one
file its argv names, so the rehearsal's sizes ARE a file
(``trinity_mini.json`` beside this one): ``ARGV`` names it and ``SIZES`` is
its content, which ``conftest.py`` lays over the configuration's own for
the reference and the flops.  Rows of 16 ids against a window of 8: the
band's edge lies inside the row."""

import json
import os

_FILE = os.path.join("tests", "benchmark", "tiny", "trinity_mini.json")

# after the configuration's argv (argparse keeps the last value of a flag)
ARGV = ["--decoder_config", _FILE, "--device", "cpu", "--precision", "fp32",
        "--log_every", "2"]
with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "trinity_mini.json")) as _f:
    SIZES = {k: v for k, v in json.load(_f).items() if k != "comment"}
# new values of flags the traffic's argv already has; every flag stays
SHRINK = {"--bs": "4", "--seq_len": "16"}
# over the traffic's data: the rows' length and the vocabulary's slice
DATA = {"seq_len": 16, "vocab": 64,
        "doc_len": {"median": 6, "sigma": 0.8}}
# no running statistics: the bfloat16 control (float32 is stated here), put
# in the program's place for the three steps, is shown by the first
# gradient's norms (CPU readings, 3 seeds: the control at least 2.6e-3
# where the sound program reads at most 2.3e-6)
CONTROL = {"sizes": {}, "breaks": "grad_norm_gap"}
