"""The CPU rehearsal of ``resnet50_cifar10``: two bottlenecks, batch 16,
float32.  ``conftest.py`` finds this file by the configuration's name and
applies it; no test builds the configuration at the sizes of its own file."""

# after the configuration's argv (argparse keeps the last value of a flag)
ARGV = ["--device", "cpu", "--precision", "fp32", "--log_every", "2"]
# over the configuration's sizes (what the reference and the flops read)
SIZES = dict(stage_sizes=[1, 1], widths=[64, 128], strides=[1, 2])
# new values of flags the traffic's argv already has; every flag stays
SHRINK = {"--bs": "16"}
# over the traffic's data; the tests set ``rows`` themselves
DATA = {}
# the lower-precision control is shown at all four stages with one
# bottleneck each (the rounding adds up with depth: at two stages it reads
# 0.026, at the cell's own size 0.058 on the chip, PERF.md section 2), by
# the forward pass layer by layer: the running statistics
CONTROL = {"sizes": dict(stage_sizes=[1, 1, 1, 1],
                         widths=[64, 128, 256, 512], strides=[1, 2, 2, 2]),
           "breaks": "stats_gap"}


def program(monkeypatch):
    """The program has no flag for a ResNet's depth: its ``resnet50`` is
    replaced by the two bottlenecks of ``SIZES``."""
    from faster_distributed_training_tpu import models
    from faster_distributed_training_tpu.models import resnet
    monkeypatch.setitem(models._RESNETS, "resnet50",
                        resnet._factory(resnet.BottleNeck, (1, 1)))
