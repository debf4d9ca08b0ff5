"""Operations from shapes: the model FLOPs of one training step of a
configuration family (forward + backward, no recomputation, a multiply-add
counted as two), and the table of peaks.  A configuration names its
function as ``"flops": "benchmark.flops:<name>"``; a later family brings a
module of its own, with its kernels' operations and bytes.
"""

from __future__ import annotations

import json
import os

TRAIN_FACTOR = 3          # backward = 2 x forward for matmuls and convs


def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmark/peaks.json ({sorted(table)}): add it "
                       f"with its source, there is no default")
    return table[device_kind]


# -- ResNet for 32x32 inputs ---------------------------------------------------

def resnet_forward_flops(sizes: dict) -> int:
    """Forward FLOPs of ONE image: every convolution and the classifier."""
    h, w, cin = sizes["image"]
    total = 0

    def conv(k, ci, co, ho, wo):
        return 2 * k * k * ci * co * ho * wo

    k = sizes["stem"]["kernel"]
    h, w = h // sizes["stem"]["stride"], w // sizes["stem"]["stride"]
    total += conv(k, cin, sizes["widths"][0], h, w)
    cin = sizes["widths"][0]
    e = sizes["expansion"]
    for stage, (blocks, f) in enumerate(zip(sizes["stage_sizes"],
                                            sizes["widths"])):
        for i in range(blocks):
            stride = sizes["strides"][stage] if i == 0 else 1
            total += conv(1, cin, f, h, w)               # 1x1 reduce
            ho, wo = h // stride, w // stride
            total += conv(3, f, f, ho, wo)               # 3x3 (strided)
            total += conv(1, f, e * f, ho, wo)           # 1x1 expand
            if stride != 1 or cin != e * f:
                total += conv(1, cin, e * f, ho, wo)     # shortcut
            cin, h, w = e * f, ho, wo
    return total + 2 * cin * sizes["num_classes"]


def resnet(sizes: dict, batch: int, seq_len: int = 0) -> int:
    return TRAIN_FACTOR * batch * resnet_forward_flops(sizes)


def resolve(spec: str):
    import importlib
    module, name = spec.split(":")
    return getattr(importlib.import_module(module), name)
