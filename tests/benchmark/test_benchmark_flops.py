"""benchmark/flops.py against hand counts, against XLA's own count of a
forward pass at a tiny size, and the table of peaks."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import flops  # noqa: E402


def config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def test_resnet_forward_flops_match_the_hand_count():
    sizes = config("resnet50_cifar10")["sizes"]
    # by hand, MACs of one 32x32 image: stem 27.64 = 1.769M; stage 1 at
    # 32x32 (three blocks, first with a 64->256 shortcut), ... summed by
    # the closed form below, written out independently of flops.py
    mac = 3 * 3 * 3 * 64 * 32 * 32
    cin, hw = 64, 32
    for blocks, f, stride in ((3, 64, 1), (4, 128, 2), (6, 256, 2),
                              (3, 512, 2)):
        for i in range(blocks):
            s = stride if i == 0 else 1
            out = hw // s
            mac += cin * f * hw * hw + 9 * f * f * out * out \
                + f * 4 * f * out * out
            if i == 0:
                mac += cin * 4 * f * out * out
            cin, hw = 4 * f, out
    mac += 2048 * 10
    assert flops.resnet_forward_flops(sizes) == 2 * mac
    assert flops.resnet(sizes, 1024) == 3 * 1024 * 2 * mac
    assert 2.5e9 < 2 * mac < 2.7e9


def test_resnet_forward_flops_against_xla_cost_analysis():
    import jax
    import jax.numpy as jnp

    from benchmark.configs import resnet50_cifar10_reference as ref

    sizes = dict(config("resnet50_cifar10")["sizes"],
                 stage_sizes=[1, 1, 1, 1], image=[16, 16, 3])
    params = ref.init_params(sizes, 0)
    x = jnp.ones((2, 16, 16, 3), jnp.float32)
    cost = jax.jit(lambda p, x: ref.forward(p, x, sizes)[0]).lower(
        params, x).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    ours = 2 * flops.resnet_forward_flops(sizes)
    assert 0.95 * ours <= cost["flops"] <= 1.3 * ours


def test_peaks_table_names_its_source_and_refuses_an_unknown_device():
    v5e = flops.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(KeyError, match="no default"):
        flops.peaks("TPU v9 imaginary")
