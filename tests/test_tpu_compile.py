"""Every Pallas kernel the package ships, compiled by the chip's own
compiler for a DESCRIBED v5e:2x2 topology (no chip attached) at the
paper widths — what Pallas interpret mode cannot see: tile alignment,
VMEM budgets, scalar stores, and that a Mosaic kernel on a mesh of more
than one device only partitions inside ``shard_map``.

One file on purpose: the worker that runs it loads the TPU library and
keeps its lock.  The topology is described inside a module-scoped
fixture (never at import), the compiles run in the test's own process,
and the persistent compilation cache is off around them (such an entry
cannot be read back without a chip).  Nothing executes, so these say
nothing about results or times.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from faster_distributed_training_tpu.ops import pallas_target

BF16 = jnp.bfloat16
FLASH_SHAPES = [(256, 8, 256, 64), (64, 8, 512, 64), (8, 8, 2048, 64),
                (2, 8, 8192, 64)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def meshes(topo):
    devs = np.asarray(topo.devices)
    return {"dp4": Mesh(devs.reshape(4), ("dp",)),
            "dp2tp2": Mesh(devs.reshape(2, 2), ("dp", "tp"))}


def _compiled_text(topo, fn, *args):
    """Lower+compile ``fn`` for the described chip(s) the args' shardings
    name; the kernels key on those devices' platform, not on the CPU
    backend this process runs on.  x64 off as in every real run
    (conftest turns it on for gradcheck-style tests; Mosaic index maps
    are 32-bit)."""
    with jax.enable_x64(False), pallas_target.compiling_for(topo.devices):
        return jax.jit(fn).lower(*args).compile().as_text()


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _flash_args(shape, sharding, mask_sharding):
    B, H, L, D = shape
    qkv = _struct((B, H, L, D), BF16, sharding)
    return qkv, qkv, qkv, _struct((B, L), jnp.int32, mask_sharding)


def _flash_fwd(flash):
    return lambda q, k, v, m: flash(q, k, v, m)


def _flash_train(flash):
    """fwd+bwd with in-kernel dropout: what the train step holds."""
    def loss(q, k, v, m):
        out = flash(q, k, v, m, dropout_rate=0.1,
                    dropout_seed=jnp.uint32(7))
        return jnp.sum(out.astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))


@pytest.mark.parametrize("mode", ["fwd", "fwd_bwd_dropout"])
@pytest.mark.parametrize("shape", FLASH_SHAPES,
                         ids=lambda s: "x".join(map(str, s)))
def test_flash_attention_compiles(topo, one_chip, shape, mode):
    from faster_distributed_training_tpu.ops.flash_attention import (
        flash_attention)

    def flash(q, k, v, m, **kw):
        return flash_attention(q, k, v, mask=m[:, None, None, :], **kw)

    fn = _flash_fwd(flash) if mode == "fwd" else _flash_train(flash)
    text = _compiled_text(topo, fn, *_flash_args(shape, one_chip, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("mode", ["fwd", "train"])
@pytest.mark.parametrize("window", [2048, None])
def test_banded_attention_compiles(topo, one_chip, window, mode):
    """The decoder's attention at its cell's shape: one packed row of
    8,192 ids, 32 query heads on 4 key-value heads of 128, the sliding
    window and the full causal band; forward, and forward with both
    backward kernels."""
    from faster_distributed_training_tpu.ops.flash_attention import (
        banded_attention)
    q = _struct((1, 32, 8192, 128), BF16, one_chip)
    kv = _struct((1, 4, 8192, 128), BF16, one_chip)
    fn = lambda q, k, v: banded_attention(q, k, v, window)  # noqa: E731
    if mode == "train":
        fn = jax.grad(lambda q, k, v: jnp.sum(banded_attention(
            q, k, v, window).astype(jnp.float32)), argnums=(0, 1, 2))
    text = _compiled_text(topo, fn, q, kv, kv)
    names = set(re.findall(r"fdt_flash_[a-z_]+banded", text))
    assert "fdt_flash_fwd_banded" in names
    if mode == "train":
        assert {"fdt_flash_bwd_dq_banded",
                "fdt_flash_bwd_dkv_banded"} <= names


def test_grouped_expert_products_compile(topo, one_chip):
    """The expert layer's grouped products at the cell's shape: 8,192
    tokens x 8 slots against 16 held experts of 2048 x 1024, forward and
    backward (megablox's gmm and its transposed twin; a sum's gradient
    needs no forward, so two kernels stay)."""
    from faster_distributed_training_tpu.ops.grouped_matmul import (
        grouped_matmul)
    xs = _struct((65536, 2048), BF16, one_chip)
    w = _struct((16, 2048, 1024), BF16, one_chip)
    sizes = _struct((16,), jnp.int32, one_chip)
    fn = jax.grad(lambda x, w, s: jnp.sum(grouped_matmul(
        x, w, s, "gmm").astype(jnp.float32)), argnums=(0, 1))
    text = _compiled_text(topo, fn, xs, w, sizes)
    assert text.count("tpu_custom_call") >= 2
    assert "jit_gmm" in text and "jit_tgmm" in text


def test_mlp_head_compiles(topo, one_chip):
    """fused_mlp_pallas at the classifier-head shape (bs64: pooled
    [64, 512] -> d_hidden 1024 -> 4 classes)."""
    from faster_distributed_training_tpu.ops.fused_mlp import (
        fused_mlp_pallas)
    s = lambda *shape: _struct(shape, BF16, one_chip)  # noqa: E731
    text = _compiled_text(
        topo, lambda x, w1, b1, w2, b2: fused_mlp_pallas(x, w1, b1, w2, b2),
        s(64, 512), s(1024, 512), s(1, 1024), s(4, 1024), s(1, 4))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("quant", [None, "int8"])
def test_fused_ffn_compiles(topo, one_chip, quant):
    """The generalized fused-FFN kernel at d512/ff1024 over bs64/seq512
    rows, with both dropouts on; the int8 arm also emits its two amaxes
    (the scalar-store-to-VMEM refusal this PR repaired)."""
    from faster_distributed_training_tpu.ops.fused_ffn import (
        ffn_core_generalized)
    d, ff = 512, 1024
    s = lambda *shape: _struct(shape, BF16, one_chip)  # noqa: E731
    f32 = _struct((), jnp.float32, one_chip)

    def fn(h, lns, lnb, w1, b1, w2, b2, *scales):
        out, amax2 = ffn_core_generalized(
            h, lns, lnb, w1, b1, w2, b2, jnp.uint32(1), jnp.uint32(2),
            0, 0, 0, 0.1, 0.1, 1e-6, 512, 512, quant_fmt=quant,
            quant_scales=scales if quant else None)
        return out, amax2

    args = [s(64, 512, d), s(d), s(d), s(d, ff), s(ff), s(ff, d), s(d)]
    if quant:
        args += [f32] * 4
    assert "tpu_custom_call" in _compiled_text(topo, fn, *args)


@pytest.mark.parametrize("fmt,qdtype", [("int8", jnp.int8),
                                        ("fp8", jnp.float8_e4m3fn)])
def test_quant_matmul_compiles(topo, one_chip, fmt, qdtype):
    """quant_dot_pallas at the fused-qkv GEMM of bs64/seq512:
    (16384, 512) x (512, 1536)."""
    from faster_distributed_training_tpu.ops.quant import quant_dot_pallas
    f32 = _struct((), jnp.float32, one_chip)
    text = _compiled_text(
        topo, lambda xq, wq, sx, sw: quant_dot_pallas(xq, wq, sx, sw, fmt,
                                                      BF16),
        _struct((16384, 512), qdtype, one_chip),
        _struct((512, 1536), qdtype, one_chip), f32, f32)
    assert "tpu_custom_call" in text


# -- a Mesh over the four described chips: every kernel inside shard_map ----

@pytest.mark.parametrize("mesh_name,spec", [
    ("dp4", P("dp", None, None, None)),
    ("dp2tp2", P("dp", "tp", None, None))])
def test_flash_on_mesh_compiles(topo, meshes, mesh_name, spec):
    """flash fwd+bwd with dropout at bs64/seq512 through the ONE
    shard_map layer: batch over dp (pure data mesh), heads over tp."""
    from faster_distributed_training_tpu.parallel import kernel_shard
    mesh = meshes[mesh_name]

    def flash(q, k, v, m, **kw):
        return kernel_shard.flash_attention_sharded(q, k, v, m, mesh, **kw)

    args = _flash_args((64, 8, 512, 64), NamedSharding(mesh, spec),
                       NamedSharding(mesh, P("dp", None)))
    text = _compiled_text(topo, _flash_train(flash), *args)
    assert "tpu_custom_call" in text


def test_mlp_head_on_dp4_compiles(topo, meshes):
    from faster_distributed_training_tpu.parallel import kernel_shard
    mesh = meshes["dp4"]
    rep = NamedSharding(mesh, P())
    s = lambda *shape: _struct(shape, BF16, rep)  # noqa: E731

    def loss(x, w1, b1, w2, b2):
        out = kernel_shard.fused_mlp_sharded(x, w1, b1, w2, b2, mesh)
        return jnp.sum(out.astype(jnp.float32))

    text = _compiled_text(
        topo, jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)),
        _struct((64, 512), BF16, NamedSharding(mesh, P("dp", None))),
        s(1024, 512), s(1, 1024), s(4, 1024), s(1, 4))
    assert "tpu_custom_call" in text


def test_quant_dense_on_dp4_compiles(topo, meshes):
    """The int8 quant GEMM of the fused-qkv site on a pure data mesh:
    rows over dp, kernel replicated, per shard through the same layer."""
    from faster_distributed_training_tpu.parallel import kernel_shard
    mesh = meshes["dp4"]
    f32 = _struct((), jnp.float32, NamedSharding(mesh, P()))
    text = _compiled_text(
        topo, lambda x, w, sx, sw: kernel_shard.quant_dense_sharded(
            x, w, sx, sw, "int8", mesh, tp_dim=2),
        _struct((16384, 512), BF16, NamedSharding(mesh, P("dp", None))),
        _struct((512, 3, 8, 64), BF16, NamedSharding(mesh, P())), f32, f32)
    assert "tpu_custom_call" in text


# -- the conv path has no kernel: what XLA plans for it is the thing to hold ---

STAGE1 = (1024, 32, 32, 256)       # the benchmark cell's stage 1, bf16
U = int(np.prod(STAGE1)) * 2       # bytes of one array of the block's output


@pytest.fixture(scope="module")
def stage1(topo, one_chip):
    """{conv_remat: compiled}: ResNet-50's stage 1 at the benchmark cell's
    batch, three bottlenecks (256 -> 64 -> 64 -> 256) forward+backward at
    bf16[1024,32,32,256], with ``conv_bn_train``'s custom_vjps and under
    plain autodiff.  The cell's batch on purpose: XLA lays these arrays
    out with the batch in lanes or sublanes, and at batch 128 one block
    alone moves the SAME bytes on both paths."""
    import flax.linen as nn
    from faster_distributed_training_tpu.models.resnet import BottleNeck

    def compiled(conv_remat):
        class Stage(nn.Module):
            @nn.compact
            def __call__(self, x, train):
                for _ in range(3):
                    x = BottleNeck(64, dtype=BF16,
                                   conv_remat=conv_remat)(x, train)
                return x

        stage = Stage()

        def loss(p, x, stats):
            out, _ = stage.apply({"params": p, "batch_stats": stats}, x,
                                 True, mutable=["batch_stats"])
            return jnp.sum(jnp.square(out.astype(jnp.float32)))

        with jax.enable_x64(False):
            variables = jax.eval_shape(
                lambda: stage.init(jax.random.PRNGKey(0),
                                   jnp.zeros(STAGE1, BF16), True))
            params, stats = (jax.tree.map(
                lambda a: _struct(a.shape, a.dtype, one_chip), variables[k])
                for k in ("params", "batch_stats"))
            return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
                params, _struct(STAGE1, BF16, one_chip), stats).compile()

    return {True: compiled(True), False: compiled(False)}


def test_expanding_1x1_backward_keeps_no_conv_output(stage1):
    """With conv_bn_train's expanding-1x1 path the program's scratch is
    smaller than plain autodiff's by at least one array of the block's
    output size U (the third convolutions' outputs no longer live to the
    backward; 2.0 U when written, 1.5 U before ISSUE 30) and it moves at
    least 2 U fewer HBM bytes (the backward alone: 4.0 U in ISSUE 28)."""
    path, autodiff = stage1[True], stage1[False]
    assert path.as_text().count("/fdt/conv1x1_bn_bwd/") > 0
    assert "conv1x1_bn_bwd" not in autodiff.as_text()
    temp_saved = (autodiff.memory_analysis().temp_size_in_bytes
                  - path.memory_analysis().temp_size_in_bytes)
    bytes_saved = (autodiff.cost_analysis()["bytes accessed"]
                   - path.cost_analysis()["bytes accessed"])
    assert temp_saved >= U, (temp_saved / U)
    assert bytes_saved >= 2 * U, (bytes_saved / U)


def test_expanding_1x1_forward_writes_no_conv_output(stage1):
    """The forward's statistics come from the convolution's input (ISSUE
    30), so nothing reads ``y`` but its normalisation and XLA makes that,
    the block's ``add`` and the ``relu`` the convolution's epilogue: (a)
    the program moves at least 7 U fewer HBM bytes than plain autodiff
    (9.25 U when written: 45.28 U against 54.53 U; 4.0 U with ISSUE 28's
    backward alone); (b) each block holds ONE forward fusion named after
    its third layer's convolution that takes an array of the block's size
    (the shortcut) and writes one (the block's output)."""
    path, autodiff = stage1[True], stage1[False]
    text = path.as_text()
    assert text.count("/fdt/conv1x1_bn_stats/") > 0
    assert "conv1x1_bn_stats" not in autodiff.as_text()
    bytes_saved = (autodiff.cost_analysis()["bytes accessed"]
                   - path.cost_analysis()["bytes accessed"])
    assert bytes_saved >= 7 * U, (bytes_saved / U)

    block = "bf16[%s]" % ",".join(map(str, STAGE1))
    shape_of = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\S+)", text,
                               re.M))
    for i in range(3):
        op_name = (f'/jvp(Stage)/BottleNeck_{i}/FusedConvBNLayer_2/'
                   f'conv_general_dilated"')
        fusions = [line for line in text.splitlines()
                   if " fusion(" in line and op_name in line]
        assert len(fusions) == 1, (i, fusions)
        result, operands = re.match(
            r"\s*(?:ROOT )?%[\w.\-]+ = (.*?) fusion\((.*?)\), kind=",
            fusions[0]).groups()
        assert block in result, (i, result)
        assert any(shape_of.get(o, "").startswith(block)
                   for o in re.findall(r"%[\w.\-]+", operands)), (i, operands)
