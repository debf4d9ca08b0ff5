"""Core substrate tests: config parsing, PRNG streams, mesh + sharding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import faster_distributed_training_tpu as fdt
from faster_distributed_training_tpu.config import (
    build_parser, config_from_args, parse_mesh)
from faster_distributed_training_tpu.parallel import (
    batch_spec, fsdp_partition_params, make_mesh, shard_pytree)


def test_config_reference_flags():
    # The reference CLI surface (resnet50_test.py:46-59) must parse unchanged.
    args = build_parser().parse_args(
        ["--bs", "256", "--lr", "0.01", "--ngd", "--meta_learning",
         "--epoch", "30", "--alpha", "0.4", "--distributed"])
    cfg = config_from_args(args)
    assert cfg.batch_size == 256 and cfg.lr == 0.01
    assert cfg.use_ngd and cfg.meta_learning and cfg.distributed
    assert cfg.epochs == 30 and cfg.alpha == 0.4


def _cache_dir_updates(monkeypatch, platform):
    """cli.enable_compilation_cache()'s jax.config.update calls, recorded
    instead of applied."""
    from faster_distributed_training_tpu import cli

    calls = []
    monkeypatch.setattr(cli, "_configured_platform", lambda: platform)
    monkeypatch.setattr(cli, "quiet_cpu_aot_flags", lambda: None)
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    cli.enable_compilation_cache()
    return cli, [v for k, v in calls if k == "jax_compilation_cache_dir"]


@pytest.mark.parametrize("case", ["env_set_tpu", "env_set_cpu",
                                  "unset_tpu", "unset_cpu",
                                  "unset_unknown", "two_processes"])
def test_compile_cache_rule(case, monkeypatch, tmp_path):
    """The compile cache is placed from OUTSIDE: with
    JAX_COMPILATION_CACHE_DIR set the program sets no directory in code
    (JAX reads the variable); unset, it is ONE fixed path inside the
    checkout — the same for tpu and cpu, no hash of host flags, no pid,
    no temp name — and the same in every process."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    fixed = os.path.join(repo, ".jax_cache")
    if case == "two_processes":
        from faster_distributed_training_tpu import cli
        code = ("from faster_distributed_training_tpu import cli; "
                "print(cli.CACHE_DIR)")
        out = subprocess.run([sys.executable, "-c", code], cwd=repo,
                             capture_output=True, text=True, timeout=300,
                             env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.strip().splitlines()[-1] == cli.CACHE_DIR == fixed
        return
    mode, platform = case.split("_", 1)[0], case.rsplit("_", 1)[1]
    platform = "" if platform == "unknown" else platform
    if mode == "env":
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        _, dirs = _cache_dir_updates(monkeypatch, platform)
        assert dirs == []
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        cli, dirs = _cache_dir_updates(monkeypatch, platform)
        assert dirs == [fixed] and cli.CACHE_DIR == fixed


@pytest.mark.parametrize("case", ["parent_off_jax", "dead_child_exits_1"])
def test_ablation_one_process_per_chip(case, tmp_path):
    """scripts/bag_of_tricks.py's parent only spawns and collects: it
    never imports jax (a chip belongs to the process that touched JAX),
    and an arm that dies makes it exit non-zero.  Runs the real parent
    in a subprocess with the spawn stubbed and a platform no JAX could
    initialise, from a temporary directory (it writes figures/ under
    its working directory)."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(repo, "scripts", "bag_of_tricks.py")
    child_ok = case == "parent_off_jax"
    code = f"""
import importlib.util, json, os, sys
spec = importlib.util.spec_from_file_location("bag_of_tricks", {script!r})
tricks = importlib.util.module_from_spec(spec)
spec.loader.exec_module(tricks)
seen = []
class Done:
    returncode = {0 if child_ok else 1}
    stderr = "boom"
    def __init__(self, arm):
        self.stdout = json.dumps({{
            "arm": arm, "epoch_times": [9.0, 2.0, 2.0],
            "device": {{"platform": "tpu", "kind": "TPU v5 lite",
                       "count": 1}}}})
def spawn(argv, env=None, **k):
    seen.append(("jax" in sys.modules, env["FDT_TRICKS_CHILD"]))
    return Done(env["FDT_TRICKS_CHILD"])
tricks.subprocess.run = spawn
try:
    tricks.main()
    rc = 0
except SystemExit as e:
    rc = e.code
print(json.dumps({{"rc": rc, "spawns": seen,
                  "jax_imported": "jax" in sys.modules,
                  "wrote": sorted(os.listdir("figures"))}}))
"""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("FDT_TRICKS_")}
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=tmp_path, capture_output=True,
        text=True, timeout=300,
        env={**env, "JAX_PLATFORMS": "no_such_platform"})
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    # one child per arm, one at a time, none started from a parent
    # that holds JAX
    assert [arm for _, arm in got["spawns"]] == [
        "resnet50_on", "resnet50_off", "transformer_on", "transformer_off"]
    assert not any(held for held, _ in got["spawns"])
    assert not got["jax_imported"]
    assert got["rc"] == (0 if child_ok else 1)
    assert got["wrote"] == ["tricks_time.png", "tricks_times.json"]
    if child_ok:
        record = json.loads(out.stdout.strip().splitlines()[-2])
        assert record["tricks_speedup_resnet50_e2e"] == 1.0


def test_config_mixup_mode_flag():
    # every mixup variant is reachable from the CLI (VERDICT r1 weak #2)
    from faster_distributed_training_tpu.train.steps import resolve_mixup_mode
    for mode in ("static", "intra", "meta", "attn", "none"):
        cfg = config_from_args(
            build_parser().parse_args(["--mixup_mode", mode]))
        assert cfg.mixup_mode == mode
        assert resolve_mixup_mode(cfg) == mode
    # '' auto-resolves per the reference pairing
    assert resolve_mixup_mode(config_from_args(
        build_parser().parse_args(["--meta_learning"]))) == "meta"
    assert resolve_mixup_mode(config_from_args(
        build_parser().parse_args(["--alpha", "0"]))) == "none"
    assert resolve_mixup_mode(config_from_args(
        build_parser().parse_args([]))) == "static"


def test_config_tricks_off_rewrites_every_speed_lever():
    # the bag-of-tricks ablation switch (VERDICT r3 #2): --tricks off
    # must flip EVERY lever at once via resolve_tricks (applied inside
    # config_from_args)
    cfg = config_from_args(build_parser().parse_args(["--tricks", "off"]))
    assert cfg.tricks == "off"
    assert cfg.precision == "fp32"
    assert cfg.attention == "dense"
    assert cfg.mlp_impl == "naive"
    assert cfg.dropout_impl == "xla"
    assert cfg.dropout_rng_impl == "threefry"
    assert cfg.prefetch_depth == 0 and cfg.workers == 0
    # default: every lever stays on
    on = config_from_args(build_parser().parse_args([]))
    assert on.tricks == "on" and on.precision == "bf16"
    assert on.dropout_impl == "hash" and on.prefetch_depth > 0


def test_tricks_off_builds_unfused_reference_layout():
    # the OFF arm reproduces the reference's three separate QKV Linears
    # (transformer.py:196-227) and the naive stored-activation MLP
    import jax
    import jax.numpy as jnp

    from faster_distributed_training_tpu.cli import build_model
    from faster_distributed_training_tpu.config import (TrainConfig,
                                                        resolve_tricks)

    cfg = resolve_tricks(TrainConfig(
        model="transformer", num_classes=4, seq_len=8, n_layers=1,
        d_model=16, d_ff=32, n_heads=2, tricks="off"))
    model = build_model(cfg, vocab_size=32)
    assert model.fused_qkv is False and model.mlp_impl == "naive"
    variables = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1),
         "mixup": jax.random.PRNGKey(2)},
        jnp.zeros((2, 8), jnp.int32), train=False)
    attn = variables["params"]["layer_0"]["attn"]
    assert {"query", "key", "value", "out"} <= set(attn)
    assert "qkv" not in attn
    # resnet OFF arm: autodiff conv+BN, fp32
    rcfg = resolve_tricks(TrainConfig(model="resnet18", tricks="off"))
    rmodel = build_model(rcfg)
    assert rmodel.conv_remat is False and rmodel.dtype == jnp.float32


def test_resolve_attention_seq_length_routing(monkeypatch, devices8):
    """'' auto-resolution (r6, measured 2D crossover surface): dense at
    seq<=256 on TPU while the materialized probs fit the routing memory
    budget, flash beyond either bound, ring under an sp axis, dense
    off-TPU; explicit --attention always wins."""
    from faster_distributed_training_tpu.cli import resolve_attention
    from faster_distributed_training_tpu.config import TrainConfig
    from faster_distributed_training_tpu.parallel import make_mesh

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_attention(
        TrainConfig(seq_len=256, batch_size=256)) == "dense"
    assert resolve_attention(
        TrainConfig(seq_len=512, batch_size=256)) == "flash"
    # r6 2D surface: large batches stay dense at short seq while the
    # probs fit, flash past the memory bound
    assert resolve_attention(
        TrainConfig(seq_len=128, batch_size=512)) == "dense"
    assert resolve_attention(
        TrainConfig(seq_len=128, batch_size=1024)) == "dense"
    assert resolve_attention(
        TrainConfig(seq_len=256, batch_size=512)) == "dense"
    # bs1024/seq256: 3*4*B*H*L^2 = 6.4 GB probs > the 4 GB budget
    assert resolve_attention(
        TrainConfig(seq_len=256, batch_size=1024)) == "flash"
    # seq=384 sits past the L-crossover (flash from seq>=384 up)
    assert resolve_attention(
        TrainConfig(seq_len=384, batch_size=256)) == "flash"
    # the memory-headroom env override flips the bound, not the code
    monkeypatch.setenv("FDT_DENSE_ATTN_BUDGET_MB", "8192")
    assert resolve_attention(
        TrainConfig(seq_len=256, batch_size=1024)) == "dense"
    monkeypatch.setenv("FDT_DENSE_ATTN_BUDGET_MB", "0")
    assert resolve_attention(
        TrainConfig(seq_len=128, batch_size=64)) == "flash"
    monkeypatch.delenv("FDT_DENSE_ATTN_BUDGET_MB")
    assert resolve_attention(TrainConfig(seq_len=512,
                                         attention="dense")) == "dense"
    # r11 4-impl surface: a dedicated sp axis routes sequence-parallel —
    # ulysses when the axis divides heads AND seq (lower interconnect
    # volume, the measured-arm-backed preference), ring otherwise
    sp_mesh = make_mesh(("dp", "sp"), (1, 8), devices8)
    assert resolve_attention(TrainConfig(seq_len=2048), sp_mesh) == "ulysses"
    assert resolve_attention(
        TrainConfig(seq_len=2048, n_heads=6), sp_mesh) == "ring"
    # seq % sp != 0: NEITHER sp strategy can serve it (shard_map needs
    # the sequence to divide the axis) — falls through to the 1D
    # surface instead of routing an impl that would fail at trace time
    assert resolve_attention(
        TrainConfig(seq_len=2050), sp_mesh) == "flash"
    # a (data, model) tp mesh goes sequence-parallel only from the first
    # measured long-context cell up; below it the 1D surface rules
    tp_mesh = make_mesh(("dp", "tp"), (4, 2), devices8)
    assert resolve_attention(TrainConfig(seq_len=2048), tp_mesh) == "ulysses"
    assert resolve_attention(
        TrainConfig(seq_len=2048, n_heads=7), tp_mesh) == "ring"
    assert resolve_attention(
        TrainConfig(seq_len=2049), tp_mesh) == "flash"   # seq % tp != 0
    assert resolve_attention(
        TrainConfig(seq_len=256, batch_size=256), tp_mesh) == "dense"
    assert resolve_attention(
        TrainConfig(seq_len=512, batch_size=256), tp_mesh) == "flash"
    # mixed sp+tp mesh: divisibility must be validated against the axis
    # the model will EXECUTE over (seq_parallel_axis prefers sp) — seq
    # 2050 divides tp=2 but not sp=4, and routing it by the tp check
    # would crash shard_map at trace time over the sp axis
    mix_mesh = make_mesh(("dp", "sp", "tp"), (1, 4, 2), devices8)
    assert resolve_attention(TrainConfig(seq_len=2050), mix_mesh) == "flash"
    assert resolve_attention(TrainConfig(seq_len=2048),
                             mix_mesh) == "ulysses"
    # axis ALIAS unification (r11 satellite): '--mesh dp=4,model=2'
    # builds a canonical tp axis, so routing can't miss it by name
    alias_mesh = make_mesh(("dp", "model"), (4, 2), devices8)
    assert "tp" in alias_mesh.axis_names
    assert resolve_attention(TrainConfig(seq_len=2048),
                             alias_mesh) == "ulysses"
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert resolve_attention(TrainConfig(seq_len=512)) == "dense"
    assert resolve_attention(TrainConfig(seq_len=512), tp_mesh) == "dense"


# (bs, seq, mesh condition, routed impl).  Mesh condition "" = mesh-
# independent (1D / no model axis), evaluated as on a TPU; "sp" = the
# mesh has a sequence-capable model axis (a dedicated sp axis, or tp —
# the axis NAME doesn't change the shard_map math) whose size divides
# both heads and seq (ulysses eligible); "sp_ragged" = model axis
# present but the heads don't divide (ring, which accepts any head
# count).  The cells are the ones the r5/r6/r11 chip readings covered;
# none has been measured since (ROADMAP S8).
_ROUTED_CELLS = (
    (256, 256, "", "dense"),
    (512, 128, "", "dense"),
    (1024, 128, "", "dense"),
    (512, 256, "", "dense"),
    (1024, 256, "", "flash"),     # 3 fp32 score tensors = 6.4 GB > 4 GB
    (256, 384, "", "flash"),
    (64, 512, "", "flash"),
    (8, 2048, "sp", "ulysses"),
    (8, 2048, "sp_ragged", "ring"),
    (4, 4096, "sp", "ulysses"),
    (4, 4096, "sp_ragged", "ring"),
)


@pytest.mark.parametrize(
    "bs,seq,cond,impl", _ROUTED_CELLS,
    ids=[f"{bs}-{seq}-{cond or '1d'}" for bs, seq, cond, _ in _ROUTED_CELLS])
def test_resolve_attention_routes_cell(bs, seq, cond, impl):
    """Each cell of the auto-router's surface goes where
    resolve_attention's rule says, through the REAL function with a
    mesh matching the cell's condition."""
    assert expect_route(bs, seq, cond) == impl


def expect_route(bs, seq, cond):
    """What resolve_attention's code actually returns for a cell —
    evaluated through the REAL function with a mesh matching the
    cell's condition."""
    import jax

    from faster_distributed_training_tpu.cli import resolve_attention
    from faster_distributed_training_tpu.config import TrainConfig
    from faster_distributed_training_tpu.parallel import make_mesh

    if cond == "":
        # mesh-independent rows are the r6 TPU dense/flash crossover
        from unittest import mock
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            return resolve_attention(
                TrainConfig(seq_len=seq, batch_size=bs))
    # sp rows: an 8-way sequence-capable axis; "sp" = divisible heads
    # (default h=8), "sp_ragged" = heads the axis doesn't divide
    if len(jax.devices()) < 8:
        import pytest
        pytest.skip("sp surface rows need an 8-device mesh, host "
                    f"exposes {len(jax.devices())}")
    mesh = make_mesh(("dp", "sp"), (1, 8), jax.devices()[:8])
    heads = 8 if cond == "sp" else 6
    return resolve_attention(
        TrainConfig(seq_len=seq, batch_size=bs, n_heads=heads), mesh)


def test_ffn_impl_pallas_mesh_routing(devices8, monkeypatch):
    """--ffn_impl pallas: data-sharded meshes (dp/fsdp/sp) keep the
    kernel (shard_map per-shard path, mesh handed to the model); since
    r19 tp meshes ALSO keep it (Megatron column/row tiles through
    parallel/kernel_shard.py) when d_ff/seq divide — the flax
    composition survives only as the registered warned fallback
    (non-dividing shapes, or FDT_KERNEL_SHARD=0)."""
    import warnings as _w

    from faster_distributed_training_tpu.cli import build_model
    from faster_distributed_training_tpu.config import TrainConfig
    from faster_distributed_training_tpu.parallel import make_mesh

    cfg = TrainConfig(model="transformer", num_classes=4, seq_len=8,
                      n_layers=1, d_model=16, d_ff=32, n_heads=2,
                      ffn_impl="pallas")
    for axes, shape, expect in ((("dp",), (8,), "pallas"),
                                (("dp", "sp"), (1, 8), "pallas"),
                                (("dp", "tp"), (1, 8), "pallas"),
                                (("dp",), (1,), "pallas")):
        mesh = make_mesh(axes, shape, devices8[:int(np.prod(shape))])
        with _w.catch_warnings(record=True) as rec:
            _w.simplefilter("always")
            model = build_model(cfg, vocab_size=32, mesh=mesh)
        assert model.ffn_impl == expect, (axes, shape)
        assert not any("falling back to the flax" in str(r.message)
                       for r in rec), (axes, shape)
        if any(s > 1 for s in shape):
            assert model.mesh is mesh   # the sharded path needs the mesh
    # non-dividing seq (seq=12 doesn't divide tp=8): warned fallback
    mesh = make_mesh(("dp", "tp"), (1, 8), devices8)
    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter("always")
        model = build_model(cfg.replace(seq_len=12), vocab_size=32,
                           mesh=mesh)
    assert model.ffn_impl == "flax"
    assert any("cannot run the Megatron" in str(r.message) for r in rec)
    # kill switch: the pre-r19 reroute comes back
    monkeypatch.setenv("FDT_KERNEL_SHARD", "0")
    mesh = make_mesh(("dp", "tp"), (1, 8), devices8)
    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter("always")
        model = build_model(cfg, vocab_size=32, mesh=mesh)
    assert model.ffn_impl == "flax"
    assert any("FDT_KERNEL_SHARD=0" in str(r.message) for r in rec)


def test_config_mesh_and_fsdp():
    args = build_parser().parse_args(["--mesh", "dp=2,tp=4"])
    cfg = config_from_args(args)
    assert cfg.mesh_axes == ("dp", "tp") and cfg.mesh_shape == (2, 4)
    assert parse_mesh("") == ((), ())
    with pytest.raises(ValueError):
        parse_mesh("dp")
    # bare --fsdp defaults the whole mesh onto the fsdp axis
    cfg2 = config_from_args(build_parser().parse_args(["--fsdp"]))
    assert cfg2.mesh_axes == ("fsdp",)
    # --fsdp with an explicit mesh lacking an fsdp axis is an error, not a no-op
    with pytest.raises(ValueError):
        config_from_args(build_parser().parse_args(["--fsdp", "--mesh", "dp=8"]))
    # overrides kwarg applies last
    cfg3 = config_from_args(build_parser().parse_args([]), epochs=5)
    assert cfg3.epochs == 5


def test_prng_streams_distinct_and_deterministic():
    k = fdt.prng.root_key(0)
    a = fdt.prng.stream(k, "mixup")
    b = fdt.prng.stream(k, "dropout")
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(a), np.asarray(fdt.prng.stream(k, "mixup")))
    # step folding works under jit (traced step)
    f = jax.jit(lambda s: fdt.prng.at_step(fdt.prng.stream(k, "mixup"), s))
    assert not np.array_equal(np.asarray(f(0)), np.asarray(f(1)))


def test_make_mesh_auto(devices8):
    m = make_mesh(("dp",), devices=devices8)
    assert m.shape["dp"] == 8
    m2 = make_mesh(("dp", "tp"), (4, 2), devices8)
    assert m2.shape["dp"] == 4 and m2.shape["tp"] == 2
    # smaller than available -> first prod(shape) devices (device narrowing)
    m3 = make_mesh(("dp",), (3,), devices8)
    assert m3.size == 3 and list(np.ravel(m3.devices)) == devices8[:3]
    with pytest.raises(ValueError):
        make_mesh(("dp",), (16,), devices8)


def test_batch_sharding_runs_collective(mesh8):
    x = jnp.arange(16.0).reshape(16, 1)
    xs = jax.device_put(x, NamedSharding(mesh8, batch_spec(mesh8)))
    # a jit'd mean over a sharded batch must compile in a psum and match
    got = jax.jit(lambda a: a.mean())(xs)
    assert np.isclose(float(got), float(x.mean()))


def test_zero1_shards_only_opt_state(mesh8):
    # ZeRO-1 (ZeroRedundancyOptimizer analog, transformer_test.py:4,221-222):
    # params replicated, optimizer state sharded over the data axis.
    from faster_distributed_training_tpu.config import TrainConfig
    from faster_distributed_training_tpu.models import resnet18
    from faster_distributed_training_tpu.optim import build_optimizer
    from faster_distributed_training_tpu.parallel.placement import (
        make_put_batch, shard_train_state, train_state_shardings)
    from faster_distributed_training_tpu.train import (create_train_state,
                                                       make_train_step)

    bs = 16
    cfg = TrainConfig(model="resnet18", batch_size=bs, zero1=True,
                      optimizer="sgd", precision="fp32", mixup_mode="none",
                      epochs=1)
    model = resnet18(num_classes=10)
    tx, _ = build_optimizer(cfg, steps_per_epoch=2)
    state = create_train_state(model, tx, jnp.zeros((bs, 32, 32, 3)),
                               jax.random.PRNGKey(0),
                               init_kwargs={"train": True})
    shardings = train_state_shardings(state, mesh8, cfg)
    # every param leaf replicated
    assert all(s.spec == P()
               for s in jax.tree.leaves(shardings.params))
    # at least one big optimizer-state leaf sharded over dp
    opt_specs = [s.spec for s in jax.tree.leaves(shardings.opt_state)]
    assert any("dp" in tuple(sp) for sp in opt_specs), opt_specs
    with mesh8:
        state = shard_train_state(state, mesh8, cfg)
        batch = make_put_batch(mesh8)({
            "image": np.zeros((bs, 32, 32, 3), np.float32),
            "label": np.arange(bs, dtype=np.int32) % 10})
        step = jax.jit(make_train_step(cfg), donate_argnums=0)
        state, m = step(state, batch)
    assert np.isfinite(float(m["loss"]))


def test_fsdp_partition_params(devices8):
    mesh = make_mesh(("fsdp",), (8,), devices8)
    params = {
        "w_big": jnp.zeros((256, 64)),      # shard dim 0 (256 % 8 == 0, largest)
        "w_odd": jnp.zeros((255, 7)),       # nothing divisible -> replicated
        "bias": jnp.zeros((64,)),           # too small -> replicated
    }
    specs = fsdp_partition_params(params, mesh, min_size=1024)
    assert specs["w_big"] == P("fsdp", None)
    assert specs["w_odd"] == P()
    assert specs["bias"] == P()
    sharded = shard_pytree(params, specs, mesh)
    assert sharded["w_big"].sharding.spec == P("fsdp", None)
    # sharded compute still correct
    s = jax.jit(jnp.sum)(sharded["w_big"])
    assert float(s) == 0.0


def test_compiled_memory_bytes():
    """Static peak-memory estimate from an AOT-compiled executable — the
    fallback for backends without runtime memory_stats (utils/profiling)."""
    import jax
    import jax.numpy as jnp

    from faster_distributed_training_tpu.utils.profiling import (
        compiled_memory_bytes)

    compiled = jax.jit(lambda x: (x @ x).sum()).lower(
        jnp.ones((64, 64))).compile()
    mem = compiled_memory_bytes(compiled)
    assert mem is None or mem >= 64 * 64 * 4  # at least the argument buffer
