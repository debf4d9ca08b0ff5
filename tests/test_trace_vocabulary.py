"""One ``fdt/*`` vocabulary on the profiler's clock (telemetry/spans.py's
tables): the step's device scopes reach the program's debug locations and
never its text; every Pallas kernel has a unique ``fdt_*`` name that its
jaxpr carries; the four epoch loops tile an iteration with sibling host
phases that share one ``step``; a ``--log_every`` read-back lands in the
step record that follows it; the fenced step time reaches the operator's
fold; and the trace reader reads a chip trace's scopes."""

import ast
import glob
import gzip
import json
import os
import re
import shutil
import threading

import jax
import jax.numpy as jnp
import pytest

from faster_distributed_training_tpu.config import TrainConfig
from faster_distributed_training_tpu.telemetry import (
    TelemetryRecorder, aggregate_run, spans, trace_report)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(ROOT, "faster_distributed_training_tpu")


# -- B. device scopes ------------------------------------------------------

def _locations(lowered) -> set:
    """The name-stack paths of a lowering's debug locations: what XLA
    turns into each instruction's ``op_name`` metadata."""
    return set(re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True)))


@pytest.fixture(scope="module")
def resnet_ngd_step():
    """The lowered tiny ResNet + NGD + mixup step over uint8 images (the
    benchmark cell's path at resnet18 / batch 8); nothing is compiled."""
    from faster_distributed_training_tpu.models import resnet18
    from faster_distributed_training_tpu.optim import build_optimizer
    from faster_distributed_training_tpu.train import create_train_state
    from faster_distributed_training_tpu.train.steps import make_train_step
    bs = 8
    cfg = TrainConfig(model="resnet18", batch_size=bs, alpha=0.2,
                      use_ngd=True, lr=0.01, epochs=2, precision="bf16")
    tx, _ = build_optimizer(cfg, steps_per_epoch=2)
    state = jax.eval_shape(lambda: create_train_state(
        resnet18(num_classes=10), tx, jnp.zeros((bs, 32, 32, 3)),
        jax.random.PRNGKey(0), init_kwargs={"train": False}))
    batch = {"image": jax.ShapeDtypeStruct((bs, 32, 32, 3), jnp.uint8),
             "label": jax.ShapeDtypeStruct((bs,), jnp.int32)}
    lowered = jax.jit(make_train_step(cfg), donate_argnums=0).lower(
        state, batch)
    return lowered, _locations(lowered)


@pytest.fixture(scope="module")
def lm_fused_step():
    """The LM branch inside ``make_fused_train_step``'s scan body (tiny
    transformer, K=2, fp16 so that fdt/grad_reduce has operations)."""
    from faster_distributed_training_tpu.cli import build_model
    from faster_distributed_training_tpu.optim import build_optimizer
    from faster_distributed_training_tpu.train import create_train_state
    from faster_distributed_training_tpu.train.steps import (
        make_fused_train_step)
    cfg = TrainConfig(model="transformer", task="lm", batch_size=4,
                      seq_len=16, n_layers=1, d_model=16, d_ff=32, n_heads=2,
                      optimizer="sgd", precision="fp16", epochs=1)
    tx, _ = build_optimizer(cfg, steps_per_epoch=2)
    model = build_model(cfg, vocab_size=64)
    state = jax.eval_shape(lambda: create_train_state(
        model, tx, jnp.zeros((4, 16), jnp.int32), jax.random.PRNGKey(0),
        init_kwargs={"train": True}))
    batches = {"tokens": jax.ShapeDtypeStruct((2, 4, 16), jnp.int32),
               "mask": jax.ShapeDtypeStruct((2, 4, 16), jnp.int32)}
    lowered = jax.jit(make_fused_train_step(cfg, 2)).lower(state, batches)
    return lowered, _locations(lowered)


@pytest.mark.parametrize("scope", [
    "fdt/augment", "fdt/mixup", "jvp(fdt/model)", "transpose(jvp(fdt/model))",
    "jvp(fdt/loss)", "fdt/optimizer", "fdt/optimizer/ngd",
    # a cond's branch is a function of its own in the lowering: its
    # locations start at the cond (the compiled HLO joins the two, below)
    "fdt/optimizer/ngd/cond", "cond/branch_1_fun/fisher_update"])
def test_resnet_ngd_step_carries_scope(resnet_ngd_step, scope):
    _, locs = resnet_ngd_step
    assert any(scope in loc for loc in locs), scope


@pytest.mark.parametrize("scope", [
    "jvp(fdt/model)", "transpose(jvp(fdt/model))", "jvp(fdt/loss)",
    "fdt/grad_reduce", "fdt/optimizer"])
def test_lm_branch_in_the_fused_scan_carries_scope(lm_fused_step, scope):
    _, locs = lm_fused_step
    # the scan's body is a function of its own in the lowering (a
    # closed_call under the while): its locations start at the scope
    assert "jit(step_k)/while/body/closed_call" in locs
    assert any(scope in loc for loc in locs), scope


@pytest.fixture(scope="module")
def decoder_step():
    """The decoder's train step at its rehearsal size (one dense and two
    expert layers, sliding and full attention)."""
    import os

    from faster_distributed_training_tpu.cli import build_model
    from faster_distributed_training_tpu.optim import build_optimizer
    from faster_distributed_training_tpu.train import create_train_state
    from faster_distributed_training_tpu.train.steps import make_train_step
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = TrainConfig(
        model="decoder", task="lm", batch_size=2, seq_len=16,
        optimizer="adamw", schedule="constant", precision="fp32", epochs=1,
        decoder_config=os.path.join(root, "tests", "benchmark", "tiny",
                                    "trinity_mini.json"))
    tx, _ = build_optimizer(cfg, steps_per_epoch=2)
    model = build_model(cfg, vocab_size=64)
    state = jax.eval_shape(lambda: create_train_state(
        model, tx, jnp.zeros((2, 16), jnp.int32), jax.random.PRNGKey(0),
        init_kwargs={"train": True}))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
    lowered = jax.jit(make_train_step(cfg)).lower(state, batch)
    return lowered, _locations(lowered)


@pytest.mark.parametrize("scope", [
    "jvp(fdt/model)", "transpose(jvp(fdt/model))", "fdt/attention",
    "fdt/moe_route", "fdt/moe_dispatch", "fdt/moe_experts",
    "fdt/moe_combine", "fdt/optimizer"])
def test_decoder_step_carries_scope(decoder_step, scope):
    """The decoder's scopes all lie inside ``fdt/model``, forward and
    backward (a configuration's ``scopes`` match them first)."""
    _, locs = decoder_step
    inside = [loc for loc in locs if scope in loc]
    assert inside, scope
    if scope.startswith("fdt/moe") or scope == "fdt/attention":
        assert all("fdt/model" in loc for loc in inside)
        assert any("transpose(jvp(fdt/model))" in loc for loc in inside)


@pytest.mark.parametrize("program", ["resnet_ngd_step", "lm_fused_step",
                                     "decoder_step"])
def test_program_text_carries_no_scope(program, request):
    """Scopes live in debug locations only: the text the observatory
    fingerprints and the compile cache keys on does not move."""
    lowered, _ = request.getfixturevalue(program)
    assert "fdt/" not in lowered.as_text()
    assert "fisher_update" not in lowered.as_text()


def test_compiled_ngd_update_has_scopes_in_op_name_metadata():
    """A compiled program's HLO: ``op_name`` metadata is made of the
    locations (NGD's update alone, small enough that no compile cache keeps
    it), through the ``cond`` that gates the Fisher refresh."""
    from faster_distributed_training_tpu.optim.ngd import scale_by_ngd
    tx = scale_by_ngd(update_period=4)
    params = {"w": jnp.ones((12, 10)), "b": jnp.ones((10,))}

    def update(g, st):
        with jax.named_scope("fdt/optimizer"):
            return tx.update(g, st)

    hlo = jax.jit(update).lower(params, tx.init(params)).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    assert any("fdt/optimizer/ngd/" in n for n in names)
    assert any(re.search(r"fdt/optimizer/ngd/.*cond.*fisher_update", n)
               for n in names)


def _package_calls(attr: str):
    """(path relative to the package, Call node) of every ``<x>.<attr>(...)``
    in the package, read from the source."""
    for path in sorted(glob.glob(os.path.join(PKG, "**", "*.py"),
                                 recursive=True)):
        for node in ast.walk(ast.parse(open(path).read())):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == attr):
                yield os.path.relpath(path, PKG), node


def test_every_named_scope_in_the_package_is_a_row_of_the_table():
    """``jax.named_scope("...")`` literals: each is in
    ``telemetry/spans.py``'s docstring, the one vocabulary table."""
    found = {node.args[0].value: path
             for path, node in _package_calls("named_scope")
             if node.args and isinstance(node.args[0], ast.Constant)}
    assert found["fdt/conv1x1_bn_stats"] == found["fdt/conv1x1_bn_bwd"] == (
        os.path.join("ops", "conv_bn.py"))
    for name, where in found.items():
        assert f"``{name}``" in spans.__doc__, (name, where)


# -- B. kernel names -------------------------------------------------------

def _pallas_call_names() -> dict:
    """{(file, line): [name literals]} of every ``pl.pallas_call(...)`` in
    the package."""
    sites = {}
    for path, node in _package_calls("pallas_call"):
        kw = {k.arg: k.value for k in node.keywords}
        names = [c.value for c in ast.walk(kw["name"])
                 if isinstance(c, ast.Constant)
                 and isinstance(c.value, str)] if "name" in kw else []
        sites[path, node.lineno] = names
    return sites


def test_every_pallas_call_site_has_a_unique_documented_name():
    sites = _pallas_call_names()
    assert len(sites) == 10, sorted(sites)
    names = [n for site in sites.values() for n in site]
    assert all(site for site in sites.values()), sites
    assert all(re.fullmatch(r"fdt_[a-z0-9_]+", n) for n in names), names
    # one site names two (with / without the saved statistics), three
    # name a banded twin (the K-blocked kernels over a causal band)
    assert len(set(names)) == len(names) == 14
    for n in names:
        assert f"``{n}``" in spans.__doc__, n


def _flash_qkv(B=1, H=2, L=128, D=64):
    q = jnp.zeros((B, H, L, D), jnp.bfloat16)
    return q, q, q, jnp.ones((B, L), jnp.int32)


def _flash_grad(monkeypatch, **env):
    from faster_distributed_training_tpu.ops.flash_attention import (
        flash_attention)
    monkeypatch.setenv("FDT_FORCE_PALLAS_INTERPRET", "1")
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    q, k, v, m = _flash_qkv()

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, mask=m[:, None, None, :]).astype(jnp.float32))
    return str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))


def _jaxpr_flash_saved_stats(monkeypatch):
    return _flash_grad(monkeypatch)


def _jaxpr_flash_recompute(monkeypatch):
    return _flash_grad(monkeypatch, FDT_FLASH_SAVE_STATS="0")


def _jaxpr_flash_fwd_only(monkeypatch):
    from faster_distributed_training_tpu.ops.flash_attention import (
        flash_attention)
    monkeypatch.setenv("FDT_FORCE_PALLAS_INTERPRET", "1")
    q, k, v, m = _flash_qkv()
    return str(jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, mask=m[:, None, None, :]))(q, k, v))


def _jaxpr_flash_kblocked(monkeypatch):
    import importlib
    fa = importlib.import_module(
        "faster_distributed_training_tpu.ops.flash_attention")
    q, k, v, _ = _flash_qkv()
    n3 = lambda x: x.reshape(2, 128, 64)  # noqa: E731
    seed = jnp.zeros((1, 3), jnp.uint32)

    def both(q, k, v, g):
        out, lse = fa._flash_fwd_kblocked(n3(q), n3(k), n3(v), None,
                                          seed3=seed, n_heads=2)
        return fa._flash_bwd_kblocked(
            q, k, v, None, seed, 0.0, out.reshape(q.shape), lse)(g)
    return str(jax.make_jaxpr(both)(q, k, v, q))


def _jaxpr_mlp(monkeypatch):
    from faster_distributed_training_tpu.ops.fused_mlp import (
        fused_mlp_pallas)
    b = jnp.bfloat16
    return str(jax.make_jaxpr(fused_mlp_pallas)(
        jnp.zeros((8, 16), b), jnp.zeros((32, 16), b), jnp.zeros((1, 32), b),
        jnp.zeros((4, 32), b), jnp.zeros((1, 4), b)))


def _jaxpr_quant(monkeypatch):
    from faster_distributed_training_tpu.ops.quant import quant_dot_pallas
    return str(jax.make_jaxpr(lambda x, w, sx, sw: quant_dot_pallas(
        x, w, sx, sw, "int8", jnp.bfloat16))(
        jnp.zeros((32, 16), jnp.int8), jnp.zeros((16, 8), jnp.int8),
        jnp.float32(1), jnp.float32(1)))


def _ffn_args(d=16, ff=32):
    f = jnp.float32
    return (jnp.zeros((2, 8, d), f), jnp.ones((d,), f), jnp.zeros((d,), f),
            jnp.zeros((d, ff), f), jnp.zeros((ff,), f), jnp.zeros((ff, d), f),
            jnp.zeros((d,), f))


def _jaxpr_ffn(monkeypatch):
    from faster_distributed_training_tpu.ops import fused_ffn
    h, lns, lnb, w1, b1, w2, b2 = _ffn_args()
    return str(jax.make_jaxpr(
        lambda *a: fused_ffn._ffn_fwd_pallas(
            *a, jnp.zeros((1, 5), jnp.uint32), 0.0, 0.0, 1e-6, 8, 8))(
        h.reshape(-1, 16), lns, lnb, w1, b1, w2, b2))


def _jaxpr_ffn_general(monkeypatch):
    from faster_distributed_training_tpu.ops.fused_ffn import (
        ffn_core_generalized)
    return str(jax.make_jaxpr(lambda *a: ffn_core_generalized(
        *a, jnp.uint32(1), jnp.uint32(2), 0, 0, 0, 0.0, 0.0, 1e-6, 8, 8))(
        *_ffn_args()))


def _jaxpr_flash_banded(monkeypatch):
    from faster_distributed_training_tpu.ops.flash_attention import (
        banded_attention)
    monkeypatch.setenv("FDT_FORCE_PALLAS_INTERPRET", "1")
    q = jnp.zeros((1, 4, 128, 64), jnp.float32)
    kv = jnp.zeros((1, 2, 128, 64), jnp.float32)
    return str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(banded_attention(q, k, v, 32)),
        argnums=(0, 1, 2)))(q, kv, kv))


@pytest.mark.parametrize("trace,names", [
    (_jaxpr_flash_banded, ["fdt_flash_fwd_banded", "fdt_flash_bwd_dq_banded",
                           "fdt_flash_bwd_dkv_banded"]),
    (_jaxpr_flash_fwd_only, ["fdt_flash_fwd"]),
    (_jaxpr_flash_saved_stats, ["fdt_flash_fwd_lse", "fdt_flash_bwd_fused"]),
    (_jaxpr_flash_recompute, ["fdt_flash_fwd", "fdt_flash_bwd_recompute"]),
    (_jaxpr_flash_kblocked, ["fdt_flash_fwd_kblocked", "fdt_flash_bwd_dq",
                             "fdt_flash_bwd_dkv"]),
    (_jaxpr_mlp, ["fdt_fused_mlp"]),
    (_jaxpr_quant, ["fdt_quant_matmul"]),
    (_jaxpr_ffn, ["fdt_fused_ffn_fwd"]),
    (_jaxpr_ffn_general, ["fdt_fused_ffn_fwd_general"]),
], ids=lambda v: v.__name__[7:] if callable(v) else None)
def test_kernel_name_reaches_the_jaxpr(monkeypatch, trace, names):
    """Traced, never run: each site's ``pallas_call`` equation carries
    its ``fdt_*`` name, and no other site's."""
    text = trace(monkeypatch)
    found = set(re.findall(r"fdt_[a-z0-9_]+", text))
    assert found == set(names), (found, names)


# -- A. host phases --------------------------------------------------------

class _Annotations:
    """A recording stand-in for ``jax.profiler.TraceAnnotation``: every
    enter and exit on the dispatching thread, in order."""

    def __init__(self):
        self.events = []
        self.thread = threading.get_ident()
        outer = self

        class Annotation:
            def __init__(self, name, **kw):
                self.name, self.kw = name, kw

            def __enter__(self):
                if threading.get_ident() == outer.thread:
                    outer.events.append(("B", self.name, self.kw.get("step")))
                return self

            def __exit__(self, *exc):
                if threading.get_ident() == outer.thread:
                    outer.events.append(("E", self.name, None))
                return False
        self.cls = Annotation


@pytest.fixture(scope="module")
def lm_corpus(tmp_path_factory):
    from faster_distributed_training_tpu.data.stream import (
        synthetic_corpus, write_lm_corpus)
    d = str(tmp_path_factory.mktemp("lm_stream"))
    write_lm_corpus(d, synthetic_corpus(40, seed=3, words_per_doc=(25, 50)),
                    seq_len=16, rows_per_shard=16, val_fraction=0.15)
    return d


LOOPS = {
    # loop: (config, the phases of one iteration, in order)
    "host_k1": (dict(data_path="host", steps_per_dispatch=1),
                ["data_wait", "h2d", "dispatch", "hooks"]),
    "host_k2": (dict(data_path="host", steps_per_dispatch=2),
                ["data_wait", "h2d", "dispatch", "hooks"]),
    "resident_k2": (dict(data_path="resident", steps_per_dispatch=2),
                    ["dispatch", "hooks"]),
    "stream_k2": (dict(data_path="stream", steps_per_dispatch=2,
                       stream_window=4),
                  ["data_wait", "dispatch", "hooks"]),
}


@pytest.fixture(scope="module")
def loop_runs(lm_corpus, tmp_path_factory):
    """One short run through each of the four epoch loops with the
    stand-in in ``TraceAnnotation``'s place: {loop: (events, records)}."""
    from faster_distributed_training_tpu.cli import run_training
    out = {}
    for loop, (kw, _phases) in LOOPS.items():
        rec = _Annotations()
        mp = pytest.MonkeyPatch()
        mp.setattr(jax.profiler, "TraceAnnotation", rec.cls)
        try:
            cfg = TrainConfig(
                model="transformer", dataset="stream", task="lm",
                stream_dir=lm_corpus, batch_size=8, seq_len=16, n_layers=1,
                d_model=16, d_ff=32, n_heads=2, epochs=1, optimizer="sgd",
                precision="fp32", plot=False, workers=0, log_every=4,
                donate=False, checkpoint_every=64,
                checkpoint_dir=str(tmp_path_factory.mktemp(loop)), **kw)
            res = run_training(cfg, log=lambda *_: None)
        finally:
            mp.undo()
        with open(os.path.join(res["telemetry_dir"],
                               "host_00000.jsonl")) as f:
            recs = [json.loads(line) for line in f]
        out[loop] = (rec.events, [r for r in recs if r["kind"] == "step"],
                     [r for r in recs if r["kind"] == "epoch_fence"])
    return out


def _training_phases(events):
    """The hot loop's phases up to the closing fence, as (name, step), and
    the deepest nesting of ``fdt/*`` annotations seen among them."""
    labels = {f"fdt/{p}" for p in spans.PHASES}
    out, depth, deepest = [], 0, 0
    for kind, name, step in events:
        if kind == "B":
            depth += 1
            if name in labels:
                deepest = max(deepest, depth)
                out.append((name[4:], step))
        else:
            depth -= 1
            if name == "fdt/epoch_fence":
                break
    return out, deepest


@pytest.mark.parametrize("loop", sorted(LOOPS))
def test_loop_tiles_an_iteration_with_sibling_phases(loop_runs, loop):
    events, records, _ = loop_runs[loop]
    phases, deepest = _training_phases(events)
    assert deepest == 1, "a phase opened inside another fdt/* span"
    assert phases[-1][0] == "epoch_fence"
    by_step = {}
    for name, step in phases[:-1]:
        by_step.setdefault(step, []).append(name)
    want = LOOPS[loop][1]
    dispatched = {s: names for s, names in by_step.items()
                  if "dispatch" in names}
    assert len(dispatched) == len(records) >= 3
    data = [n for n in want if n in ("data_wait", "h2d")]
    for r in records:
        names = dispatched[r["step"] - r["k"] + 1]   # the phases' identifier
        at = names.index("dispatch")
        assert names[at:] == [n for n in want if n not in data] + (
            ["readback"] if r.get("sync_ms") else []), (r["step"], names)
        # before the dispatch only the loop's data phases, an h2d only
        # after the data_wait that read its batch (device_prefetch primes
        # its depth inside the first iteration and stages nothing once
        # the loader is exhausted)
        head = names[:at]
        assert set(head) <= set(data), (r["step"], names)
        assert all(head[i - 1] == "data_wait" for i, n in enumerate(head)
                   if n == "h2d" and i), (r["step"], names)
    steady = next(r for r in records[1:] if not r.get("sync_ms"))
    assert dispatched[steady["step"] - steady["k"] + 1] == want
    assert phases[-1][1] == records[-1]["step"]


@pytest.mark.parametrize("loop", sorted(LOOPS))
def test_read_back_lands_in_the_step_record(loop_runs, loop):
    """A ``--log_every`` boundary's record: ``sync_ms``, a fenced window
    (unless it held the compiling dispatch), and a ``wall_ms`` that covers
    the read-back — the record is written after it."""
    _, records, closing = loop_runs[loop]
    synced = [r for r in records if "sync_ms" in r]
    assert len(synced) >= 2
    for r in synced:
        assert r["n"] % 4 < r["k"]                       # a boundary
        assert r["wall_ms"] >= (r["data_ms"] + r["dispatch_ms"]
                                + r["block_ms"] + r["sync_ms"]) - 0.01
    fenced = [r for r in records if "fence_steps" in r]
    assert fenced and all(r in synced for r in fenced)
    first = next(r for r in records if r.get("compile"))
    assert all(r["step"] - r["fence_steps"] >= first["step"] for r in fenced)
    # windows are back to back: each ends where the next begins
    assert all(b["step"] - b["fence_steps"] == a["step"]
               for a, b in zip(fenced, fenced[1:]))
    assert all(r["fence_ms"] > 0 and "h2d_ms" in r for r in fenced)
    # run_epoch's own fence closes the epoch's last window
    assert len(closing) == 1
    last = closing[0]
    assert last["step"] == records[-1]["step"]
    assert last["step"] - last["fence_steps"] == fenced[-1]["step"]
    assert last["fence_ms"] > 0 and last["sync_ms"] >= 0
    assert all(0.0 <= r["h2d_ms"] <= r["data_ms"] + 0.01 for r in records
               if "host" in loop)


def test_sampling_keeps_a_record_that_carries_a_fence(tmp_path):
    rec = TelemetryRecorder(str(tmp_path), process_index=0, process_count=1,
                            step_every=100, log=lambda *_: None)
    for i in range(1, 9):
        rec.record_step(i, 0, i, 1, 5.0, 4.0, 8,
                        fence=(4, 640.0) if i % 4 == 0 else None,
                        sync_ms=300.0 if i % 4 == 0 else 0.0)
    rec.close()
    with open(rec.path) as f:
        steps = [r for r in map(json.loads, f) if r["kind"] == "step"]
    assert [r["step"] for r in steps] == [4, 8]
    assert all(r["fence_steps"] == 4 and r["fence_ms"] == 640.0
               and r["sync_ms"] == 300.0 for r in steps)


def test_schema_lint_passes_with_the_new_fields():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "check_telemetry_schema",
        os.path.join(ROOT, "scripts", "check_telemetry_schema.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main() == 0


# -- the operator's step time ----------------------------------------------

def _write_host(directory, pi, records, closing=()):
    rec = TelemetryRecorder(str(directory), process_index=pi,
                            process_count=2, log=lambda *_: None)
    for r in records:
        rec.record_step(**r)
    for r in closing:
        rec.record_event("epoch_fence", **r)
    rec.close()


def _steps(n, dispatch_ms, fence_every=0, step_ms=0.0):
    return [dict(step=i, epoch=0, n=i, k=1, wall_ms=dispatch_ms + 1,
                 dispatch_ms=dispatch_ms, examples=8,
                 fence=((fence_every, fence_every * step_ms)
                        if fence_every and i % fence_every == 0 else None))
            for i in range(1, n + 1)]


@pytest.mark.parametrize("case,want", [
    # both hosts fenced: the enqueue (3.8 ms) is nowhere; the slow host is
    ("fenced", {"source": "fenced", "p50": 160.0, "straggler": [1]}),
    # epochs shorter than --log_every: the epoch's own fence is the window
    ("epoch_fence", {"source": "fenced", "p50": 160.0, "straggler": [1]}),
    # files without fences fold as before, by dispatch_ms / k
    ("dispatch", {"source": "dispatch", "p50": 3.8, "straggler": []}),
])
def test_fold_prefers_the_fenced_step_time(tmp_path, case, want):
    every = 10 if case == "fenced" else 0
    closing = ([dict(step=40, epoch=0, fence_steps=40, sync_ms=9.0)]
               if case == "epoch_fence" else [])
    _write_host(tmp_path, 0, _steps(40, 3.8, every, 160.0),
                [dict(c, fence_ms=40 * 160.0) for c in closing])
    _write_host(tmp_path, 1, _steps(40, 3.8, every, 400.0),
                [dict(c, fence_ms=40 * 400.0) for c in closing])
    summary = aggregate_run(str(tmp_path))
    assert summary["hosts"]["0"]["step_time_source"] == want["source"]
    assert summary["hosts"]["0"]["step_ms_p50"] == pytest.approx(want["p50"])
    assert [s["host"] for s in summary["stragglers"]] == want["straggler"]
    assert summary["hosts"]["0"]["steps"] == 40


# -- the trace reader on a chip trace --------------------------------------

@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    """Two consecutive executions of the ResNet-50 bs1024 NGD step on one
    TPU v5e (the second refreshes the Fisher factors), cut from a
    ``--profile_steps`` trace taken with a fresh compile cache (PR 24):
    operations' names cut to 56 characters, the ``tf_op`` metadata stat
    kept."""
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "cut"
    d.mkdir(parents=True)
    src = os.path.join(ROOT, "tests", "fixtures", "trace_report",
                       "resnet50_two_steps.xplane.pb.gz")
    with gzip.open(src, "rb") as f, open(d / "cut.xplane.pb", "wb") as g:
        shutil.copyfileobj(f, g)
    return str(d.parent.parent.parent)


def test_reader_splits_a_chip_trace_by_scope(chip_trace):
    rep = trace_report.report(chip_trace)
    assert rep["steps"] == 2 and rep["step_program"].startswith("jit_step")
    scopes = rep["scopes"]
    # read by hand from the same trace (PERF.md section 5)
    assert scopes["transpose(jvp(fdt/model))"] == pytest.approx(81.42, abs=.01)
    assert scopes["jvp(fdt/model)"] == pytest.approx(38.85, abs=0.01)
    assert scopes["fdt/augment"] == pytest.approx(28.09, abs=0.01)
    assert scopes["fdt/optimizer/ngd"] == pytest.approx(3.86, abs=0.01)
    assert scopes["unscoped"] < 0.05 * rep["device_ms_per_step"]
    assert sum(scopes.values()) == pytest.approx(rep["device_ms_per_step"])
    plain, refresh = rep["per_step"]
    assert plain["fisher_update_ms"] == 0.0
    # the refresh IS the fourth step's excess
    assert refresh["ms"] - plain["ms"] == pytest.approx(
        refresh["fisher_update_ms"], abs=0.3)
    assert rep["host"]["fdt/dispatch"]["count"] == 2
    assert set(rep["host"]) == {"fdt/dispatch", "fdt/h2d", "fdt/data_wait"}
    text = trace_report.render(rep)
    assert "fdt/optimizer/ngd/fisher_update" in text and "unscoped" in text


@pytest.mark.parametrize("op_name,scope", [
    ("jit(step)/jvp(fdt/model)/ResNet/conv", "jvp(fdt/model)"),
    ("jit(step)/transpose(jvp(fdt/model))/ResNet/conv",
     "transpose(jvp(fdt/model))"),
    ("jit(step)/fdt/model/transpose(jvp(fdt/model))/ResNet/BottleNeck_0/"
     "FusedConvBNLayer_2/fdt/conv1x1_bn_bwd/nhwk,nhwc->kc/dot_general",
     "transpose(jvp(fdt/model))/fdt/conv1x1_bn_bwd"),
    ("jit(step)/jvp(fdt/model)/ResNet/BottleNeck_0/FusedConvBNLayer_2/"
     "fdt/conv1x1_bn_stats/nhwk,nhwl->kl/dot_general",
     "jvp(fdt/model)/fdt/conv1x1_bn_stats"),
    ("jit(step)/jvp(fdt/model)/ResNet/BottleNeck_0/FusedConvBNLayer_2/"
     "conv_general_dilated", "jvp(fdt/model)"),
    ("jit(step)/fdt/optimizer/ngd/vmap()/mul", "fdt/optimizer/ngd"),
    ("jit(step)/fdt/optimizer/ngd/cond/branch_1_fun/fisher_update/eigh",
     "fdt/optimizer/ngd/fisher_update"),
    ("cond/branch_1_fun/fisher_update/reduce_sum",
     "fdt/optimizer/ngd/fisher_update"),
    ("jit(step)/fdt/optimizer/add", "fdt/optimizer"),
    ("jit(step)/transpose(jvp(fdt/loss))/mul", "fdt/loss"),
    ("jit(step)/while/body/fdt/augment/dynamic_slice", "fdt/augment"),
    ("jit(step)/jit(_threefry_fold_in)/slice", None),
    ("", None),
])
def test_scope_of_matches_the_path_as_a_substring(op_name, scope):
    assert trace_report.scope_of(op_name) == scope


@pytest.mark.parametrize("name,op_name,kernel", [
    ("%jvp_fdt_flash_fwd_lse_.1 = (bf16[64,512,64]) custom-call(",
     "jit(step)/jvp(fdt_flash_fwd_lse)/pallas_call", "fdt_flash_fwd_lse"),
    ("%transpose_jvp_fdt_flash_bwd_fused__.1 = custom-call(", "",
     "fdt_flash_bwd_fused"),
    ("%fusion.12 = f32[8]", "jit(step)/jvp(fdt/model)/dot_general", None),
])
def test_kernel_of_reads_either_name(name, op_name, kernel):
    assert trace_report.kernel_of(name, op_name) == kernel


def test_attribute_gives_each_instant_to_the_innermost_event():
    """A loop's event spans its body's; a body without a scope inherits
    the loop's; idle time goes to nobody."""
    spent = trace_report.attribute([
        (0.0, 100.0, "a"), (10.0, 20.0, None), (30.0, 50.0, "b"),
        (120.0, 130.0, None), (130.0, 150.0, "b")])
    assert spent == {"a": 80.0, "b": 40.0, trace_report.UNSCOPED: 10.0}
