"""The decoder (models/decoder.py), its expert layer (models/moe.py), the
grouped products (ops/grouped_matmul.py) and the banded attention kernels
(ops/flash_attention.py) against plain references, at a small size on the
CPU in float32 from seeded weights.

Tolerances.  Both sides are float32 and differ in the ORDER of their sums
(the program streams attention over key blocks and groups its expert
products; the reference masks dense ones), which at these sizes reads 1e-6
and below.  ``TOL`` is 2e-5 relative to each tensor's largest entry: thirty
times that room, and a hundred times under what bfloat16 in float32's place
gives (4e-3, one part in 2**8), which ``test_bfloat16_would_fail`` shows
failing it.
"""

import functools
import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from faster_distributed_training_tpu.models import decoder, moe  # noqa: E402
from faster_distributed_training_tpu.ops import attention as xla_attention  # noqa: E402
from faster_distributed_training_tpu.ops.flash_attention import (  # noqa: E402
    BANDED_LSE, banded_attention)
from faster_distributed_training_tpu.ops.grouped_matmul import (  # noqa: E402
    grouped_matmul)
from faster_distributed_training_tpu.train.steps import step_counters  # noqa: E402

reference = importlib.import_module(
    "benchmark.configs.trinity_mini_reference")

TOL = 2e-5
SLIDING, FULL = "sliding_attention", "full_attention"
with open(os.path.join(ROOT, "tests", "benchmark", "tiny",
                       "trinity_mini.json")) as f:
    TINY = json.load(f)


def close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    return float(np.max(np.abs(got - want))) <= tol * max(
        float(np.max(np.abs(want))), 1e-30)


def assert_trees_close(got, want, tol=TOL):
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    bad = [jax.tree_util.keystr(path) for (path, g), w
           in zip(flat_got, flat_want) if not close(g, w, tol)]
    assert not bad, f"leaves beyond {tol}: {bad}"


def one_layer(kind, dense, **over):
    return dict(TINY, num_hidden_layers=1, layer_types=[kind],
                num_dense_layers=int(dense), **over)


def program_loss(sizes_dict, params, tokens, dtype=jnp.float32):
    model = decoder.Decoder(decoder.sizes_from(sizes_dict), dtype=dtype)
    stats = model.init(jax.random.PRNGKey(0), tokens).get("batch_stats", {})

    def loss(p):
        logits = model.apply({"params": p, "batch_stats": stats}, tokens)
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
        picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return -jnp.mean(picked), logits
    (value, logits), grads = jax.value_and_grad(loss, has_aux=True)(params)
    return value, logits, grads


def reference_logits(sizes_dict, params, tokens):
    mm = reference.product
    return jnp.stack([
        mm("td,dv->tv", reference.hidden(params, row, sizes_dict, mm),
           params["lm_head"]) for row in tokens])


def tokens_for(sizes_dict, rows=2, length=16, seed=3):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, length), 0,
                              sizes_dict["vocab_size"], jnp.int32)


BLOCKS = {"dense-sliding": (SLIDING, True), "expert-sliding": (SLIDING, False),
          "expert-full": (FULL, False)}


@pytest.mark.parametrize("name", sorted(BLOCKS))
def test_block_matches_reference(name):
    """Logits, loss and every gradient leaf of a one-layer model of each
    kind; rows of 16 ids against a window of 8, so the band's edge is
    inside the row."""
    kind, dense = BLOCKS[name]
    sizes = one_layer(kind, dense)
    tokens = tokens_for(sizes)
    params = reference.init_params(dict(sizes, seq_len=16), 11)
    value, logits, grads = program_loss(sizes, params, tokens)
    want, want_grads = jax.value_and_grad(
        lambda p: reference.loss_fn(p, {"tokens": tokens}, sizes,
                                    {"precision": "fp32"}, 0, 0)[0])(params)
    assert close(logits, reference_logits(sizes, params, tokens))
    assert abs(float(value) - float(want)) <= TOL * abs(float(want))
    assert_trees_close(grads, want_grads)


def test_whole_tiny_model_matches_reference():
    tokens = tokens_for(TINY)
    params = reference.init_params(dict(TINY, seq_len=16), 5)
    value, logits, grads = program_loss(TINY, params, tokens)
    want, want_grads = jax.value_and_grad(
        lambda p: reference.loss_fn(p, {"tokens": tokens}, TINY,
                                    {"precision": "fp32"}, 0, 0)[0])(params)
    assert abs(float(value) - float(want)) <= TOL * abs(float(want))
    assert_trees_close(grads, want_grads, 5 * TOL)   # three layers deep


def test_bfloat16_would_fail():
    """The tolerance is tight enough: the same model computed in bfloat16
    is beyond it on the logits."""
    sizes = one_layer(SLIDING, True)
    tokens = tokens_for(sizes)
    params = reference.init_params(dict(sizes, seq_len=16), 11)
    _, logits, _ = program_loss(sizes, params, tokens, dtype=jnp.bfloat16)
    assert not close(logits, reference_logits(sizes, params, tokens))


def test_half_batch_fault_and_control_change_the_loss():
    tokens = tokens_for(TINY, rows=1)
    params = reference.init_params(dict(TINY, seq_len=16), 5)
    run = lambda **kw: float(reference.loss_fn(       # noqa: E731
        params, {"tokens": tokens}, TINY, {"precision": "bf16"}, 0, 0,
        **kw)[0])
    sound = run()
    assert abs(run(fault="half_batch") - sound) > 1e-3 * sound
    assert abs(run(low=True) - sound) > 1e-4 * sound


# -- the expert layer ----------------------------------------------------------

def whole_layer(width=8, top_k=2, d=32, f=16, seed=7):
    """An uncut expert layer's sizes, weights and tokens."""
    sizes = one_layer(FULL, False, hidden_size=d, moe_intermediate_size=f,
                      num_experts=width, num_experts_per_tok=top_k)
    sizes.pop("published"), sizes.pop("deployment")
    params = reference.init_params(dict(sizes, seq_len=16),
                                   seed)["layer_0"]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 12, d),
                          jnp.float32)
    return sizes, params, u


def share_of(params, lo, held):
    cut = {k: v[lo:lo + held] if k.startswith("experts_") else v
           for k, v in params.items()}
    return cut


def apply_share(sizes, params, u, lo, held, n_shared, impl=None):
    layer = moe.ExpertLayer(
        impl=impl,
        router_width=sizes["num_experts"], held=held, lo=lo,
        top_k=sizes["num_experts_per_tok"],
        width=sizes["moe_intermediate_size"], n_shared=n_shared,
        route_scale=sizes["route_scale"])
    p = share_of(params, lo, held)
    if not n_shared:
        p = {k: v for k, v in p.items() if k != "shared"}
    out, mutated = layer.apply({"params": p}, u, mutable=[moe.COUNTERS])
    return out, step_counters(mutated)


@pytest.mark.parametrize("impl", ["ragged", "gmm"])
def test_shares_add_up_to_the_whole_layer(impl):
    """THE SHARE TEST: over the 4 shares of a whole layer of 8 experts,
    the routed parts summed, with the shared expert counted once, equal
    the uncut reference's layer; and the slots the shares count add up to
    every token-slot."""
    sizes, params, u = whole_layer()
    mm = reference.product
    want = jnp.stack([reference.expert_layer(params, row, sizes, mm)
                      for row in u])
    total, slots = 0.0, 0.0
    for i in range(4):
        with jax.enable_x64(False):    # megablox's interpreter is 32-bit
            out, stats = apply_share(sizes, params, u, lo=2 * i, held=2,
                                     n_shared=0, impl=impl)
        total, slots = total + out, slots + float(stats["moe_slots"])
    shared = moe.MLP(sizes["moe_intermediate_size"]).apply(
        {"params": params["shared"]}, u)
    assert close(total + shared, want)
    assert slots == u.shape[0] * u.shape[1] * sizes["num_experts_per_tok"]


@pytest.mark.parametrize("top_k", [1, 2])
def test_every_slot_landing_here_is_still_exact(top_k):
    """No capacity: a router that sends EVERY token-slot to the held
    experts (with one expert a token: all of them to ONE expert) still
    gives the reference's result, and ``moe_slots`` counts T x top_k."""
    sizes, params, u = whole_layer(top_k=top_k)
    u = jnp.abs(u)                       # so that u . (ones) > 0
    router = jnp.zeros_like(params["router"])
    favoured = [3] if top_k == 1 else [2, 3]
    router = router.at[:, jnp.asarray(favoured)].set(0.1)
    params = dict(params, router=router)
    out, stats = apply_share(sizes, params, u, lo=2, held=2, n_shared=1)
    mm = reference.product
    held_only = dict(params)             # the reference holds 0 .. held:
    order = [2, 3, 0, 1, 4, 5, 6, 7]     # renumber so that 2, 3 come first
    held_only["router"] = router[:, jnp.asarray(order)]
    for k in ("experts_gate_proj", "experts_up_proj", "experts_down_proj"):
        held_only[k] = params[k][jnp.asarray(order)][:2]
    cut = dict(sizes, num_experts=2, published={"num_experts": 8})
    want = jnp.stack([reference.expert_layer(held_only, row, cut, mm)
                      for row in u])
    assert close(out, want)
    assert float(stats["moe_slots"]) == u.shape[0] * u.shape[1] * top_k
    assert float(stats["moe_load_max"]) == (2.0 if top_k == 1 else 1.0)


def test_expert_layer_gradients_reach_router_experts_shared_and_input():
    sizes, params, u = whole_layer()

    def loss(p, x):
        layer = moe.ExpertLayer(router_width=8, held=4, lo=0, top_k=2,
                                width=16, route_scale=sizes["route_scale"])
        return jnp.sum(jnp.sin(layer.apply({"params": p}, x)))

    def want(p, x):
        cut = dict(sizes, num_experts=4, published={"num_experts": 8})
        return jnp.sum(jnp.sin(jnp.stack([
            reference.expert_layer(p, row, cut, reference.product)
            for row in x])))
    p = share_of(params, 0, 4)
    got = jax.grad(loss, (0, 1))(p, u)
    assert_trees_close(got, jax.grad(want, (0, 1))(p, u))
    for leaf in jax.tree.leaves(got):
        assert float(jnp.max(jnp.abs(leaf))) > 0


def test_grouped_matmul_rows_past_the_last_group_are_zero():
    lhs = jax.random.normal(jax.random.PRNGKey(0), (64, 16), jnp.float32)
    rhs = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 24), jnp.float32)
    sizes = jnp.asarray([5, 0, 17], jnp.int32)
    for impl in ("ragged", "gmm"):
        # x64 off as in every real run (conftest turns it on; megablox's
        # interpreted index arithmetic is 32-bit)
        with jax.enable_x64(False):
            out = grouped_matmul(lhs, rhs, sizes, impl)
            d_lhs, d_rhs = jax.grad(lambda a, b: jnp.sum(jnp.sin(
                grouped_matmul(a, b, sizes, impl))), (0, 1))(lhs, rhs)
        assert close(out[:5], lhs[:5] @ rhs[0])
        assert close(out[5:22], lhs[5:22] @ rhs[2])
        assert float(jnp.max(jnp.abs(out[22:]))) == 0.0
        assert float(jnp.max(jnp.abs(d_lhs[22:]))) == 0.0
        assert close(d_rhs[2], lhs[5:22].T @ jnp.cos(lhs[5:22] @ rhs[2]))
        assert float(jnp.max(jnp.abs(d_rhs[1]))) == 0.0


# -- the banded attention ------------------------------------------------------

def dense_band(q, k, v, window):
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    length = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    i, j = jnp.arange(length)[:, None], jnp.arange(length)[None]
    keep = j <= i
    if window is not None:
        keep &= i - j < window
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


# (row length, window): L < W, L = W + 1, L no multiple of the 512-row
# tile and over one tile with a window inside a tile and across tiles,
# and the full causal band over two tiles
BANDS = [(40, 64), (65, 64), (700, 96), (1100, 600), (1100, None)]


@pytest.mark.parametrize("path", ["blockwise", "kernels"])
@pytest.mark.parametrize("length,window", BANDS)
def test_banded_attention_matches_dense_masked_softmax(path, length, window,
                                                       monkeypatch):
    """Forward and all three gradients, 8 query heads a key-value head,
    on both off-TPU paths: the XLA blockwise twin and the banded Pallas
    kernels under the interpret seam."""
    monkeypatch.setenv("FDT_FORCE_PALLAS_INTERPRET",
                       "1" if path == "kernels" else "0")
    keys = jax.random.split(jax.random.PRNGKey(length), 3)
    with jax.enable_x64(False):
        q = jax.random.normal(keys[0], (1, 16, length, 16), jnp.float32)
        k = jax.random.normal(keys[1], (1, 2, length, 16), jnp.float32)
        v = jax.random.normal(keys[2], (1, 2, length, 16), jnp.float32)
        got = banded_attention(q, k, v, window)
        want = dense_band(q, k, v, window)
        assert close(got, want)
        grads = jax.grad(lambda *a: jnp.sum(jnp.sin(banded_attention(
            *a, window))), (0, 1, 2))(q, k, v)
        wants = jax.grad(lambda *a: jnp.sum(jnp.sin(dense_band(
            *a, window))), (0, 1, 2))(q, k, v)
        for g, w in zip(grads, wants):
            assert close(g, w, 5 * TOL)


# -- what --remat keeps --------------------------------------------------------

def equations(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from equations(sub)


def forward_kernels(fn, *args):
    """How often the banded forward kernel is in ``fn``'s gradient."""
    jaxpr = jax.make_jaxpr(jax.grad(fn))(*args).jaxpr
    return sum(eqn.primitive.name == "pallas_call"
               and eqn.params["name"] == "fdt_flash_fwd_banded"
               for eqn in equations(jaxpr))


def kept(fn, *args):
    """[(aval, where from)] of what ``fn``'s backward keeps of its forward,
    arguments and constants left out."""
    from jax._src.ad_checkpoint import saved_residuals
    return [(aval, why) for aval, why in saved_residuals(fn, *args)
            if not why.startswith(("from the argument", "from a constant",
                                   "from a literal"))]


def named(residuals, name):
    return [aval for aval, why in residuals if f"named '{name}'" in why]


# "expert-block-cut": 5 experts a token over the 4 held (the hybrid cell's
# 22 over 8), so the compact index is narrower than top_k
REMAT_CASES = {"dense-block": one_layer(SLIDING, True),
               "expert-block": one_layer(FULL, False),
               "expert-block-cut": one_layer(FULL, False,
                                             num_experts_per_tok=5),
               "whole-tiny": TINY}


@pytest.mark.parametrize("path", ["blockwise", "kernels"])
@pytest.mark.parametrize("name", sorted(REMAT_CASES))
def test_remat_keeps_named_residuals_and_changes_nothing(name, path,
                                                         monkeypatch):
    """With ``remat=True`` (a) the backward keeps, a layer, the attention
    kernel's ``out`` and ``lse`` (on the kernels' path: the blockwise twin
    has no such residuals and names nothing) and an expert layer's routing
    integers, the compact index and its inverse among them whether
    ``top_k`` is at most ``held`` or more; and the forward banded kernel is
    in the gradient once a layer, as without remat; (b) loss and every
    gradient leaf are ``remat=False``'s."""
    monkeypatch.setenv("FDT_FORCE_PALLAS_INTERPRET",
                       "1" if path == "kernels" else "0")
    sizes_dict = REMAT_CASES[name]
    sizes = decoder.sizes_from(sizes_dict)
    layers = len(sizes.layer_types)
    expert_layers = layers - sizes.num_dense_layers
    with jax.enable_x64(False):
        tokens = tokens_for(sizes_dict)
        params = reference.init_params(dict(sizes_dict, seq_len=16), 11)

        def loss(p, remat):
            logits = decoder.Decoder(sizes, remat=remat).apply(
                {"params": p}, tokens)
            logp = jax.nn.log_softmax(logits[:, :-1], -1)
            return -jnp.mean(jnp.take_along_axis(
                logp, tokens[:, 1:, None], axis=-1))
        plain, rematted = (functools.partial(loss, remat=r)
                           for r in (False, True))
        residuals = kept(rematted, params)
        on_kernels = layers if path == "kernels" else 0
        B, L = tokens.shape
        out_shape = (B, sizes.num_attention_heads, L, sizes.head_dim)
        assert len(named(residuals, BANDED_LSE)) == on_kernels
        # ``out`` is also the block's primal, so JAX keeps it behind a
        # full-width reduce_precision and reports that in the name's place
        assert sum(aval.shape == out_shape and "flash_attention.py" in why
                   for aval, why in residuals) == on_kernels
        for routing in moe.ROUTING_RESIDUALS:
            assert len(named(residuals, routing)) == expert_layers
        assert forward_kernels(rematted, params) == on_kernels
        assert forward_kernels(plain, params) == on_kernels
        want, want_grads = jax.value_and_grad(plain)(params)
        value, grads = jax.value_and_grad(rematted)(params)
    assert abs(float(value) - float(want)) <= TOL * abs(float(want))
    assert_trees_close(grads, want_grads)


@pytest.mark.parametrize("name",
                         ["dense-block", "expert-block", "expert-block-cut"])
def test_remat_keeps_lse_without_the_kernels_lanes(name, monkeypatch):
    """The bytes ONE rematted block keeps besides its input are the
    arithmetic of its docstring: ``out`` [B, H, L, D] and ``lse``
    [B * H, L] float32 (not the kernel's 128-lane [B * H, L, 128]), and on
    an expert block the routing integers: ``chosen`` [T, k], the rows'
    tokens, the compact index [T, m] and its inverse [T * m] with
    m = min(k, held), ``sizes`` [held]; nothing else of the routing (the
    router weights are a select over the scores: no gather's index array
    is handed over)."""
    monkeypatch.setenv("FDT_FORCE_PALLAS_INTERPRET", "1")
    sizes = decoder.sizes_from(REMAT_CASES[name])
    kind, dense = sizes.layer_types[0], bool(sizes.num_dense_layers)
    B, L = 2, 16
    H, D, k = (sizes.num_attention_heads, sizes.head_dim,
               sizes.num_experts_per_tok)
    with jax.enable_x64(False):
        x = jax.random.normal(jax.random.PRNGKey(0),
                              (B, L, sizes.hidden_size), jnp.float32)
        block = decoder.RematBlock(sizes, kind, dense)
        params = block.init(jax.random.PRNGKey(1), x)["params"]
        residuals = kept(lambda p, u: jnp.sum(block.apply({"params": p}, u)),
                         params, x)
    got = sum(aval.size * aval.dtype.itemsize for aval, _ in residuals)
    want = 4 * B * H * L * D + 4 * B * H * L
    if not dense:
        slots, rows = B * L * k, B * L * min(k, sizes.held)
        want += 4 * (slots + 3 * rows + sizes.held)
    assert got == want, [(aval.str_short(), why) for aval, why in residuals]
    assert named(residuals, BANDED_LSE)[0].shape == (B * H, L)


def test_blockwise_window_needs_no_kernel():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 50, 8), jnp.float32)
    got = xla_attention.blockwise_attention(q, q, q, None, causal=True,
                                            window=7, block_k=16)
    assert close(got, dense_band(q, q, q, 7))


def test_sizes_come_from_the_one_file(tmp_path):
    sizes = decoder.load_sizes(os.path.join(
        ROOT, "benchmark", "configs", "trinity_mini.json"))
    assert (sizes.router_width, sizes.held, sizes.lo) == (128, 16, 0)
    assert sizes.vocab_size == 25024 and len(sizes.layer_types) == 5
    with pytest.raises(ValueError, match="do not divide"):
        decoder.sizes_from({**TINY, "num_experts": 3})
