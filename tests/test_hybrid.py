"""The hybrid decoder (models/hybrid.py), its Mamba-2 mixer (models/mamba.py),
the chunked SSD scan (ops/ssd.py) and the latent relu2 expert layer
(models/moe.py) against plain references, at a small size on the CPU in
float32 from seeded weights: the configuration's reference
(``benchmark/configs/nemotron3_super_reference.py``, whose scan is the
QUADRATIC form) and, for the scan, the sequential recurrence.

Tolerances.  Both sides are float32 and differ in the order of their sums
(chunks against one quadratic sum or one step at a time, grouped expert
products against masked dense ones), which at these sizes reads 1e-6 and
below.  ``TOL`` is 2e-5 relative to each tensor's largest entry, as in
tests/test_decoder.py: twenty times that room, and a hundred times under
what a bfloat16 scan state gives (``test_bfloat16_state_would_fail``:
3e-3 over 32 chunks) or the model computed in bfloat16
(``test_bfloat16_would_fail``).
"""

import importlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from faster_distributed_training_tpu.models import decoder, hybrid, moe  # noqa: E402
from faster_distributed_training_tpu.models.mamba import Mamba2Mixer  # noqa: E402
from faster_distributed_training_tpu.ops import ssd  # noqa: E402

reference = importlib.import_module(
    "benchmark.configs.nemotron3_super_reference")

TOL = 2e-5
F32 = jnp.float32
with open(os.path.join(ROOT, "tests", "benchmark", "tiny",
                       "nemotron3_super.json")) as f:
    TINY = {k: v for k, v in json.load(f).items() if k != "comment"}
mm = reference.product


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


# -- the chunked scan ----------------------------------------------------------

def scan_inputs(length, b=2, H=4, P=3, G=2, N=5, seed=0, dt_scale=1.0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (b, length, H, P), F32)
    dt = dt_scale * jax.nn.softplus(jax.random.normal(k[1], (b, length, H),
                                                      F32))
    A = -jnp.exp(jax.random.uniform(k[2], (H,), F32, 0.0, 2.7))
    B = jax.random.normal(k[3], (b, length, G, N), F32)
    C = jax.random.normal(k[4], (b, length, G, N), F32)
    return x, dt, A, B, C


def sequential(x, dt, A, B, C):
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t, y_t = h_t C_t, one step
    at a time."""
    b, _, H, P = x.shape
    G, N = B.shape[-2:]
    Bh, Ch = jnp.repeat(B, H // G, 2), jnp.repeat(C, H // G, 2)

    def step(h, t):
        xt, dtt, Bt, Ct = t
        h = (jnp.exp(dtt * A)[..., None, None] * h
             + (dtt[..., None] * xt)[..., None] * Bt[:, :, None, :])
        return h, jnp.einsum("bhpn,bhn->bhp", h, Ct)
    _, y = jax.lax.scan(step, jnp.zeros((b, H, P, N), F32),
                        [a.swapaxes(0, 1) for a in (x, dt, Bh, Ch)])
    return y.swapaxes(0, 1)


def quadratic(x, dt, A, B, C):
    """The reference's quadratic form, row by row."""
    return jnp.stack([reference.ssd(*row, mm) for row in zip(
        x, dt, [A] * x.shape[0], B, C)])


# (row length, chunk): a multiple of the chunk, none, shorter than one
SCANS = [(32, 8), (37, 8), (5, 8), (64, 64)]


@pytest.mark.parametrize("want", ["sequential", "quadratic"])
@pytest.mark.parametrize("length,chunk", SCANS)
def test_chunked_scan_matches_recurrence_and_quadratic_form(length, chunk,
                                                            want):
    """Forward and the gradients of all five inputs."""
    other = {"sequential": sequential, "quadratic": quadratic}[want]
    with jax.enable_x64(False):
        args = scan_inputs(length)
        assert close(ssd.ssd_scan(*args, chunk), other(*args))
        grads = jax.grad(lambda *a: jnp.sum(jnp.sin(ssd.ssd_scan(
            *a, chunk))), tuple(range(5)))(*args)
        wants = jax.grad(lambda *a: jnp.sum(jnp.sin(other(*a))),
                         tuple(range(5)))(*args)
    for g, w in zip(grads, wants):
        assert close(g, w)


def test_bfloat16_state_would_fail(monkeypatch):
    """The tolerance is tight enough: the state carried across 32 chunks in
    bfloat16 is beyond it.  Small steps keep the decays near one, so the
    state lives long, as a trained mixer's does."""
    with jax.enable_x64(False):
        args = scan_inputs(256, dt_scale=0.05)
        want = sequential(*args)
        assert close(ssd.ssd_scan(*args, 8), want)
        monkeypatch.setattr(ssd, "STATE_DTYPE", jnp.bfloat16)
        assert not close(ssd.ssd_scan(*args, 8), want)


# -- layers and the whole model --------------------------------------------------

def one_layer(kind, **over):
    return dict(TINY, num_hidden_layers=1, hybrid_override_pattern=kind,
                **over)


def program_loss(sizes_dict, params, tokens, dtype=F32):
    model = decoder.build(decoder.sizes_from(sizes_dict), dtype=dtype)

    def loss(p):
        logits = model.apply({"params": p}, tokens)
        logp = jax.nn.log_softmax(logits[:, :-1].astype(F32), -1)
        picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return -jnp.mean(picked), logits
    (value, logits), grads = jax.value_and_grad(loss, has_aux=True)(params)
    return value, logits, grads


def reference_logits(sizes_dict, params, tokens):
    return jnp.stack([
        mm("td,dv->tv", reference.hidden(params, row, sizes_dict, mm),
           params["lm_head"]) for row in tokens])


def tokens_for(sizes_dict, rows=2, length=16, seed=3):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, length), 0,
                              sizes_dict["vocab_size"], jnp.int32)


def reference_loss(sizes_dict, params, tokens):
    return jax.value_and_grad(lambda p: reference.loss_fn(
        p, {"tokens": tokens}, sizes_dict, {"precision": "fp32"}, 0, 0)[0])(
            params)


@pytest.mark.parametrize("kind", ["M", "E", "*"])
def test_layer_matches_reference(kind):
    """Logits, loss and every gradient leaf of a one-layer model of each
    kind; rows of 16 ids against a chunk of 8."""
    sizes = one_layer(kind)
    tokens = tokens_for(sizes)
    params = reference.init_params(dict(sizes, seq_len=16), 11)
    with jax.enable_x64(False):
        value, logits, grads = program_loss(sizes, params, tokens)
        want, want_grads = reference_loss(sizes, params, tokens)
        assert close(logits, reference_logits(sizes, params, tokens))
    assert abs(float(value) - float(want)) <= TOL * abs(float(want))
    assert_trees_close(grads, want_grads)


@pytest.mark.parametrize("remat", [False, True])
def test_whole_tiny_model_matches_reference(remat):
    """Every kind of layer, in the file's pattern; the program's tree is the
    reference's, leaf for leaf; ``--remat`` changes nothing."""
    tokens = tokens_for(TINY)
    params = reference.init_params(dict(TINY, seq_len=16), 5)
    model = decoder.build(decoder.sizes_from(TINY), remat=remat)
    with jax.enable_x64(False):
        own = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
        assert jax.tree.structure(own["params"]) == jax.tree.structure(params)
        assert jax.tree.map(lambda a, b: a.shape, own["params"], params) \
            == jax.tree.map(lambda a: a.shape, params)

        def loss(p):
            logits = model.apply({"params": p}, tokens)
            logp = jax.nn.log_softmax(logits[:, :-1], -1)
            return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None],
                                                 axis=-1))
        value, grads = jax.value_and_grad(loss)(params)
        want, want_grads = reference_loss(TINY, params, tokens)
    assert abs(float(value) - float(want)) <= TOL * abs(float(want))
    assert_trees_close(grads, want_grads, 5 * TOL)   # five layers deep


def test_bfloat16_would_fail():
    sizes = one_layer("M")
    tokens = tokens_for(sizes)
    params = reference.init_params(dict(sizes, seq_len=16), 11)
    with jax.enable_x64(False):
        _, logits, _ = program_loss(sizes, params, tokens, dtype=jnp.bfloat16)
        assert not close(logits, reference_logits(sizes, params, tokens))


def test_multi_token_prediction_is_refused():
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        decoder.sizes_from(dict(TINY, num_nextn_predict_layers=1))


def test_sizes_come_from_the_one_file():
    sizes = decoder.load_sizes(os.path.join(
        ROOT, "benchmark", "configs", "nemotron3_super.json"))
    assert isinstance(sizes, hybrid.HybridSizes)
    assert sizes.pattern == "MEMEMEM*EME"
    assert (sizes.router_width, sizes.held, sizes.num_experts_per_tok) \
        == (512, 8, 22)
    # the held inner width is heads x head dim, not expand x hidden
    assert sizes.mamba_heads * sizes.mamba_head_dim == 1024
    assert (sizes.moe_latent_size, sizes.shared_width, sizes.act) \
        == (1024, 5376, "relu2")


# -- the share test (model-configs guide, section 4) --------------------------------

WHOLE = dict(TINY, mamba_num_heads=4, n_groups=2, num_attention_heads=4,
             num_key_value_heads=2, n_routed_experts=8, published={})


def whole_layer(kind, seed=7, part="mixer"):
    """An uncut layer's sizes, weights (of its mixer, or of its shared
    expert) and two rows of inputs."""
    sizes = dict(WHOLE, num_hidden_layers=1, hybrid_override_pattern=kind)
    params = reference.init_params(dict(sizes, seq_len=16),
                                   seed)["layer_0"][part]
    u = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (2, 16, sizes["hidden_size"]), F32)
    return sizes, params, u


def mixer_share(p, sizes, s, shares=2):
    """Share ``s`` of a Mamba-2 mixer: its heads, their B/C group, their
    channels of every projection, of the conv and of the gated norm."""
    H, P, G, N, inner, _ = reference.mamba_sizes(sizes)
    h, g = H // shares, G // shares
    heads = np.arange(s * h * P, (s + 1) * h * P)
    group = np.arange(s * g * N, (s + 1) * g * N)
    own = np.arange(s * h, (s + 1) * h)
    cols = np.concatenate([heads, inner + heads, 2 * inner + group,
                           2 * inner + G * N + group,
                           2 * inner + 2 * G * N + own])
    conv = np.concatenate([heads, inner + group, inner + G * N + group])
    return {"in_proj": p["in_proj"][:, cols],
            "conv1d_weight": p["conv1d_weight"][:, conv],
            "conv1d_bias": p["conv1d_bias"][conv],
            "dt_bias": p["dt_bias"][own], "A_log": p["A_log"][own],
            "D": p["D"][own], "norm": {"scale": p["norm"]["scale"][heads]},
            "out_proj": p["out_proj"][heads]}


def test_mixer_head_shares_add_up_to_the_whole_mixer():
    """Two shares of a mixer of 4 heads and 2 B/C groups, each with its
    heads, one group and its channels of the gated norm, sum to the uncut
    reference's mixer."""
    sizes, params, u = whole_layer("M")
    want = jnp.stack([reference.mamba(params, row, sizes, mm) for row in u])
    mixer = Mamba2Mixer(2, sizes["mamba_head_dim"], 1,
                        sizes["ssm_state_size"], sizes["conv_kernel"],
                        sizes["chunk_size"], sizes["norm_eps"])
    with jax.enable_x64(False):
        total = sum(mixer.apply({"params": mixer_share(params, sizes, s)}, u)
                    for s in range(2))
    assert close(total, want)


def test_attention_head_shares_add_up_to_the_whole_layer():
    """Two shares of 2 query heads and their 1 key-value head each."""
    sizes, params, u = whole_layer("*")
    want = jnp.stack([reference.attention(params, row, sizes, mm)
                      for row in u])
    D = sizes["head_dim"]
    layer = hybrid.GQAttention(2, 1, D)
    total = 0.0
    for s in range(2):
        q = slice(s * 2 * D, (s + 1) * 2 * D)
        kv = slice(s * D, (s + 1) * D)
        share = {"q_proj": params["q_proj"][:, q],
                 "k_proj": params["k_proj"][:, kv],
                 "v_proj": params["v_proj"][:, kv],
                 "o_proj": params["o_proj"][q]}
        with jax.enable_x64(False):
            total = total + layer.apply({"params": share}, u)
    assert close(total, want)


@pytest.mark.parametrize("impl", ["ragged", "gmm"])
def test_expert_shares_add_up_to_the_whole_layer(impl):
    """Two shares of 4 of a whole layer's 8 latent relu2 experts: every
    share computes the router and the latent projections alike, the
    routed parts summed with the shared expert counted once equal the
    uncut reference's layer, and the slots the shares count add up to
    every token-slot."""
    sizes, params, u = whole_layer("E")
    shared = whole_layer("E", part="shared")[1]
    want = jnp.stack([reference.expert_layer(params, row, sizes, mm)
                      + reference.shared_expert(shared, row, mm)
                      for row in u])
    total = moe.MLP(sizes["moe_shared_expert_intermediate_size"],
                    act="relu2").apply({"params": shared}, u)     # once
    slots = 0.0
    for s in range(2):
        layer = routed_layer(sizes, held=4, lo=4 * s, impl=impl)
        share = {k: v[4 * s:4 * s + 4] if k.startswith("experts_") else v
                 for k, v in params.items()}
        with jax.enable_x64(False):   # megablox's interpreter is 32-bit
            out, mutated = layer.apply({"params": share}, u,
                                       mutable=[moe.COUNTERS])
        slots += float(sum(jax.tree.leaves(mutated[moe.COUNTERS]
                                           ["moe_slots"])))
        total = total + out
    assert close(total, want)
    assert slots == u.shape[0] * u.shape[1] * sizes["num_experts_per_tok"]


def routed_layer(sizes, held, lo, impl=None, top_k=None):
    return moe.ExpertLayer(
        router_width=8, held=held, lo=lo,
        top_k=top_k or sizes["num_experts_per_tok"],
        width=sizes["moe_intermediate_size"], n_shared=0,
        route_scale=sizes["routed_scaling_factor"], act="relu2",
        latent=sizes["moe_latent_size"], impl=impl)


def rows_at(fn, width, *args):
    """{rows} of every two-dimensional value ``width`` wide in ``fn``'s
    lowered program."""
    text = jax.jit(fn).lower(*args).as_text()
    return {int(r) for r in re.findall(rf"tensor<(\d+)x{width}x", text)}


def cut_layer(top_k, impl, fill):
    """Two of 8 latent experts held, ``top_k`` a token: (loss of the
    layer's output and its counters, the reference's loss, weights,
    tokens).  ``fill``: a router that sends every token to both held
    experts; else the seeded one, under which some slots land."""
    sizes, params, u = whole_layer("E")
    sizes = dict(sizes, num_experts_per_tok=top_k, n_routed_experts=2,
                 published={"n_routed_experts": 8})
    if fill:
        u = jnp.abs(u)                   # so that u . (ones) > 0
        params = dict(params, router=params["router"].at[:, :2].set(1.0))
    params = dict(params, experts_up_proj=params["experts_up_proj"][:2],
                  experts_down_proj=params["experts_down_proj"][:2])
    layer = routed_layer(sizes, held=2, lo=0, impl=impl, top_k=top_k)

    def loss(p, x):
        out, mutated = layer.apply({"params": p}, x, mutable=[moe.COUNTERS])
        return jnp.sum(jnp.sin(out)), mutated[moe.COUNTERS]

    def want(p, x):
        return jnp.sum(jnp.sin(jnp.stack([
            reference.expert_layer(p, row, sizes, mm) for row in x])))
    return sizes, loss, want, params, u


@pytest.mark.parametrize("impl", ["ragged", "gmm"])
@pytest.mark.parametrize("top_k", [3, 5])
def test_every_token_on_every_held_expert_fills_the_cut_buffers(top_k,
                                                                impl):
    """More experts a token than are held (the cell's 22 of 8): the
    expert-order buffers hold T x held rows, not T x top_k, and nothing of
    the gradient works at T x top_k rows of the latent width (the combine,
    its transpose and the dispatch's go through the compact index).  A
    router that sends every token to both held experts fills them to the
    last row, and the layer's output and every gradient are still the
    reference's."""
    sizes, loss, want, params, u = cut_layer(top_k, impl, fill=True)
    with jax.enable_x64(False):       # megablox's interpreter is 32-bit
        (value, counted), grads = jax.value_and_grad(
            loss, (0, 1), has_aux=True)(params, u)
        f = sizes["moe_intermediate_size"]
        rows = {v.aval.shape[0]          # of the grouped products' results
                for e in jax.make_jaxpr(loss)(params, u).jaxpr.eqns
                for v in e.outvars if v.aval.shape[1:] == (f,)}
        latent = rows_at(jax.value_and_grad(loss, (0, 1), has_aux=True),
                         sizes["moe_latent_size"], params, u)
    wanted, want_grads = jax.value_and_grad(want, (0, 1))(params, u)
    tokens = u.shape[0] * u.shape[1]
    assert rows == {tokens * 2}          # held rows a token, not top_k
    assert tokens * 2 in latent and tokens * top_k not in latent
    assert float(sum(jax.tree.leaves(counted["moe_slots"]))) == tokens * 2
    assert abs(float(value) - float(wanted)) <= TOL * abs(float(wanted))
    assert_trees_close(grads, want_grads)


@pytest.mark.parametrize("impl", ["ragged", "gmm"])
@pytest.mark.parametrize("top_k", [3, 5])
def test_some_slots_landing_on_a_cut_layer_match_the_reference(top_k, impl):
    """The seeded router: tokens land none, one or both of their slots on
    the two held experts, so a token's entries of the compact index past
    its landed slots name absent slots' rows; output and every gradient
    are the reference's."""
    sizes, loss, want, params, u = cut_layer(top_k, impl, fill=False)
    with jax.enable_x64(False):
        (value, counted), grads = jax.value_and_grad(
            loss, (0, 1), has_aux=True)(params, u)
        chosen, _ = moe.route(u.reshape(-1, u.shape[-1]), params["router"],
                              top_k, 1.0)
    wanted, want_grads = jax.value_and_grad(want, (0, 1))(params, u)
    landed = jnp.sum(chosen < 2, axis=1)
    assert {0, 1, 2} <= set(np.asarray(landed).tolist())
    assert float(sum(jax.tree.leaves(counted["moe_slots"]))) \
        == float(jnp.sum(landed))
    assert abs(float(value) - float(wanted)) <= TOL * abs(float(wanted))
    assert_trees_close(grads, want_grads)


@pytest.mark.parametrize("top_k,held", [(3, 2), (5, 2), (2, 4)])
def test_compact_index_is_a_permutation_of_the_rows(top_k, held):
    """Every gather through the compact index reads each expert-order row
    once: ``slots`` is a permutation of the T x m rows and ``rows`` its
    inverse; a token's entries are its landed slots' rows in slot order,
    then rows past the landed ones (absent slots')."""
    T, m = 16, min(top_k, held)
    with jax.enable_x64(False):
        chosen = jnp.argsort(jax.random.uniform(
            jax.random.PRNGKey(top_k), (T, 8)), axis=1)[:, :top_k]
        here = chosen < held
        order = jnp.argsort(jnp.where(here, chosen, held).reshape(-1),
                            stable=True)
        inv = jnp.zeros_like(order).at[order].set(jnp.arange(T * top_k))
        landed = int(jnp.sum(here))
        _, slots, rows = moe._compact(here, inv.reshape(T, top_k),
                                      jnp.int32(landed), m)
    slots, rows, order = (np.asarray(a) for a in (slots, rows, order))
    assert sorted(slots.reshape(-1)) == list(range(T * m))
    assert (rows[slots.reshape(-1)] == np.arange(T * m)).all()
    for t in range(T):
        mine = [t * top_k + k for k in range(top_k) if here[t, k]]
        assert list(order[slots[t, :len(mine)]]) == mine
        assert (slots[t, len(mine):] >= landed).all()
    assert 0 < landed < T * m


def gathered_route(x32, router_kernel, top_k, route_scale):
    """``moe.route`` as it was written with a gather of the chosen
    scores, whose transpose is a scatter-add."""
    scores = jax.nn.sigmoid(jnp.dot(x32, router_kernel, precision=moe.HI))
    _, chosen = jax.lax.top_k(scores, top_k)
    chosen = chosen.astype(jnp.int32)
    w = jnp.take_along_axis(scores, chosen, axis=1)
    return chosen, w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20) \
        * route_scale


@pytest.mark.parametrize("router_width,top_k", [(512, 22), (128, 8)])
def test_route_weights_match_the_gathered_scores(router_width, top_k):
    """The router weights picked by a select over the router's width are
    the gathered scores': the same experts, the weights and the gradients
    to the router and the tokens within 1e-6 (only the normalising sum's
    fusion may differ).  The gradients are probed with a seeded cotangent
    that differs from slot to slot: one nearly equal over a token's slots
    makes the normalisation's gradient a difference of near-equal terms,
    which turns that ulp into 1e-4 on either side alike."""
    def both(fn, a, k, probe):
        return fn(a, k, top_k, 2.5), jax.grad(lambda a, k: jnp.sum(
            probe * fn(a, k, top_k, 2.5)[1]), (0, 1))(a, k)

    @jax.jit
    def sides(*args):
        return both(moe.route, *args), both(gathered_route, *args)
    with jax.enable_x64(False):
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
        kernel = jax.random.normal(jax.random.PRNGKey(1), (8, router_width))
        probe = jax.random.normal(jax.random.PRNGKey(2), (16, top_k))
        ((chosen, w), got), ((want_chosen, want_w), want) = sides(
            x, kernel, probe)
    assert (np.asarray(chosen) == np.asarray(want_chosen)).all()
    assert close(w, want_w, 1e-6)
    assert_trees_close(got, want, 1e-6)


def scattered_order(chosen, lo, held):
    """(``inv``, ``sizes``) as ``routed_experts`` wrote them: the stable
    sort's inverse and the experts' counts by scatters."""
    local = chosen.reshape(-1) - lo
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))
    return inv, jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]


@pytest.mark.parametrize("lands,top_k,held", [
    ("none", 3, 2), ("some", 3, 2), ("all", 2, 4), ("every_held", 5, 2),
    ("some", 22, 8)])
def test_sizes_and_inverse_match_the_scatters(lands, top_k, held):
    """``sizes`` and the sort's inverse counted from a one-hot are the
    scatters' integers, bit for bit: where no slot lands (the held experts
    lie past the chosen ones), where some do, where every slot lands
    (``top_k < held``, each token's experts among the held) and where
    every token chose every held expert (``top_k >= held``)."""
    T, width = 16, 24
    with jax.enable_x64(False):
        ranked = jnp.argsort(jax.random.uniform(
            jax.random.PRNGKey(top_k), (T, width)), axis=1).astype(jnp.int32)
        if lands in ("all", "every_held"):
            # each token's first min(top_k, held) experts are held ones,
            # in a shuffled order
            first = jnp.argsort(ranked[:, :held], axis=1).astype(jnp.int32)
            rest = jnp.sort(ranked, axis=1)[:, held:]
            ranked = jnp.concatenate([first, rest], axis=1)
        chosen = ranked[:, :top_k]
        lo = width if lands == "none" else 0
        (here, order, inv, sizes), (want_inv, want_sizes) = jax.jit(
            lambda c: (moe._expert_order(c, lo, held),
                       scattered_order(c, lo, held)))(chosen)
    landed = int(jnp.sum(here))
    if lands == "some":
        assert 0 < landed < T * min(top_k, held)
    else:
        assert landed == {"none": 0, "all": T * top_k,
                          "every_held": T * held}[lands]
    assert (np.asarray(inv) == np.asarray(want_inv)).all()
    assert (np.asarray(sizes) == np.asarray(want_sizes)).all()
    assert (np.asarray(order)[np.asarray(inv)] == np.arange(T * top_k)).all()


def test_routing_compiles_without_scalar_gathers_or_scatters():
    """The gradient of a small expert layer under the decoder's
    ``--remat`` policy, compiled: no gather reads the [T, router_width]
    scores, and nothing scatters into them, into the ``held + 1`` bins of
    ``sizes`` or into the T x top_k places of the sort's inverse (the
    compact index's inverse, T x min(top_k, held), is a scatter still)."""
    T, d, router_width, held, top_k = 12, 4, 16, 2, 3
    layer = moe.ExpertLayer(router_width=router_width, held=held, lo=0,
                            top_k=top_k, width=8, n_shared=0, impl="ragged",
                            act="relu2")
    with jax.enable_x64(False):
        x = jax.ShapeDtypeStruct((1, T, d), jnp.float32)
        params = jax.eval_shape(lambda: layer.init(
            jax.random.PRNGKey(1), jnp.zeros(x.shape))["params"])
        loss = jax.checkpoint(
            lambda p, a: jnp.sum(layer.apply({"params": p}, a) ** 2),
            policy=decoder.REMAT_POLICY)
        text = jax.jit(jax.grad(loss, (0, 1))).lower(
            params, x).compile().as_text()

    # (result, first operand) shapes of each ``op``; the compiled text
    # names an operand without its shape, so it is looked up
    defined = dict(re.findall(r"(%[\w.-]+) = \w+\[([\d,]*)\]", text))

    def shapes(op):
        return [(out, defined.get(src)) for out, src in re.findall(
            rf"= \w+\[([\d,]*)\]\S* {op}\((%[\w.-]+)", text)]
    scores = f"{T},{router_width}"
    assert scores not in {src for _, src in shapes("gather")}
    into = {out for out, _ in shapes("scatter")}
    assert not into & {scores, f"{T * router_width}", f"{held + 1}",
                       f"{T * top_k}"}, into
    assert f"{T * min(top_k, held)}" in into    # the pattern finds scatters


def test_flops_and_bytes_of_the_cell():
    """The cell's counts from shapes: the chunked scans' forward products
    x 3 at chunk 128 and their least bytes, and the step's model FLOPs."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron3_super.json")) as f:
        sizes = json.load(f)
    per_chunk = 2 * 128 ** 2 * 128 + 2 * 16 * 128 ** 2 * 64 \
        + 4 * 16 * 128 * 64 * 128
    assert reference.ssd_flops(sizes, 1, 8192) == 3 * 5 * 64 * per_chunk
    per_token = 3 * (2 * 1024 + 4 * 16 + 4 * 128) + 4 * 1024
    assert reference.ssd_bytes(sizes, 1, 8192) == 5 * 8192 * per_token
    assert 2.0e13 < reference.train_flops(sizes, 1, 8192) < 2.2e13
