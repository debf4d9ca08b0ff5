"""A sparse expert layer that holds a share of its experts.

The layer is told which experts it holds, ``[lo, lo + held)`` of
``router_width``.  It routes every token over ALL ``router_width`` experts
(sigmoid scores in float32, the ``top_k`` largest after the balancing bias,
weights normalised over the chosen and scaled), and computes the part of
the result that its own experts give: the token-slots whose expert is held
are sorted by expert, each held expert runs as grouped matrix products over
the stacked weights (``ops/grouped_matmul.py``: work follows the slots that
landed here), and each token sums its weighted slots.  The shared expert is
added whole.

An expert's activation comes from the model's file (``ACTS``): ``silu`` is
a SwiGLU, ``down(silu(gate x) * up x)``; ``relu2`` a gateless pair,
``down(relu(up x)^2)``.  With ``latent`` > 0 the routed experts work in a
latent space: the tokens are projected down to it before the dispatch
(``fc1_latent_proj``) and each token's weighted sum back up after the
combine (``fc2_latent_proj``); the router and the shared expert read the
tokens at full width.

Static shapes and no capacity: a token's ``top_k`` experts are distinct,
so at most ``m = min(top_k, held)`` of its slots land here, and the buffers
hold ``T x m`` rows, enough for a step in which every token chose every
held expert.  No token is dropped.  Held slots sort first, so the rows cut
off the end of the expert order are absent slots.  Nothing else works at
``T x top_k`` rows either: a compact index [T, m], a permutation of the
``T x m`` rows (each token's landed slots' rows in slot order, then rows
of absent slots, which the grouped products return as zeros), stands for
the un-sort, so the combine, its transpose and the dispatch's transpose
each gather ``T x m`` rows, every row once.  What the experts held
elsewhere would add is left out: on one chip the layer runs without its
exchange, and nothing stands in for the absent chips (expert parallelism's
exchange is ROADMAP R3's remainder).

Device scopes (telemetry/spans.py): ``fdt/moe_route``, ``fdt/moe_dispatch``,
``fdt/moe_experts``, ``fdt/moe_combine``, and ``fdt/moe_latent`` round the
two latent projections.  Counters, sown into the collection
``spans.COUNTERS`` (the train step makes it mutable, nothing stores it;
the step's metrics carry them to the loop's read-back):
``moe_slots``, the token-slots that landed on held experts, and
``moe_load_max``, the fullest held expert's slots over the mean of the held
ones.

The balancing bias that the published routing adds to the scores before
the top-k (``expert_bias``) is the constant zero here: its source gives a
coefficient and no update rule, and a bias nothing updates is no state.
When a rule arrives the bias becomes a ``batch_stats`` leaf (ROADMAP R3).

The routing arithmetic is dense compare-and-reduce, not scalar gathers
and scatters whose indices are data (those run one entry at a time): the
chosen experts' weights are picked from the scores by a select over the
router's width (``route``; its transpose is a select too), and each held
expert's count and each slot's place in the expert order are sums over a
one-hot of the slots' experts (``_expert_order``).  The sort, the gathers
of rows and the compact index's inverse stay what they are.

Inside a block under ``--remat`` (models/decoder.py) the routing
decision's integers are kept by name (``ROUTING_RESIDUALS``: ``chosen``,
the rows' tokens, ``sizes``, the compact index and its inverse: 1.0 MB a
layer at 8,192 tokens and 8 experts a token, 1.5 MB at 22 a token over 8
held), so the backward's replay repeats neither the top-k, nor the sort,
nor the counts, nor the compact index; it repeats the router's scores and
the select that picks the chosen weights from them, the gathers of rows
and the grouped products.  Saved or recomputed they are the same integers.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from faster_distributed_training_tpu.ops.grouped_matmul import grouped_matmul
from faster_distributed_training_tpu.telemetry.spans import COUNTERS

HI = jax.lax.Precision.HIGHEST

# The routing decision's integers by checkpoint_name (the module docstring's
# last paragraph): ``chosen`` [T, k], ``source`` [T * m] (the token of each
# expert-order row), ``sizes`` [held], the compact index ``compact``
# [T, m] and its inverse ``compact_inv`` [T * m] (``_compact``), with
# m = min(k, held).  Without a policy that saves them the names are the
# identity.
CHOSEN, SOURCE, SIZES, COMPACT, COMPACT_INV = ROUTING_RESIDUALS = (
    "moe_chosen", "moe_source", "moe_sizes", "moe_compact",
    "moe_compact_inv")


def _expert_order(chosen, lo: int, held: int):
    """The token-slots of ``chosen`` [T, top_k] in expert order: (``here``
    [T * top_k], the slots whose expert is held, ``lo .. lo + held``;
    ``order``, the slots stably sorted by held expert, absent slots last;
    ``inv``, each slot's place in ``order``; ``sizes`` [held], the slots
    each held expert received).  ``sizes`` and ``inv`` are counted from a
    one-hot of the slots' bins [held + 1, T * top_k]: a bin's count is its
    row's sum, and a slot's place is its bin's start plus the slots of its
    bin before it, a running sum down the row, which is the stable sort's
    inverse; a scatter of ones into ``held + 1`` bins, or of T x top_k
    places, would run one entry at a time."""
    local = chosen.reshape(-1) - lo
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held)          # absent experts last
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    hot = jnp.arange(held + 1, dtype=jnp.int32)[:, None] == key
    counts = jnp.sum(hot, axis=1, dtype=jnp.int32)
    start = jnp.cumsum(counts, dtype=jnp.int32) - counts
    place = start[:, None] + jnp.cumsum(hot, axis=1, dtype=jnp.int32) - 1
    inv = jnp.sum(jnp.where(hot, place, 0), axis=0)
    return here, order, inv, counts[:held]


def _compact(here, inv, landed, m: int):
    """(``pick`` [T, m, top_k], entry (t, j, k): slot k is token t's j-th
    landed one; the compact index ``slots`` [T, m]; its inverse ``rows``
    [T * m]).  ``slots`` is a permutation of the expert order's ``T * m``
    rows: entry (t, j) is the row of token t's j-th landed slot and, past
    its landed ones, one of the rows past ``landed`` (absent slots', which
    the grouped products leave zero), each once, so that every gather
    through it reads each row once.  ``here`` [T, top_k] marks the landed
    slots, ``inv`` [T, top_k] each slot's row in the expert order."""
    T = here.shape[0]
    n = T * m
    rank = jnp.cumsum(here, axis=1, dtype=jnp.int32) - 1
    count = rank[:, -1:] + 1
    pick = here[:, None] & (rank[:, None] == jnp.arange(m)[:, None])
    free = m - count
    first = landed + jnp.cumsum(free, axis=0) - free - count
    j = jnp.arange(m, dtype=jnp.int32)
    slots = jnp.where(j < count, jnp.sum(jnp.where(pick, inv[:, None], 0),
                                         axis=2), first + j)
    rows = jnp.zeros((n,), jnp.int32).at[slots.reshape(-1)].set(
        jnp.arange(n, dtype=jnp.int32))
    return (pick, checkpoint_name(slots, COMPACT),
            checkpoint_name(rows, COMPACT_INV))


@jax.custom_vjp
def _dispatch(x, source, slots):
    """Row ``r`` of the result is token ``source[r]``: the tokens' rows
    laid out slot by slot in expert order.  Transposed, a token sums the
    rows of its entries of ``slots`` [T, m] (zero past its landed slots),
    a gather where autodiff would scatter-add."""
    return x[source]


def _dispatch_fwd(x, source, slots):
    return x[source], slots


def _dispatch_bwd(slots, g):
    d = g[slots.reshape(-1)].reshape(*slots.shape, g.shape[-1])
    return jnp.sum(d.astype(jnp.float32), axis=1).astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def _weighted_sum(y, w):
    return jnp.sum(y.astype(jnp.float32) * w[..., None], axis=1)


@jax.custom_vjp
def _combine(ys, w, slots, rows):
    """Token t's float32 sum of ``w[t, j] * ys[slots[t, j]]`` (``w``
    [T, m] is zero past its landed slots).  Transposed, the weighted
    cotangents are formed in token order at [T, m] in ``ys``' dtype and
    row r takes its entry's (one gather through ``rows``); a weight's
    cotangent is its row's dot with its token's cotangent: no
    ``T x top_k`` rows."""
    return _weighted_sum(ys[slots], w)


def _combine_fwd(ys, w, slots, rows):
    y = ys[slots]
    return _weighted_sum(y, w), (y, w, rows)


def _combine_bwd(res, g):
    y, w, rows = res
    d = (g[:, None] * w[..., None]).astype(y.dtype).reshape(-1, y.shape[-1])
    d_w = jnp.sum(y.astype(jnp.float32) * g[:, None], axis=2)
    return d[rows], d_w, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def route(x32, router_kernel, top_k: int, route_scale: float,
          route_norm: bool = True):
    """(chosen experts [T, k] int32, their weights [T, k] float32) from
    float32 tokens [T, d]: sigmoid scores over the router's whole width,
    the ``top_k`` largest (the balancing bias is zero), weights from the
    scores.  A chosen expert's score is picked by a select over the
    router's width and a sum, in which one entry matches: the same float,
    and its transpose is a select too, where a gather of T x k scalars and
    its scatter-add would run one entry at a time."""
    scores = jax.nn.sigmoid(jnp.dot(x32, router_kernel.astype(jnp.float32),
                                    precision=HI))
    _, chosen = jax.lax.top_k(scores, top_k)
    chosen = checkpoint_name(chosen.astype(jnp.int32), CHOSEN)
    experts = jnp.arange(scores.shape[1], dtype=jnp.int32)
    w = jnp.sum(jnp.where(chosen[:, :, None] == experts, scores[:, None], 0.0),
                axis=2)
    if route_norm:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return chosen, w * route_scale


def relu2(h):
    return jnp.square(jax.nn.relu(h))


# an expert's activation, as the model's file names it (``mlp_hidden_act``,
# ``hidden_act``): whether it has a gate, and what is applied
ACTS = {"silu": jax.nn.silu, "relu2": relu2}


def ffn(x, gate, up, down, mm, act: str = "silu"):
    """``down(silu(gate x) * up x)`` where ``act`` is ``silu`` (a SwiGLU),
    ``down(relu(up x)^2)`` where it is ``relu2`` (no gate), each product
    by ``mm``."""
    if act == "silu":
        return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)
    if act not in ACTS:
        raise ValueError(f"expert activation {act!r}; have {sorted(ACTS)}")
    return mm(ACTS[act](mm(x, up)), down)


def routed_experts(x, chosen, weights, gate, up, down, lo: int,
                   impl: Optional[str] = None, act: str = "silu"):
    """The held experts' part of the layer's result for tokens ``x``
    [T, d], in ``x``'s dtype: experts ``lo .. lo + up.shape[0]`` of the
    router's numbering are held, as stacked weights ``gate``/``up``
    [held, d, f] (no ``gate`` under a gateless ``act``) and ``down``
    [held, f, d].  Also returns the slots a held expert received, [held]
    int32.  The expert-order buffers hold ``T x min(top_k, held)`` rows:
    a token's experts are distinct, so no more of its slots land here, and
    the combine and both transposes gather as many through the compact
    index (the module docstring).  The counts and the sort's inverse come
    from ``_expert_order``'s one-hot, not from scatters."""
    T = x.shape[0]
    top_k = chosen.shape[1]
    held = up.shape[0]
    m = min(top_k, held)
    with jax.named_scope("fdt/moe_dispatch"):
        here, order, inv, sizes = _expert_order(chosen, lo, held)
        # the rows past T x m are absent slots
        source = checkpoint_name(order[:T * m] // top_k, SOURCE)
        sizes = checkpoint_name(sizes, SIZES)
        pick, slots, rows = _compact(here.reshape(T, top_k),
                                     inv.reshape(T, top_k), jnp.sum(sizes), m)
        xs = _dispatch(x, source, slots)
    with jax.named_scope("fdt/moe_experts"):
        ys = ffn(xs, gate, up, down,
                 lambda a, w: grouped_matmul(a, w, sizes, impl), act)
    with jax.named_scope("fdt/moe_combine"):
        # token t's j-th landed slot's weight: one slot of top_k matches
        w = jnp.sum(jnp.where(pick, weights[:, None], 0.0), axis=2)
        out = _combine(ys, w, slots, rows)
    return out.astype(x.dtype), sizes


class MLP(nn.Module):
    """``ffn`` over dense weights, no bias: a SwiGLU under ``act`` silu, a
    gateless pair under relu2."""
    width: int
    dtype: Any = jnp.float32
    act: str = "silu"

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        init = nn.initializers.lecun_normal()
        gate = (self.param("gate_proj", init, (d, self.width), jnp.float32)
                if self.act == "silu" else None)
        up = self.param("up_proj", init, (d, self.width), jnp.float32)
        down = self.param("down_proj", init, (self.width, d), jnp.float32)
        return ffn(x, gate, up, down,
                   lambda a, w: jnp.dot(a, w.astype(self.dtype)), self.act)


class ExpertLayer(nn.Module):
    """Shared(u) + the held experts' part of sum_{e in top-k} w_e Expert_e(u)."""
    router_width: int            # experts the router scores (all chips')
    held: int                    # experts held here
    lo: int                      # the first held expert's number
    top_k: int
    width: int                   # an expert's (and the shared one's) width
    n_shared: int = 1
    route_scale: float = 1.0
    route_norm: bool = True
    dtype: Any = jnp.float32
    impl: Optional[str] = None   # ops/grouped_matmul.py's choice when None
    act: str = "silu"            # a key of ACTS
    latent: int = 0              # the routed experts' latent width; 0: none

    @nn.compact
    def __call__(self, x):
        if not 0 <= self.lo <= self.router_width - self.held:
            raise ValueError(f"held experts [{self.lo}, "
                             f"{self.lo + self.held}) lie outside the "
                             f"router's {self.router_width}")
        B, L, d = x.shape
        flat = x.reshape(B * L, d)
        init = nn.initializers.lecun_normal()
        router = self.param("router", init, (d, self.router_width),
                            jnp.float32)
        stacked = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                               batch_axis=(0,))
        e = self.latent or d
        gate = None
        if self.act == "silu":
            gate = self.param("experts_gate_proj", stacked,
                              (self.held, e, self.width), jnp.float32)
        up = self.param("experts_up_proj", stacked,
                        (self.held, e, self.width), jnp.float32)
        down = self.param("experts_down_proj", stacked,
                          (self.held, self.width, e), jnp.float32)
        with jax.named_scope("fdt/moe_route"):
            chosen, weights = route(flat.astype(jnp.float32), router,
                                    self.top_k, self.route_scale,
                                    self.route_norm)
        cast = lambda w: None if w is None else w.astype(  # noqa: E731
            self.dtype)
        tokens = flat
        if self.latent:
            to_latent = self.param("fc1_latent_proj", init, (d, e),
                                   jnp.float32)
            from_latent = self.param("fc2_latent_proj", init, (e, d),
                                     jnp.float32)
            with jax.named_scope("fdt/moe_latent"):
                tokens = jnp.dot(flat, cast(to_latent))
        routed, sizes = routed_experts(tokens, chosen, weights, cast(gate),
                                       cast(up), cast(down), self.lo,
                                       self.impl, self.act)
        if self.latent:
            with jax.named_scope("fdt/moe_latent"):
                routed = jnp.dot(routed, cast(from_latent))
        if self.is_mutable_collection(COUNTERS) \
                and not self.is_initializing():
            landed = jnp.sum(sizes).astype(jnp.float32)
            self.sow(COUNTERS, "moe_slots", landed,
                     reduce_fn=lambda _, new: new)
            self.sow(COUNTERS, "moe_load_max",
                     jnp.max(sizes).astype(jnp.float32) * self.held
                     / jnp.maximum(landed, 1.0),
                     reduce_fn=lambda _, new: new)
        out = routed.reshape(B, L, d)
        if self.n_shared:
            out = out + MLP(self.width * self.n_shared, self.dtype, self.act,
                            name="shared")(x)
        return out

