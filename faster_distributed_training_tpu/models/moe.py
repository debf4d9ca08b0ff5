"""A sparse expert layer that holds a share of its experts.

The layer is told which experts it holds, ``[lo, lo + held)`` of
``router_width``.  It routes every token over ALL ``router_width`` experts
(sigmoid scores in float32, the ``top_k`` largest after the balancing bias,
weights normalised over the chosen and scaled), and computes the part of
the result that its own experts give: the token-slots whose expert is held
are sorted by expert, the SwiGLU of each held expert runs as grouped matrix
products over the stacked weights (``ops/grouped_matmul.py``: work follows
the slots that landed here), and each token sums its weighted slots.  The
shared expert is added whole.

Static shapes and no capacity: the buffers hold all ``T x top_k``
token-slots, so a step in which every slot lands on a held expert is still
exact.  No token is dropped.  What the experts held elsewhere would add is
left out: on one chip the layer runs without its exchange, and nothing
stands in for the absent chips (expert parallelism's exchange is ROADMAP
R3's remainder).

Device scopes (telemetry/spans.py): ``fdt/moe_route``, ``fdt/moe_dispatch``,
``fdt/moe_experts``, ``fdt/moe_combine``.  Counters, sown into the
collection ``spans.COUNTERS`` (the train step makes it mutable, nothing
stores it; the step's metrics carry them to the loop's read-back):
``moe_slots``, the token-slots that landed on held experts, and
``moe_load_max``, the fullest held expert's slots over the mean of the held
ones.

The balancing bias that the published routing adds to the scores before
the top-k (``expert_bias``) is the constant zero here: its source gives a
coefficient and no update rule, and a bias nothing updates is no state.
When a rule arrives the bias becomes a ``batch_stats`` leaf (ROADMAP R3).

Inside a block under ``--remat`` (models/decoder.py) the routing
decision's integers are kept by name (``ROUTING_RESIDUALS``: ``chosen``,
``order``, ``inv``, ``sizes``; 0.8 MB a layer at 8,192 tokens and 8 experts
a token), so the backward's replay repeats neither the top-k, nor the
sort, nor the scatters; the router's scores, the gathers and the grouped
products are replayed.  Saved or recomputed they are the same integers.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from faster_distributed_training_tpu.ops.grouped_matmul import grouped_matmul
from faster_distributed_training_tpu.telemetry.spans import COUNTERS

HI = jax.lax.Precision.HIGHEST

# The routing decision's integers by checkpoint_name (the module docstring's
# last paragraph): ``chosen`` [T, k], ``order`` and ``inv`` [T * k],
# ``sizes`` [held].  Without a policy that saves them the names are the
# identity.
CHOSEN, ORDER, INV, SIZES = ROUTING_RESIDUALS = (
    "moe_chosen", "moe_order", "moe_inv", "moe_sizes")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, order, inv, top_k):
    """Row ``r`` of the result is token ``order[r] // top_k``: the tokens'
    rows laid out slot by slot in expert order.  Its transpose is a gather
    too (by the inverse permutation, then a sum over a token's slots), where
    autodiff would scatter-add ``T x top_k`` rows."""
    return x[order // top_k]


def _dispatch_fwd(x, order, inv, top_k):
    return x[order // top_k], (order, inv)


def _dispatch_bwd(top_k, res, g):
    order, inv = res
    d = g[inv].reshape(-1, top_k, g.shape[-1])
    return jnp.sum(d.astype(jnp.float32), axis=1).astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _unsort(y, order, inv):
    """Expert order back to token order: ``y[inv]``; transposed,
    ``g[order]``."""
    return y[inv]


def _unsort_fwd(y, order, inv):
    return y[inv], (order, inv)


def _unsort_bwd(res, g):
    order, inv = res
    return g[order], None, None


_unsort.defvjp(_unsort_fwd, _unsort_bwd)


def route(x32, router_kernel, top_k: int, route_scale: float,
          route_norm: bool = True):
    """(chosen experts [T, k] int32, their weights [T, k] float32) from
    float32 tokens [T, d]: sigmoid scores over the router's whole width,
    the ``top_k`` largest (the balancing bias is zero), weights from the
    scores."""
    scores = jax.nn.sigmoid(jnp.dot(x32, router_kernel.astype(jnp.float32),
                                    precision=HI))
    _, chosen = jax.lax.top_k(scores, top_k)
    chosen = checkpoint_name(chosen.astype(jnp.int32), CHOSEN)
    w = jnp.take_along_axis(scores, chosen, axis=1)
    if route_norm:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return chosen, w * route_scale


def routed_experts(x, chosen, weights, gate, up, down, lo: int,
                   impl: Optional[str] = None):
    """The held experts' part of the layer's result for tokens ``x``
    [T, d], in ``x``'s dtype: experts ``lo .. lo + gate.shape[0]`` of the
    router's numbering are held, as stacked SwiGLU weights ``gate``/``up``
    [held, d, f] and ``down`` [held, f, d].  Also returns the slots a held
    expert received, [held] int32."""
    T, d = x.shape
    top_k = chosen.shape[1]
    held = gate.shape[0]
    with jax.named_scope("fdt/moe_dispatch"):
        local = chosen.reshape(-1) - lo
        here = (local >= 0) & (local < held)
        key = jnp.where(here, local, held)          # absent experts last
        order = checkpoint_name(
            jnp.argsort(key, stable=True).astype(jnp.int32), ORDER)
        inv = checkpoint_name(
            jnp.zeros_like(order).at[order].set(
                jnp.arange(order.shape[0], dtype=jnp.int32)), INV)
        sizes = checkpoint_name(
            jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held], SIZES)
        xs = _dispatch(x, order, inv, top_k)
    with jax.named_scope("fdt/moe_experts"):
        h = (jax.nn.silu(grouped_matmul(xs, gate, sizes, impl))
             * grouped_matmul(xs, up, sizes, impl))
        ys = grouped_matmul(h, down, sizes, impl)
    with jax.named_scope("fdt/moe_combine"):
        y = _unsort(ys, order, inv).reshape(T, top_k, d)
        w = jnp.where(here.reshape(T, top_k), weights, 0.0)
        out = jnp.sum(y.astype(jnp.float32) * w[..., None], axis=1)
    return out.astype(x.dtype), sizes


class SwiGLU(nn.Module):
    """down(silu(gate(u)) * up(u)), no bias."""
    width: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        init = nn.initializers.lecun_normal()
        gate = self.param("gate_proj", init, (d, self.width), jnp.float32)
        up = self.param("up_proj", init, (d, self.width), jnp.float32)
        down = self.param("down_proj", init, (self.width, d), jnp.float32)
        c = lambda w: w.astype(self.dtype)                  # noqa: E731
        return jnp.dot(jax.nn.silu(jnp.dot(x, c(gate))) * jnp.dot(x, c(up)),
                       c(down))


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
        gate = self.param("experts_gate_proj", stacked,
                          (self.held, d, self.width), jnp.float32)
        up = self.param("experts_up_proj", stacked,
                        (self.held, d, self.width), jnp.float32)
        down = self.param("experts_down_proj", stacked,
                          (self.held, self.width, d), jnp.float32)
        with jax.named_scope("fdt/moe_route"):
            chosen, weights = route(flat.astype(jnp.float32), router,
                                    self.top_k, self.route_scale,
                                    self.route_norm)
        cast = lambda w: w.astype(self.dtype)               # noqa: E731
        routed, sizes = routed_experts(flat, chosen, weights, cast(gate),
                                       cast(up), cast(down), self.lo,
                                       self.impl)
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
            out = out + SwiGLU(self.width * self.n_shared, self.dtype,
                               name="shared")(x)
        return out

