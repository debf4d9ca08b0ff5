"""Plain reference of the ``trinity_mini`` configuration: one chip's share of
Trinity-Mini's decoder (arcee-ai/Trinity-Mini ``config.json``, model type
``afmoe``) as a next-token language model.  Straightforward ``jax.numpy`` in
float32 with every product at ``highest``; imports nothing of the program.
Every size is read from the configuration's file under the published key
names; what no key states is listed in the file under ``assumed``.

    h0 = Embed[ids] * sqrt(hidden)                         (mup_enabled)
    a  = x + RMS_post_attn(Attn(RMS_in(x)))
    y  = a + RMS_post_mlp(MLP(RMS_pre_mlp(a)))             each layer
    logits = RMS_final(y_last) Head                        (untied)

``Attn(u)``: q = u Wq (H x D), k, v = u Wk, u Wv (Hkv x D), g = u Wg (H x D);
per-head RMS norm of q and k; on ``sliding_attention`` layers rotary
positions (rotate-half over the whole head) and the window ``i - j <
sliding_window``; every layer causal (``j <= i``); softmax in float32; a
key-value head serves H / Hkv query heads; out = (softmax(s) v * sigmoid(g))
Wo.  ``MLP``: SwiGLU of ``intermediate_size`` on the ``num_dense_layers``
leading layers; after them the expert layer: p = sigmoid(u Wr) over the
router's whole width (``published.num_experts``), S = top-k of p + bias
(the bias is zero: nothing updates it), w_e = route_scale * p_e / (sum_S p + 1e-20), and

    MoE(u) = Shared(u) + sum over e in S that are HELD of w_e Expert_e(u)

with experts ``0 .. num_experts`` of the router's numbering held (the first
share of the file's deployment, which is the one the program holds).
What the absent experts would add is left out, as in the program, and the
partial result goes on to the next layer.  A held expert is computed here
as a dense product over every token under a mask of weights.

Memory: each layer runs under ``jax.checkpoint``; attention takes a block
of query rows at a time against all keys under the band's mask; the loss
takes the logits in row blocks.  The reference then fits beside the 16
bytes a parameter ``benchmark/reference/steps.py`` holds.

``low`` is the control: the operands of every matrix product rounded to the
nearest precision below the one ``training.precision`` states (bfloat16
under ``fp32``; float8 e4m3 with one scale a tensor under ``bf16``).
``fault="half_batch"`` is the planted fault: with one row a step there is
no half of a batch to leave out, so it leaves out the second half of every
row's TARGETS (the loss is the mean over the first half's).  No
normalisation keeps running statistics: ``loss_fn`` returns an empty tree.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
STATS_START = {}
SLIDING = "sliding_attention"
Q_ROWS = 512            # query rows of attention taken at a time
LOGIT_ROWS = 1024       # rows of logits taken at a time


# -- sizes -------------------------------------------------------------------

def router_width(sizes: dict) -> int:
    return int(sizes.get("published", {}).get("num_experts",
                                              sizes["num_experts"]))


def layout(sizes: dict) -> dict:
    """{path: (shape, fan-in)} in the names the program's tree uses; a
    fan-in of 0 marks a norm's scale."""
    d, ff = sizes["hidden_size"], sizes["intermediate_size"]
    f = sizes["moe_intermediate_size"]
    h, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd, held = sizes["head_dim"], sizes["num_experts"]
    out = {("embed_tokens",): ((sizes["vocab_size"], d), d),
           ("final_norm", "scale"): ((d,), 0),
           ("lm_head",): ((d, sizes["vocab_size"]), d)}
    for i in range(sizes["num_hidden_layers"]):
        layer = (f"layer_{i}",)
        for name in ("input_norm", "post_attn_norm", "pre_mlp_norm",
                     "post_mlp_norm"):
            out[layer + (name, "scale")] = ((d,), 0)
        attn = layer + ("attn",)
        out[attn + ("q_proj",)] = ((d, h * hd), d)
        out[attn + ("k_proj",)] = ((d, hkv * hd), d)
        out[attn + ("v_proj",)] = ((d, hkv * hd), d)
        out[attn + ("gate_proj",)] = ((d, h * hd), d)
        out[attn + ("o_proj",)] = ((h * hd, d), h * hd)
        out[attn + ("q_norm", "scale")] = ((hd,), 0)
        out[attn + ("k_norm", "scale")] = ((hd,), 0)
        if i < sizes["num_dense_layers"]:
            mlp = layer + ("mlp",)
            out[mlp + ("gate_proj",)] = ((d, ff), d)
            out[mlp + ("up_proj",)] = ((d, ff), d)
            out[mlp + ("down_proj",)] = ((ff, d), ff)
            continue
        moe = layer + ("moe",)
        out[moe + ("router",)] = ((d, router_width(sizes)), d)
        out[moe + ("experts_gate_proj",)] = ((held, d, f), d)
        out[moe + ("experts_up_proj",)] = ((held, d, f), d)
        out[moe + ("experts_down_proj",)] = ((held, f, d), f)
        fs = f * sizes.get("num_shared_experts", 0)
        if fs:
            out[moe + ("shared", "gate_proj")] = ((d, fs), d)
            out[moe + ("shared", "up_proj")] = ((d, fs), d)
            out[moe + ("shared", "down_proj")] = ((fs, d), fs)
    return out


def init_params(sizes: dict, seed):
    """Uniform in +-sqrt(3 / fan-in) for the table, the head and every
    kernel (so the embedding times sqrt(hidden) has unit scale); a norm's
    scale uniform in [0.9, 1.1] (no leaf starts at a constant, so every
    leaf's gradient says something)."""
    tree: dict = {}
    key = jax.random.PRNGKey(seed)
    for i, (path, (shape, fan)) in enumerate(sorted(layout(sizes).items())):
        k = jax.random.fold_in(key, i)
        if fan == 0:
            leaf = jax.random.uniform(k, shape, jnp.float32, 0.9, 1.1)
        else:
            bound = math.sqrt(3.0 / fan)
            leaf = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = leaf
    return tree


# -- the control's rounding --------------------------------------------------

def fp8(x):
    """Round to float8 e4m3 with one scale per tensor; the gradient passes
    straight through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + lax.stop_gradient(q - x)


def bf16(x):
    q = x.astype(jnp.bfloat16).astype(jnp.float32)
    return x + lax.stop_gradient(q - x)


# training.precision -> how the control rounds a product's operands
LOWER = {"fp32": bf16, "bf16": fp8}


def product(spec, a, b, lower=None):
    if lower is not None:
        a, b = lower(a), lower(b)
    return jnp.einsum(spec, a, b, precision=HI,
                      preferred_element_type=jnp.float32)


# -- layers ------------------------------------------------------------------

def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * scale


def rotary(x, theta):
    """x [L, heads, D]: rotate-half over the whole head, position = row."""
    length, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[:, None]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def swiglu(p, u, mm):
    return mm("tf,fd->td",
              jax.nn.silu(mm("td,df->tf", u, p["gate_proj"]))
              * mm("td,df->tf", u, p["up_proj"]), p["down_proj"])


def attention(p, u, sizes, kind, mm):
    """u [L, d] -> [L, d]."""
    length = u.shape[0]
    h, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd, eps = sizes["head_dim"], sizes["rms_norm_eps"]
    q = mm("td,de->te", u, p["q_proj"]).reshape(length, h, hd)
    k = mm("td,de->te", u, p["k_proj"]).reshape(length, hkv, hd)
    v = mm("td,de->te", u, p["v_proj"]).reshape(length, hkv, hd)
    gate = mm("td,de->te", u, p["gate_proj"])
    q = rms_norm(q, p["q_norm"]["scale"], eps)
    k = rms_norm(k, p["k_norm"]["scale"], eps)
    window = None
    if kind == SLIDING:
        q = rotary(q, float(sizes["rope_theta"]))
        k = rotary(k, float(sizes["rope_theta"]))
        window = int(sizes["sliding_window"])
    rows = min(Q_ROWS, length)
    if length % rows:
        raise ValueError(f"rows of {length} ids are no multiple of {rows}")
    # [blocks, rows, Hkv, group, D]: each key-value head's group of queries
    qb = q.reshape(length // rows, rows, hkv, h // hkv, hd)
    cols = jnp.arange(length)

    @jax.checkpoint
    def block(args):
        q_blk, first = args
        s = mm("rkgd,ckd->kgrc", q_blk, k) / math.sqrt(hd)
        at = first + jnp.arange(rows)
        keep = cols[None, :] <= at[:, None]
        if window is not None:
            keep &= at[:, None] - cols[None, :] < window
        s = jnp.where(keep[None, None], s, -jnp.inf)
        return mm("kgrc,ckd->rkgd", jax.nn.softmax(s, axis=-1), v)

    ctx = lax.map(block, (qb, jnp.arange(0, length, rows)))
    ctx = ctx.reshape(length, h * hd) * jax.nn.sigmoid(gate)
    return mm("te,ed->td", ctx, p["o_proj"])


def expert_layer(p, u, sizes, mm):
    """Shared(u) + the held experts' part of the routed sum; u [T, d]."""
    top_k, held = sizes["num_experts_per_tok"], sizes["num_experts"]
    lo = 0                 # share 0 of the deployment: experts 0 .. held
    scores = jax.nn.sigmoid(mm("td,de->te", u, p["router"]))
    bias = jnp.zeros((scores.shape[1],), jnp.float32)      # expert_bias
    _, chosen = lax.top_k(scores + bias, top_k)
    w = jnp.take_along_axis(scores, chosen, axis=1)
    if sizes.get("route_norm", True):
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    w = w * float(sizes.get("route_scale", 1.0))
    # [T, held]: a held expert's weight for each token, 0 where not chosen
    mine = jnp.sum(jnp.where(
        chosen[:, :, None] == lo + jnp.arange(held)[None, None], w[:, :, None],
        0.0), axis=1)

    @jax.checkpoint
    def one(acc, e):
        gate, up, down, w_e = e
        y = swiglu({"gate_proj": gate, "up_proj": up, "down_proj": down},
                   u, mm)
        return acc + y * w_e[:, None], None

    out, _ = lax.scan(one, jnp.zeros_like(u),
                      (p["experts_gate_proj"], p["experts_up_proj"],
                       p["experts_down_proj"], mine.T))
    if "shared" in p:
        out = out + swiglu(p["shared"], u, mm)
    return out


def layer(p, x, sizes, kind, mm):
    eps = sizes["rms_norm_eps"]
    a = attention(p["attn"], rms_norm(x, p["input_norm"]["scale"], eps),
                  sizes, kind, mm)
    x = x + rms_norm(a, p["post_attn_norm"]["scale"], eps)
    u = rms_norm(x, p["pre_mlp_norm"]["scale"], eps)
    m = swiglu(p["mlp"], u, mm) if "mlp" in p \
        else expert_layer(p["moe"], u, sizes, mm)
    return x + rms_norm(m, p["post_mlp_norm"]["scale"], eps)


def hidden(params, row, sizes, mm):
    """The last layer's output after the final norm, for one row of ids."""
    x = params["embed_tokens"][row]
    if sizes.get("mup_enabled"):
        x = x * math.sqrt(sizes["hidden_size"])
    for i, kind in enumerate(sizes["layer_types"]):
        x = jax.checkpoint(functools.partial(
            layer, sizes=sizes, kind=kind, mm=mm))(params[f"layer_{i}"], x)
    return rms_norm(x, params["final_norm"]["scale"], sizes["rms_norm_eps"])


def row_loss(params, row, n_targets, sizes, mm):
    """Sum of the next-token cross-entropies of a row's first
    ``n_targets`` targets: position t predicts token t + 1."""
    x = hidden(params, row, sizes, mm)
    length = row.shape[0]
    rows = min(LOGIT_ROWS, length)
    if length % rows:
        raise ValueError(f"rows of {length} ids are no multiple of {rows}")
    targets = jnp.concatenate([row[1:], row[:1]])
    counts = jnp.arange(length) < n_targets

    @jax.checkpoint
    def block(args):
        x_blk, t_blk, c_blk = args
        logp = jax.nn.log_softmax(
            mm("td,dv->tv", x_blk, params["lm_head"]), axis=-1)
        picked = jnp.take_along_axis(logp, t_blk[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(c_blk, picked, 0.0))

    shape = (length // rows, rows)
    return jnp.sum(lax.map(block, (x.reshape(shape + x.shape[1:]),
                                   targets.reshape(shape),
                                   counts.reshape(shape))))


def loss_fn(params, batch, sizes: dict, training: dict, seed, step,
            low: bool = False, fault: str = ""):
    """(mean next-token cross-entropy, {}).  Packed rows carry no padding,
    so every target counts; ``half_batch`` counts only the first half of
    each row's."""
    tokens = batch["tokens"]
    length = tokens.shape[1]
    n_targets = (length - 1) // 2 if fault == "half_batch" else length - 1
    lower = LOWER[training["precision"]] if low else None
    mm = functools.partial(product, lower=lower)
    total = sum(row_loss(params, tokens[b], n_targets, sizes, mm)
                for b in range(tokens.shape[0]))
    return total / (tokens.shape[0] * n_targets), {}


# -- operations from shapes --------------------------------------------------

def band_pairs(seq_len: int, window) -> int:
    """(query, key) pairs of the causal band of one row."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def attention_flops(sizes: dict, batch: int, seq_len: int) -> int:
    """Model FLOPs of one training step inside attention proper (scores,
    softmax's two products, values; not the projections): the band's
    pairs, forward x 3."""
    pairs = sum(band_pairs(seq_len, sizes["sliding_window"]
                           if kind == SLIDING else None)
                for kind in sizes["layer_types"])
    return 3 * batch * pairs * 4 * sizes["num_attention_heads"] \
        * sizes["head_dim"]


def moe_layers(sizes: dict) -> int:
    return sizes["num_hidden_layers"] - sizes["num_dense_layers"]


def expert_slot_flops(sizes: dict) -> int:
    """Training FLOPs of ONE token-slot through one held expert: three
    products of hidden x expert width, forward x 3."""
    return 3 * 2 * 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def train_flops(sizes: dict, batch: int, seq_len: int) -> int:
    """This chip's model FLOPs of one training step: the matrix products
    of every layer held and of the head over the vocabulary's slice, the
    band's pairs (not the square), the routed experts at even load
    (tokens x experts a token x held / router width slots a layer);
    forward x 3, a multiply-add counted as two, recomputation not
    counted."""
    d, ff = sizes["hidden_size"], sizes["intermediate_size"]
    f = sizes["moe_intermediate_size"]
    h, hkv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                  sizes["head_dim"])
    tokens = batch * seq_len
    per_token = sizes["num_hidden_layers"] * 2 * d * hd * (3 * h + 2 * hkv)
    per_token += sizes["num_dense_layers"] * 6 * d * ff
    per_token += moe_layers(sizes) * (
        2 * d * router_width(sizes)
        + 6 * d * f * sizes.get("num_shared_experts", 0))
    per_token += 2 * d * sizes["vocab_size"]
    slots = tokens * sizes["num_experts_per_tok"] * sizes["num_experts"] \
        / router_width(sizes)
    return int(3 * tokens * per_token
               + attention_flops(sizes, batch, seq_len)
               + moe_layers(sizes) * slots * expert_slot_flops(sizes))
