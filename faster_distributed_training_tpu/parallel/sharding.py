"""Partition-spec builders: DP batch sharding, FSDP/ZeRO-3 param sharding, TP rules.

The reference's three data-parallel strategies (DataParallel
resnet50_test.py:466; DDP :716; FSDP+CPUOffload transformer_test.py:387-392)
all collapse to sharding choices on one mesh:

  DP    — batch sharded over ("dp","fsdp"), params replicated.
  FSDP  — batch sharded AND every large param sharded on its largest
          divisible axis over "fsdp" (ZeRO-3); XLA compiles the gradient
          psum into reduce_scatter + all_gather automatically.
  TP    — regex rules mapping transformer param names to head/hidden axes.

Host offload (CPUOffload(offload_params=True), transformer_test.py:46-48)
maps to `memory_kind="pinned_host"` shardings with explicit device_put.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def batch_spec(mesh: Mesh, *extra_axes: Optional[str]) -> P:
    """PartitionSpec for a [batch, ...] array: batch over every data-ish mesh axis."""
    data_axes = mesh_data_axes(mesh)
    if not data_axes:
        data_axes = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names)[:1]
    lead = data_axes if len(data_axes) != 1 else data_axes[0]
    return P(lead, *extra_axes)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def mesh_data_axes(mesh: Optional[Mesh]) -> tuple:
    """The mesh's data axes with size > 1 (batch-sharding candidates)."""
    if mesh is None:
        return ()
    return tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names
                 and mesh.shape[a] > 1)


def shard_activation(x, mesh: Optional[Mesh], dims: Sequence) -> Any:
    """`with_sharding_constraint(x, P(*dims))`, defensively filtered.

    `dims` has one entry per array dim: None, an axis name, or a tuple
    of axis names.  Axes the mesh doesn't have (or has at size 1) are
    dropped, as is any dim annotation whose axis sizes don't divide the
    dim — so the SAME model code is a no-op on a 1D dp mesh and a real
    constraint on a (data, model) mesh (SNIPPETS [3]'s `with_sharding`
    pattern).  Semantically always the identity: it only constrains
    XLA's partitioner, never the values."""
    if mesh is None:
        return x
    spec, any_axis = [], False
    for i, d in enumerate(dims):
        if d is None:
            spec.append(None)
            continue
        names = (d,) if isinstance(d, str) else tuple(d)
        names = tuple(a for a in names if a in mesh.axis_names
                      and mesh.shape[a] > 1)
        total = int(np.prod([mesh.shape[a] for a in names])) if names else 1
        if not names or x.shape[i] % total:
            spec.append(None)
            continue
        spec.append(names if len(names) > 1 else names[0])
        any_axis = True
    if not any_axis:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))


def _largest_divisible_axis(shape: Sequence[int], n: int) -> Optional[int]:
    best, best_dim = None, 0
    for i, d in enumerate(shape):
        if d % n == 0 and d > best_dim:
            best, best_dim = i, d
    return best


def fsdp_partition_params(params: Any, mesh: Mesh, axis: str = "fsdp",
                          min_size: int = 1024) -> Any:
    """ZeRO-3-style spec pytree: shard each tensor's largest divisible dim.

    Tensors with fewer than `min_size` total elements stay replicated —
    sharding a 64-element BN scale just adds collective latency.
    Returns a pytree of PartitionSpec matching `params`.
    """
    if axis not in mesh.axis_names:
        return jax.tree.map(lambda _: P(), params)
    n = mesh.shape[axis]

    def spec_for(x):
        shape = np.shape(x)
        if n <= 1 or not shape or int(np.prod(shape)) < min_size:
            return P()
        i = _largest_divisible_axis(shape, n)
        if i is None:
            return P()
        spec = [None] * len(shape)
        spec[i] = axis
        return P(*spec)

    return jax.tree.map(spec_for, params)


def shard_pytree(tree: Any, specs: Any, mesh: Mesh) -> Any:
    """device_put a pytree according to a matching pytree of PartitionSpec."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs)


# ---------------------------------------------------------------------------
# Tensor parallelism — name-based rules for the transformer (models/transformer.py)
# ---------------------------------------------------------------------------

_TP_RULES = (
    # attention projections: shard the head (output-feature) dim.
    # Patterns match models/transformer.py param paths plus common
    # hf/flax spellings.  The fused QKV kernel is (d_model, 3, h, d_k),
    # its bias (3, h, d_k) — the head axis is the shardable one.
    (r".*(attn|attention).*/qkv/kernel", P(None, None, "tp", None)),
    (r".*(attn|attention).*/qkv/bias", P(None, "tp", None)),
    (r".*(attn|attention).*/(query|key|value)/kernel", P(None, "tp")),
    (r".*(attn|attention).*/(query|key|value)/bias", P("tp")),
    (r".*(attn|attention).*/out/kernel", P("tp", None)),
    # MLP: first linear shards hidden out (+bias), second shards hidden in
    (r".*(ffn|mlp).*/(dense_0|fc1|wi)/kernel", P(None, "tp")),
    (r".*(ffn|mlp).*/(dense_0|fc1|wi)/bias", P("tp")),
    (r".*(ffn|mlp).*/(dense_1|fc2|wo)/kernel", P("tp", None)),
    # embeddings: shard the vocab dim of the token table only
    (r".*token_embedding", P("tp", None)),
)


def param_path_name(path) -> str:
    """'/'-joined name for a tree_map_with_path key path — THE framework
    convention for matching param names against sharding rules."""
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def tensor_parallel_rules(flat_name: str) -> P:
    """Map a '/'-joined param path to a TP PartitionSpec (P() if no rule hits)."""
    low = flat_name.lower()
    for pat, spec in _TP_RULES:
        if re.match(pat, low):
            return spec
    return P()


def apply_tp_rules(params: Any, mesh: Mesh) -> Any:
    """Spec pytree from _TP_RULES; falls back to replication."""
    if "tp" not in mesh.axis_names or mesh.shape["tp"] <= 1:
        return jax.tree.map(lambda _: P(), params)

    def lookup(path, _):
        return tensor_parallel_rules(param_path_name(path))

    return jax.tree_util.tree_map_with_path(lookup, params)


# ---------------------------------------------------------------------------
# ZeRO optimizer-state sharding — shape-aware rules (ISSUE 16 tentpole)
# ---------------------------------------------------------------------------
#
# The params overlay above cannot cover the optimizer state: NGD's
# grouped factor states (optim/ngd.py GroupState) do NOT mirror param
# shapes — w is (G, rank, dim) stacked over group members — so rules
# here match by leaf ROLE + SHAPE, not by param-tree position.  The two
# registries below are THE inspectable spec (SNIPPETS [2] idiom): every
# opt-state leaf any of our optimizers produce must classify into one
# OPT_STATE_RULES entry or one REPLICATED_OPT_STATE entry, enforced by
# scripts/check_sharding_rules.py (a new optimizer leaf cannot silently
# regress to replicated).

ZERO_MIN_SIZE = 1024

# rule name -> how the leaf is recognized and sharded (documentation
# table; classify_opt_state_leaf is the executable form).
OPT_STATE_RULES: Dict[str, str] = {
    "param_mirror":
        "leaf path ends with a param path and shapes agree (optax trace/"
        "adam mu,nu/madgrad s,v,z embed the param tree whole) — inherit "
        "the param's tp spec, else shard the largest divisible axis",
    "ngd_group_factor":
        "path contains .groups[ (GroupState w (G,rank,dim), d (G,rank),"
        " rho (G,)) — shard the leading group axis; per-member math is "
        "vmapped over G so splitting it is pure batching",
    "ngd_axis_factor":
        "path contains .axes[ (ungrouped OnlineNaturalGradientState "
        "w (rank,dim), d (rank,)) — shard the largest divisible axis",
}

# leaf classes that stay replicated ON PURPOSE, with the reason the
# lint requires.  Keyed by class name; classify returns these names.
REPLICATED_OPT_STATE: Dict[str, str] = {
    "scalar":
        "rank-0 counters and scales (t/step/count/rho/loss-scale) — "
        "nothing to shard, and every chip needs them each step",
    "small":
        f"fewer than {ZERO_MIN_SIZE} elements — sharding a bias-sized "
        "slot just adds collective latency (same floor as FSDP params)",
    "indivisible":
        "no axis divisible by the zero-axis size — padding slots would "
        "break the bitwise checkpoint-interchange contract",
    "unmatched":
        "no rule recognized the leaf role — conservatively replicated; "
        "scripts/check_sharding_rules.py fails until a rule (or an "
        "explicit entry here) covers the new optimizer's leaf class",
}


def _param_suffix_table(params: Any, param_specs: Any) -> Dict[str, tuple]:
    """keystr -> (shape, spec) for every param leaf; opt-state mirror
    leaves are recognized because optax embeds the param tree whole, so
    their keystr ENDS WITH the param's keystr."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    spec_flat = jax.tree_util.tree_flatten_with_path(
        param_specs, is_leaf=lambda x: isinstance(x, P))[0]
    table = {}
    for (path, leaf), (_, spec) in zip(flat, spec_flat):
        table[jax.tree_util.keystr(path)] = (np.shape(leaf), spec)
    return table


def classify_opt_state_leaf(key: str, shape, suffixes: Dict[str, tuple],
                            n: int, axis: str = "tp",
                            min_size: int = ZERO_MIN_SIZE
                            ) -> Tuple[str, P]:
    """(rule-or-replicate-class name, PartitionSpec) for one opt-state leaf.

    `key` is the jax.tree_util.keystr of the leaf inside the opt_state
    pytree, `suffixes` the _param_suffix_table of the (tp-overlaid)
    params.  Shape-aware on purpose: the same field name means different
    things in different optimizers, but role + shape is unambiguous.
    """
    shape = tuple(shape)
    if not shape:
        return "scalar", P()
    numel = int(np.prod(shape))

    def largest_axis_spec(rule: str) -> Tuple[str, P]:
        if numel < min_size:
            return "small", P()
        i = _largest_divisible_axis(shape, n)
        if i is None:
            return "indivisible", P()
        spec = [None] * len(shape)
        spec[i] = axis
        return rule, P(*spec)

    # NGD factor states first: their trees also contain param-named
    # fragments nowhere (groups are keyed "r2:n128:d64:k16"), but check
    # role markers before the mirror suffix test for clarity.  keystr
    # renders NamedTuple fields as attribute access (".groups[…]").
    if ".groups[" in key:
        # GroupState: leading axis is the stacked group-member axis G;
        # _group_precondition is vmapped over it, so sharding G is pure
        # batching.  Fall back to any divisible axis (w's dim often
        # divides when G does not).
        if shape[0] % n == 0 and numel >= min_size:
            spec = [None] * len(shape)
            spec[0] = axis
            return "ngd_group_factor", P(*spec)
        return largest_axis_spec("ngd_group_factor")
    if ".axes[" in key:
        return largest_axis_spec("ngd_axis_factor")

    for pkey, (pshape, pspec) in suffixes.items():
        if key.endswith(pkey) and shape == tuple(pshape):
            if pspec != P():
                return "param_mirror", pspec
            return largest_axis_spec("param_mirror")

    return "unmatched", P()


def zero_opt_state_specs(opt_state: Any, params: Any, param_specs: Any,
                         mesh: Mesh, axis: str = "tp",
                         min_size: int = ZERO_MIN_SIZE) -> Any:
    """Spec pytree for the optimizer state, ZeRO-sharded over `axis`.

    Momentum/adam/madgrad slots inherit the matching param's (possibly
    tp-overlaid) spec; NGD factor states shard by role + shape (they do
    not mirror params); scalars and sub-floor leaves replicate with a
    registered reason.  Returns all-P() when the axis is absent/size 1.
    """
    if axis not in mesh.axis_names or mesh.shape[axis] <= 1:
        return jax.tree.map(lambda _: P(), opt_state)
    n = mesh.shape[axis]
    suffixes = _param_suffix_table(params, param_specs)

    def per_leaf(path, leaf):
        key = jax.tree_util.keystr(path)
        _, spec = classify_opt_state_leaf(
            key, np.shape(leaf), suffixes, n, axis=axis,
            min_size=min_size)
        return spec

    return jax.tree_util.tree_map_with_path(per_leaf, opt_state)


# ---------------------------------------------------------------------------
# Per-stage parameter residency over pp (ISSUE 19 tentpole)
# ---------------------------------------------------------------------------
#
# r22 left every param replicated over pp, so a 4-stage model still had
# to fit one slice's HBM.  The overlay below gives stage-owned leaves —
# params under a ``layer_{i}`` subtree, whose stage home
# pipeline.param_stage_home reads off the ONE rule table — a 'pp' entry
# on a free axis of their (tp/fsdp-overlaid) spec, so each stage's
# chips hold 1/pp of the layer weights and (through the param_mirror
# inheritance in classify_opt_state_leaf) 1/pp of their optimizer
# mirrors.  Values are untouched: GSPMD materializes a leaf at use from
# its shards, so pp=2 ≡ pp=1 parity and the bitwise checkpoint
# interchange (specs live in the restore template, never the arrays)
# both survive.  The registries are the inspectable spec, enforced by
# scripts/check_sharding_rules.py exactly like OPT_STATE_RULES: a new
# param leaf class cannot silently re-replicate over pp.
#
# Honest scope note (the CPU-measurable claim): this is RESIDENCY —
# bytes at rest per chip scale with 1/pp (the ``memory`` telemetry
# event's per-chip byte table shows it).  On the steady path the
# unrolled tick loop applies each layer once per tick, and GSPMD
# gathers a stage's shard set at first use and CSEs the gather across
# ticks (ZeRO-3-class traffic, one gather per layer per step); the
# real-HBM/real-DCN traffic read is the live-TPU carryover item in
# ROADMAP.md.

PP_RESIDENCY_RULES: Dict[str, str] = {
    "stage_owned":
        "param under layer_{i} (pipeline.param_stage_home maps i to its "
        "stage) — 'pp' added on the largest free axis (one not already "
        "carrying fsdp/tp) divisible by the pp size; optimizer mirrors "
        "inherit the spec via classify_opt_state_leaf's param_mirror "
        "rule, multiplying the ZeRO reduction on dp x tp x pp meshes",
}

# param leaf classes that stay replicated over pp ON PURPOSE, with the
# registered reason the lint requires (the REPLICATED_OPT_STATE idiom).
REPLICATED_PP_PARAMS: Dict[str, str] = {
    "shared_embed":
        "embedding tables (token/pos/segment) — consumed by stage 0's "
        "input assembly and (tied LM head) the last stage's logits, so "
        "no single stage owns them; logical home stage 0",
    "shared_head":
        "ln_final / pooler / classifier / lm_head — applied after the "
        "staged encoder on the reassembled full batch; logical home is "
        "the last stage",
    "pp_small":
        f"stage-owned but fewer than {ZERO_MIN_SIZE} elements (LN "
        "scales/biases) — sharding a bias-sized leaf just adds "
        "collective latency (same floor as FSDP/ZeRO)",
    "pp_indivisible":
        "stage-owned but no free axis divisible by the pp size — "
        "padding would break the bitwise checkpoint interchange",
    "pp_unmatched":
        "param_stage_home recognized neither a layer home nor a shared "
        "role — conservatively replicated; "
        "scripts/check_sharding_rules.py fails until a rule (or an "
        "explicit entry here) covers the new leaf class",
}


def classify_pp_param_leaf(role: str, shape, base_spec: P, n: int,
                           axis: str = "pp",
                           min_size: int = ZERO_MIN_SIZE
                           ) -> Tuple[str, P]:
    """(class name, PartitionSpec) for one param leaf under per-stage
    residency.  ``role`` is pipeline.param_stage_home's verdict
    ('stage_owned' / 'shared_embed' / 'shared_head' / 'unknown');
    ``base_spec`` the leaf's existing (fsdp/tp-overlaid) spec, whose
    occupied axes are off-limits.  Only stage-owned leaves shard: the
    'pp' entry lands on the largest FREE axis divisible by ``n``."""
    shape = tuple(shape)
    if role in ("shared_embed", "shared_head"):
        return role, base_spec
    if role != "stage_owned":
        return "pp_unmatched", base_spec
    if not shape or int(np.prod(shape)) < min_size:
        return "pp_small", base_spec
    entries = tuple(base_spec) + (None,) * (len(shape) - len(base_spec))
    best, best_dim = None, 0
    for i, d in enumerate(shape):
        if entries[i] is None and d % n == 0 and d > best_dim:
            best, best_dim = i, d
    if best is None:
        return "pp_indivisible", base_spec
    out = list(entries)
    out[best] = axis
    return "stage_owned", P(*out)


def pp_residency_specs(params: Any, base_specs: Any, pipeline,
                       mesh: Mesh, min_size: int = ZERO_MIN_SIZE) -> Any:
    """Overlay per-stage residency onto the model-param spec tree:
    stage-owned leaves (per ``pipeline``'s rule table) gain a 'pp'
    entry per classify_pp_param_leaf; everything else keeps its base
    spec.  Identity when the mesh has no pp axis of size > 1."""
    if "pp" not in mesh.axis_names or mesh.shape["pp"] <= 1:
        return base_specs
    from faster_distributed_training_tpu.parallel.pipeline import (
        param_stage_home)
    n = mesh.shape["pp"]

    def per_leaf(path, leaf, base):
        role, _ = param_stage_home(pipeline, param_path_name(path))
        _, spec = classify_pp_param_leaf(role, np.shape(leaf), base, n,
                                         min_size=min_size)
        return spec

    return jax.tree_util.tree_map_with_path(
        per_leaf, params, base_specs)


def mirror_param_specs(opt_state: Any, params: Any,
                       param_specs: Any) -> Any:
    """Spec pytree placing each opt-state PARAM-MIRROR leaf (optax
    trace/adam mu,nu/madgrad s,v,z — recognized exactly like
    classify_opt_state_leaf: keystr suffix match + shape agreement) on
    its param's spec; P() everywhere else.

    This is the residency slice of the ZeRO overlay factored out so
    placement can apply it on pp meshes even under --no_zero_opt: a
    stage-owned param whose adam moments stay replicated would cap HBM
    at one slice's optimizer state, silently undoing the r23 tentpole
    for the (much larger) opt-state fraction.  When the full ZeRO
    overlay also runs it agrees on every mirror leaf (same suffix
    table, same inheritance), so applying both is idempotent."""
    suffixes = _param_suffix_table(params, param_specs)

    def per_leaf(path, leaf):
        key = jax.tree_util.keystr(path)
        shape = tuple(np.shape(leaf))
        for pkey, (pshape, pspec) in suffixes.items():
            if key.endswith(pkey) and shape == tuple(pshape):
                return pspec
        return P()

    return jax.tree_util.tree_map_with_path(per_leaf, opt_state)


# elements below this stay on device even under --offload_opt_state:
# streaming a bias-sized slot over PCIe costs more latency than the
# HBM it frees.  64Ki elements ~= 256 KB fp32.
OFFLOAD_MIN_ELEMENTS = 65536


def offload_opt_leaf(shape) -> bool:
    """Whether an opt-state leaf joins the host tier under
    --offload_opt_state.  Size-based: the big factor/momentum slots
    dominate HBM and amortize the PCIe round-trip; small slots stay
    resident (see README's offload cost model)."""
    shape = tuple(shape)
    return bool(shape) and int(np.prod(shape)) >= OFFLOAD_MIN_ELEMENTS


# ---------------------------------------------------------------------------
# Overlapped gradient reduce-scatter (ISSUE 16 tentpole, part C)
# ---------------------------------------------------------------------------

def bucketed_grad_reduce(grads: Any, mesh: Optional[Mesh],
                         axis: Optional[str] = None,
                         bucket_bytes: int = 4 << 20) -> Any:
    """Value-identity resharding pass that makes XLA lower the gradient
    reduction as bucketed reduce-scatter instead of one giant all-reduce.

    Flattens same-dtype gradient leaves into ~`bucket_bytes` 1-D buckets,
    constrains each bucket to P(axis), and splits back.  Because the
    constraint is on an intermediate, GSPMD materializes the scattered
    form right after the backward produces each bucket and defers the
    matching all-gather to first use — inside the K-dispatch `lax.scan`
    that means the collective for microbatch i overlaps microbatch
    i+1's compute.  Pure reshard: never changes values (reduce ORDER may
    shift float bits, which is why --overlap_grad_reduce defaults off
    and the K-twin pins compare the flag-off path).
    """
    if mesh is None:
        return grads
    if axis is None:
        axis = next((a for a in ("tp", "fsdp", "dp")
                     if a in mesh.axis_names and mesh.shape[a] > 1), None)
    if axis is None or axis not in mesh.axis_names or mesh.shape[axis] <= 1:
        return grads
    n = mesh.shape[axis]
    scattered = NamedSharding(mesh, P(axis))

    flat, treedef = jax.tree.flatten(grads)
    out = list(flat)
    by_dtype: Dict[Any, list] = {}
    for i, g in enumerate(flat):
        if not hasattr(g, "dtype") or g.ndim is None:
            continue
        by_dtype.setdefault(jnp.result_type(g), []).append(i)

    def flush(idxs):
        if not idxs:
            return
        vec = jnp.concatenate([flat[i].reshape(-1) for i in idxs])
        # materialize the logical (fully dp-reduced) gradient BEFORE the
        # scatter constraint: straight off the backward pass these leaves
        # are pending partial-sums over the data axes, and GSPMD resharding
        # a partial-sum value to P(axis) double-reduces it (measured:
        # exactly dp× gradients on a dp4 mesh, CPU and TPU partitioners
        # alike).  The P() pin forces the one true all-reduce here; XLA's
        # collective optimizer then fuses it with the adjacent
        # dynamic-slice into the reduce-scatter this pass exists for.
        vec = jax.lax.with_sharding_constraint(
            vec, NamedSharding(mesh, P()))
        pad = (-vec.size) % n
        if pad:
            vec = jnp.pad(vec, (0, pad))
        vec = jax.lax.with_sharding_constraint(vec, scattered)
        off = 0
        for i in idxs:
            size = flat[i].size
            out[i] = vec[off:off + size].reshape(flat[i].shape)
            off += size

    for dtype, idxs in by_dtype.items():
        itemsize = jnp.dtype(dtype).itemsize
        bucket, bucket_bytes_used = [], 0
        for i in idxs:
            bucket.append(i)
            bucket_bytes_used += flat[i].size * itemsize
            if bucket_bytes_used >= bucket_bytes:
                flush(bucket)
                bucket, bucket_bytes_used = [], 0
        flush(bucket)

    return jax.tree.unflatten(treedef, out)
