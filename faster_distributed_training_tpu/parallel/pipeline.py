"""Pipeline parallelism over the pp mesh axis: the ONE rule table.

The third parallelism axis (after dp/fsdp batch sharding and tp/sp
tensor/sequence sharding): the transformer's L encoder layers are
partitioned into ``pp`` contiguous STAGES, the batch is split into M
MICROBATCHES, and the stages process microbatches in a rotating
schedule — stage s works on microbatch ``t - s`` at tick ``t``, so the
activation leaving stage s-1 at tick t-1 is exactly what stage s
consumes at tick t.  The stage-boundary hop is the only per-tick
communication (a [microbatch, L, d_model] collective-permute over pp),
which is why pp is the axis that spans DCN between slices
(parallel/mesh.py::_AXIS_SPEED — pp ranks slowest, placed outermost,
preferred by the hybrid DCN factoring).

Every routing decision the pipeline makes — stage assignment,
microbatch count, collective placement, bubble accounting — is decided
HERE and dumped as one inspectable table (``pipeline_rules``) into the
run's ``manifest.json`` beside the r15 compile table (cli.run_training),
in the spirit of SNIPPETS [2]'s ``compile_step_with_plan``: no scattered
call sites, one place to read what the pipeline did.

Execution model (models/transformer.py, gated on a ``pp_spec`` call
argument so ``pp=1`` traces stay byte-identical to r21):

  * the [B, L, d] encoder input is reshaped to M microbatches of B/M;
  * a stage buffer [S, B/M, L, d], sharded ``P("pp", data_axes, ...)``
    over dim 0, holds each stage's current input;
  * each of T = M + S - 1 ticks rotates the buffer down one stage
    (the collective-permute), inserts the next microbatch at stage 0,
    and applies every stage's layer block to its slot;
  * the last stage's outputs are collected in microbatch order and
    reassembled into [B, L, d] — bitwise the same VALUES as running the
    microbatches sequentially, so the pp=2 ≡ pp=1 comparison sits in
    the documented cross-program-family allclose class (batch-dim
    tiling + microbatch reduction order), while within a pp program
    family everything stays bitwise (the r8 scan-rounding precedent).
    Since r23 the parity contract also holds with dropout LIVE on the
    hash engine (dense attention, flax FFN): the tick loop threads a
    PipelineTickCtx through the layers — per-site seeds stashed at the
    first (fold-count-0) make_rng draw so later ticks and bubble slots
    never consume draws, and every dropout site offsets its hash
    stream by the microbatch's GLOBAL row0, so each microbatch sees
    exactly its slice of pp=1's mask.  The same ctx carries the
    delayed-scaling amax cadence that lets --quant compose (one
    history roll per optimizer step; see PipelineTickCtx).
    build_pipeline_spec warns for the remaining non-parity dropout
    combos (xla engine, pallas FFN, flash/ring/ulysses attention).

The schedule is 1F1B in the combined fwd+bwd sense: jax.grad
differentiates through the rotation, so the backward pipeline replays
the ticks in reverse — stage s's backward for microbatch m runs as soon
as stage s+1's has (the reversed rotation), one-forward-one-backward
per stage per tick with no GPipe-style full-forward buffer beyond the
[S, ...] stage buffer itself.  ``schedule="interleaved"`` (the
Megatron v=2 assignment) deals round-robin layer chunks to the stages
and the tick loop traverses the resulting VIRTUAL stages in depth
order: the buffer grows to V = 2S slots, slot j applies depth-chunk j
(``virtual_chunks`` is the contract), and physical stage j % S hosts
slot j — so every microbatch still applies layer 0..L-1 in order and
the pp=2 ≡ pp=1 parity class is schedule-independent.  In this
rotate-all formulation each tick computes ALL of a stage's chunks, so
interleaving buys placement fidelity (two non-adjacent depth regions
per stage, twice the boundary hops), NOT the Megatron bubble win:
fill/drain lengthens to V-1 ticks and the rule table records the
honest (V-1)/(M+V-1).  The chunk-granularity staggered schedule that
realizes the v× bubble reduction is a named live-TPU ROADMAP
follow-on.

Fill/drain ticks (the bubble) compute on recycled microbatch data
(never zeros — an all-zero constant block invites XLA constant-folding
the slot's backward into 0*inf NaN constants at x64): the garbage
outputs are never selected into the loss, so their cotangents are zero
and the extra work is exactly the analytic bubble fraction
(V - 1) / (M + V - 1) over the V virtual-stage slots (V = S for 1f1b)
— the executed program genuinely pays the bubble it reports
(``pipeline_bubble_pct``).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional, Sequence, Tuple

from faster_distributed_training_tpu.parallel.mesh import pp_size

SCHEDULES = ("1f1b", "interleaved")

_LAYER_RE = re.compile(r"(?:^|/)layer_(\d+)(?:/|$)")

# markers for the post-encoder shared leaves (param_stage_home): params
# applied AFTER the staged region on the reassembled batch, logically
# homed on the last stage.  Anything matching none of the tables below
# classifies "unknown" and the sharding lint fails until it is covered
# (sharding.REPLICATED_PP_PARAMS "pp_unmatched").
_HEAD_MARKERS = ("ln_final", "pooler", "cls_", "lm_head")


def param_stage_home(spec: "PipelineSpec", flat_name: str
                     ) -> Tuple[str, Optional[int]]:
    """(role, stage) for a '/'-joined param/batch_stats path — THE
    stage-home rule every residency surface shares (the sharding
    overlay, the rule table, the lint):

      ('stage_owned',  s)    — leaf under layer_{i}, i in stage s's
                               assignment;
      ('shared_embed', 0)    — embedding tables (consumed by stage 0's
                               input assembly; the tied LM head also
                               reads the token table on the LAST stage,
                               which is why they replicate over pp);
      ('shared_head',  S-1)  — ln_final/pooler/classifier/lm_head,
                               applied after the staged region;
      ('unknown',      None) — nothing matched; the lint fails until a
                               rule covers the new leaf class.
    """
    low = flat_name.lower()
    m = _LAYER_RE.search(low)
    if m:
        li = int(m.group(1))
        for s, layers in enumerate(spec.stage_layers):
            if li in layers:
                return "stage_owned", s
        return "unknown", None
    if "embedding" in low:
        return "shared_embed", 0
    if any(mk in low for mk in _HEAD_MARKERS):
        return "shared_head", spec.n_stages - 1
    return "unknown", None


class PipelineTickCtx:
    """Trace-time context the staged tick loop threads through the
    layer modules (models/transformer.py staged branch) so the
    per-TICK invocation pattern reproduces pp=1's per-STEP semantics
    for the two stateful per-site mechanisms:

      * dropout seeds (``site_seed``): pp=1 draws each site's seed
        once per step; the staged encoder invokes every layer once per
        tick, so repeated make_rng calls would fold a different count
        per tick and bubble slots would consume draws.  The ctx stashes
        the FIRST invocation's draw (Flax fold count 0 — the same key
        pp=1's single call derives) and replays it every later tick;
        combined with the global row offset (``row0`` — the microbatch
        id times the microbatch size, NOT the tick or slot index) each
        microbatch addresses exactly its slice of pp=1's hash-dropout
        index stream.
      * delayed-scaling amax cadence (``amax_pre``/``amax_push``):
        one history roll per optimizer step instead of one per tick —
        every tick quantizes at the PRE-step scale (pp=1's scale), the
        first REAL (non-bubble) invocation rolls the history, later
        real invocations max their microbatch amax into slot 0, and
        bubble invocations never touch it (their recycled fill/drain
        data could exceed the true batch max).  max-of-microbatch-
        maxes == the full-batch amax bitwise, so the post-step scale
        state matches pp=1 exactly (tests/test_pp_residency.py pins
        it).

    The object is created fresh inside the staged branch at every
    trace (including the once-per-dispatch trace of the K-step scan
    body), so nothing leaks across traces; the tick loop sets
    ``microbatch``/``bubble`` before each slot invocation (the loop is
    unrolled python, so module calls observe the current values at
    trace time)."""

    def __init__(self, n_microbatches: int, microbatch_rows: int):
        self.n_microbatches = int(n_microbatches)
        self.microbatch_rows = int(microbatch_rows)
        self.microbatch = 0      # clamped microbatch id of the current slot
        self.bubble = False      # fill/drain invocation (output discarded)
        self._seeds: dict = {}
        self._amax_rolled: set = set()
        self._amax_pre: dict = {}

    @property
    def row0(self) -> int:
        """Global batch-row offset of the current microbatch — the
        static offset dropout sites add to address pp=1's index
        stream."""
        return self.microbatch * self.microbatch_rows

    def site_seed(self, site: str, draw):
        """The site's per-step dropout seed: ``draw()`` (a make_rng
        bits draw) on the first invocation, the stashed tracer after —
        later ticks and bubble slots never consume rng draws."""
        if site not in self._seeds:
            self._seeds[site] = draw()
        return self._seeds[site]

    def amax_pre(self, site: str, hist):
        """The site's PRE-step amax history (stashed at first touch):
        every tick's scale comes from it, exactly like pp=1's single
        scale_from_history read."""
        if site not in self._amax_pre:
            self._amax_pre[site] = hist
        return self._amax_pre[site]

    def amax_push(self, site: str, hist, amax):
        """One-roll-per-step history update; returns the new history
        value.  Bubble invocations return ``hist`` untouched."""
        if self.bubble:
            return hist
        import jax.numpy as jnp
        from faster_distributed_training_tpu.ops.quant import (
            update_amax_history)
        if site in self._amax_rolled:
            return hist.at[0].set(jnp.maximum(hist[0], amax))
        self._amax_rolled.add(site)
        return update_amax_history(self.amax_pre(site, hist), amax)


@dataclasses.dataclass(frozen=True)
class PipelineSpec:
    """Static description of one pipelined encoder — everything the
    traced program and the rule table need.  ``mesh`` rides along (not
    part of equality-relevant identity: specs are rebuilt per Trainer,
    never hashed into jit keys — the pp program is selected by python
    branching before trace)."""
    n_layers: int
    n_stages: int
    n_microbatches: int
    stage_layers: Tuple[Tuple[int, ...], ...]   # layer indices per stage
    schedule: str = "1f1b"
    mesh: Optional[object] = None

    @property
    def n_virtual(self) -> int:
        """Virtual-stage count V: the number of depth-ordered chunks
        the tick loop traverses (== n_stages for contiguous 1F1B
        assignment, 2 * n_stages under v=2 interleaving)."""
        return len(virtual_chunks(self))

    @property
    def n_ticks(self) -> int:
        return self.n_microbatches + self.n_virtual - 1

    @property
    def bubble_pct(self) -> float:
        return 100.0 * bubble_fraction(self.n_virtual,
                                       self.n_microbatches)


def partition_stages(n_layers: int, n_stages: int,
                     schedule: str = "1f1b"
                     ) -> Tuple[Tuple[int, ...], ...]:
    """Layer indices per stage.

    "1f1b": contiguous balanced blocks — earlier stages take the extra
    layer when n_layers % n_stages != 0 (they also host the un-staged
    embedding, but the tie-break is mostly cosmetic: the schedule's
    critical path is the max per-stage block either way).

    "interleaved": layers dealt round-robin in contiguous CHUNKS of
    L / (S * v) with v=2 virtual stages per physical stage (the
    Megatron v-interleave ASSIGNMENT) — each stage touches two
    non-adjacent regions of the depth at the price of twice the
    boundary hops.  Requires L % (2S) == 0 so the V = 2S depth-ordered
    chunks are equal-sized and slot j lands on stage j % S exactly
    (the placement rule constrain_stage_buffer encodes); falls back to
    the contiguous split otherwise.  Execution stays depth-ordered
    either way: the tick loop runs virtual_chunks, never a stage's
    concatenated layer list."""
    if not 1 <= n_stages <= n_layers:
        raise ValueError(f"cannot split {n_layers} layers into "
                         f"{n_stages} pipeline stages")
    if schedule not in SCHEDULES:
        raise ValueError(f"unknown pipeline schedule {schedule!r} "
                         f"(one of {SCHEDULES})")
    if schedule == "interleaved" and n_layers % (2 * n_stages) == 0:
        v = 2
        chunk = n_layers // (n_stages * v)
        chunks = [tuple(range(i, i + chunk))
                  for i in range(0, n_layers, chunk)]
        out = [[] for _ in range(n_stages)]
        for idx, ch in enumerate(chunks):
            out[idx % n_stages].extend(ch)
        return tuple(tuple(s) for s in out)
    base, extra = divmod(n_layers, n_stages)
    bounds, lo = [], 0
    for s in range(n_stages):
        hi = lo + base + (1 if s < extra else 0)
        bounds.append(tuple(range(lo, hi)))
        lo = hi
    return tuple(bounds)


def virtual_chunks(spec: PipelineSpec) -> Tuple[Tuple[int, ...], ...]:
    """The depth-ordered virtual-stage chunks the tick loop executes:
    each chunk is a maximal run of consecutive layers from one stage's
    assignment, and the chunks are ordered by first layer — so slot j
    applying chunk j walks every microbatch through layer 0..L-1 in
    depth order REGARDLESS of schedule (the property the pp ≡ pp=1
    parity pins).  Contiguous 1F1B assignment yields one run per stage
    (chunks == stage_layers, V == S); v=2 interleaving yields V == 2S
    equal runs with chunk j owned by stage j % S — the mapping
    constrain_stage_buffer's [v, S] placement view relies on."""
    runs = []
    for layers in spec.stage_layers:
        start = 0
        for k in range(1, len(layers) + 1):
            if k == len(layers) or layers[k] != layers[k - 1] + 1:
                runs.append(tuple(layers[start:k]))
                start = k
    runs.sort(key=lambda r: r[0])
    flat = [i for r in runs for i in r]
    if flat != sorted(flat):
        raise ValueError(f"stage assignment {spec.stage_layers} has "
                         f"overlapping depth runs — no depth-ordered "
                         f"traversal exists")
    return tuple(runs)


def bubble_fraction(n_slots: int, n_microbatches: int) -> float:
    """Idle fraction of the pipelined dispatch: (V-1)/(M+V-1) over the
    V virtual-stage slots (V == S for 1f1b).  Each slot is active for
    exactly M of the T = M+V-1 ticks (fill for the early slots' tail,
    drain for the late slots' head)."""
    if n_slots <= 1:
        return 0.0
    return (n_slots - 1) / float(n_microbatches + n_slots - 1)


def schedule_ticks(n_stages: int, n_microbatches: int
                   ) -> Tuple[Tuple[Tuple[int, int], ...], ...]:
    """The schedule as data, for tests/telemetry: per tick, the active
    (stage, microbatch) pairs.  Stage s processes microbatch t-s when
    0 <= t-s < M; everything else is a bubble slot."""
    out = []
    for t in range(n_microbatches + n_stages - 1):
        out.append(tuple((s, t - s) for s in range(n_stages)
                         if 0 <= t - s < n_microbatches))
    return tuple(out)


def stage_idle_ticks(spec: PipelineSpec) -> Tuple[int, ...]:
    """Bubble slot-ticks per stage — the per-stage accounting the
    ``pp_stage`` telemetry records carry (idle ms = these ticks x a
    measured tick time; not measured on the chip).  Each of a stage's V/S slots
    idles exactly V-1 = T-M of the T ticks under the rotation
    schedule, so a stage's idle total is (V/S)(V-1): S-1 for 1f1b,
    2(2S-1) under v=2 interleaving (the lengthened fill/drain the
    module docstring owns up to)."""
    slots_per_stage = spec.n_virtual // spec.n_stages
    return tuple(slots_per_stage * (spec.n_ticks - spec.n_microbatches)
                 for _ in range(spec.n_stages))


def resolve_microbatches(batch_size: int, n_stages: int,
                         requested: int = 0) -> int:
    """Microbatch count M for a global batch: the requested value when
    given (must divide the batch), else the largest divisor of
    batch_size in [S, 2S] — 2S halves the bubble vs M=S, and staying a
    divisor keeps every microbatch the same shape (one compiled stage
    program, no ragged tail).  Falls back toward S, then to the largest
    divisor <= batch_size."""
    if requested:
        # validate the range BEFORE the divisibility check: python's
        # `8 % -2 == 0`, so a negative count would sail through and
        # surface as an obscure reshape/trace failure far from the flag
        if not 1 <= requested <= batch_size:
            raise ValueError(
                f"--pp_microbatches {requested} must be in "
                f"[1, batch_size={batch_size}]")
        if batch_size % requested:
            raise ValueError(
                f"--pp_microbatches {requested} does not divide the "
                f"global batch {batch_size}")
        return requested
    for m in range(2 * n_stages, n_stages - 1, -1):
        if m and batch_size % m == 0:
            return m
    for m in range(min(n_stages, batch_size), 0, -1):
        if batch_size % m == 0:
            return m
    return 1


def build_pipeline_spec(cfg, mesh,
                        attention_impl: Optional[str] = None
                        ) -> Optional[PipelineSpec]:
    """The spec for this (cfg, mesh), or None when the mesh has no pp
    axis of size > 1 — the None path is what keeps pp=1 programs
    byte-identical (callers select today's unstaged code path on None,
    they never trace a degenerate 1-stage pipeline).

    ``attention_impl``: the RESOLVED attention implementation when the
    caller knows it (cli passes build_model's choice); None falls back
    to cfg.attention, where "" (auto) is treated conservatively for
    the dropout-parity predicate below."""
    stages = pp_size(mesh)
    if stages <= 1:
        return None
    if cfg.model != "transformer":
        raise ValueError(
            f"--mesh with pp={stages}: pipeline parallelism stages the "
            f"transformer encoder; model {cfg.model!r} has no staged "
            f"form")
    # quant composes since r23: the staged encoder threads a
    # PipelineTickCtx amax cadence through QuantDense so each site's
    # history rolls once per optimizer STEP (quantizing every tick at
    # the pre-step scale and folding the per-microbatch amaxes into one
    # max — bitwise the full-batch amax), instead of the per-tick rolls
    # that made r22 refuse.  The cadence is schedule-independent (every
    # chunk invocation per tick is either real or bubble under 1f1b and
    # interleaved alike), so the old refusal is gone entirely; scale-
    # state parity vs pp=1 is pinned by tests/test_pp_residency.py.
    # The ONE remaining refusal: --remat.  nn.remat makes every tick's
    # layer call its own checkpoint trace, so the cadence's cross-tick
    # history stash would leak tracers between traces — the staged
    # branch disables the ctx under remat, which would silently restore
    # the per-tick rolls r22 refused.  Refuse loudly instead.
    remat = bool(getattr(cfg, "remat", False))
    if getattr(cfg, "quant", "none") not in (None, "", "none") and remat:
        raise ValueError(
            f"--quant with pp={stages} and --remat: the per-step amax "
            f"cadence that makes delayed scaling match pp=1 cannot "
            f"cross nn.remat's per-tick checkpoint traces; drop --remat "
            f"on pp meshes with quant, or train unquantized")
    impl = (getattr(cfg, "dropout_impl", "none") or "none")
    if impl != "none":
        attn = (attention_impl if attention_impl is not None
                else (getattr(cfg, "attention", "") or ""))
        # hash-engine dropout composes since r23: the staged encoder
        # threads PipelineTickCtx through the FastDropout sites and the
        # dense attention path — per-site seeds stashed at the first
        # (fold-count-0) make_rng draw, each microbatch offset to its
        # GLOBAL rows of the hash index stream — so pp ≡ pp=1 holds
        # with dropout LIVE for the hash engine on dense attention with
        # the flax FFN.  The remaining non-parity combos keep a warning:
        # "xla" (threefry masks fold per invocation), the pallas fused
        # FFN (in-kernel rows address the microbatch-local index
        # space), and the flash/ring/ulysses kernels (dropout streams
        # keyed on local (b,h) inside their scan/shard_map).
        parity = (impl == "hash"
                  and (getattr(cfg, "ffn_impl", "flax") or "flax")
                  != "pallas"
                  and attn == "dense"
                  and not remat)
        if not parity:
            import warnings
            warnings.warn(
                f"pp={stages} with dropout_impl={impl!r}, "
                f"attention={attn or 'auto'!r}, "
                f"ffn_impl={getattr(cfg, 'ffn_impl', 'flax')!r}, "
                f"remat={remat}: this "
                f"combination draws a different dropout stream than "
                f"pp=1 — still valid dropout, but the pp ≡ pp=1 parity "
                f"class needs the hash engine on dense attention with "
                f"the flax FFN, no remat (or --dropout_impl none)",
                stacklevel=2)
    schedule = getattr(cfg, "pp_schedule", "1f1b") or "1f1b"
    m = resolve_microbatches(cfg.batch_size, stages,
                             int(getattr(cfg, "pp_microbatches", 0) or 0))
    return PipelineSpec(
        n_layers=cfg.n_layers, n_stages=stages, n_microbatches=m,
        stage_layers=partition_stages(cfg.n_layers, stages, schedule),
        schedule=schedule, mesh=mesh)


def constrain_stage_buffer(buf, spec: PipelineSpec):
    """The pipeline's single placement rule, applied to the [V, mb, L,
    d] stage buffer: the slot dim over pp (each stage's slots live on
    its slice — the rotation becomes the DCN collective-permute), the
    microbatch dim over the data axes (microbatches stay batch-sharded
    within a slice).  tp/sp activation constraints keep applying
    INSIDE the layers unchanged.

    With V == S (1f1b) dim 0 shards over pp directly.  Under v=2
    interleaving (V == 2S, depth-ordered slots, chunk j owned by stage
    j % S) a contiguous dim-0 shard would pile adjacent chunks onto
    one stage, so the buffer is viewed as [v, S, mb, ...] — the STAGE
    dim shards over pp, placing slot j = p*S + s on stage s = j % S,
    exactly the round-robin assignment the rule table records."""
    from faster_distributed_training_tpu.parallel.sharding import (
        shard_activation)
    V, S = buf.shape[0], spec.n_stages
    if V == S:
        return shard_activation(
            buf, spec.mesh,
            ("pp", ("dp", "fsdp")) + (None,) * (buf.ndim - 2))
    grouped = buf.reshape((V // S, S) + buf.shape[1:])
    grouped = shard_activation(
        grouped, spec.mesh,
        (None, "pp", ("dp", "fsdp")) + (None,) * (buf.ndim - 2))
    return grouped.reshape(buf.shape)


def pipeline_rules(spec: Optional[PipelineSpec], cfg=None) -> dict:
    """The inspectable routing/rule table dumped into manifest.json
    beside the compile table (cli.run_training) — stage assignment,
    microbatch count, collective placement and bubble accounting in one
    place, so "what did the pipeline decide" is a file read, not a
    code trace."""
    if spec is None:
        return {"enabled": False, "n_stages": 1}
    return {
        "enabled": True,
        "schedule": spec.schedule,
        "n_stages": spec.n_stages,
        "n_layers": spec.n_layers,
        "n_microbatches": spec.n_microbatches,
        "n_virtual_stages": spec.n_virtual,
        "n_ticks": spec.n_ticks,
        "bubble_pct": round(spec.bubble_pct, 3),
        "stage_idle_ticks": list(stage_idle_ticks(spec)),
        # the EXECUTION order (slot j applies chunk j): depth order by
        # construction whatever the assignment — the record that makes
        # "interleaved ran the layers in order" a file read
        "depth_order": [[f"layer_{i}" for i in ch]
                        for ch in virtual_chunks(spec)],
        "stages": [
            {"stage": s,
             "layers": [f"layer_{i}" for i in layers],
             # embedding/head are un-staged (replicated over pp — see
             # param_residency below); the table records their logical
             # home so per-stage accounting can attribute them
             "extra": (["embeddings"] if s == 0 else [])
             + (["ln_final", "head"] if s == spec.n_stages - 1 else [])}
            for s, layers in enumerate(spec.stage_layers)],
        # placement rules, verbatim what the traced program constrains:
        "activation_placement":
            "stage buffer [S, B/M, L, d] = P('pp', ('dp','fsdp'))",
        "boundary_collective":
            "collective-permute over pp (the DCN hop), one "
            "[B/M, L, d] activation per tick",
        "param_residency": _param_residency_rules(spec, cfg),
        "batch_axes": "dp/fsdp only (pp never shards the batch)",
    }


def _param_residency_rules(spec: PipelineSpec, cfg=None) -> dict:
    """The per-stage residency block of the rule table (ISSUE 19
    tentpole): which leaf classes live on their pp coordinate, which
    replicate and why — sharding.py's PP registries plus the stage-home
    assignment, in one inspectable record.  ``enabled`` reflects
    cfg.pp_residency (--no_pp_residency restores the r22 replicated-
    over-pp layout, e.g. for pp on a single slice where HBM is shared
    anyway — see README's decision table)."""
    from faster_distributed_training_tpu.parallel.sharding import (
        PP_RESIDENCY_RULES, REPLICATED_PP_PARAMS, ZERO_MIN_SIZE)
    enabled = bool(getattr(cfg, "pp_residency", True)) if cfg is not None \
        else True
    return {
        "enabled": enabled,
        "axis": "pp",
        "min_size": ZERO_MIN_SIZE,
        # every param/opt-state/batch_stats leaf resolves its stage
        # home through param_stage_home; stage-owned leaves shard over
        # pp (optimizer mirrors inherit via the param_mirror rule,
        # multiplying with ZeRO-within-a-stage), the rest replicate
        # with a registered reason:
        "sharded": dict(PP_RESIDENCY_RULES) if enabled else {},
        "replicated": (dict(REPLICATED_PP_PARAMS) if enabled else {
            "all": "pp_residency disabled (--no_pp_residency): params "
                   "and optimizer state keep the r22 replicated-over-pp "
                   "layout"}),
        "stage_home": {
            **{f"layer_{i}": s
               for s, layers in enumerate(spec.stage_layers)
               for i in layers},
            "embeddings": 0,
            "head": spec.n_stages - 1,
        },
    }
