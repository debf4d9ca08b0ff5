"""Compile observatory: per-PROGRAM evidence for every jit the run builds.

r12 gave every run per-dispatch telemetry; this module climbs one level
to the PROGRAM.  ROADMAP's "instant restart" item says real-hardware
MTTR is compile-dominated, yet until now no run recorded what its
compiles actually cost, whether the persistent compilation cache served
them, or whether a program quietly re-traced — and the ZeRO item needs
``opt_state_bytes_per_chip`` before anyone can size that win.  Three
pieces close the gap:

  * :class:`ProgramObservatory` + :class:`ObservedJit` — the Trainer's
    jitted programs (train per (path, K), eval, the device-resident
    epoch re-shard) go through an EXPLICIT ``lower()`` / ``compile()``
    on their first call per input signature, so every program records:
    compile wall ms, a stable HLO fingerprint (sha256 of
    ``lowered.as_text()``), a persistent-compilation-cache verdict
    (cache-dir stat before/after, falling back to the
    min-compile-time threshold — the method used is recorded beside the
    verdict), and the executable's ``memory_analysis()`` byte breakdown
    (argument/output/temp/generated).  Steady-state calls go straight to
    the AOT executable (measured ~0.5 us over the jit C++ fast path on
    CPU — program collection happens at compile boundaries, never
    per-dispatch, which is what keeps telemetry's cost a per-compile
    one).
  * the RETRACE detector — lowerings are counted per program name; a
    name lowering again with the SAME signature, or with a signature
    that differs only in dtype/weak-type (the classic non-weak-type
    scalar leak), or past ``max_variants`` total (a shape leak), emits a
    loud ``retrace`` telemetry event AND a Python warning.  Legitimate
    shape polymorphism (text bucket widths, the padded final eval batch)
    shows up as counted VARIANTS of one name, not as retraces;
    tests/test_programs.py pins the exact program set a CPU run
    compiles, so an accidental extra program fails tier-1.
  * HBM attribution helpers — :func:`state_bytes_table` splits the
    train state's per-chip bytes params vs opt_state vs batch_stats
    (``opt_state_bytes_per_chip`` is THE number ROADMAP's ZeRO item is
    specified against), and
    :func:`sharding_fingerprint` / :func:`sharding_table` are the
    sharding-DRIFT guard: the Trainer fingerprints the live state's
    shardings after step 1 and re-checks at every epoch boundary,
    raising the r11 params-drift bug class from "measured once" to
    "guarded" (cheap hash always on; ``--debug`` keeps the per-leaf
    table so a drift names the leaves that moved).

Every event lands in the r12 JSONL stream (kinds are APPEND-ONLY:
``program``, ``retrace``, ``memory`` join the r12 set) and the program
table is merged into ``manifest.json`` at run end, so a telemetry
directory answers "what did this run compile and what did it cost"
without the process that wrote it.

Kill switch: ``FDT_PROGRAM_OBS=0`` — the Trainer falls back to plain
``jax.jit`` dispatch (byte-identical programs, no program events).
``FDT_HLO_FINGERPRINT=0`` skips the ``as_text()`` hash for very large
programs (the rest of the record is unaffected).

r17 instant restart: when a
:class:`~faster_distributed_training_tpu.resilience.executable_cache
.ExecutableCache` is installed on the observatory, observe_compile
becomes lookup-before-compile / store-after-compile and every program
record carries a ``cache_source`` verdict —  ``"deserialized"`` (the
executable tier served it; compile_ms is the deserialize time),
``"persistent_dir"`` (XLA's persistent cache dir served the compile),
or ``"compiled"`` (full price paid, and the executable tier stored it
for the next restart).  ``summary()``'s ``total_compile_ms`` therefore
reads as the run's total program-ACQUISITION cost either way, which is
exactly the restart-MTTR compile component.
"""

from __future__ import annotations

import collections
import hashlib
import os
import re
import threading
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Tuple

ENV_KILL = "FDT_PROGRAM_OBS"
ENV_FINGERPRINT = "FDT_HLO_FINGERPRINT"

# process-global observatory (the spans.set_recorder idiom): modules
# that predate telemetry (data/device_resident.py's epoch re-shard)
# reach it without threading it through their constructors
_ACTIVE = None


def set_observatory(obs) -> Optional[object]:
    """Install the process-global observatory; returns the previous one
    so callers can restore it (tests nest)."""
    global _ACTIVE
    prev, _ACTIVE = _ACTIVE, obs
    return prev


def get_observatory():
    return _ACTIVE


def observatory_enabled() -> bool:
    return os.environ.get(ENV_KILL, "1") != "0"


def _leaf_sig(x) -> Tuple[tuple, str, bool]:
    """(shape, dtype, weak) of one argument leaf — the aval identity the
    retrace detector compares.  Python scalars are weak-typed (jax
    semantics); arrays carry their own weak_type flag."""
    import numpy as np

    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return (tuple(shape), str(dtype),
                bool(getattr(x, "weak_type", False)))
    a = np.asarray(x)
    return (tuple(a.shape), str(a.dtype),
            isinstance(x, (bool, int, float, complex)))


def args_signature(args, argnums) -> tuple:
    """Hashable signature of the designated positional args: tree
    structure + per-leaf (shape, dtype, weak)."""
    import jax

    parts = []
    for i in argnums:
        leaves, treedef = jax.tree_util.tree_flatten(args[i])
        parts.append((treedef, tuple(_leaf_sig(x) for x in leaves)))
    return tuple(parts)


def _sig_shapes(sig) -> tuple:
    """The shape-only projection of a signature — two signatures with
    equal shapes but unequal dtypes/weak flags are the scalar-leak
    retrace class."""
    return tuple((treedef, tuple(s[0] for s in leaf_sigs))
                 for treedef, leaf_sigs in sig)


def _sig_text(sig, limit: int = 240) -> str:
    """Compact human-readable aval summary for retrace diagnostics."""
    bits = []
    for _treedef, leaf_sigs in sig:
        for shape, dtype, weak in leaf_sigs:
            bits.append(f"{dtype}{list(shape)}" + ("w" if weak else ""))
    txt = ",".join(bits)
    return txt if len(txt) <= limit else txt[:limit] + "..."


def memory_analysis_dict(compiled) -> Optional[Dict[str, int]]:
    """The executable's memory_analysis() as plain bytes fields, None
    when the backend exposes none.  Shares field meaning with
    utils.profiling.compiled_memory_bytes (which nets out aliased
    donated buffers for the single peak estimate); here the raw
    components are kept separate — attribution, not one headline."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out = {}
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
        out[field.replace("_size_in_bytes", "_bytes")] = int(
            getattr(ma, field, 0) or 0)
    return out


_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "all-to-all", "collective-permute")
_COLLECTIVE_RE = re.compile(
    r" (" + "|".join(_COLLECTIVES) + r")(?:-start)?\(")


def hlo_op_counts(lowered_text: str, compiled) -> Optional[Dict[str, int]]:
    """Static counts of what the program holds: ``tpu_custom_call``
    (Pallas/Mosaic kernels, from the LOWERED module — present whether
    the executable was compiled or served from a cache) and each
    collective (from the COMPILED module: the partitioner inserts most
    of them; absent keys mean the backend returned no text)."""
    if not lowered_text:
        return None
    out = {"tpu_custom_call": lowered_text.count("tpu_custom_call")}
    try:
        text = compiled.as_text()
    except Exception:
        return out
    found = collections.Counter(_COLLECTIVE_RE.findall(text))
    out.update({op: found[op] for op in _COLLECTIVES})
    return out


_cache_hits = [0, False]    # [count, listener registered]


def _persistent_cache_hits() -> int:
    """Running count of jax's own persistent-compilation-cache hit
    events in this process (listener registered on first use)."""
    if not _cache_hits[1]:
        import jax.monitoring

        def on_event(name, **_kw):
            if name == "/jax/compilation_cache/cache_hits":
                _cache_hits[0] += 1
        jax.monitoring.register_event_listener(on_event)
        _cache_hits[1] = True
    return _cache_hits[0]


class ProgramObservatory:
    """Owns the run's compile record.  Thread-safe (the checkpoint
    background writer never compiles, but nothing here assumes that).

    ``recorder`` (a TelemetryRecorder, optional) receives one
    ``program`` event per observed compile and one ``retrace`` event per
    detection; :meth:`summary` is the table RunTelemetry merges into
    manifest.json at run end."""

    def __init__(self, recorder=None, log: Callable[[str], None] = print,
                 max_variants: int = 8):
        self.recorder = recorder
        self._log = log
        self.max_variants = int(max_variants)
        self._lock = threading.Lock()
        # name -> [entry dicts in lowering order]; entries keep their
        # signature under the private "_sig" key (stripped from events)
        self.programs: Dict[str, List[dict]] = {}
        self.retraces: List[dict] = []
        self._variant_flood_warned: set = set()
        # r17 instant-restart wiring, both installed post-construction
        # by cli.run_training:
        #  * executable_cache (resilience/executable_cache.py) turns
        #    observe_compile into lookup-before-compile /
        #    store-after-compile — a restarted process deserializes its
        #    programs (cache_source="deserialized") instead of
        #    recompiling; any cache failure degrades to plain compile;
        #  * goodput (resilience/goodput.py) receives every observed
        #    program-acquisition cost (lower + compile OR deserialize)
        #    so restart MTTR can split into _compile_s vs _restore_s
        #    components — the compile-dominated half was invisible
        #    before.
        self.executable_cache = None
        self.goodput = None

    # -- the compile path --------------------------------------------------

    def wrap(self, name: str, jitted, sig_argnums: Tuple[int, ...] = ()
             ) -> "ObservedJit":
        return ObservedJit(name, jitted, self, sig_argnums=sig_argnums)

    def observe_compile(self, name: str, jitted, args,
                        sig: Optional[tuple] = None):
        """Explicit lower+compile of ``jitted`` for ``args`` under
        observation; returns the AOT compiled callable, or None when the
        AOT path is unavailable (caller falls back to plain jit dispatch
        — observability must never kill training)."""
        try:
            t0 = time.monotonic()
            lowered = jitted.lower(*args)
            lower_ms = (time.monotonic() - t0) * 1e3
            lowered_text = self._lowered_text(lowered)
            fingerprint = (hashlib.sha256(lowered_text.encode())
                           .hexdigest()[:16] if lowered_text else "")
            # r17 executable cache: lookup-before-compile.  A hit
            # deserializes the stored executable (compile_ms below IS
            # the deserialize time — the restart-MTTR number the A/B
            # reads); any load failure returned None and the plain
            # compile below serves the program.  Fingerprint "" (the
            # FDT_HLO_FINGERPRINT=0 escape) has no key and skips the
            # tier entirely.
            ec = self.executable_cache
            exec_key = (ec.key_for(name, fingerprint)
                        if ec is not None and fingerprint else None)
            compiled = None
            if exec_key is not None:
                t0 = time.monotonic()
                compiled = ec.load(exec_key, lowered)
            if compiled is not None:
                compile_ms = (time.monotonic() - t0) * 1e3
                cache, method = "bypassed", "executable_cache"
                source = "deserialized"
            else:
                before = self._cache_listing()
                hits0 = _persistent_cache_hits()
                t0 = time.monotonic()
                compiled = lowered.compile()
                compile_ms = (time.monotonic() - t0) * 1e3
                if _persistent_cache_hits() > hits0:
                    # jax itself said so: the only verdict that tells a
                    # FAST hit from a compile too quick to be stored
                    cache, method = "hit", "jax_event"
                else:
                    cache, method = self._cache_verdict(before, compile_ms)
                # "persistent_dir": XLA's own persistent cache served
                # the compile (the executable tier's designed fallback)
                source = "persistent_dir" if cache == "hit" else "compiled"
                if exec_key is not None:
                    if cache in ("miss", "off", "below_threshold"):
                        ec.store(exec_key, compiled)  # best-effort, counted
                    else:
                        # served (or unverifiable, remote-dir "unknown"):
                        # a persistent-cache-served executable does NOT
                        # serialize round-trippably on XLA:CPU (missing
                        # function symbols at deserialize) — only fresh
                        # compiles are stored; the persistent dir keeps
                        # serving this program at restart regardless
                        ec.note_skipped_served()
            mem = memory_analysis_dict(compiled)
            ops = hlo_op_counts(lowered_text, compiled)
        except Exception as e:
            self._log(f"[programs] could not observe-compile {name!r} "
                      f"({e!r}); plain jit dispatch serves it (no program "
                      f"record)")
            return None
        if self.goodput is not None:
            # program-acquisition cost (trace + compile-or-deserialize):
            # the MTTR compile component a restarted process pays
            try:
                self.goodput.add_compile((lower_ms + compile_ms) / 1e3)
            except Exception:
                pass  # accounting must never kill the compile path
        self._record(name, sig, lower_ms, compile_ms, fingerprint, cache,
                     method, mem, source, ops)
        return compiled

    def _record(self, name, sig, lower_ms, compile_ms, fingerprint,
                cache, method, mem, source: str = "compiled",
                ops: Optional[dict] = None) -> None:
        with self._lock:
            entries = self.programs.setdefault(name, [])
            self._detect_retrace(name, entries, sig)
            entry = {"variant": len(entries),
                     "compile_ms": round(compile_ms, 2),
                     "lower_ms": round(lower_ms, 2),
                     "fingerprint": fingerprint,
                     "cache": cache, "cache_method": method,
                     "cache_source": source,
                     "avals": _sig_text(sig) if sig else "",
                     "_sig": sig}
            if mem:
                entry.update(mem)
            if ops:
                # manifest-only (not in the JSONL program event): what
                # chip_smoke.py reads to prove kernels and collectives
                # are in the program that ran
                entry["hlo_ops"] = ops
            entries.append(entry)
        if self.recorder is not None:
            ev = {"name": name, "lowerings": len(entries),
                  "variant": entry["variant"],
                  "compile_ms": entry["compile_ms"],
                  "lower_ms": entry["lower_ms"],
                  "fingerprint": entry["fingerprint"],
                  "cache": entry["cache"],
                  "cache_method": entry["cache_method"],
                  "cache_source": entry["cache_source"],
                  "avals": entry["avals"]}
            if mem:
                ev.update(mem)
            self.recorder.record_event("program", **ev)

    def _detect_retrace(self, name, entries, sig) -> None:
        """Called under the lock BEFORE the new entry lands.  Three
        accidental-retrace classes (module docstring); legitimate shape
        variants pass silently."""
        reason = None
        prev = None
        if sig is not None:
            for e in entries:
                if e["_sig"] == sig:
                    reason, prev = "duplicate-avals", e
                    break
                if (e["_sig"] is not None
                        and _sig_shapes(e["_sig"]) == _sig_shapes(sig)):
                    reason, prev = "dtype-or-weak-type-leak", e
                    break
        if (reason is None and len(entries) + 1 > self.max_variants
                and name not in self._variant_flood_warned):
            self._variant_flood_warned.add(name)
            reason = "variant-flood"
        if reason is None:
            return
        msg = (f"program {name!r} re-traced ({reason}): lowering "
               f"#{len(entries) + 1}, avals "
               f"[{_sig_text(sig) if sig else '?'}]"
               + (f" vs prior [{prev['avals']}]" if prev else "")
               + " — an accidental retrace re-pays the whole compile "
                 "(check for a non-weak-type scalar or shape leak)")
        warnings.warn(msg, stacklevel=3)
        self._log(f"[programs] WARNING: {msg}")
        ev = {"name": name, "reason": reason,
              "lowerings": len(entries) + 1,
              "avals": _sig_text(sig) if sig else "",
              "prev_avals": prev["avals"] if prev else ""}
        self.retraces.append(ev)
        if self.recorder is not None:
            self.recorder.record_event("retrace", **ev)

    # -- cache + fingerprint ----------------------------------------------

    def _lowered_text(self, lowered) -> str:
        """The lowered module's text — hashed into the HLO fingerprint
        and scanned for kernel custom calls; "" under the
        FDT_HLO_FINGERPRINT=0 escape or on failure."""
        if os.environ.get(ENV_FINGERPRINT, "1") == "0":
            return ""
        try:
            return lowered.as_text()
        except Exception:
            return ""

    @staticmethod
    def _cache_config() -> Tuple[Optional[str], float]:
        import jax

        d = getattr(jax.config, "jax_compilation_cache_dir", None)
        mn = getattr(jax.config,
                     "jax_persistent_cache_min_compile_time_secs", 1.0)
        return d or None, float(mn or 0.0)

    def _cache_listing(self) -> Optional[set]:
        d, _ = self._cache_config()
        if not d or "://" in d or not os.path.isdir(d):
            return None
        try:
            return set(os.listdir(d))
        except OSError:
            return None

    def _cache_verdict(self, before: Optional[set],
                       compile_ms: float) -> Tuple[str, str]:
        """(verdict, method): "miss" = a new cache entry appeared (this
        compile paid full price and stored it), "hit" = no new entry and
        the compile was above the store threshold (served from cache),
        "below_threshold" = too fast to ever be stored, "off" = no cache
        configured at all, "unknown" = a cache IS configured but cannot
        be stat'd (a remote gs:// cache dir) and the compile was above
        the store threshold — hit and miss are indistinguishable from
        timing alone there.  The method field records which rule
        produced the verdict ("dir_stat" vs "timing_threshold")."""
        d, min_secs = self._cache_config()
        after = self._cache_listing()
        if before is None or after is None:
            if not d:
                return "off", "none"
            # a cache dir exists but can't be stat'd (object store URI):
            # the threshold heuristic is all we have
            return (("below_threshold"
                     if compile_ms < min_secs * 1e3 else "unknown"),
                    "timing_threshold")
        if after - before:
            return "miss", "dir_stat"
        if compile_ms < min_secs * 1e3:
            return "below_threshold", "dir_stat"
        return "hit", "dir_stat"

    # -- the run-level table ----------------------------------------------

    def summary(self) -> dict:
        """The manifest section: per program name, lowerings + every
        variant's compile record; plus the retrace list and the run's
        total compile spend."""
        with self._lock:
            progs = []
            total_ms = 0.0
            for name, entries in sorted(self.programs.items()):
                variants = [{k: v for k, v in e.items() if k != "_sig"}
                            for e in entries]
                total_ms += sum(e["compile_ms"] for e in entries)
                progs.append({"name": name, "lowerings": len(entries),
                              "variants": variants})
            return {"programs": progs,
                    "retraces": list(self.retraces),
                    "total_compile_ms": round(total_ms, 1)}


class ObservedJit:
    """A jitted callable under observation: the first call per input
    signature goes through the observatory's explicit lower/compile;
    every later call goes straight to the AOT executable.

    ``sig_argnums`` names the positional args whose avals may legally
    vary between calls (the batch; text buckets compile one variant per
    width) — everything else (the train state) is signature-stable by
    contract.  If that contract is ever violated the AOT call raises
    before executing (donation untouched), the wrapper re-observes, and
    the duplicate lowering surfaces as a ``retrace`` event — the
    detector and the dispatcher are the same mechanism.  Any observe
    failure degrades permanently to plain jit dispatch for this
    program."""

    def __init__(self, name: str, jitted, observatory: ProgramObservatory,
                 sig_argnums: Tuple[int, ...] = ()):
        self.name = name
        self._jit = jitted
        self._obs = observatory
        self._sig_argnums = tuple(sig_argnums)
        self._by_sig: Dict[tuple, Any] = {}
        self._single = None        # the fast path while one variant exists
        self._fallback = False

    def __call__(self, *args):
        if self._fallback:
            return self._jit(*args)
        one = self._single
        if one is not None:
            try:
                return one(*args)
            except (TypeError, ValueError):
                # signature changed under us (both checks run BEFORE
                # execution, so donated buffers are untouched): resolve
                # through the slow path below
                pass
        sig = args_signature(args, self._sig_argnums)
        fn = self._by_sig.get(sig)
        if fn is not None:
            try:
                return fn(*args)
            except (TypeError, ValueError):
                # a non-signature arg's avals moved (the state): the
                # re-observe below records the duplicate as a retrace
                fn = None
        fn = self._obs.observe_compile(self.name, self._jit, args, sig=sig)
        if fn is None:
            self._fallback = True
            return self._jit(*args)
        self._by_sig[sig] = fn
        self._single = fn if len(self._by_sig) == 1 else None
        return fn(*args)


def wrap_jit(name: str, jitted, sig_argnums: Tuple[int, ...] = ()):
    """Wrap through the process-global observatory when one is active;
    identity otherwise (zero overhead for library use without
    telemetry)."""
    obs = get_observatory()
    if obs is None:
        return jitted
    return obs.wrap(name, jitted, sig_argnums=sig_argnums)


# -- HBM attribution ------------------------------------------------------

# the state table's field vocabulary, shared with the telemetry schema
# registry (scripts/check_telemetry_schema.py resolves the
# record_event("memory", **state_bytes_table(...)) splat through this
# tuple — renaming a field here without the registry fails tier-1)
STATE_MEMORY_FIELDS = (
    "scope", "params_bytes_per_chip", "params_leaves",
    "opt_state_bytes_per_chip", "opt_state_leaves",
    "batch_stats_bytes_per_chip", "batch_stats_leaves",
    "total_bytes_per_chip", "top_leaves", "opt_state_tiers",
    "pp_residency")


def leaf_bytes_per_chip(leaf) -> int:
    """Bytes ONE chip holds for this leaf: the sum of its addressable
    shards on a single device (replicated leaf -> full nbytes; a leaf
    sharded tp-ways -> nbytes/tp).  Host numpy leaves (a just-restored
    state) count their full size — they land replicated."""
    shards = getattr(leaf, "addressable_shards", None)
    if shards:
        dev = shards[0].device
        return int(sum(s.data.nbytes for s in shards if s.device == dev))
    return int(getattr(leaf, "nbytes", 0))


def leaf_spec_axes(leaf) -> set:
    """The set of mesh axis names a live leaf's PartitionSpec uses
    (tuple entries flattened); empty for replicated/host leaves.  The
    r23 pp-residency column of the HBM table is built from it."""
    sh = getattr(leaf, "sharding", None)
    spec = getattr(sh, "spec", None)
    axes: set = set()
    if spec is None:
        return axes
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            axes.update(a for a in entry if a)
        else:
            axes.add(entry)
    return axes


def leaf_tier(leaf) -> str:
    """Placement tier of one live leaf, for the ZeRO per-leaf
    attribution: 'offloaded' (pinned_host memory kind), 'sharded'
    (split across devices), 'replicated' (full copy per chip), or
    'host' (plain numpy — a restored-not-yet-placed state)."""
    sh = getattr(leaf, "sharding", None)
    if sh is None:
        return "host"
    if getattr(sh, "memory_kind", None) == "pinned_host":
        return "offloaded"
    try:
        if sh.is_fully_replicated:
            return "replicated"
    except Exception:
        pass
    return "sharded"


def state_bytes_table(state, top: int = 5) -> dict:
    """Per-chip byte attribution of a TrainState, split params vs
    opt_state vs batch_stats.  ``opt_state_bytes_per_chip`` is the
    number ROADMAP's ZeRO item sized its win against (r15 committed the
    replicated baseline; the ZeRO overlay's drop is measured from it);
    ``top_leaves`` names the largest individual leaves with their
    placement tier, and ``opt_state_tiers`` attributes every opt-state
    leaf to its sharded/replicated/offloaded tier so the ZeRO layout is
    auditable per run."""
    import jax

    out: dict = {"scope": "state"}
    sized: List[Tuple[int, str, str]] = []
    total = 0
    tiers: Dict[str, Dict[str, int]] = {}
    # r23 per-stage residency column: how many leaves of each group
    # actually occupy a pp coordinate, and how many bytes one chip
    # holds for them — the per-run record that ~1/S of the stage-owned
    # state lives on each stage (all zeros on every pp=1 or
    # --no_pp_residency run)
    ppres: Dict[str, Dict[str, int]] = {}
    for group in ("params", "opt_state", "batch_stats"):
        tree = getattr(state, group, None)
        flat = jax.tree_util.tree_flatten_with_path(tree)[0]
        b = 0
        pp_leaves = pp_bytes = 0
        for path, leaf in flat:
            n = leaf_bytes_per_chip(leaf)
            b += n
            tier = leaf_tier(leaf)
            sized.append((n, group + jax.tree_util.keystr(path), tier))
            if "pp" in leaf_spec_axes(leaf):
                pp_leaves += 1
                pp_bytes += n
            if group == "opt_state":
                agg = tiers.setdefault(tier,
                                       {"leaves": 0, "bytes_per_chip": 0})
                agg["leaves"] += 1
                agg["bytes_per_chip"] += n
        out[f"{group}_bytes_per_chip"] = b
        out[f"{group}_leaves"] = len(flat)
        ppres[group] = {"leaves": pp_leaves, "bytes_per_chip": pp_bytes}
        total += b
    out["total_bytes_per_chip"] = total
    out["top_leaves"] = [
        {"path": p, "bytes_per_chip": n, "tier": t}
        for n, p, t in sorted(sized, reverse=True)[:top]]
    out["opt_state_tiers"] = tiers
    out["pp_residency"] = ppres
    return out


# -- sharding drift guard -------------------------------------------------

def sharding_table(state) -> Dict[str, str]:
    """{leaf path: sharding descriptor} over the whole train state —
    the debug-mode side of the drift guard (a drift names its leaves).
    Host (numpy) leaves read "host": a restored-but-not-yet-re-placed
    state legitimately differs from the live one, which is why the
    Trainer re-anchors the fingerprint after every restore instead of
    comparing across one."""
    import jax

    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        sh = getattr(leaf, "sharding", None)
        out[jax.tree_util.keystr(path)] = repr(sh) if sh is not None \
            else "host"
    return out


def sharding_fingerprint(state) -> str:
    """Cheap always-on hash of the live state's actual shardings —
    computed after step 1 and re-checked at epoch boundaries by the
    Trainer.  The r11 bug class this guards: without the output
    constraint, XLA re-sharded donated params between steps (measured:
    pos_embedding drifted onto sp after step 1); the constraint fixed
    it, this keeps it fixed."""
    h = hashlib.sha1()
    for path, desc in sorted(sharding_table(state).items()):
        h.update(path.encode())
        h.update(desc.encode())
    return h.hexdigest()[:16]
