"""Persistent EXECUTABLE cache: restart-time compiles become reads.

r14 made recovery slice-granular and r15 made every compile measurable;
this module closes the loop ROADMAP calls "instant restart": on real
hardware a restarted (or rejoining, or warm-spare) process spends its
MTTR almost entirely re-building XLA executables it has compiled many
times before.  The r15 compile observatory already owns the exact seam
— an explicit ``lower()``/``compile()`` per program — so this tier
slots in as a lookup-before-compile / store-after-compile hook
(:class:`~faster_distributed_training_tpu.telemetry.programs
.ProgramObservatory`): a fresh process deserializes its (train, eval,
epoch-reshard, serve-predict) programs instead of recompiling them and
records ``cache_source="deserialized"`` per program in the manifest
``compile`` table (the A/B against ``cache_source="compiled"`` is
``scripts/pod_restart_smoke.py --cache``'s, on the CPU; restart MTTR is
not measured on the chip).

Mechanics
---------

* Entries are whole objects through the r14
  :class:`~faster_distributed_training_tpu.resilience.storage
  .StorageBackend` (atomic put, ranged read) under
  ``<checkpoint_dir>/_exec_cache/`` by default — the same durable
  medium the pod's markers and sharded checkpoints ride, so a slice
  restarting on a DIFFERENT machine (the case that matters) still finds
  them.  The payload is ``jax.experimental.serialize_executable``'s
  serialized executable framed with a magic + length header; a torn or
  truncated object fails the frame check (or the deserializer) and the
  caller falls back to a plain compile — **a corrupt cache entry must
  never block recovery** (counted in :attr:`stats`, warned once).
* The *pytree* halves of ``serialize()``'s triple (``in_tree`` /
  ``out_tree``) are deliberately NOT stored: the train state's treedef
  embeds the optax transformation (unpicklable closures), and the
  observatory has a live ``Lowered`` in hand at lookup time anyway —
  ``lowered.in_tree``/``lowered.out_tree`` are bit-identical across
  processes for the same program, so the cache stores only the
  executable bytes and re-derives the trees locally.  (Lowering still
  runs on a cache hit; tracing is the cheap half — the measured CPU
  split for the tier-1 train step is ~0.2 s deserialize vs ~2.5 s
  compile.)
* Keys: sha256 over the r15 HLO fingerprint (sha of
  ``lowered.as_text()`` — shapes, shardings, donation policy context)
  PLUS the environment the executable is only valid in: jax + jaxlib
  versions, backend, device kind and count, mesh axes/shape, the
  donation flag.  Any component moving (a jaxlib
  upgrade, a different slice topology) changes the key and the old
  entries are simply never read again.
* Where ``serialize_executable`` is unavailable or refuses a program
  (an exotic backend, a multi-controller executable an old runtime
  can't round-trip), the tier degrades to XLA's own persistent
  compilation cache directory: :func:`arm_persistent_cache` zeroes
  ``jax_persistent_cache_min_compile_time_secs`` so even sub-second
  programs (the CPU tier-1 suite, serve predict) populate and hit it —
  the r15 ``below_threshold`` verdict trap — and the observatory
  records ``cache_source="persistent_dir"`` when that tier served the
  compile.

Enablement: ``--executable_cache on`` (or an explicit directory/key
prefix), env ``FDT_EXEC_CACHE`` (``0`` kills it, ``on``/path arms it —
the smoke seam).  The cache rides the observatory, so
``FDT_PROGRAM_OBS=0`` disables it too.
"""

from __future__ import annotations

import hashlib
import os
from typing import Callable, Dict, Optional

from faster_distributed_training_tpu.resilience import storage as storage_mod

ENV_CACHE = "FDT_EXEC_CACHE"

# retention GC bounds (r19 satellite; r17 caveat "no retention GC
# yet"): the _exec_cache/ prefix is bounded by entry count AND total
# payload bytes with LRU eviction by last_used — a long-lived
# checkpoint_dir no longer accretes one executable per (HLO x
# environment) key forever.  Env overrides for tests.
ENV_MAX_ENTRIES = "FDT_EXEC_CACHE_MAX_ENTRIES"
ENV_MAX_BYTES = "FDT_EXEC_CACHE_MAX_BYTES"
DEFAULT_MAX_ENTRIES = 64
DEFAULT_MAX_BYTES = 512 * 1024 * 1024

# sidecar suffix recording an entry's last USE (hits don't rewrite the
# payload — a zero-byte touch file's mtime is the LRU clock instead)
_USED_SUFFIX = ".last_used"

# frame: magic + 8-byte big-endian payload length + payload.  Anything
# that fails the frame check is treated as corrupt and recompiled.
_MAGIC = b"FDTXEC01"


def environment_key(mesh=None, donate: Optional[bool] = None,
                    extra: str = "") -> str:
    """Fingerprint of everything OUTSIDE the HLO that an executable is
    only valid under: jax/jaxlib versions, backend + device kind/count,
    mesh axes/shape, donation flag.  A restarted slice on an
    upgraded runtime gets a clean miss, never a poisoned load."""
    import jax

    bits = [f"jax={jax.__version__}"]
    try:
        import jaxlib
        bits.append(f"jaxlib={getattr(jaxlib, '__version__', '?')}")
    except ImportError:
        bits.append("jaxlib=")
    try:
        dev = jax.local_devices()[0]
        bits.append(f"backend={jax.default_backend()}")
        bits.append(f"device={getattr(dev, 'device_kind', str(dev))}")
        bits.append(f"devices={jax.device_count()}")
    except Exception:
        bits.append("backend=?")
    if mesh is not None:
        try:
            bits.append("mesh=" + ",".join(
                f"{k}={v}" for k, v in dict(mesh.shape).items()))
        except Exception:
            bits.append(f"mesh={mesh!r}")
    if donate is not None:
        bits.append(f"donate={bool(donate)}")
    if extra:
        bits.append(str(extra))
    return hashlib.sha256("|".join(bits).encode()).hexdigest()[:16]


def _lowered_devices(lowered):
    """The device assignment ``lowered`` was lowered for (the devices of
    its shardings / mesh).  jax.stages.Lowered has no public accessor
    for it on jax 0.9.0; None (jax's all-local-devices default) if the
    private one ever moves."""
    devs = getattr(getattr(lowered, "_lowering", None), "_device_list",
                   None)
    return list(devs) if devs else None


def serialize_available() -> bool:
    """Whether this jax ships the executable serialization API at all
    (the per-program round-trip can still fail; callers degrade)."""
    try:
        from jax.experimental import serialize_executable  # noqa: F401
        return True
    except ImportError:
        return False


def arm_persistent_cache() -> None:
    """Satellite fix for the r15 ``below_threshold`` verdict trap: with
    the executable cache armed, the persistent compilation cache is the
    DESIGNED fallback tier — but its default 1 s store floor
    (``jax_persistent_cache_min_compile_time_secs``, set by
    ``cli.enable_compilation_cache``) means every sub-second program
    (the whole CPU tier-1 suite, serve predict) neither populates nor
    hits it.  Zero the floor so the fallback tier actually serves the
    programs the executable tier exists for."""
    import jax

    try:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    except Exception:
        pass  # an exotic jax without the knob keeps its default


class ExecutableCache:
    """Serialized-executable store keyed by (HLO fingerprint ×
    environment), read/written through a StorageBackend.

    All methods are best-effort by contract: :meth:`load` returns None
    on ANY failure (missing, torn, version-skewed, deserializer error)
    and :meth:`store` swallows its own; the observatory's compile path
    must be exactly as available with the cache as without it."""

    def __init__(self, directory: str,
                 backend: Optional[storage_mod.StorageBackend] = None,
                 mesh=None, donate: Optional[bool] = None,
                 log: Callable[[str], None] = print,
                 max_entries: Optional[int] = None,
                 max_bytes: Optional[int] = None):
        self.directory = os.path.abspath(directory)
        self.backend = backend if backend is not None \
            else storage_mod.posix_backend()
        self.env_key = environment_key(mesh=mesh, donate=donate)
        self._log = log
        self._warned: set = set()
        self.max_entries = int(
            os.environ.get(ENV_MAX_ENTRIES, "") or
            (DEFAULT_MAX_ENTRIES if max_entries is None else max_entries))
        self.max_bytes = int(
            os.environ.get(ENV_MAX_BYTES, "") or
            (DEFAULT_MAX_BYTES if max_bytes is None else max_bytes))
        self.stats: Dict[str, int] = {
            "hits": 0, "misses": 0, "stores": 0, "corrupt": 0,
            "store_failures": 0, "skipped_served": 0, "evicted": 0}
        self.backend.ensure_dir(self.directory)

    # -- keys --------------------------------------------------------------

    def key_for(self, name: str, fingerprint: str) -> str:
        """Object key for one program: the HLO fingerprint crossed with
        the environment key; the (sanitized) program name rides along
        for human-debuggable listings only."""
        digest = hashlib.sha256(
            f"{fingerprint}|{self.env_key}".encode()).hexdigest()[:24]
        safe = "".join(c if c.isalnum() else "-" for c in name)[:40]
        return os.path.join(self.directory, f"exec_{safe}_{digest}")

    # -- load / store ------------------------------------------------------

    def load(self, key: str, lowered):
        """Deserialize the executable at ``key`` for this ``lowered``
        program (whose in/out trees supply the pytree halves the store
        deliberately omits).  None on miss OR on any failure — recovery
        must degrade to a plain compile, never block on a bad entry."""
        try:
            raw = self.backend.read_bytes(key)
        except (OSError, ValueError):
            self.stats["misses"] += 1
            return None
        try:
            if len(raw) < 16 or raw[:8] != _MAGIC:
                raise ValueError("bad frame magic")
            n = int.from_bytes(raw[8:16], "big")
            if len(raw) != 16 + n:
                raise ValueError(f"truncated entry ({len(raw) - 16}/{n} "
                                 f"payload bytes)")
            from jax.experimental import serialize_executable as se
            # load over the devices the program was LOWERED for: left
            # to its default, jax 0.9 loads over every local device and
            # a program for fewer devices than the host has is refused
            compiled = se.deserialize_and_load(
                raw[16:], lowered.in_tree, lowered.out_tree,
                execution_devices=_lowered_devices(lowered))
        except Exception as e:
            self.stats["corrupt"] += 1
            self._warn_once(
                "corrupt", f"[exec_cache] entry {os.path.basename(key)} "
                f"failed to deserialize ({e!r}); recompiling (a corrupt "
                f"cache entry never blocks recovery)")
            return None
        self.stats["hits"] += 1
        self._touch(key)
        return compiled

    def store(self, key: str, compiled) -> bool:
        """Serialize + publish one executable (atomic whole-object put).
        Best-effort: a backend/serializer failure is counted + warned
        once, never raised into the compile path."""
        try:
            from jax.experimental import serialize_executable as se
            payload, _in_tree, _out_tree = se.serialize(compiled)
            self.backend.put_bytes(
                key, _MAGIC + len(payload).to_bytes(8, "big") + payload)
        except Exception as e:
            self.stats["store_failures"] += 1
            self._warn_once(
                "store", f"[exec_cache] could not store "
                f"{os.path.basename(key)} ({e!r}); this program recompiles "
                f"on the next restart")
            return False
        self.stats["stores"] += 1
        self.gc()
        return True

    # -- retention GC ------------------------------------------------------

    def _touch(self, key: str) -> None:
        """Best-effort LRU clock tick: a hit refreshes the entry's
        ``.last_used`` sidecar mtime instead of rewriting the payload."""
        try:
            self.backend.put_bytes(key + _USED_SUFFIX, b"")
        except Exception:
            pass

    def _last_used(self, key: str) -> float:
        """last_used for LRU ordering: the sidecar's mtime when present
        (a hit touched it), else the entry's own (its store time)."""
        try:
            if self.backend.exists(key + _USED_SUFFIX):
                return self.backend.mtime(key + _USED_SUFFIX)
        except Exception:
            pass
        try:
            return self.backend.mtime(key)
        except Exception:
            return 0.0

    def entries(self):
        """[(key, bytes, last_used)] for every cache entry under the
        directory (sidecars excluded)."""
        out = []
        try:
            keys = self.backend.list_prefix(
                self.backend.join(self.directory, "exec_"))
        except Exception:
            return out
        for k in keys:
            if k.endswith(_USED_SUFFIX):
                continue
            try:
                out.append((k, self.backend.size(k), self._last_used(k)))
            except Exception:
                continue
        return out

    def gc(self) -> int:
        """Retention GC (r19 satellite): keep the most-recently-used
        entries while count <= max_entries and total bytes <= max_bytes;
        evict the LRU tail (entry + sidecar).  Best-effort like every
        other method — a GC failure must never block the compile path.
        Returns the number of entries evicted."""
        ents = self.entries()
        if not ents:
            return 0
        ents.sort(key=lambda e: e[2], reverse=True)   # newest first
        evicted = 0
        kept = total = 0
        for key, nbytes, _ in ents:
            kept += 1
            total += nbytes
            # the MRU entry always survives, even past the byte bound:
            # evicting a single over-budget executable right after its
            # own store would permanently disable the cache for that
            # program (every restart recompiling while stats show
            # stores and evictions balancing)
            if kept == 1 or (kept <= self.max_entries
                             and total <= self.max_bytes):
                continue
            try:
                self.backend.delete(key)
                try:
                    self.backend.delete(key + _USED_SUFFIX)
                except Exception:
                    pass
                evicted += 1
            except Exception:
                continue
        if evicted:
            self.stats["evicted"] += evicted
            self._warn_once(
                "gc", f"[exec_cache] retention GC evicted {evicted} LRU "
                f"entr{'y' if evicted == 1 else 'ies'} (bounds: "
                f"{self.max_entries} entries / {self.max_bytes >> 20} "
                f"MiB; {ENV_MAX_ENTRIES}/{ENV_MAX_BYTES} override)")
        return evicted

    def note_skipped_served(self) -> None:
        """The observatory declined to store an executable because the
        compile was SERVED from XLA's persistent cache dir rather than
        compiled fresh (measured on this container's XLA:CPU: a
        cache-served executable serializes to a payload missing its
        compiled function symbols — ``Symbols not found`` at
        deserialize; only fresh compiles round-trip).  Not a failure:
        the persistent dir itself keeps serving such programs at
        restart (cache_source="persistent_dir"), and the executable
        tier populates the first time the program compiles against
        cold caches."""
        self.stats["skipped_served"] += 1

    def _warn_once(self, topic: str, msg: str) -> None:
        if topic not in self._warned:
            self._warned.add(topic)
            self._log(msg)


def build_executable_cache(cfg, backend=None, mesh=None,
                           log: Callable[[str], None] = print
                           ) -> Optional[ExecutableCache]:
    """ExecutableCache from a TrainConfig, or None when disabled.

    ``--executable_cache``: ``""``/``off`` = disabled (default), ``on``
    = ``<checkpoint_dir>/_exec_cache`` through the run's storage
    backend, anything else = an explicit directory.  ``FDT_EXEC_CACHE``
    overrides (``0`` = force off — the kill switch; ``on``/path = force
    on, the smoke seam).  Arming the cache also zeroes the
    persistent-compilation-cache store floor (:func:`arm_persistent_
    cache`) so the fallback tier serves sub-second programs."""
    spec = (getattr(cfg, "executable_cache", "") or "").strip()
    env = os.environ.get(ENV_CACHE, "").strip()
    if env == "0":
        return None
    if env:
        spec = env
    if spec in ("", "off", "0"):
        return None
    if spec in ("on", "1"):
        directory = os.path.join(
            getattr(cfg, "checkpoint_dir", "."), "_exec_cache")
    else:
        directory = spec
    if not serialize_available():
        log("[exec_cache] jax.experimental.serialize_executable is "
            "unavailable in this environment — the executable tier is "
            "off; the persistent compilation cache (store floor zeroed) "
            "is the only restart-compile tier this run")
        arm_persistent_cache()
        return None
    arm_persistent_cache()
    cache = ExecutableCache(directory, backend=backend, mesh=mesh,
                            donate=bool(getattr(cfg, "donate", True)),
                            log=log)
    cache.gc()    # a long-lived prefix shrinks to bounds at arm time
    log(f"[exec_cache] persistent executable cache armed at {directory} "
        f"(env key {cache.env_key}; a restarted process deserializes "
        f"its programs instead of recompiling)")
    return cache
