"""Pod-coordinated restart protocol + cluster health watchdog.

The r7 supervisor restarts *the process it lives in*.  On a multi-host
pod that is not enough: a crash on one host leaves its peers blocked
forever inside the next collective — the dominant badput source the
large-scale systems literature identifies (MegaScale's hang/partial-
failure taxonomy, Pathways' single-controller failure handling): no
process INSIDE a blocked collective can observe that a peer died.  Two
cooperating pieces close the gap, both living on the shared checkpoint
filesystem (the same marker-file idiom as the r9 two-phase commit — the
one medium every host can reach without a working collective):

  * **Restart coordination protocol** (:class:`PodCoordinator`): a
    monotonically increasing *generation* directory
    ``_pod/gen_<g>/``.  A host that fails locally writes ``FAIL_<pi>``
    into the current generation; every host polls the failure markers at
    the preemption-sync cadence, abandons the attempt
    (:class:`PeerFailure`) and re-enters ``Supervisor.run`` — whose next
    attempt computes the SAME next generation (1 + the newest generation
    carrying a FAIL marker) on every host, so the pod converges on one
    restart.  Each attempt then restores through ``restore_latest``'s
    cross-host step-agreement, so all hosts provably resume from the
    same checkpoint step; the (seed, epoch, step)-pure batch order means
    the data iterators re-agree on position for free (pinned by
    tests/test_pod_restart.py, not assumed).

  * **Health watchdog**: a per-host heartbeat thread touches
    ``HB_<pi>`` with the current step every ``hb_interval_s`` seconds;
    :meth:`check` flags a peer whose heartbeat is stale past
    ``peer_timeout_s`` (the host died without writing FAIL — SIGKILL,
    kernel panic, machine loss).  The same thread watches the LOCAL
    step clock: a dispatch exceeding ``step_timeout_s`` means this
    host's main thread is wedged (hung device program, a collective
    blocked on a dead peer) — the watchdog is the only thing still able
    to act, so it escalates by durably writing its own ``FAIL`` marker
    (kind="hang") and hard-aborting the process; the peers observe the
    marker (or the heartbeat going stale) and the pod converges on a
    restart instead of deadlocking.

Detection/restore latencies feed the goodput tracker (``detect_s``,
``restore_s``, ``restart_backoff_s`` → ``restart_mttr_s``) so MTTR is a
first-class metric beside goodput_pct.

Simulation seam (mirrors the r9 manager seam): ``process_index`` /
``process_count`` default to the real jax runtime but can be overridden
— two coordinators sharing one directory ARE a simulated two-host pod,
and :func:`pod_identity` reads ``FDT_POD_INDEX``/``FDT_POD_COUNT`` so
the pod_restart_smoke script can run a REAL two-process simulated pod
(coordination cross-process through the fs; jax stays single-process
per host, so each host computes the identical full state).  In that
fs-simulated mode :meth:`gather_restored_step` supplies the restore
step-agreement barrier that real pods get from the jax collective.

Clock caveat: marker timestamps are host wall clocks; the detect_s
latency derived from a PEER's marker is exact in the single-machine
simulations and subject to NTP skew across real hosts (seconds — noise
against multi-second detection cadences, documented rather than
hidden).

r14 — storage backend + slices: every marker read/write/list routes
through a :class:`~faster_distributed_training_tpu.resilience.storage.
StorageBackend`, so the ``_pod/gen_<g>/`` namespace can live on an
object store when the pod's slices do not share a filesystem (the
tier-1 fake object store proves the protocol needs no rename
primitive).  ``FDT_SLICE_INDEX``/``FDT_SLICE_COUNT``
(:func:`slice_identity`) partition the pod into slices with
slice-qualified marker names, and a failure confined to ONE foreign
slice no longer forces a whole-pod restart: the survivors park in a
bounded ``await_readmission`` hold (HOLD markers carrying their step),
the restarted slice REJOINS the incident's generation
(``begin_attempt`` detects own-slice-only FAILs), restores through a
slice-scoped barrier, catches up to the agreed target (max over
survivor holds — provably >= the restored checkpoint step) and joins
the ``RJREADY`` readiness barrier; every host then advances the
generation in place and resumes.  Whole-pod restart remains the
fallback for every ambiguous corner: hold/rejoin timeout, a second
failure outside the incident slice, or rejoin-retry residue (the
durable ``RJ_ABORT`` marker degrades everyone to the r10 protocol).

r17 — warm spares: a STANDBY process (``FDT_SLICE_SPARE=<id>`` /
``--warm_spares N``, :func:`spare_identity`) parks outside the pod —
mesh built, programs warmed through the persistent executable cache,
params restored to the last COMMIT and refreshed at each new one —
and, when an incident confined to one slice parks the survivors in
their hold, CLAIMS a failed seat with a durable first-writer-wins
``CLAIM`` marker (:meth:`PodCoordinator.spare_wait`) and swaps in
through the EXISTING rejoin machinery under the adopted member
identity: the survivors' ``_await_readmission`` never learns the
difference — it sees the seat's RJRENTER/RJRESTORE/RJREADY markers
as always.  A relaunch of the original host finds the CLAIM and
raises :class:`SeatTaken` (redundant by protocol, not restartable);
every post-claim ambiguity degrades through ``RJ_ABORT`` to the
whole-pod fallback like any rejoin."""

from __future__ import annotations

import os
import re
import signal
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from faster_distributed_training_tpu.resilience import storage as storage_mod

ENV_POD_INDEX = "FDT_POD_INDEX"
ENV_POD_COUNT = "FDT_POD_COUNT"
ENV_SLICE_INDEX = "FDT_SLICE_INDEX"
ENV_SLICE_COUNT = "FDT_SLICE_COUNT"
ENV_SLICE_SPARE = "FDT_SLICE_SPARE"

_GEN_DIR = re.compile(r"^gen_(?P<gen>\d{6})$")
# strict: the atomic writer stages `FAIL_<pi>.tmp<pid>` beside the real
# marker — listing-based discovery must never parse those as markers.
# Multi-slice pods qualify marker names with the slice (`FAIL_s001_00002`)
# so a per-slice observer can partition an incident without a reverse
# lookup; single-slice pods keep the r10 names byte-for-byte.
_FAIL = re.compile(r"^FAIL_(?:s(?P<si>\d{3})_)?(?P<pi>\d{5})$")
# one-per-generation rejoin-abort marker: a rejoining slice that cannot
# complete re-admission publishes it so the parked survivors fall back
# to a whole-pod restart immediately instead of waiting out their hold
_RJ_ABORT = "RJ_ABORT"


class PeerFailure(RuntimeError):
    """A peer host failed (FAIL marker observed) or went heartbeat-stale
    — this attempt is abandoned so the whole pod re-enters the
    supervisor together.  RESTARTABLE: the supervisor retries it like
    any crash (the next attempt converges on the same new generation on
    every host)."""


class SeatTaken(RuntimeError):
    """This host's pod seat was claimed by a warm spare while the host
    was down (durable first-writer-wins ``CLAIM`` marker, r17): the
    spare IS the seat now, so this relaunch is redundant by protocol.
    NOT restartable — retrying can never win the seat back; the
    supervisor re-raises it immediately (a platform that auto-relaunches
    should treat the exit as terminal for this incident, or re-launch
    the process as a fresh spare: FDT_SLICE_SPARE)."""


class StepTimeout(RuntimeError):
    """This host's own step made no progress for ``step_timeout_s`` and
    the watchdog escalated (its FAIL marker is already on the shared
    fs).  Raised by the main-thread poll when the hang RELEASES (test
    harnesses); in production the escalation hard-aborts the process
    before this can be raised — the platform's re-launch plays the
    supervisor's role."""


def pod_identity(env=os.environ) -> Tuple[int, int, bool]:
    """(process_index, process_count, simulated).

    ``FDT_POD_INDEX``/``FDT_POD_COUNT`` override the jax runtime — the
    simulation seam the pod_restart_smoke script and the tier-1 tests
    use (jax stays single-process; only the RESTART coordination and
    the checkpoint two-phase commit run cross-process).  Without them,
    the real runtime."""
    if env.get(ENV_POD_COUNT):
        return (int(env.get(ENV_POD_INDEX, "0")), int(env[ENV_POD_COUNT]),
                True)
    import jax
    return jax.process_index(), jax.process_count(), False


def slice_identity(env=os.environ, process_index: Optional[int] = None,
                   process_count: Optional[int] = None
                   ) -> Tuple[int, int, bool]:
    """(slice_index, slice_count, simulated) — the multi-SLICE seam
    beside :func:`pod_identity` (r14).

    ``FDT_SLICE_COUNT`` arms it: the pod's processes are partitioned
    into ``slice_count`` contiguous equal blocks (process ``pi`` lives
    on slice ``pi * slice_count // process_count`` — the layout real
    multislice launchers use, one process range per slice) and the
    coordinator scopes failure handling per slice: a dead slice can be
    restarted and RE-ADMITTED while the others hold, instead of forcing
    a whole-pod restart.  ``FDT_SLICE_INDEX`` overrides this host's own
    derived index for exotic layouts (the derived map still names the
    PEERS' slices, so overriding only one host inconsistently is
    unsupported — documented, not guessed around).  Without the env,
    (0, 1, False): single-slice, the r10 behavior byte-for-byte."""
    raw = env.get(ENV_SLICE_COUNT)
    if not raw:
        return 0, 1, False
    sc = int(raw)
    if sc <= 1:
        return 0, 1, False
    if process_index is None or process_count is None:
        pi, pc, _sim = pod_identity(env)
        process_index = pi if process_index is None else process_index
        process_count = pc if process_count is None else process_count
    raw_si = env.get(ENV_SLICE_INDEX)
    if raw_si not in (None, ""):
        return int(raw_si), sc, True
    return (int(process_index) * sc // max(int(process_count), 1), sc, True)


def spare_identity(env=os.environ) -> Optional[int]:
    """The warm-spare seam beside :func:`pod_identity` (r17):
    ``FDT_SLICE_SPARE=<id>`` marks this process a STANDBY spare — not
    one of the pod's ``process_count`` members, but a pre-admitted
    stand-in that parks (mesh built, programs warmed through the
    executable cache, params restored to the last COMMIT) and claims a
    failed slice's seat at re-admission time.  None = a normal member.
    ``--warm_spares N`` is the launcher-side contract: spawn N extra
    processes each carrying a distinct FDT_SLICE_SPARE id AND an
    out-of-pod ``FDT_POD_INDEX`` (``pod_count + id`` by convention —
    build_resilience derives that index regardless, but the telemetry
    recorder reads the env directly and its host JSONL file must not
    collide with a member's)."""
    raw = env.get(ENV_SLICE_SPARE)
    if raw in (None, ""):
        return None
    try:
        return int(raw)
    except ValueError:
        # fail FAST: two spares launched with malformed ids that both
        # silently mapped to 0 would share the synthetic pod index —
        # exactly the marker/shard/telemetry collision the out-of-pod
        # index exists to rule out
        raise ValueError(
            f"malformed {ENV_SLICE_SPARE}={raw!r}: want an integer "
            f"spare id (each spare process needs a DISTINCT one)")


def _write_json_atomic(path: str, obj) -> None:
    """Atomic marker write on the POSIX default backend — kept as a
    module-level helper for tests that plant markers directly; the
    coordinator itself routes every marker through its configured
    backend (r14).  The backend's staging name carries pid AND thread
    ident: heartbeats are written from both the watchdog thread and the
    main thread, and a shared staging path would let one thread's
    publish consume the other's."""
    storage_mod.posix_backend().put_json(path, obj)


def _read_json(path: str) -> Optional[dict]:
    return storage_mod.posix_backend().read_json(path)


class PodCoordinator:
    """Owns ``<directory>/gen_<g>/`` and this host's markers in it.

    Lifecycle: the supervisor calls :meth:`begin_attempt` before every
    attempt (starts the heartbeat/watchdog thread on first use) and
    :meth:`record_failure` when one dies; the train loop calls
    :meth:`check` once per dispatch (cadence-gated internally) and wraps
    each epoch in :meth:`watch_steps` so the step watchdog only runs
    while dispatches are actually expected to complete (never during
    eval or restore — heartbeats continue regardless, proving liveness
    to the peers).  ``abort_fn`` is the escalation seam: the default
    SIGKILLs the process (the main thread may be wedged in C code where
    nothing softer is guaranteed to run); tests inject a releasing
    hook."""

    def __init__(self, directory: str, process_index: Optional[int] = None,
                 process_count: Optional[int] = None, sync_every: int = 8,
                 peer_timeout_s: float = 60.0, step_timeout_s: float = 0.0,
                 hb_interval_s: float = 2.0, gather_timeout_s: float = 120.0,
                 goodput=None, log: Callable[[str], None] = print,
                 abort_fn: Optional[Callable[[str], None]] = None,
                 slice_index: Optional[int] = None,
                 slice_count: Optional[int] = None,
                 readmit_timeout_s: float = 0.0,
                 backend: Optional[storage_mod.StorageBackend] = None,
                 spare_index: Optional[int] = None):
        if process_index is None or process_count is None:
            pi, pc, _sim = pod_identity()
            process_index = pi if process_index is None else process_index
            process_count = pc if process_count is None else process_count
        self.directory = os.path.abspath(directory)
        self.pi = int(process_index)
        self.pc = int(process_count)
        # multi-slice identity (r14): slice_count>1 partitions the pod
        # into contiguous process blocks and arms slice-granular
        # re-admission (readmit_timeout_s>0); default = one slice, the
        # r10 whole-pod protocol byte-for-byte
        if slice_index is None or slice_count is None:
            si, sc, _ssim = slice_identity(
                process_index=self.pi, process_count=self.pc)
            slice_index = si if slice_index is None else slice_index
            slice_count = sc if slice_count is None else slice_count
        self.si = int(slice_index)
        self.sc = max(int(slice_count), 1)
        self.readmit_timeout_s = float(readmit_timeout_s)
        # warm-spare identity (r17): a spare is NOT one of the pod's pc
        # members — it parks under a synthetic out-of-pod index (pc +
        # spare id, so its markers can never collide with a member's)
        # until _spare_try_claim wins a failed seat and _adopt_seat
        # re-keys pi/si to the claimed member identity
        if spare_index is None:
            spare_index = spare_identity()
        self.spare_index = spare_index
        if spare_index is not None:
            self.pi = self.pc + int(spare_index)
        self._claimed: Optional[Tuple[int, int]] = None  # (gen, seat)
        self._spare_swap_t0: Optional[float] = None
        # every marker read/write/list routes through the storage
        # backend — with per-slice filesystems the backend (an object
        # store, or its tier-1 fake) IS what makes the `_pod/gen_<g>/`
        # namespace span slices
        self.backend = backend if backend is not None \
            else storage_mod.posix_backend()
        self.sync_every = max(int(sync_every), 1)
        self.peer_timeout_s = float(peer_timeout_s)
        self.step_timeout_s = float(step_timeout_s)
        self.hb_interval_s = float(hb_interval_s)
        self.gather_timeout_s = float(gather_timeout_s)
        self._goodput = goodput
        self._log = log
        self._abort = abort_fn or self._default_abort
        # slice re-admission state (all main-thread only)
        self._rejoining = False
        self._rejoin_target: Optional[int] = None
        self._release_target: Optional[int] = None
        self._align_target: Optional[int] = None
        # set by the resilience wiring to the checkpoint manager's
        # ``wait`` — a survivor drains its in-flight background save
        # before publishing HOLD (see _await_readmission)
        self.drain_fn: Optional[Callable[[], None]] = None
        # EXIT markers older than this coordinator are a PREVIOUS run's
        # completions (the same checkpoint_dir reused to train further)
        # and must not poison this run — see _exited_peers
        self._created_t = time.time()
        self._gen: Optional[int] = None
        self._gen_dir: Optional[str] = None
        self._attempt_wall_t = time.time()
        self._last_polled = -1
        # shared with the watchdog thread (plain attrs: CPython atomic
        # loads/stores; the thread only READS them)
        self._step = 0
        self._progress_t = time.monotonic()
        self._watching = False
        self._escalated = False
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- marker paths ------------------------------------------------------

    def _gen_path(self, gen: int) -> str:
        return os.path.join(self.directory, f"gen_{gen:06d}")

    def slice_of(self, pi: int) -> int:
        """The slice a pod process lives on: contiguous equal blocks
        (the :func:`slice_identity` layout).  Own index may be
        env-overridden; peers are always the derived map."""
        if pi == self.pi:
            return self.si
        if self.sc <= 1:
            return 0
        return int(pi) * self.sc // self.pc

    def _slice_members(self, si: int) -> List[int]:
        return [p for p in range(self.pc) if self.slice_of(p) == si]

    def _marker_name(self, kind: str, pi: int) -> str:
        """Slice-qualified on multi-slice pods (``FAIL_s001_00002``),
        the bare r10 form otherwise — byte-compatible with existing
        coordination directories."""
        if self.sc > 1:
            return f"{kind}_s{self.slice_of(pi):03d}_{pi:05d}"
        return f"{kind}_{pi:05d}"

    def _marker(self, kind: str, pi: int, gen_dir: Optional[str] = None
                ) -> str:
        return os.path.join(gen_dir or self._require_gen(),
                            self._marker_name(kind, pi))

    def _require_gen(self) -> str:
        if self._gen_dir is None:
            # a caller (direct restore, record_failure before any
            # attempt) outran begin_attempt: join the protocol at the
            # generation begin_attempt would compute
            self.begin_attempt()
        return self._gen_dir

    def _generations(self) -> List[Tuple[int, str]]:
        """Generation dirs discovered through the backend's one-level
        entry listing (an object store has no directories — a
        generation exists once any marker lands in it, which
        begin_attempt's immediate heartbeat guarantees)."""
        gens = set()
        for name in self.backend.list_entries(self.directory):
            m = _GEN_DIR.match(name)
            if m:
                gens.add(int(m.group("gen")))
        return [(g, self._gen_path(g)) for g in sorted(gens)]

    def _failures(self, gen_dir: str) -> Dict[int, dict]:
        out = {}
        for n in self.backend.list_entries(gen_dir):
            m = _FAIL.match(n)
            if m:
                out[int(m.group("pi"))] = self.backend.read_json(
                    os.path.join(gen_dir, n)) or {}
        return out

    # -- restart coordination protocol -------------------------------------

    def begin_attempt(self) -> int:
        """Enter the pod's current generation: 1 + the newest generation
        holding any FAIL marker (0 on a clean directory).  Every host
        computes this from the same shared-backend state, so hosts that
        restarted for DIFFERENT reasons (own crash vs observed peer
        failure) still converge on one generation — and a fresh process
        launched into an old incident's directory joins at the incident's
        next generation rather than rewinding the counter.

        Slice re-admission (r14): when the newest incident's FAIL
        markers are confined to THIS host's slice and re-admission is
        armed, the restarting slice does NOT advance the generation —
        it re-enters the incident's generation in rejoin mode
        (``rejoining`` True) while the surviving slices are parked in
        their ``await_readmission`` hold; :meth:`rejoin_sync` completes
        the handshake.  A second rejoin attempt in the same generation
        (own rejoin residue found) aborts to the whole-pod path via the
        durable ``RJ_ABORT`` marker, so retry ambiguity always degrades
        to the proven r10 protocol rather than a racy re-rejoin."""
        g, newest_fail = 0, None
        for gen, d in self._generations():
            if self._failures(d):
                newest_fail = (gen, d)
                g = gen + 1
        self._rejoining = False
        self._rejoin_target = None
        if (self._readmit_enabled() and newest_fail is not None
                and (self._gen is None or self._gen <= newest_fail[0])):
            gen, d = newest_fail
            fails = self._failures(d)
            if all(self.slice_of(p) == self.si for p in fails):
                mine = os.path.join(d, self._marker_name("RJRENTER", self.pi))
                # r17 warm spares: the seat is arbitrated through ONE
                # atomic point — the same first-writer-wins CLAIM
                # create_if_absent a spare uses.  A check-then-proceed
                # here would race a spare's claim in the gap between
                # this relaunch's begin_attempt and its first durable
                # rejoin marker (both processes would then drive the
                # seat's barriers under one identity), so the ORIGINAL
                # claims its own seat too; losing means a spare owns it.
                if not self._claim_own_seat(d, gen):
                    claim = os.path.join(
                        d, self._marker_name("CLAIM", self.pi))
                    got = self.backend.read_json(claim) or {}
                    raise SeatTaken(
                        f"pod seat {self.pi} (slice {self.si}) was "
                        f"claimed by warm spare "
                        f"{got.get('spare', '?')} in generation {gen} — "
                        f"the spare swapped in for this incident and "
                        f"this relaunch is redundant (re-launch with "
                        f"FDT_SLICE_SPARE to park as the new spare)")
                if self.backend.exists(os.path.join(d, _RJ_ABORT)):
                    pass          # a slice member already aborted rejoin
                elif self.backend.exists(mine):
                    # own rejoin residue: this slice already tried to
                    # rejoin this generation and died mid-handshake —
                    # publish the abort so survivors stop holding, then
                    # take the whole-pod path
                    self._rejoin_abort(d, "rejoin retry in generation "
                                          f"{gen} — falling back")
                else:
                    g = gen
                    self._rejoining = True
        if self._gen is not None:
            if (not self._rejoining and g > self._gen
                    and self._goodput is not None):
                self._goodput.count("restart_generations", g - self._gen)
            g = max(g, self._gen) if not self._rejoining else g
        changed = g != self._gen
        self._gen = g
        self._gen_dir = self._gen_path(g)
        self.backend.ensure_dir(self._gen_dir)
        # an attempting host is by definition not done: clear our own
        # completion marker (a previous run's residue when the same
        # checkpoint_dir is relaunched; peers also time-scope what
        # they honor — _exited_peers)
        self.backend.delete(
            os.path.join(self.directory, self._marker_name("EXIT", self.pi)))
        self._attempt_wall_t = time.time()
        self._last_polled = -1
        self._escalated = False
        self._progress_t = time.monotonic()
        self._write_heartbeat()
        if changed or self._rejoining:
            self._log(f"[pod] host {self.pi}/{self.pc} "
                      + (f"REJOINING generation {g} (slice {self.si} "
                         f"re-admission)" if self._rejoining
                         else f"entering generation {g}"))
        self._ensure_thread()
        self._prune_generations()
        return g

    def _claim_own_seat(self, gen_dir: str, gen: int) -> bool:
        """The relaunched ORIGINAL's side of seat arbitration (r17):
        claim our own seat through the same first-writer-wins
        ``create_if_absent`` a spare uses — winning (or finding our own
        previous claim: a rejoin retry, or the spare re-entering
        begin_attempt post-adoption) means the seat is ours; losing to
        a spare's claim means standing down (SeatTaken at the caller).
        An unreadable existing claim is treated as spare-owned: with
        the seat's ownership ambiguous, a redundant stand-down is safe
        and a double identity is not."""
        if self._claimed == (gen, self.pi):
            return True          # the adopted spare re-entering
        import json
        key = os.path.join(gen_dir, self._marker_name("CLAIM", self.pi))
        try:
            won = self.backend.create_if_absent(
                key, json.dumps({"pi": self.pi, "spare": None,
                                 "unix_time": round(time.time(), 3)}
                                ).encode("utf-8"))
        except OSError:
            return False         # can't arbitrate -> don't take the seat
        if won:
            self._claimed = (gen, self.pi)
            return True
        got = self.backend.read_json(key)
        if got is not None and got.get("spare") is None \
                and got.get("pi") == self.pi:
            # our OWN earlier claim (a previous rejoin attempt of this
            # same relaunched host) — the seat is still ours; the
            # RJRENTER-residue check below decides retry vs RJ_ABORT
            self._claimed = (gen, self.pi)
            return True
        return False

    def record_failure(self, exc: BaseException,
                       step: Optional[int] = None) -> None:
        """Durably publish this host's failure to the pod (atomic marker
        write).  Best-effort: a failing shared fs must not mask the
        original exception."""
        kind = ("hang" if isinstance(exc, StepTimeout)
                else "peer" if isinstance(exc, PeerFailure) else "crash")
        try:
            self._write_fail(kind, f"{type(exc).__name__}: {exc}", step)
        except OSError as e:
            self._log(f"[pod] host {self.pi}: could not write FAIL marker "
                      f"({e!r}) — peers will detect via heartbeat staleness")

    def record_completion(self, step: Optional[int] = None) -> None:
        """Durably mark this host's run COMPLETE (``EXIT_<pi>`` at the
        coordination-directory ROOT, outside any generation, so it
        survives generation pruning).  Written by the supervisor on a
        successful run.  An exited peer is success, not failure: the
        staleness monitor ignores it (hosts finish at slightly
        different times — its heartbeat going quiet must not restart
        the stragglers), but the restore-agreement barrier fails FAST
        on it — a host restarting after a peer already finished can
        never rejoin the pod, and learning that immediately beats
        waiting out gather_timeout_s per attempt."""
        try:
            self.backend.put_json(
                os.path.join(self.directory,
                             self._marker_name("EXIT", self.pi)),
                {"step": self._step if step is None else int(step),
                 "unix_time": round(time.time(), 3)})
        except OSError as e:
            self._log(f"[pod] host {self.pi}: could not write EXIT marker "
                      f"({e!r}) — a later-restarting peer will wait out "
                      f"its restore barrier instead of failing fast")

    def _exited_peers(self) -> List[int]:
        """Peers that completed THIS run: EXIT markers newer than this
        coordinator's creation.  An older marker is a PREVIOUS run's
        completion (the same checkpoint_dir relaunched to train
        further) — honoring it would permanently disable staleness
        detection for that peer and fail fresh restore barriers with
        "pod already finished", so it is ignored (and each host deletes
        its own stale marker in begin_attempt).  The in-process
        supervisor restart — the path the fail-fast exists for — keeps
        its coordinator across attempts, so a peer completing mid-run
        always postdates it.  Cross-host NTP skew (seconds) is noise
        against the run-length gap that separates the two cases."""
        out = []
        for pi in range(self.pc):
            if pi == self.pi:
                continue
            got = self.backend.read_json(
                os.path.join(self.directory, self._marker_name("EXIT", pi)))
            if got is not None and got.get("unix_time", 0.0) > self._created_t:
                out.append(pi)
        return out

    def _write_fail(self, kind: str, reason: str,
                    step: Optional[int] = None) -> None:
        self._write_fail_for(self.pi, kind, reason, step=step)

    def _write_fail_for(self, pi: int, kind: str, reason: str,
                        step: Optional[int] = None) -> None:
        """FAIL marker under a given identity.  Besides our own
        failures, a SURVIVOR writes a proxied marker on behalf of a
        heartbeat-stale peer slice (SIGKILL/machine loss wrote nothing)
        so the relaunched slice finds a durable incident record to key
        its re-admission on."""
        payload = {"kind": kind, "reason": reason[:500],
                   "step": self._step if step is None else int(step),
                   "unix_time": round(time.time(), 3)}
        if pi != self.pi:
            payload["proxied_by"] = self.pi
        self.backend.put_json(self._marker("FAIL", pi), payload)

    def check(self, step: int) -> None:
        """Main-thread poll, called once per dispatch; raises
        :class:`PeerFailure` / :class:`StepTimeout` when the attempt
        must be abandoned.  Cadence-gated with the same boundary-
        crossing algebra as the preemption agreement bit (sync_every;
        robust to K-step dispatch boundaries), EXCEPT after a local
        watchdog escalation, which must surface on the very next poll.

        Multi-slice (r14): a rejoining slice drives its re-admission
        handshake here instead of failure polling (the incident's own-
        slice FAIL markers are residue, not news), and a survivor that
        released from its hold below the agreed target finishes the
        release once it has caught up to it."""
        self._step = int(step)
        self._progress_t = time.monotonic()
        if self._rejoining:
            self.rejoin_sync(step)
            return
        if self._release_target is not None:
            if step >= self._release_target:
                self._finish_release(self._release_target)
            return
        prev, self._last_polled = self._last_polled, step
        if not self._escalated and prev >= 0 \
                and step // self.sync_every <= prev // self.sync_every:
            return
        self._raise_observed_failures()

    def _readmit_enabled(self) -> bool:
        return self.readmit_timeout_s > 0 and self.sc > 1

    def _raise_observed_failures(self) -> None:
        gen_dir = self._require_gen()
        fails = self._failures(gen_dir)
        now = time.time()
        own = fails.pop(self.pi, None)
        if fails:
            peers = sorted(fails)
            newest = max((f.get("unix_time", now) for f in fails.values()),
                         default=now)
            detect = max(now - newest, 0.0)
            failed_slices = {self.slice_of(p) for p in fails}
            if (self._readmit_enabled() and own is None
                    and self.si not in failed_slices
                    and len(failed_slices) == 1):
                # the incident is confined to ONE foreign slice: park in
                # a bounded hold and let the platform restart + re-admit
                # that slice, instead of burning a whole-pod restart
                if self._goodput is not None:
                    self._goodput.add("detect_s", detect)
                self._await_readmission(set(fails), failed_slices.pop())
                return
            if self._goodput is not None:
                self._goodput.count("peer_failures")
                self._goodput.add("detect_s", detect)
            raise PeerFailure(
                f"host(s) {peers} failed in generation {self._gen} "
                f"({fails[peers[0]].get('kind', '?')}: "
                f"{fails[peers[0]].get('reason', '?')}); abandoning this "
                f"attempt so the pod restarts together "
                f"(observed {detect:.2f}s after the marker landed)")
        if own is not None:
            # our OWN marker with nobody else's: the watchdog escalated a
            # local hang and the abort was intercepted (test harness) —
            # surface it as the restartable fault it is
            raise StepTimeout(
                f"host {self.pi}: step watchdog escalated "
                f"({own.get('reason', 'no step progress')}); restarting")
        stale = self._stale_peers(now)
        if stale:
            pi0, age = stale[0]
            stale_slices = {self.slice_of(p) for p, _a in stale}
            if (self._readmit_enabled() and self.si not in stale_slices
                    and len(stale_slices) == 1):
                # a silently-dead foreign slice (SIGKILL/machine loss —
                # nothing was written): publish proxied FAIL markers so
                # the relaunched slice finds the incident record it
                # keys its rejoin on, then hold for re-admission
                if self._goodput is not None:
                    self._goodput.add("detect_s", age)
                for p, a in stale:
                    try:
                        self._write_fail_for(
                            p, "stale",
                            f"heartbeat silent {a:.1f}s > peer_timeout_s="
                            f"{self.peer_timeout_s:.0f} (proxied)")
                    except OSError:
                        pass
                self._await_readmission({p for p, _a in stale},
                                        stale_slices.pop())
                return
            if self._goodput is not None:
                self._goodput.count("peer_failures")
                # detect_s = failure-to-observed latency.  The peer died
                # (silently — no FAIL marker) at roughly its last
                # heartbeat, so the full silence AGE is the latency
                # (over-estimates by at most hb_interval_s); it is
                # necessarily >= peer_timeout_s — a silent death cannot
                # be detected faster than the staleness threshold
                self._goodput.add("detect_s", age)
            raise PeerFailure(
                f"host(s) {[p for p, _ in stale]} heartbeat-stale "
                f"(oldest {age:.1f}s > peer_timeout_s="
                f"{self.peer_timeout_s:.0f}) in generation {self._gen} — "
                f"treating as dead and restarting the pod")

    def _stale_peers(self, now: float) -> List[Tuple[int, float]]:
        """[(peer index, silence age)] for peers silent past the
        timeout.  A missing heartbeat is aged from this attempt's start
        (peers that merely haven't launched yet get the same grace as
        slow first heartbeats)."""
        if self.pc <= 1 or self.peer_timeout_s <= 0:
            return []
        gen_dir = self._require_gen()
        exited = set(self._exited_peers())
        out = []
        for pi in range(self.pc):
            if pi == self.pi or pi in exited:
                # an exited peer FINISHED — its quiet heartbeat is
                # success, not death; stragglers keep running
                continue
            try:
                t = self.backend.mtime(self._marker("HB", pi, gen_dir))
            except OSError:
                t = self._attempt_wall_t
            age = now - t
            if age > self.peer_timeout_s:
                out.append((pi, age))
        return out

    # -- slice-granular elastic re-admission (r14) -------------------------

    @property
    def rejoining(self) -> bool:
        """True while this host's slice is re-entering the incident's
        generation: restore + catch-up to the survivors' agreed step,
        completed by :meth:`rejoin_sync`."""
        return self._rejoining

    @property
    def saves_suspended(self) -> bool:
        """True while this host must not take checkpoint-cadence ticks:
        a rejoining slice catching up, or a released survivor still
        below the agreed target.  A save tick taken here could never
        commit — the rest of the pod is not taking it — and would only
        burn the commit-barrier timeout into a counted save failure."""
        return self._rejoining or self._release_target is not None

    def consume_cadence_align(self) -> Optional[int]:
        """One-shot: the step every host re-anchors its checkpoint
        cadence to after a completed re-admission (the train loop feeds
        it to ``AsyncCheckpointManager.align_cadence``).  Hold and
        catch-up phases suppressed different ticks on different hosts;
        re-anchoring everyone at the agreed target restores the "pure
        function of the step sequence" property the pod's two-phase
        commit barrier depends on."""
        t, self._align_target = self._align_target, None
        return t

    def _await_readmission(self, fail_pis: set, failed_si: int) -> None:
        """Survivor side: the incident is confined to ONE foreign
        slice, so instead of raising :class:`PeerFailure` (whole-pod
        restart), park at this dispatch boundary in a bounded hold —
        publish a ``HOLD`` marker carrying our step (the rejoiner's
        catch-up target is the max over all survivors' holds), then
        poll for the restarted slice's ``RJREADY`` barrier.  Falls back
        to the whole-pod restart on timeout, on a rejoin abort, or on
        any additional failure outside the incident slice.  The local
        hang watchdog is paused for the duration (parked is not wedged;
        heartbeats keep proving liveness to the peers)."""
        gen_dir = self._require_gen()
        members = self._slice_members(failed_si)
        t0 = time.monotonic()
        deadline = t0 + self.readmit_timeout_s
        self._log(f"[pod] host {self.pi}: slice {failed_si} failed "
                  f"(host(s) {sorted(fail_pis)}); holding at step "
                  f"{self._step} for re-admission "
                  f"(timeout {self.readmit_timeout_s:.0f}s)")
        target = None
        try:
            with self.pause_watch():
                # drain this host's in-flight background save BEFORE
                # publishing HOLD: the rejoiners gate their restore
                # walk on the COMPLETE hold set, so "every HOLD
                # present" must imply "every survivor's durable writes
                # (including process 0's COMMIT) have landed or
                # terminally failed" — without this, a rejoiner can
                # walk mid-commit and its slice peers disagree on the
                # newest checkpoint (RestoreDivergence burns the whole
                # re-admission).  A drain stuck on a dead slice's DONE
                # barrier is bounded by the manager's commit timeout;
                # exceeding the rejoiners' hold window degrades to the
                # whole-pod fallback, never to divergence.
                if self.drain_fn is not None:
                    try:
                        self.drain_fn()
                    except Exception:
                        pass     # a failed save is already counted
                try:
                    self.backend.put_json(self._marker("HOLD", self.pi),
                                          {"step": self._step})
                except OSError as e:
                    self._readmit_fallback(
                        f"could not publish HOLD marker: {e!r}")
                while True:
                    if self.backend.exists(os.path.join(gen_dir, _RJ_ABORT)):
                        self._readmit_fallback(
                            "the restarting slice aborted its rejoin")
                    fails = self._failures(gen_dir)
                    fails.pop(self.pi, None)
                    extra = sorted(p for p in fails
                                   if self.slice_of(p) != failed_si)
                    if extra:
                        self._readmit_fallback(
                            f"additional failure on host(s) {extra}")
                    readys = [self.backend.read_json(
                        self._marker("RJREADY", p, gen_dir))
                        for p in members]
                    if readys and all(r is not None for r in readys):
                        target = max(int(r["step"]) for r in readys)
                        break
                    if time.monotonic() > deadline:
                        self._readmit_fallback(
                            f"re-admission timed out after "
                            f"{self.readmit_timeout_s:.0f}s")
                    time.sleep(0.05)
        finally:
            # parked time is badput either way (released or fallen
            # back) — the slice-MTTR hold component
            if self._goodput is not None:
                self._goodput.add("readmission_hold_s",
                                  time.monotonic() - t0)
        if self._step >= target:
            self._finish_release(target)
        else:
            # parked below the pod's agreed target (we observed the
            # failure earlier than a faster peer): resume stepping with
            # saves suspended and finish the release at the target
            self._release_target = int(target)
            self._log(f"[pod] host {self.pi}: released from hold at step "
                      f"{self._step}; catching up to the agreed step "
                      f"{target}")

    def _readmit_fallback(self, why: str) -> None:
        if self._goodput is not None:
            self._goodput.count("pod_fallback_restarts")
            self._goodput.count("peer_failures")
        raise PeerFailure(
            f"slice re-admission failed in generation {self._gen} ({why}) "
            f"— falling back to a whole-pod restart")

    def rejoin_sync(self, step: int) -> None:
        """Rejoining-slice side of re-admission, driven from the
        attempt path (right after restore — the target may already be
        reached) and from :meth:`check` during catch-up.  First call
        agrees the catch-up target (max over the survivors' HOLD
        steps — provably >= the restored checkpoint step, since a
        commit at step S implies every host passed S); once this
        host's step reaches it, the slice joins its ``RJREADY``
        readiness barrier and every pod host releases: the generation
        advances IN PLACE (fresh marker namespace, no restart) and
        training resumes from the agreed step."""
        if not self._rejoining:
            return
        self._step = int(step)
        if self._rejoin_target is None:
            self._rejoin_target = self._agree_rejoin_target()
        target = self._rejoin_target
        if step < target:
            return
        gen_dir = self._require_gen()
        members = self._slice_members(self.si)
        self.backend.put_json(self._marker("RJREADY", self.pi),
                              {"step": int(target)})
        deadline = time.monotonic() + self.readmit_timeout_s
        with self.pause_watch():
            while True:
                readys = [self.backend.read_json(
                    self._marker("RJREADY", p, gen_dir)) for p in members]
                if all(r is not None for r in readys):
                    break
                foreign = sorted(
                    p for p in self._failures(gen_dir)
                    if self.slice_of(p) != self.si)
                if foreign:
                    self._rejoin_fallback(
                        gen_dir, f"host(s) {foreign} failed during "
                                 f"re-admission")
                if time.monotonic() > deadline:
                    self._rejoin_fallback(
                        gen_dir, "slice readiness barrier timed out")
                time.sleep(0.05)
        self._rejoining = False
        self._rejoin_target = None
        self._finish_release(target)

    def _agree_rejoin_target(self) -> int:
        """The catch-up step: max over every survivor's HOLD marker
        (bounded wait for the complete set — survivors publish within
        one poll cadence of the incident)."""
        gen_dir = self._require_gen()
        survivors = [p for p in range(self.pc)
                     if self.slice_of(p) != self.si]
        deadline = time.monotonic() + self.readmit_timeout_s
        with self.pause_watch():
            while True:
                holds = [self.backend.read_json(
                    self._marker("HOLD", p, gen_dir)) for p in survivors]
                if holds and all(h is not None for h in holds):
                    return max(int(h["step"]) for h in holds)
                foreign = sorted(
                    p for p in self._failures(gen_dir)
                    if self.slice_of(p) != self.si)
                if foreign:
                    self._rejoin_fallback(
                        gen_dir, f"surviving host(s) {foreign} failed "
                                 f"while agreeing the catch-up target")
                if time.monotonic() > deadline:
                    self._rejoin_fallback(
                        gen_dir, "survivors never published their HOLD "
                                 "markers")
                time.sleep(0.05)

    def _rejoin_fallback(self, gen_dir: str, why: str) -> None:
        """Rejoiner-side fallback: durably abort (so parked survivors
        release into the whole-pod path immediately instead of waiting
        out their hold) and raise the restartable failure."""
        self._rejoining = False
        self._rejoin_target = None
        self._rejoin_abort(gen_dir, why)
        if self._goodput is not None:
            self._goodput.count("pod_fallback_restarts")
        raise PeerFailure(
            f"slice {self.si} re-admission failed in generation "
            f"{self._gen} ({why}) — falling back to a whole-pod restart")

    def _rejoin_abort(self, gen_dir: str, why: str) -> None:
        import json
        try:
            self.backend.create_if_absent(
                os.path.join(gen_dir, _RJ_ABORT),
                json.dumps({"pi": self.pi, "why": why[:300],
                            "unix_time": round(time.time(), 3)}
                           ).encode("utf-8"))
        except OSError:
            pass     # survivors still fall back via their hold timeout

    def _finish_release(self, target: int) -> None:
        """Completion of a re-admission, symmetric on every host:
        advance to the next generation IN PLACE (fresh marker
        namespace — the incident's FAIL/HOLD/RJREADY residue stays
        behind in the old one, which any later whole-pod restart
        computes past anyway), refresh the liveness clocks, and expose
        the cadence re-align target for the train loop."""
        self._release_target = None
        self._align_target = int(target)
        if self._goodput is not None:
            self._goodput.count("slice_readmissions")
        if self._spare_swap_t0 is not None:
            # r17: this host is a warm spare completing its first
            # release after claiming a seat — the claim→release wall
            # time IS the swap (restore + catch-up + readiness barrier;
            # programs were warmed while parked), reported as
            # goodput's warm_spare_swap_s.  Tracked beside the
            # badput segments, not among them: the window contains the
            # restore segment and productive catch-up steps.
            if self._goodput is not None:
                self._goodput.add_warm_spare_swap(
                    time.monotonic() - self._spare_swap_t0)
                self._goodput.count("warm_spare_swaps")
            self._spare_swap_t0 = None
        g = (self._gen or 0) + 1
        self._gen = g
        self._gen_dir = self._gen_path(g)
        self.backend.ensure_dir(self._gen_dir)
        # peers complete their release at their own pace: age their
        # missing heartbeats in the new generation from NOW, not from
        # the attempt start, or a slow releaser would look stale
        self._attempt_wall_t = time.time()
        self._last_polled = -1
        self._progress_t = time.monotonic()
        self._write_heartbeat()
        self._log(f"[pod] host {self.pi}: slice re-admission complete at "
                  f"step {target}; advancing to generation {g} in place")

    # -- warm spares (r17) -------------------------------------------------

    def spare_wait(self, refresh_fn: Optional[Callable[[], None]] = None,
                   stop_fn: Optional[Callable[[], bool]] = None,
                   poll_s: float = 0.1) -> Optional[dict]:
        """Park this STANDBY process (``spare_index`` armed) until a
        seat is claimable: heartbeat at the coordination-dir root
        (``SPAREHB_<id>`` — never parsed as a member heartbeat), call
        ``refresh_fn`` each poll (the caller's "re-restore params at
        each new COMMIT" hook — an optimization, never fatal), and scan
        the NEWEST generation for an incident confined to one slice
        whose survivors have all published HOLD.  Returns the claim
        dict after :meth:`_adopt_seat`, or None when the pod completed
        (every member's time-scoped EXIT marker present) or ``stop_fn``
        fired.

        Claiming waits for the COMPLETE survivor HOLD set first: holds
        prove the survivors drained their in-flight saves and committed
        to re-admission — claiming earlier would race the whole-pod
        restart path on an incident the survivors may classify
        differently.  Every ambiguous corner after the claim (missing
        co-spares for a multi-seat slice, survivor failure, timeout)
        rides the existing rejoin machinery and degrades to the durable
        ``RJ_ABORT`` whole-pod fallback."""
        if self.spare_index is None:
            raise RuntimeError("spare_wait on a non-spare coordinator")
        last_hb = 0.0
        while True:
            if stop_fn is not None and stop_fn():
                return None
            now = time.time()
            if now - last_hb >= self.hb_interval_s:
                try:
                    self.backend.put_json(
                        os.path.join(self.directory,
                                     f"SPAREHB_{self.spare_index:03d}"),
                        {"unix_time": round(now, 3)})
                except OSError:
                    pass
                last_hb = now
            if refresh_fn is not None:
                try:
                    refresh_fn()
                except Exception as e:
                    self._log(f"[spare] refresh failed ({e!r}); the swap "
                              f"will restore cold instead")
            done = 0
            for p in range(self.pc):
                got = self.backend.read_json(
                    os.path.join(self.directory,
                                 self._marker_name("EXIT", p)))
                # time-scoped like _exited_peers (previous-run residue in
                # a reused dir must not send a fresh spare home), with a
                # 10 ms tolerance: EXIT times are written rounded to the
                # millisecond, so a completion landing in the same
                # millisecond this coordinator was created could round
                # BELOW _created_t and park the spare forever — the
                # residue gap the scoping guards against is run-LENGTH,
                # not milliseconds
                if got is not None and got.get(
                        "unix_time", 0.0) > self._created_t - 0.01:
                    done += 1
            if done == self.pc:
                self._log(f"[spare] spare {self.spare_index}: pod "
                          f"completed without an incident; standing down")
                return None
            claim = self._spare_try_claim()
            if claim is not None:
                return claim
            time.sleep(poll_s)

    def _spare_try_claim(self) -> Optional[dict]:
        gens = self._generations()
        if not gens:
            return None
        gen, d = gens[-1]       # only the newest generation can hold a
        #                         live incident — a released or restarted
        #                         pod has already created a newer one
        fails = self._failures(d)
        if not fails or self.backend.exists(os.path.join(d, _RJ_ABORT)):
            return None
        failed_slices = {self.slice_of(p) for p in fails}
        if len(failed_slices) != 1:
            return None         # multi-slice incident: whole-pod territory
        si = failed_slices.pop()
        members = self._slice_members(si)
        survivors = [p for p in range(self.pc) if self.slice_of(p) != si]
        if not survivors:
            return None         # a whole-pod death has nothing to hold
        for p in survivors:
            if self.backend.read_json(self._marker("HOLD", p, d)) is None:
                return None     # survivors not (yet) parked for re-admission
        import json
        for p in members:
            if self.backend.exists(
                    os.path.join(d, self._marker_name("RJRENTER", p))):
                # the real slice is already rejoining this seat —
                # stand down rather than race it
                return None
            key = os.path.join(d, self._marker_name("CLAIM", p))
            try:
                won = self.backend.create_if_absent(
                    key, json.dumps({"pi": p, "spare": self.spare_index,
                                     "unix_time": round(time.time(), 3)}
                                    ).encode("utf-8"))
            except OSError:
                return None
            if won:
                self._adopt_seat(p, si, gen, d)
                return {"seat": p, "slice": si, "generation": gen}
        return None             # every seat already claimed by other spares

    def _adopt_seat(self, seat: int, si: int, gen: int,
                    gen_dir: str) -> None:
        """The spare becomes pod process ``seat``: pi/si re-key to the
        claimed member identity, the coordinator enters the incident's
        generation in REJOIN mode (the same machinery a relaunched
        slice uses — restore through the slice-scoped barrier, catch up
        to the survivors' agreed step, join RJREADY), and member
        heartbeats start under the adopted name so the pod sees the
        seat alive again."""
        self._log(f"[spare] spare {self.spare_index} CLAIMED seat {seat} "
                  f"(slice {si}, generation {gen}); swapping in")
        self.pi = int(seat)
        self.si = int(si)
        self._gen = int(gen)
        self._gen_dir = gen_dir
        self._claimed = (int(gen), int(seat))
        self._rejoining = True
        self._rejoin_target = None
        self._spare_swap_t0 = time.monotonic()
        if self._goodput is not None:
            self._goodput.count("warm_spare_claims")
        self._attempt_wall_t = time.time()
        self._last_polled = -1
        self._escalated = False
        self._progress_t = time.monotonic()
        self._write_heartbeat()
        self._ensure_thread()

    # -- restore step agreement (fs-simulated pods) ------------------------

    def gather_restored_step(self, step: int,
                             phase: str = "agree") -> np.ndarray:
        """Span-wrapped ("rendezvous" — barrier waits are the pod
        restore's dominant cost and telemetry must attribute them):
        see :meth:`_gather_restored_step_impl`."""
        from faster_distributed_training_tpu.telemetry import spans
        with spans.span("rendezvous"):
            return self._gather_restored_step_impl(step, phase)

    def _gather_restored_step_impl(self, step: int,
                                   phase: str = "agree") -> np.ndarray:
        """Filesystem allgather of every host's restored checkpoint step
        (−1 = nothing restored) — the restore agreement barrier for
        fs-SIMULATED pods, where jax is single-process per host and the
        manager's real ``all_gather_across_processes`` would see only
        itself.  Same rendezvous property as the collective: every host
        blocks here until all have joined (so process 0's pre-agreement
        residue sweep stays race-free), and a FAIL marker or timeout
        raises :class:`PeerFailure` instead of deadlocking on a host
        that died mid-restore.  ``phase`` names the barrier — the
        manager enters twice per restore ("enter" = pre-walk
        rendezvous after draining in-flight writes, "agree" = the
        post-walk step agreement), and each phase needs its own marker
        file.  One restore per generation (the supervisor wiring
        guarantees it — each attempt enters a fresh generation after
        any failure).

        Slice re-admission (r14): while rejoining, the barrier spans
        only THIS slice's hosts (the survivors are parked in their
        hold, not restoring) under ``RJ``-prefixed marker names — the
        original attempt's whole-pod RESTORE markers in the same
        generation are not re-read; the incident slice's own FAIL
        residue is expected and ignored, and any failure path aborts
        the rejoin durably so the survivors fall back fast."""
        gen_dir = self._require_gen()
        kind = "RESTORE" if phase == "agree" else f"R{phase.upper()}"
        members = list(range(self.pc))
        if self._rejoining:
            kind = "RJ" + kind
            members = self._slice_members(self.si)
            if phase == "enter" and self._rejoin_target is None:
                # BEFORE the restore walk: wait for the COMPLETE
                # survivor HOLD set.  Each survivor drains its in-flight
                # background save before publishing HOLD, so once all
                # holds exist the committed-checkpoint frontier is
                # frozen (survivors are parked, process 0's commit
                # either landed or terminally failed) and every member
                # of this slice walks the SAME newest checkpoint —
                # without the gate, a walk racing process 0's
                # background COMMIT splits the slice on
                # RestoreDivergence and burns the re-admission.
                self._rejoin_target = self._agree_rejoin_target()
        self.backend.put_json(self._marker(kind, self.pi),
                              {"step": int(step)})
        deadline = time.monotonic() + self.gather_timeout_s
        while True:
            vals = []
            for pi in members:
                got = self.backend.read_json(self._marker(kind, pi, gen_dir))
                if got is None:
                    break
                vals.append(got["step"])
            else:
                return np.asarray(vals, np.int32)
            fails = {p: f for p, f in self._failures(gen_dir).items()
                     if p != self.pi
                     and (not self._rejoining
                          or self.slice_of(p) != self.si)}
            if fails:
                if self._rejoining:
                    self._rejoin_fallback(
                        gen_dir, f"host(s) {sorted(fails)} failed while "
                                 f"this slice was restoring")
                raise PeerFailure(
                    f"host(s) {sorted(fails)} failed while this host was "
                    f"waiting in the restore-agreement barrier "
                    f"(generation {self._gen})")
            done = [p for p in self._exited_peers() if p in members
                    and self.backend.read_json(
                        self._marker(kind, p, gen_dir)) is None]
            if done:
                # a peer that already COMPLETED the run will never join
                # this barrier — fail fast (every retry will fail the
                # same way until the restart budget runs out, each in
                # milliseconds instead of a full gather timeout)
                raise PeerFailure(
                    f"host(s) {done} already completed the run (EXIT "
                    f"marker) and can never join the generation "
                    f"{self._gen} restore barrier — the pod finished "
                    f"without this host; restore the final checkpoint "
                    f"manually or rerun against a fresh directory")
            if time.monotonic() > deadline:
                if self._rejoining:
                    self._rejoin_fallback(
                        gen_dir, f"slice restore barrier timed out after "
                                 f"{self.gather_timeout_s:.0f}s")
                raise PeerFailure(
                    f"restore-agreement barrier timed out after "
                    f"{self.gather_timeout_s:.0f}s in generation "
                    f"{self._gen}: {len(members) - len(vals)} host(s) "
                    f"never joined")
            time.sleep(0.05)

    # -- health watchdog ---------------------------------------------------

    def watch_steps(self):
        """Context manager arming the local step watchdog for an epoch's
        dispatch loop (heartbeats run regardless; only the no-progress
        escalation is scoped, so eval/restore phases can't false-
        trigger).  ``step_timeout_s`` must exceed the worst-case
        (re)compile of one dispatch — it defaults to 0 (off)."""
        from contextlib import contextmanager

        @contextmanager
        def _ctx():
            self._progress_t = time.monotonic()
            self._watching = True
            try:
                yield
            finally:
                self._watching = False
        return _ctx()

    def pause_watch(self):
        """Context manager suspending the LOCAL no-progress escalation
        around legitimate blocking work on the step thread — cadence
        saves that drain a prior write's commit barrier (up to
        commit_timeout_s, typically far beyond any sane
        step_timeout_s), the preemption emergency save — so a healthy
        host is never SIGKILLed mid-save.  Heartbeats keep running (the
        host IS alive, the peers must see that), and a genuinely
        wedged save stays bounded by its own timeout (TimeoutError →
        counted save failure) rather than needing the watchdog.  The
        step clock restarts fresh on resume."""
        from contextlib import contextmanager

        @contextmanager
        def _ctx():
            was = self._watching
            self._watching = False
            try:
                yield
            finally:
                self._progress_t = time.monotonic()
                self._watching = was
        return _ctx()

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._watchdog_body, name=f"fdt-pod-wd-{self.pi}",
                daemon=True)
            self._thread.start()

    def _watchdog_body(self) -> None:
        while not self._stop.wait(self.hb_interval_s):
            try:
                self._write_heartbeat()
            except OSError:
                pass  # a flaky shared fs must not kill the watchdog
            if (self._watching and not self._escalated
                    and self.step_timeout_s > 0
                    and time.monotonic() - self._progress_t
                    > self.step_timeout_s):
                self._escalate_hang()

    def _write_heartbeat(self) -> None:
        if self._gen_dir is None:
            return
        self.backend.put_json(self._marker("HB", self.pi),
                              {"step": self._step,
                               "unix_time": round(time.time(), 3)})

    def _escalate_hang(self) -> None:
        """Watchdog-thread escalation: the main thread has made no step
        progress for step_timeout_s — it is wedged in a dispatch or a
        collective and cannot raise for itself.  Publish the failure
        durably FIRST (so the peers restart even if the abort below is
        instant), then abort."""
        self._escalated = True
        stuck = time.monotonic() - self._progress_t
        reason = (f"no step progress for {stuck:.1f}s "
                  f"(> step_timeout_s={self.step_timeout_s:.0f}) "
                  f"at step {self._step}")
        try:
            self._write_fail("hang", reason)
        except OSError:
            pass  # peers fall back to heartbeat staleness
        if self._goodput is not None:
            self._goodput.count("step_timeouts")
        self._log(f"[pod] host {self.pi}: WATCHDOG: {reason}; FAIL marker "
                  f"written, aborting so the pod converges on a restart")
        # crash flight recorder: the SIGKILL below destroys everything
        # this process knows — the unflushed telemetry ring, which span
        # the main thread is wedged inside, the program table.  Dump it
        # from a side thread with a BOUNDED join: a wedged shared fs
        # (plausibly the same one that hung the step) must not veto the
        # abort the peers are waiting on.
        try:
            from faster_distributed_training_tpu.telemetry import flight
            if flight.configured():
                t = threading.Thread(
                    target=flight.emergency_dump,
                    args=("watchdog_abort",),
                    kwargs={"step": self._step,
                            "extra": {"watchdog_reason": reason}},
                    daemon=True)
                t.start()
                t.join(timeout=2.0)
        except Exception:
            pass
        self._abort(reason)

    @staticmethod
    def _default_abort(reason: str) -> None:
        # SIGKILL, not sys.exit/os._exit: the main thread may be wedged
        # inside a device runtime call holding locks that Python-level
        # teardown (atexit, GC finalizers, PJRT client destructors) would
        # deadlock on.  Nothing softer is guaranteed to terminate a
        # process whose main thread is stuck in C.
        os.kill(os.getpid(), signal.SIGKILL)

    # -- housekeeping ------------------------------------------------------

    def _prune_generations(self, keep: int = 3) -> None:
        """Old generation dirs are a few marker files each; process 0
        sweeps all but the newest ``keep`` so a long-lived flaky pod
        doesn't accumulate thousands of dirs.  Kept generations must
        include every one a lagging peer could still be reading (a peer
        is at most one incident behind — it restarts the moment it
        observes the newest FAIL markers)."""
        if self.pi != 0 or self._gen is None:
            return
        for gen, d in self._generations():
            if gen <= self._gen - keep:
                self.backend.delete_prefix(d)

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=self.hb_interval_s + 5.0)
            self._thread = None
