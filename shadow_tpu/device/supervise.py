"""Supervised device runs: periodic validated checkpoints, graceful
preemption, and dispatch retry/failover.

PR 2 made the *simulated* world fault-tolerant (link outages, host
crashes); this module makes the simulator process itself survivable.
Production training/inference stacks treat preemption and
checkpoint-restart as first-class, and multi-hour 10k-host or
ensemble campaigns need the same guarantees:

1. **Periodic validated checkpointing** — every ``checkpoint_every``
   sim ns the run writes a rotating checkpoint
   (``<checkpoint_save>.t<ns>``, atomic tmp+rename, last
   ``checkpoint_keep`` retained). A checkpoint is written only from a
   VALIDATED state: the loud overflow counters are clean and, with
   ``state_audit`` on, the on-device health word (engine.py AUD_*
   bits) is zero — so a corrupted state is never the one a
   crash-restart resumes from. ``checkpoint_load`` accepts the base
   path and resolves to the newest *readable* rotation entry,
   skipping truncated files.

2. **Graceful preemption** — SIGTERM/SIGINT set a drain flag; the
   in-flight dispatch segment finishes, a resume checkpoint is saved
   at the segment boundary, and the process exits with
   ``EXIT_PREEMPTED`` (75, EX_TEMPFAIL). Because the engine clamps
   event windows on the *global* stop, the resumed run is
   bit-identical to the uninterrupted one (the checkpoint contract).
   A second signal aborts hard (handlers restored, KeyboardInterrupt).

3. **Dispatch retry + the failover ladder** — a transient device
   error (RESOURCE_EXHAUSTED, device unavailable, ...) retries the
   failed segment from the last validated state with capped
   exponential backoff (``dispatch_retries`` /
   ``dispatch_retry_backoff``). After exhausting retries the ladder
   engages (``failover:``): ``shrink`` probes the mesh, re-shards
   the last validated state onto the surviving M devices
   (:func:`_shrink_recover` + capacity.reshard_state) and continues
   ON-DEVICE — losing 1 of N chips costs 1/N of throughput, not the
   run, and the continuation is bit-identical to an uninterrupted
   M-shard run (the mesh-shape determinism contract); when no
   shrink is possible it escalates to the hybrid rung. ``hybrid``
   saves the last validated state to disk and raises
   :class:`DeviceFailover`, which the Controller answers by
   re-running on the hybrid backend with a loud diagnostic instead
   of aborting — the device checkpoint remains on disk for a
   device-side resume. The ladder is drilled in CI by the
   deterministic chaos injector (device/chaos.py,
   ``experimental.chaos``; determinism_gate --chaos).

4. **The OOM degradation ladder** — a *deterministic* memory
   exhaustion (the same RESOURCE_EXHAUSTED twice in a row at the
   same validated boundary, or one that survives the retry budget)
   is a capacity fact, not a transient: each recurrence walks one
   rung that actually shrinks the footprint — split the ensemble
   into sequential replica batches (:class:`DegradeToReplicaBatch`,
   caught by the campaign), halve the dispatch segment — and replays
   bit-identically from the last validated state without charging
   ``dispatch_retries``. Out of rungs, the existing ``failover:``
   escalation applies. The ladder is the runtime backstop of the
   preflight admission gate (capacity.footprint /
   ``experimental.admission``) and is drilled by the chaos injector's
   ``oom`` seam (determinism_gate --degrade).

:func:`advance` is the single segmented-advance loop both
``DeviceRunner`` and ``EnsembleRunner`` now share: it generalizes the
overflow re-plan/retry loop PR 1 built into one recovery path for all
failure classes (capacity overflow, transient dispatch errors, audit
violations, preemption).

The loop is serial: each segment is issued (``dispatch.issue`` — jax
dispatch is asynchronous, so ``run`` returns device futures in ~ms),
then synchronized (``dispatch.sync`` — the blocking
``overflow``/``seg_rounds`` fetches, where asynchronously raised
dispatch errors surface), then its boundary work runs: overflow
re-plan, the round budget, audit validation, heartbeat, checkpoint
rotation and the known-good snapshot. At most one segment is in
flight, so every recovery class replays from the last validated
state, which is bit-identical by the determinism contract
(recomputing a deterministic segment yields the same trace).
"""

from __future__ import annotations

import glob
import os
import signal
import threading
import time
from dataclasses import dataclass, field

import numpy as np

from shadow_tpu.obs import trace as obstrace
from shadow_tpu.utils.slog import get_logger

log = get_logger("supervise")

# distinct exit code for a graceful preemption (EX_TEMPFAIL): the
# operator/scheduler can tell "resume me" apart from success (0) and
# failure (1)
EXIT_PREEMPTED = 75

# exponential backoff cap between dispatch retries (wall seconds)
BACKOFF_CAP_S = 30.0

# degradation-ladder floor: how many times the dispatch-segment rung
# may halve the segment before the ladder gives up and escalates —
# shorter segments shrink the transient working set with diminishing
# returns, and an OOM that survives 4 halvings is not segment-bound
MAX_SEG_HALVINGS = 4

# substrings marking a device error as transient (worth retrying from
# the last validated state). Matched against str(exc) — XLA surfaces
# these as XlaRuntimeError messages whose class identity varies by
# jaxlib version, so the message is the stable surface.
TRANSIENT_MARKERS = (
    "RESOURCE_EXHAUSTED",
    "UNAVAILABLE",
    "DEADLINE_EXCEEDED",
    "ABORTED",
    "device unavailable",
    "failed to connect",
    "Socket closed",
    "out of memory",
)

# the subset of TRANSIENT_MARKERS that names memory exhaustion. A
# matching error that RECURS at the same validated boundary is a
# capacity fact (the footprint does not fit), not flakiness — the
# degradation ladder, not the retry budget, is the answer.
OOM_MARKERS = (
    "RESOURCE_EXHAUSTED",
    "out of memory",
)

AUDIT_BIT_NAMES = {
    1: "heap-order/head-bounds",
    2: "clock-monotonicity",
    4: "counter-negativity",
    8: "packet-conservation",
}


class AuditFailure(RuntimeError):
    """The on-device invariant audit found a corrupted state. The run
    stops rather than writing (or running past) a checkpoint that a
    restart would trust."""


class DeviceFailover(RuntimeError):
    """Dispatch retries (and, under ``failover: shrink``, the mesh
    shrink) exhausted: carries the last validated checkpoint (for a
    later device-side resume) and the sim time it pins. The
    Controller catches this and re-runs the config on the hybrid
    backend. ``checkpoint_path`` is explicitly ``None`` when no
    state could be persisted at all (the save failed AND no rotating
    checkpoint exists) — ``persist_error`` then names the save
    failure, and the Controller's single diagnostic surfaces it: the
    hybrid rerun restarts from t=0 with no device-side resume
    point."""

    def __init__(self, message: str, checkpoint_path=None,
                 sim_time: int = 0, persist_error: str = ""):
        super().__init__(message)
        self.checkpoint_path = checkpoint_path
        self.sim_time = int(sim_time)
        self.persist_error = persist_error


class DegradeToReplicaBatch(RuntimeError):
    """OOM degradation ladder, replica-batch rung (ensembles only):
    the full-R vmap does not fit on the mesh. Carries the suggested
    per-batch replica count; the campaign catches this and re-runs
    the sweep in sequential replica batches (vmap over R/k replicas
    per batch, finals merged), which is bit-identical to the full
    vmap — each replica's trace is a pure function of its own world
    row, so stacking order and batch boundaries cannot change it."""

    def __init__(self, message: str, batch: int = 1):
        super().__init__(message)
        self.batch = max(1, int(batch))


def is_transient(exc: BaseException) -> bool:
    """Whether a dispatch error is worth retrying from the last
    validated state (vs a programming error that would just recur)."""
    text = str(exc)
    return any(m in text for m in TRANSIENT_MARKERS)


def is_oom(exc: BaseException) -> bool:
    """Whether a dispatch/compile error names memory exhaustion. OOM
    stays transient-retryable ONCE (an allocator can lose a race with
    another process and win the rerun); the second consecutive hit at
    the same validated boundary is deterministic and routes to the
    degradation ladder instead of burning the retry budget."""
    text = str(exc)
    return any(m in text for m in OOM_MARKERS)


def decode_audit(word: int) -> list[str]:
    """Health-word bitmask -> the named invariants it violates."""
    return [name for bit, name in sorted(AUDIT_BIT_NAMES.items())
            if word & bit]


def check_audit(state, where: str = "", last_good: str = "") -> None:
    """Validate the on-device health word of a (standalone [H] or
    ensemble [R, H]) state. No-op when the engine was built without
    the audit. Raises :class:`AuditFailure` naming the violated
    invariants — and the last validated checkpoint, if any — on a
    nonzero word."""
    if "aud" not in state:
        return
    from shadow_tpu._jax import jax

    aud = np.asarray(jax.device_get(state["aud"]))
    if not aud.any():
        return
    names = decode_audit(int(np.bitwise_or.reduce(aud, axis=None)))
    hint = (f"; last validated checkpoint: {last_good}" if last_good
            else "; no validated checkpoint exists yet")
    raise AuditFailure(
        f"state audit failed{f' at {where}' if where else ''}: "
        f"violated invariant(s) {names} on "
        f"{int((aud != 0).sum())} host slot(s) — the state is "
        f"corrupted and will not be checkpointed or run further"
        f"{hint}")


class PreemptionGuard:
    """SIGTERM/SIGINT drain handler, installed for the duration of a
    supervised run (context manager). The first signal sets
    ``requested`` — the advance loop finishes the in-flight dispatch
    segment, saves a resume checkpoint, and returns preempted. A
    second signal restores the original handlers and raises
    KeyboardInterrupt (hard abort escape hatch). Outside the main
    thread signal handlers cannot be installed; the guard then stays
    inactive and the run behaves as before."""

    SIGNALS = (signal.SIGTERM, signal.SIGINT)

    def __init__(self):
        self.requested = False
        self.signum: int = 0
        self.active = False
        self._orig: dict = {}

    def request(self) -> None:
        """Programmatic preemption (tests, embedding harnesses)."""
        self.requested = True

    def _handle(self, signum, frame):
        if self.requested:
            self._restore()
            raise KeyboardInterrupt(
                f"second {signal.Signals(signum).name} during drain — "
                "aborting hard (state NOT saved)")
        self.requested = True
        self.signum = signum
        log.warning(
            "received %s: draining — finishing the in-flight dispatch "
            "segment, then saving a resume checkpoint and exiting "
            "with rc %d (send the signal again to abort hard)",
            signal.Signals(signum).name, EXIT_PREEMPTED)

    def _restore(self) -> None:
        for s, h in self._orig.items():
            try:
                signal.signal(s, h)
            except (ValueError, OSError):
                pass
        self._orig.clear()
        self.active = False

    def __enter__(self) -> "PreemptionGuard":
        try:
            for s in self.SIGNALS:
                self._orig[s] = signal.signal(s, self._handle)
            self.active = True
        except ValueError:
            # not the main thread: leave signal disposition alone
            self._restore()
        return self

    def __exit__(self, *exc) -> None:
        self._restore()


def heartbeat_rates(mark, sent_totals):
    """The ONE pkts/s-since-last-heartbeat rule for the
    ``[supervise-heartbeat]`` and ``[ensemble-heartbeat]`` lines
    (DeviceRunner and EnsembleRunner both delegate here so the two
    surfaces cannot drift): given the previous ``(wall, totals)``
    mark (or None) and the current cumulative sent totals (one entry
    per line — the standalone runner passes one, the campaign one per
    replica), return ``(new_mark, rates)`` with each rate a formatted
    string. The first boundary rates "n/a" — there is no previous
    mark, and a resumed run's counters include the pre-resume total,
    so a since-start rate would lie."""
    wall = time.perf_counter()
    rates = ["n/a"] * len(sent_totals)
    if mark is not None:
        dw = wall - mark[0]
        if dw > 0:
            rates = [f"{(float(s) - float(p)) / dw:.0f}"
                     for s, p in zip(sent_totals, mark[1])]
    return (wall, [float(s) for s in sent_totals]), rates


class HeartbeatMonitor:
    """Wall-clock staleness detector on the heartbeat cadence
    (``experimental.heartbeat_stale_after`` = k; both runners own one
    per run when the knob is set). The runner calls :meth:`beat` at
    every ``[supervise-heartbeat]`` / ``[ensemble-heartbeat]``
    boundary; the expected cadence is an EWMA of the healthy gaps, and
    a gap wider than k times it is counted in ``stale_events`` with a
    loud warning — SimStats.stale_heartbeats surfaces the count.

    :meth:`stale` is the live probe the campaign server's watchdog
    polls from ITS thread: a wedged device step never reaches the next
    beat(), so only an outside observer can watch the current gap grow
    past the threshold. All state is lock-protected for exactly that
    cross-thread read. The clock is injectable (frozen-clock unit
    tests drive the gap arithmetic without sleeping)."""

    def __init__(self, k: int, clock=time.monotonic):
        # k < 2 would flag ordinary cadence jitter (a segment that
        # runs 1.3x the EWMA is normal); clamp rather than refuse so
        # a config's `1` means "as sensitive as is sane"
        self.k = max(2, int(k))
        self._clock = clock
        self._lock = threading.Lock()
        self._last = None     # wall of the previous beat
        self._expect = None   # EWMA of healthy gaps, seconds
        self.stale_events = 0

    def beat(self) -> None:
        """Record one heartbeat boundary; warn + count when the gap
        since the previous one exceeded k x the expected cadence. A
        stale gap is NOT folded into the EWMA — the expectation keeps
        tracking the healthy cadence, so one stall cannot raise the
        bar for detecting the next."""
        now = self._clock()
        with self._lock:
            if self._last is not None:
                gap = max(now - self._last, 1e-9)
                if self._expect is None:
                    self._expect = gap
                elif gap > self.k * self._expect:
                    self.stale_events += 1
                    log.warning(
                        "STALE HEARTBEAT: %.2fs since the previous "
                        "heartbeat — %.1fx the expected %.2fs cadence "
                        "(threshold %dx); the run stalled between "
                        "segment boundaries (%d stale gap(s) so far)",
                        gap, gap / self._expect, self._expect,
                        self.k, self.stale_events)
                else:
                    self._expect = 0.5 * self._expect + 0.5 * gap
            self._last = now

    def gap(self) -> float:
        """Seconds since the last beat (0.0 before the first)."""
        with self._lock:
            return (0.0 if self._last is None
                    else max(0.0, self._clock() - self._last))

    def stale(self) -> bool:
        """Live cross-thread probe: is the CURRENT gap already past
        the threshold? False until two beats have established a
        cadence — a watchdog must not kill a run that is still
        compiling its first program."""
        with self._lock:
            if self._last is None or self._expect is None:
                return False
            return (self._clock() - self._last) > \
                self.k * self._expect


def prefetch_programs(runner, ensemble: bool = False) -> None:
    """Cache-aware prefetch (the PR 6 ROADMAP leftover): when a
    capacity plan, a strategy plan, or a re-plan has just named the
    next program — a rebuilt engine whose executable the AOT cache
    may hold — start that entry's background read NOW, so the work
    that runs before the next dispatch (state transfer, checkpoint
    load, init_state) overlaps the disk read instead of the first
    ``ensure()`` paying it synchronously. Best-effort: no cache, an
    unsupported backend, or a fingerprinting failure is a silent
    no-op (the synchronous path still serves). Traced as a
    ``compile.prefetch`` instant."""
    cache = getattr(runner, "aot_cache", None)
    engine = getattr(runner, "engine", None)
    if cache is None or engine is None or cache.unsupported:
        return
    program = "run_ens" if ensemble else "run"
    if program in getattr(engine, "_aot_exec", {}):
        return              # this engine already resolved it
    from shadow_tpu.device import aotcache

    try:
        key = aotcache.program_key(engine, program)
    except Exception:       # noqa: BLE001 — ensure() will warn
        return
    cache.prefetch(key, program=program)


def surviving_devices(mesh) -> list:
    """Probe every device of a mesh for liveness (a trivial placement
    + sync per device) and return the survivors, in mesh order. The
    chaos injector's dead set is consulted first, so a scripted
    device loss (device/chaos.py) fails the probe exactly the way a
    real dead chip does — the shrink failover cannot tell them
    apart, which is the point."""
    from shadow_tpu._jax import jax
    from shadow_tpu.device import chaos as chaosmod

    inj = chaosmod.current()
    alive = []
    for d in mesh.devices.flat:
        if inj is not None and inj.is_dead(d.id):
            log.warning("device %s failed the liveness probe "
                        "(scripted device loss)", d)
            continue
        try:
            jax.block_until_ready(
                jax.device_put(np.zeros(1, np.int32), d))
        except Exception as e:      # noqa: BLE001 — any probe failure = dead
            log.warning("device %s failed the liveness probe: %s",
                        d, e)
            continue
        alive.append(d)
    return alive


def _shrink_recover(runner, exc, good_state, good_t, ensemble, ck,
                    tracer):
    """``failover: shrink`` — retries exhausted on a device error:
    probe the mesh, and if dead devices are found with at least one
    survivor, re-shard the last validated state onto the M-device
    mesh and hand back a state the advance loop continues from
    ON-DEVICE (losing 1 of N chips costs 1/N of throughput, not the
    run). Returns ``(new_state, validated_t)`` or None when no
    shrink is possible (nothing dead, nothing alive, or the state is
    unrecoverable) — the caller then escalates down the failover
    ladder.

    Determinism: the engine's traces are bit-identical across mesh
    shapes, the re-shard (capacity.reshard_state) carries every
    per-host leaf verbatim, and segment boundaries are a pure
    function of sim time — so the N-shard prefix + M-shard
    continuation equals both the uninterrupted M-shard run and the
    serial oracle (determinism_gate --chaos pins all three)."""
    from shadow_tpu._jax import jax
    from shadow_tpu.device import checkpoint

    engine = runner.engine
    old_n = engine.n_shards
    with tracer.span("reshard.probe", "reshard", sim_t0=good_t,
                     shards=old_n):
        alive = surviving_devices(engine.mesh)
    n_dead = len(list(engine.mesh.devices.flat)) - len(alive)
    if n_dead == 0:
        log.error("shrink failover: every mesh device passed the "
                  "liveness probe — the dispatch failure (%s) cannot "
                  "be attributed to a dead device; escalating", exc)
        return None
    if not alive:
        log.error("shrink failover: no mesh device survived the "
                  "liveness probe; escalating")
        return None
    # recover the last validated state host-side; a dead device owns
    # shards of the in-memory snapshot, so the fetch may fail — the
    # newest rotating checkpoint on disk is the fallback, and the
    # replay rewinds to ITS sim time (older than good_t is fine:
    # deterministic segments recompute bit-identically)
    t_good = good_t
    try:
        host_state = jax.device_get(good_state)
    except Exception as fetch_err:      # noqa: BLE001 — dead-device fetch
        if ck is None or not ck.last_path:
            log.error("shrink failover: the last validated state is "
                      "unrecoverable (%s) and no rotating checkpoint "
                      "exists; escalating", fetch_err)
            return None
        log.warning("shrink failover: could not fetch the in-memory "
                    "state (%s); re-sharding the newest readable "
                    "rotating checkpoint instead", fetch_err)
        # newest-READABLE walk (the resolve_checkpoint rule): the
        # newest entry may be the torn artifact a crash leaves —
        # forfeiting the shrink over it when an older readable entry
        # exists would be exactly the failure mode the rotation is
        # for. Replaying from an older boundary is fine:
        # deterministic segments recompute bit-identically.
        host_state = None
        for _, p_e in reversed(rotation_entries(ck.base)):
            try:
                host_state, meta = checkpoint.load_host_state(p_e)
                break
            except Exception as load_err:   # noqa: BLE001 — torn entry
                log.warning("shrink failover: rotation entry %s is "
                            "unreadable (%s); trying the previous "
                            "one", p_e, load_err)
        if host_state is None:
            log.error("shrink failover: no readable rotation entry "
                      "under %s; escalating", ck.base)
            return None
        t_good = int(meta["sim_time"])
    try:
        with tracer.span("reshard.shrink", "reshard", sim_t0=t_good,
                         from_shards=old_n, to_shards=len(alive),
                         error=str(exc)[:200]) as sp:
            state = runner._shrink_to(alive, host_state,
                                      ensemble=ensemble)
            sp.add(h_pad=runner.engine.H_pad)
    except Exception as re_err:         # noqa: BLE001 — escalate, not crash
        log.error("shrink failover: re-sharding onto the %d "
                  "surviving device(s) failed (%s); escalating",
                  len(alive), re_err)
        return None
    log.warning(
        "MESH SHRINK: %d device(s) dead (%s) — re-sharded the last "
        "validated state (t=%d ns) onto the %d surviving device(s) "
        "and continuing on-device at %d/%d of mesh throughput; "
        "checkpoints from here stamp the shrunken geometry",
        n_dead, exc, t_good, len(alive), len(alive), old_n)
    return state, t_good


def drain_possible(cfg) -> bool:
    """Whether a run under this config ever reaches a segment
    boundary before its pause — the only points a preemption drain
    can fire. Without one (no checkpoint_every, no dispatch_segment,
    no heartbeat) the whole run is ONE dispatch segment: installing
    the guard would swallow SIGTERM/SIGINT while promising a drain
    that can never happen, strictly worse than the default signal
    disposition — so the runners leave the signals alone and log
    why."""
    xp = cfg.experimental
    return bool(xp.checkpoint_every or xp.dispatch_segment
                or cfg.general.heartbeat_interval)


def make_guard(cfg):
    """The runners' guard factory: a PreemptionGuard when a drain can
    actually fire, else None (with a hint, once per run)."""
    if not cfg.experimental.checkpoint_save:
        return None
    if not drain_possible(cfg):
        log.info(
            "preemption drain inactive: the run has no segment "
            "boundaries (set experimental.checkpoint_every or "
            "dispatch_segment, or general.heartbeat_interval, to "
            "make SIGTERM drain to a resume checkpoint)")
        return None
    return PreemptionGuard()


def rotation_entries(base: str) -> list[tuple[int, str]]:
    """Existing rotation files for a checkpoint base path, sorted by
    sim time ascending: ``<base>.t<15-digit-ns>``. Non-numeric
    suffixes (in-flight ``.tmp`` files) are ignored."""
    out = []
    for p in glob.glob(glob.escape(base) + ".t*"):
        suffix = p[len(base) + 2:]
        if suffix.isdigit():
            out.append((int(suffix), p))
    return sorted(out)


def resolve_checkpoint(path: str) -> str:
    """``checkpoint_load`` resolution: a concrete file wins; otherwise
    the newest READABLE rotation entry of the base path (a truncated
    npz — the file a kill outran — is skipped with a warning, so the
    resume lands on the last validated checkpoint, exactly the
    rotation's purpose)."""
    if os.path.exists(path):
        return path
    entries = rotation_entries(path)
    if not entries:
        raise ValueError(
            f"checkpoint_load: {path!r} does not exist and has no "
            f"rotation entries ({path}.t*) — nothing to resume")
    from shadow_tpu.device import checkpoint

    for t, p in reversed(entries):
        try:
            meta = checkpoint.peek_meta(p)
            if meta.get("format") != checkpoint.FORMAT:
                raise ValueError(f"format {meta.get('format')}")
        except Exception as e:      # noqa: BLE001 — any unreadable entry
            log.warning("skipping unreadable checkpoint %s (%s); "
                        "falling back to the previous rotation entry",
                        p, e)
            continue
        log.info("checkpoint_load: %s resolved to rotation entry %s "
                 "(t=%d ns)", path, p, t)
        return p
    raise ValueError(
        f"checkpoint_load: every rotation entry of {path!r} is "
        "unreadable — nothing to resume")


class Checkpointer:
    """Rotating last-K checkpoint writer for one supervised run.
    Every write goes through the atomic tmp+rename path in
    checkpoint.save_state; pruning happens only after a successful
    replace, so there is always at least one complete checkpoint on
    disk once the first boundary passes."""

    def __init__(self, base: str, every: int, keep: int,
                 final_stop: int, extra_meta: dict = None,
                 audit_enabled: bool = False):
        self.base = base
        self.every = int(every)
        self.keep = max(1, int(keep))
        self.final_stop = int(final_stop)
        self.extra_meta = extra_meta
        self.audit_enabled = bool(audit_enabled)
        self.last_path = ""
        self.last_t = -1

    def next_after(self, t: int) -> int:
        return (t // self.every + 1) * self.every

    def save(self, engine, state, t: int) -> str:
        from shadow_tpu.device import checkpoint

        path = f"{self.base}.t{t:015d}"
        checkpoint.save_state(
            engine, state, path, t, final_stop=self.final_stop,
            extra_meta=self.extra_meta,
            audit_meta={"enabled": self.audit_enabled,
                        "violations": 0})
        self.last_path, self.last_t = path, t
        from shadow_tpu.device import chaos as chaosmod
        inj = chaosmod.current()
        if inj is not None:
            # chaos seam: a scripted checkpoint_corrupt truncates the
            # entry just landed (the decoy a SIGKILL leaves) — the
            # run continues; resume must hit the newest-readable
            # rotation fallback
            inj.on_checkpoint_saved(path)
        self._prune()
        log.info("rotating checkpoint at t=%d ns -> %s "
                 "(keep %d; resume with checkpoint_load: %s)",
                 t, path, self.keep, self.base)
        return path

    def _prune(self) -> None:
        entries = rotation_entries(self.base)
        for _, p in entries[:-self.keep]:
            try:
                os.unlink(p)
            except OSError as e:
                log.warning("could not prune old checkpoint %s: %s",
                            p, e)


@dataclass
class AdvanceResult:
    """What supervise.advance hands back to the runner, beyond the
    final state: the (per-replica) round counts and every way the
    advance can end short of `pause`."""

    rounds: np.ndarray = field(
        default_factory=lambda: np.int64(0))
    t_end: int = 0
    budget_hit: bool = False
    overflowed: bool = False
    preempted: bool = False
    resume_path: str = ""
    retries: int = 0
    # mesh shrinks absorbed (failover: shrink): each one cost a
    # drain + re-shard + engine rebuild and dropped the mesh to the
    # surviving devices
    reshards: int = 0
    # OOM degradation-ladder rungs engaged (deterministic
    # RESOURCE_EXHAUSTED): each one shrank the footprint (replica
    # batching or dispatch segment) and replayed bit-identically from
    # the last validated state
    degrades: int = 0
    # dispatch telemetry (always populated): the segments synced, the
    # wall spent blocked in dispatch.sync, and the advance loop's wall
    pipeline: dict = field(default_factory=dict)


def advance(runner, state, t_start: int, pause: int, stop: int,
            ensemble: bool = False):
    """The shared segmented-advance loop (DeviceRunner and
    EnsembleRunner both delegate here): advance [t_start, pause) in
    segments cut at heartbeat / dispatch-segment / checkpoint
    boundaries, validating the state at every boundary and recovering
    from each failure class:

    * capacity overflow  -> widen + re-plan, re-run from the last
      known-good state (PR 1's loop, non-static plans only);
    * transient dispatch error -> capped-backoff retry from the last
      validated state; exhausted -> DeviceFailover (failover: hybrid)
      or re-raise;
    * audit violation    -> AuditFailure (fatal: never checkpoint or
      run forward a corrupted state);
    * preemption request -> save a resume checkpoint at the last
      validated boundary, and return preempted.

    Each segment is issued, synchronized and validated, and its
    boundary work done, before the next one is issued. Segment
    boundaries are a pure function of sim time, so a recovery that
    replays from the last validated state cuts the same segments and
    recomputes the same trace.

    Every unit of work records a flight-recorder span (shadow_tpu/obs
    — ``dispatch.issue`` enqueues and ``dispatch.sync`` blocking
    waits with their sim windows and ICI counters, heartbeats,
    checkpoint saves, retry backoffs, re-plans, the preemption
    drain), tagged so trace_report can attribute the run's wall and
    tell device-bound from sync-bound time. Tracing only reads
    values this loop already fetched, so traces stay bit-identical
    across telemetry modes.

    Returns (state, AdvanceResult).
    """
    from shadow_tpu._jax import jax
    from shadow_tpu.device import capacity, checkpoint

    tracer = getattr(runner, "tracer", None) or obstrace.current()
    xp = runner.sim.cfg.experimental
    hb = runner.sim.cfg.general.heartbeat_interval
    seg = xp.dispatch_segment
    ck: Checkpointer = getattr(runner, "checkpointer", None)
    guard: PreemptionGuard = getattr(runner, "guard", None)
    audit_on = bool(xp.state_audit)
    retry_ok = xp.capacity_plan != "static"
    supervised = bool(ck is not None
                      or (guard is not None and guard.active)
                      or xp.dispatch_retries
                      or xp.failover != "abort")
    # last known-good snapshot: device refs are immutable, so holding
    # the pytree costs nothing to take — but it pins the previous
    # segment's buffers, so plain static runs (which can never retry)
    # still skip it; every supervised failure class needs it
    keep_good = retry_ok or supervised
    budget = runner.engine.config.max_rounds
    label = "ensemble " if ensemble else ""

    def run_segment(st, nxt):
        if ensemble:
            return runner.engine.run_ensemble(st, stop=nxt,
                                              final_stop=stop)
        return runner.engine.run(st, stop=nxt, final_stop=stop)

    def replace_state(host_state):
        # place a host-side snapshot back onto the (possibly rebuilt)
        # engine with fresh device buffers
        if ensemble:
            return capacity.transfer(
                runner.engine, runner.sim.starts, host_state,
                template=runner.engine.init_ensemble_state(
                    runner.sim.starts))
        return capacity.transfer(runner.engine, runner.sim.starts,
                                 host_state)

    def drain_save(st, t):
        """The preemption resume checkpoint: reuse the rotation entry
        just written at this boundary, else write one."""
        if ck is not None:
            if ck.last_t == t:
                return ck.last_path
            return ck.save(runner.engine, st, t)
        path = xp.checkpoint_save
        checkpoint.save_state(
            runner.engine, st, path, t, final_stop=stop,
            extra_meta=getattr(runner, "_ck_extra_meta", None),
            audit_meta={"enabled": audit_on, "violations": 0})
        return path

    chaos_inj = getattr(runner, "chaos", None)
    res = AdvanceResult()
    good_state, good_t = (state if keep_good else None), t_start
    failures = 0
    oom_streak = 0              # consecutive memory-exhaustion errors
    # at the current validated boundary; 2 = deterministic, walk the
    # degradation ladder instead of the retry budget
    seg_halvings = 0
    t = t_start                 # validated sim time
    next_hb = (t // hb + 1) * hb if hb else None
    next_ck = ck.next_after(t) if ck is not None else None
    pstats = {"segments": 0, "sync_wall_s": 0.0}
    res.pipeline = pstats
    adv_wall0 = time.perf_counter()

    def next_boundary(ti):
        """The segment boundary cut at sim time `ti` — a pure
        function of the heartbeat cadence, the dispatch segment, and
        the checkpoint cadence, so a replay from any validated
        boundary cuts the same segments as the first pass."""
        nxt = pause
        if hb:
            nxt = min(nxt, (ti // hb + 1) * hb)
        if seg:
            nxt = min(nxt, ti + seg)
        if ck is not None:
            nxt = min(nxt, ck.next_after(ti))
        return nxt

    def rewind_to_good(new_state, new_t=None):
        """Recovery epilogue shared by every replay path: install
        the re-placed state as the validated snapshot and rewind the
        clock to the last validated boundary. The replay then
        proceeds through the normal loop — deterministic segments
        recompute bit-identically, so a replayed prefix never changes
        the trace. ``new_t`` overrides the boundary the state pins
        (the shrink failover may fall back to an on-disk checkpoint
        older than the in-memory snapshot)."""
        nonlocal t, next_hb, next_ck, good_state, good_t
        if new_t is not None:
            good_t = int(new_t)
        good_state = new_state
        t = good_t
        next_hb = (t // hb + 1) * hb if hb else None
        next_ck = ck.next_after(t) if ck is not None else None
        return new_state

    def recover_transient(e):
        """Transient dispatch error (issue- or sync-side): count a
        CONSECUTIVE failure, back off, and replay from the last
        validated state. `failures` resets on every synced-and-
        validated segment: unrelated transient incidents hours apart
        must not pool into one exhausted budget — a genuinely dead
        device still exhausts it, because no segment ever syncs
        clean."""
        nonlocal failures, oom_streak
        if not is_transient(e) or good_state is None:
            raise e
        # a deterministic OOM — the SAME memory-exhaustion error
        # twice in a row at the same validated boundary — cannot be
        # retried away: it routes to the degradation ladder WITHOUT
        # charging the retry budget (pre-ladder it burned every
        # retry replaying a segment that could never fit, then
        # escalated off-device). A single OOM still retries
        # normally: allocators do lose races and win the rerun.
        oom_streak = oom_streak + 1 if is_oom(e) else 0
        if oom_streak >= 2:
            return recover_oom(e)
        failures += 1
        res.retries += 1
        # live cumulative count: the supervise heartbeat line
        # reports it mid-run, not just the end-of-run SimStats
        runner.retries = res.retries
        if failures > xp.dispatch_retries:
            if is_oom(e):
                # the retry budget ran out on a memory error:
                # shrink the FOOTPRINT (the ladder), not the mesh —
                # a smaller mesh has less memory, not more
                return recover_oom(e)
            if xp.failover == "shrink":
                shrunk = _shrink_recover(runner, e, good_state,
                                         good_t, ensemble, ck,
                                         tracer)
                if shrunk is not None:
                    new_state, t_shrunk = shrunk
                    failures = 0        # the new mesh earns a fresh
                    # budget: a second device death on the shrunken
                    # mesh walks the same retry -> shrink ladder
                    res.reshards += 1
                    runner.reshards = res.reshards
                    return rewind_to_good(new_state, t_shrunk)
            _escalate(runner, e, good_state, good_t, stop,
                      ensemble, ck)
        delay = min(
            xp.dispatch_retry_backoff * (2 ** (failures - 1)),
            BACKOFF_CAP_S)
        log.warning(
            "transient %sdevice dispatch error past t=%d ns (%s); "
            "retry %d/%d from the last validated state t=%d ns after "
            "%.1fs backoff", label, good_t, e, failures,
            xp.dispatch_retries, good_t, delay)
        if delay:
            with tracer.span("retry.backoff", "retry",
                             sim_t0=good_t, attempt=failures,
                             error=str(e)[:200]):
                time.sleep(delay)
        with tracer.span("retry.recover", "retry", sim_t0=good_t,
                         attempt=failures):
            new_state = _recover_state(runner, good_state,
                                       replace_state, ck, stop,
                                       ensemble)
        return rewind_to_good(new_state)

    def recover_oom(e):
        """The graceful-degradation ladder (the runtime backstop of
        the preflight admission gate): a deterministic OOM is a
        capacity fact, so each invocation walks ONE rung that
        actually shrinks the footprint, replays bit-identically from
        the last validated state, and leaves the retry budget
        untouched. Rungs, in order:

        1. ensembles only: raise :class:`DegradeToReplicaBatch` —
           the campaign re-runs the sweep in sequential replica
           batches (bit-identical to the full vmap);
        2. halve the dispatch segment — shorter segments bound the
           transient exchange/working-set peak (segmentation never
           changes traces: the engine clamps on the global stop);
        3. out of rungs: the existing ``failover:`` escalation.

        Every rung logs a ``degrade`` span, notifies the chaos
        injector (so a scripted repeating OOM stops firing exactly
        when a real one would — the footprint shrank), and logs the
        re-admission estimate against the budget."""
        nonlocal seg, seg_halvings, oom_streak
        # a recurrence AFTER a rung is a fresh deterministic-OOM
        # incident at streak 2 immediately — walking the next rung
        # must not charge the retry budget either
        oom_streak = 1
        res.degrades += 1
        runner.degrades = res.degrades
        rung, span_kw = "", {}
        if ensemble and \
                int(getattr(runner, "_replica_batchable", 0) or 0):
            batch = int(runner._replica_batchable)
            rung = f"replica_batch {batch}"
            tracer.instant("degrade.replica_batch", "degrade",
                           sim_t0=good_t, batch=batch,
                           error=str(e)[:200])
            if chaos_inj is not None and \
                    hasattr(chaos_inj, "on_degrade_rung"):
                chaos_inj.on_degrade_rung(rung)
            log.warning(
                "OOM ladder: deterministic memory exhaustion past "
                "t=%d ns (%s) — the full-replica vmap does not fit; "
                "re-running the sweep in sequential batches of %d "
                "replica(s) (bit-identical to the full vmap)",
                good_t, e, batch)
            raise DegradeToReplicaBatch(
                f"ensemble footprint exhausted device memory ({e}); "
                f"degrade to replica batches of {batch}",
                batch=batch) from e
        cur_seg = seg if seg else max(1, int(pause) - int(good_t))
        if cur_seg > 1 and seg_halvings < MAX_SEG_HALVINGS:
            seg = max(1, cur_seg // 2)
            seg_halvings += 1
            rung = f"dispatch_segment {cur_seg}->{seg}"
            span_kw = {"segment": seg}
        if not rung:
            log.error(
                "OOM ladder exhausted: deterministic memory "
                "exhaustion past t=%d ns (%s) with no rung left "
                "(segment floor reached); escalating via "
                "failover: %s", good_t, e, xp.failover)
            _escalate(runner, e, good_state, good_t, stop, ensemble,
                      ck)
        tracer.instant("degrade." + rung.split()[0], "degrade",
                       sim_t0=good_t, error=str(e)[:200], **span_kw)
        if chaos_inj is not None and \
                hasattr(chaos_inj, "on_degrade_rung"):
            chaos_inj.on_degrade_rung(rung)
        try:
            est = capacity.footprint(runner.engine)
            b, src = capacity.device_budget(runner.engine, xp)
            log.warning(
                "OOM ladder rung %d (%s): deterministic memory "
                "exhaustion past t=%d ns (%s); re-admission "
                "estimate ~%s per device%s — replaying from the "
                "last validated state (bit-identical: segmentation "
                "is pure host orchestration)",
                res.degrades, rung, good_t, e,
                capacity.fmt_bytes(est["per_device"]),
                (f" vs budget {capacity.fmt_bytes(b)} ({src})"
                 if b else ""))
        except Exception:       # noqa: BLE001 — telemetry only
            log.warning("OOM ladder rung %d (%s): replaying from "
                        "the last validated state", res.degrades,
                        rung)
        with tracer.span("degrade.recover", "degrade", sim_t0=good_t,
                         rung=rung):
            new_state = _recover_state(runner, good_state,
                                       replace_state, ck, stop,
                                       ensemble)
        return rewind_to_good(new_state)

    while t < pause:
        if guard is not None and guard.requested:
            # preemption drain: the previous segment finished its
            # boundary work — save the resume checkpoint at this
            # validated boundary. (A signal during the FINAL segment
            # needs no drain — the t >= pause case falls out of the
            # loop and the run completes normally.)
            tracer.instant("preempt.request", "checkpoint", sim_t0=t,
                           signum=guard.signum)
            with tracer.span("checkpoint.drain_save", "checkpoint",
                             sim_t0=t) as sp:
                res.resume_path = drain_save(state, t)
                sp.add(path=res.resume_path)
            res.preempted = True
            log.warning(
                "%srun preempted at t=%d ns: resume checkpoint -> %s "
                "(re-run with experimental.checkpoint_load: %s to "
                "continue; the resumed run is bit-identical to an "
                "uninterrupted one)", label, t, res.resume_path,
                ck.base if ck is not None else res.resume_path)
            break
        nxt = next_boundary(t)
        try:
            with tracer.span("dispatch.issue", "dispatch.issue",
                             sim_t0=t, sim_t1=nxt):
                if chaos_inj is not None:
                    # the deterministic chaos seam: counts this issue
                    # and raises the scripted error when a fault (or
                    # a previously killed device on this mesh) is
                    # scheduled here — recovered like any real
                    # dispatch failure
                    chaos_inj.on_dispatch_issue(runner.engine)
                new_state, seg_rounds = run_segment(state, nxt)
            sync0 = time.perf_counter()
            try:
                # both device_gets synchronize segment [t, nxt), so
                # asynchronously raised dispatch errors surface
                # inside this span. A raised error closes the span
                # with an error tag, so retries show on the timeline
                # as failed-sync + backoff + recover spans.
                with tracer.span("dispatch.sync", "dispatch.sync",
                                 sim_t0=t, sim_t1=nxt) as sp:
                    dims = capacity.overflow_dims(new_state)
                    seg_rounds = np.asarray(
                        jax.device_get(seg_rounds))
                    sp.add(rounds=int(np.max(seg_rounds)))
                    eff = runner.engine.effective
                    if eff.get("n_shards", 1) > 1:
                        # exchange-flush attribution: the flush is
                        # fused into the compiled round on-device,
                        # so its wall is inside the issued segment;
                        # the static per-flush ICI volume (buffers
                        # ship at capacity) rides as counters; its
                        # device time is the `engine.exchange` scope
                        # of a profiler trace
                        sp.add(exchange=eff["exchange"],
                               shards=eff["n_shards"],
                               ici_rows_per_flush=eff[
                                   "ICI_rows_per_flush"],
                               ici_bytes_per_flush=eff[
                                   "ICI_bytes_per_flush"])
            finally:
                pstats["sync_wall_s"] += time.perf_counter() - sync0
        except AuditFailure:
            raise
        except Exception as e:  # noqa: BLE001 — classified there
            state = recover_transient(e)
            continue
        pstats["segments"] += 1
        if dims:
            if not retry_ok or runner.replans >= capacity.MAX_REPLANS:
                res.rounds = res.rounds + seg_rounds
                state = new_state
                t = nxt
                res.overflowed = True
                tracer.instant("capacity.overflow", "plan",
                               sim_t0=t, dims=list(dims))
                break           # loud failure (stats.ok = False)
            runner.replans += 1
            runner._capacity_overrides = capacity.widen(
                runner._capacity_overrides, dims,
                runner.engine.effective)
            log.warning(
                "%scapacity overflow on %s in (%d, %d] ns; re-plan "
                "#%d with %s, re-running from t=%d ns", label, dims,
                t, nxt, runner.replans, runner._capacity_overrides,
                good_t)
            with tracer.span("capacity.replan", "plan",
                             sim_t0=good_t, sim_t1=nxt,
                             dims=list(dims), replan=runner.replans):
                runner.engine = runner._build_engine()
                # the re-plan just named the next program: its AOT
                # entry read overlaps the state transfer
                prefetch_programs(runner, ensemble)
                new_state = replace_state(jax.device_get(good_state))
            state = rewind_to_good(new_state)
            continue
        state = new_state
        res.rounds = res.rounds + seg_rounds
        t = nxt
        failures = 0            # the segment synced clean; see above
        oom_streak = 0          # ... and so did any OOM streak
        if int(np.max(res.rounds)) >= budget:
            # enforced cumulatively (per-invocation caps would reset
            # each segment)
            if t < pause:
                log.warning("max_rounds (%d) exhausted during "
                            "%ssegmentation; stopping", budget, label)
            res.budget_hit = True
            tracer.instant("budget.exhausted", "host", sim_t0=t,
                           budget=int(budget))
            break
        if audit_on:
            # the boundary state is validated BEFORE it becomes the
            # known-good snapshot or a checkpoint — a corrupted state
            # is never the one a retry or a restart resumes from
            check_audit(state, where=f"t={t} ns",
                        last_good=(ck.last_path if ck is not None
                                   else ""))
        if next_hb is not None and t >= next_hb and t < stop:
            with tracer.span("heartbeat", "host", sim_t0=t):
                runner._emit_heartbeats(t, state)
            next_hb += hb
        if next_ck is not None and t >= next_ck and t < stop:
            with tracer.span("checkpoint.save", "checkpoint",
                             sim_t0=t) as sp:
                sp.add(path=ck.save(runner.engine, state, t))
            next_ck = ck.next_after(t)
        if keep_good:
            good_state, good_t = state, t
    res.t_end = t
    pstats["advance_wall_s"] = round(time.perf_counter() - adv_wall0, 3)
    pstats["sync_wall_s"] = round(pstats["sync_wall_s"], 3)
    return state, res


def _recover_state(runner, good_state, replace_state, ck, stop,
                   ensemble):
    """Re-place the last validated state onto fresh device buffers for
    a dispatch retry. If even fetching the held snapshot fails (the
    device that owned it is gone), fall back to the last rotating
    checkpoint on disk."""
    from shadow_tpu._jax import jax
    from shadow_tpu.device import checkpoint

    try:
        return replace_state(jax.device_get(good_state))
    except Exception as fetch_err:      # noqa: BLE001
        if ck is None or not ck.last_path:
            raise
        log.warning("could not recover the in-memory state (%s); "
                    "reloading the last validated checkpoint %s",
                    fetch_err, ck.last_path)
        # the snapshot's owner died, so the engine's compiled
        # executables (bound to the dead device's buffers) are
        # suspect too — rebuild the engine for the retry. The AOT
        # compile cache (device/aotcache.py, attached by
        # _build_engine) turns this recompile into a warm start:
        # same capacities -> same program key -> cached executable.
        runner.engine = runner._build_engine()
        # overlap the rebuilt program's AOT entry read with the
        # checkpoint reload below
        prefetch_programs(runner, ensemble)
        template = (runner.engine.init_ensemble_state(runner.sim.starts)
                    if ensemble else None)
        state, _ = checkpoint.load_state(
            runner.engine, runner.sim.starts, ck.last_path,
            final_stop=stop, template=template)
        return state


def _escalate(runner, exc, good_state, good_t, stop, ensemble, ck):
    """Retries exhausted and no shrink absorbed the loss: the
    failover ladder's last rung. ``abort`` re-raises; ``hybrid`` —
    and ``shrink``, whose hybrid rung this is when no shrink was
    possible — persists the last validated state and raises
    DeviceFailover for the Controller's hybrid rerun. Campaigns
    never reach the hybrid rung (CPU host emulation cannot vmap
    replicas): they re-raise with the last validated checkpoint on
    disk.

    When the persist fails AND no rotating checkpoint exists, the
    failover still runs: the raised DeviceFailover carries
    ``checkpoint_path=None`` and the persist error, and the
    Controller surfaces ONE loud diagnostic naming it — previously
    this path silently degraded to a bare re-raise with no state on
    disk and no failover at all."""
    from shadow_tpu._jax import jax
    from shadow_tpu.device import checkpoint

    xp = runner.sim.cfg.experimental
    if xp.failover == "abort" or ensemble:
        raise exc
    path, t_pin = "", good_t
    if ck is not None and ck.last_path:
        path, t_pin = ck.last_path, ck.last_t
    try:
        host_good = jax.device_get(good_state)
        fo_path = ((xp.checkpoint_save + ".failover")
                   if xp.checkpoint_save else
                   os.path.join(runner.sim.cfg.general.data_directory,
                                "device_failover.npz"))
        checkpoint.save_state(
            runner.engine, host_good, fo_path, good_t,
            final_stop=stop,
            audit_meta={"enabled": bool(xp.state_audit),
                        "violations": 0})
        path, t_pin = fo_path, good_t
    except Exception as save_err:       # noqa: BLE001
        if not path:
            # no state anywhere: the Controller's diagnostic is THE
            # loud surface (one message naming the persist error) —
            # no second error log here
            raise DeviceFailover(
                f"device dispatch failed permanently after "
                f"{xp.dispatch_retries} retries ({exc}); the last "
                f"validated state at t={good_t} ns could NOT be "
                f"persisted ({save_err})",
                checkpoint_path=None, sim_time=good_t,
                persist_error=str(save_err)) from exc
        log.warning("failover: could not persist the in-memory state "
                    "(%s); the last rotating checkpoint %s (t=%d ns) "
                    "pins the device-side resume", save_err, path,
                    t_pin)
    raise DeviceFailover(
        f"device dispatch failed permanently after "
        f"{xp.dispatch_retries} retries ({exc}); last validated "
        f"state at t={t_pin} ns saved to {path or '<none>'}",
        checkpoint_path=path, sim_time=t_pin) from exc
