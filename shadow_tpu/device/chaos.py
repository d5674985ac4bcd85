"""Deterministic chaos injection at the supervise/engine seams.

The failover machinery (dispatch retry, mesh-shrink, hybrid rerun —
device/supervise.py) exists for failure modes no CI box exhibits on
demand: a chip dying mid-campaign, a checkpoint write torn by the
filesystem, a cache store hitting a full disk. This module makes
those failures SCRIPTABLE and byte-for-byte reproducible, so the
recovery ladder is drilled in CI the same way determinism is gated:
``experimental.chaos`` declares a schedule of fault points, and the
injector fires each one at a deterministic seam counter — never from
a timer, a signal, or randomness — so the same schedule against the
same config reproduces the identical run, failures included.

Fault kinds (:data:`KINDS`):

* ``device_loss`` — at the ``segment``-th dispatch issue of the
  supervised advance loop, the mesh device at position ``shard``
  is marked DEAD. Every subsequent dispatch on a mesh containing a
  dead device raises the scripted ``error`` class — exactly the
  shape of a real chip loss (retries exhaust because the segment can
  never drain clean) — until a mesh shrink rebuilds the engine on
  the survivors, after which dispatches succeed again. The liveness
  probe (supervise.surviving_devices) consults :meth:`is_dead` so a
  scripted death fails the probe the way a real one would.
* ``dispatch_error`` — a ONE-SHOT error at the ``segment``-th
  dispatch issue (transient-retry drills; a non-transient ``error``
  class drills the abort path).
* ``checkpoint_corrupt`` — after the ``entry``-th rotating
  checkpoint save lands on disk, truncate the file mid-payload (the
  artifact a SIGKILL can leave) so the newest-readable rotation
  fallback (supervise.resolve_checkpoint) must engage on resume.
* ``cache_store_fail`` — the ``store``-th AOT compile-cache store
  is refused (full-disk drill); the cache must degrade loudly to an
  unpersisted fresh compile, never abort the run.
* ``oom`` — a scripted ``RESOURCE_EXHAUSTED`` at the ``segment``-th
  dispatch issue OR the ``compile``-th program compile (exactly one).
  Unlike ``dispatch_error`` the fault REPEATS at every later count —
  a real out-of-memory is deterministic: the same too-big program
  fails every time — until the degradation ladder engages a rung
  (:meth:`ChaosInjector.on_degrade_rung`), after which it clears the
  way a real OOM clears once the footprint shrinks. This is the CI
  drill for supervise.advance's degrade ladder
  (``determinism_gate --degrade``).

Counters are seam-local and monotonic: dispatch issues count every
``dispatch.issue`` of supervise.advance (replays after a recovery
included — control flow is deterministic, so the count sequence is
too), rotation saves count Checkpointer.save calls, cache stores
count AotCache.store calls. All injector state is lock-protected and
registered in the concurrency lint's LOCK_REGISTRY
(shadow_tpu/analyze/concurrency.py).

The injector is process-global per run (``set_current`` /
``current``), installed by DeviceRunner.__init__ from the validated
config — a run without a chaos schedule installs None, so schedules
never leak across in-process runs (gates, tests).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass

from shadow_tpu.utils.slog import get_logger

log = get_logger("chaos")

KINDS = ("device_loss", "dispatch_error", "checkpoint_corrupt",
         "cache_store_fail", "oom", "server_crash")

# transient by default: UNAVAILABLE matches supervise.TRANSIENT_MARKERS
# so the scripted loss walks the real retry -> escalate ladder
DEFAULT_ERROR = "UNAVAILABLE"
# oom events default to the class a real allocator failure raises, so
# supervise.is_oom routes them onto the degradation ladder
OOM_ERROR = "RESOURCE_EXHAUSTED"


class ChaosError(RuntimeError):
    """A scripted fault. The message leads with the event's error
    class so supervise.is_transient classifies it exactly like the
    real XlaRuntimeError it stands in for."""


@dataclass(frozen=True)
class ChaosEvent:
    """One validated ``experimental.chaos`` entry."""

    kind: str
    segment: int = -1      # device_loss/dispatch_error/oom: dispatch #
    shard: int = -1        # device_loss: mesh position of the dying chip
    error: str = DEFAULT_ERROR
    entry: int = -1        # checkpoint_corrupt: rotation save #
    store: int = -1        # cache_store_fail: cache store #
    compile: int = -1      # oom: program compile #
    tick: int = -1         # server_crash: campaign-server scheduler tick #


def event_from_dict(i: int, d: dict) -> ChaosEvent:
    """One ``experimental.chaos[i]`` mapping -> a validated
    ChaosEvent. Structural validation happens at config load (the
    network.faults rule): a typo'd schedule must fail in
    milliseconds, not as a run that silently never injects."""
    section = f"experimental.chaos[{i}]"
    if not isinstance(d, dict):
        raise ValueError(f"{section} must be a mapping")
    allowed = {"kind", "segment", "shard", "error", "entry", "store",
               "compile", "tick"}
    unknown = set(d) - allowed
    if unknown:
        raise ValueError(f"unknown key(s) in {section}: "
                         f"{sorted(unknown)} (allowed: "
                         f"{sorted(allowed)})")
    kind = d.get("kind")
    if kind not in KINDS:
        raise ValueError(
            f"{section}.kind={kind!r} is not one of {list(KINDS)}")
    need = {"device_loss": ("segment", "shard"),
            "dispatch_error": ("segment",),
            "checkpoint_corrupt": ("entry",),
            "cache_store_fail": ("store",),
            "oom": (),
            "server_crash": ("tick",)}[kind]
    for key in need:
        if d.get(key) is None or int(d[key]) < 0:
            raise ValueError(
                f"{section}: {kind} needs {key!r} >= 0 (the "
                "deterministic seam counter the fault fires at)")
    if kind == "oom":
        has_seg = d.get("segment") is not None and \
            int(d["segment"]) >= 0
        has_cmp = d.get("compile") is not None and \
            int(d["compile"]) >= 0
        if has_seg == has_cmp:
            raise ValueError(
                f"{section}: oom needs exactly one of 'segment' "
                "(dispatch issue #) or 'compile' (program compile #) "
                ">= 0")
    scope = {"device_loss": ("segment", "shard", "error"),
             "dispatch_error": ("segment", "error"),
             "checkpoint_corrupt": ("entry",),
             "cache_store_fail": ("store",),
             "oom": ("segment", "compile", "error"),
             "server_crash": ("tick",)}[kind]
    for key in ("segment", "shard", "entry", "store", "compile",
                "tick", "error"):
        if key not in scope and d.get(key) is not None:
            raise ValueError(
                f"{section}: {key!r} is not valid for {kind}")
    return ChaosEvent(
        kind=kind,
        segment=int(d.get("segment", -1)),
        shard=int(d.get("shard", -1)),
        error=str(d.get("error",
                        OOM_ERROR if kind == "oom" else DEFAULT_ERROR)),
        entry=int(d.get("entry", -1)),
        store=int(d.get("store", -1)),
        compile=int(d.get("compile", -1)),
        tick=int(d.get("tick", -1)),
    )


def events_from_config(raw: list) -> list[ChaosEvent]:
    """Validate the whole ``experimental.chaos`` list (schema.py
    delegates here — the injector owns its schedule format). Already-
    validated ChaosEvent entries pass through (gate scripts build
    them directly)."""
    if not isinstance(raw, list):
        raise ValueError("experimental.chaos must be a list of fault "
                         "events")
    out = []
    for i, d in enumerate(raw):
        if isinstance(d, ChaosEvent):
            out.append(d)
            continue
        out.append(event_from_dict(i, d))
    return out


class ChaosInjector:
    """Fires a validated schedule at the supervise/engine seams.

    Every mutation of the shared counters/ledger holds ``_lock``
    (registered in the concurrency lint's LOCK_REGISTRY): the
    injector is process-global (:func:`current`), so any thread that
    reaches one of its seams shares these counters."""

    def __init__(self, events: list[ChaosEvent]):
        self._lock = threading.Lock()
        self._events = tuple(events)
        self._dead: dict = {}          # jax device id -> error class
        self._issues = 0
        self._ck_saves = 0
        self._stores = 0
        self._compiles = 0
        # scripted ooms repeat until the degradation ladder engages a
        # rung — then they clear, the way a real OOM clears once the
        # footprint shrinks (on_degrade_rung)
        self._oom_cleared = False
        self._ticks = 0                # campaign-server scheduler ticks
        self.fired: list = []          # ledger of fired events

    # -- dispatch seam (supervise.advance issue half) ------------------
    def on_dispatch_issue(self, engine) -> None:
        """Count one dispatch issue; fire any event scheduled at this
        count, then raise if the engine's mesh contains a dead device
        (a real dead chip fails every dispatch that touches it)."""
        from shadow_tpu.obs import trace as obstrace

        devices = list(engine.mesh.devices.flat)
        with self._lock:
            k = self._issues
            self._issues += 1
            oneshot = None
            for ev in self._events:
                if ev.segment != k:
                    continue
                if ev.kind == "device_loss":
                    if ev.shard >= len(devices):
                        raise ValueError(
                            f"chaos: device_loss shard {ev.shard} is "
                            f"out of range for the {len(devices)}-"
                            "device mesh")
                    dev = devices[ev.shard]
                    self._dead[dev.id] = ev.error
                    self.fired.append(
                        {"kind": "device_loss", "segment": k,
                         "shard": ev.shard, "device_id": dev.id})
                    log.warning("chaos: device %s (mesh position %d) "
                                "marked DEAD at dispatch issue %d",
                                dev, ev.shard, k)
                elif ev.kind == "dispatch_error":
                    oneshot = ev
                    self.fired.append(
                        {"kind": "dispatch_error", "segment": k,
                         "error": ev.error})
            oom = None
            if not self._oom_cleared:
                for ev in self._events:
                    if ev.kind == "oom" and ev.segment >= 0 and \
                            k >= ev.segment:
                        oom = ev
                        self.fired.append(
                            {"kind": "oom", "seam": "dispatch",
                             "segment": k, "error": ev.error})
                        break
            down = sorted((d.id, self._dead[d.id]) for d in devices
                          if d.id in self._dead)
        if oom is not None:
            obstrace.current().instant(
                "chaos.oom", "chaos", segment=k, error=oom.error)
            raise ChaosError(
                f"{oom.error}: chaos: scripted oom at dispatch issue "
                f"{k} (repeats until a degrade rung engages)")
        if oneshot is not None:
            obstrace.current().instant(
                "chaos.dispatch_error", "chaos", segment=k,
                error=oneshot.error)
            raise ChaosError(
                f"{oneshot.error}: chaos: scripted dispatch error at "
                f"issue {k}")
        if down:
            obstrace.current().instant(
                "chaos.device_down", "chaos", segment=k,
                device_ids=[d for d, _ in down])
            raise ChaosError(
                f"{down[0][1]}: chaos: mesh device(s) "
                f"{[d for d, _ in down]} are down (scripted device "
                "loss)")

    def is_dead(self, device_id) -> bool:
        """The liveness probe's hook: a scripted death must fail the
        probe exactly like a real one."""
        with self._lock:
            return device_id in self._dead

    # -- compile seam (aotcache.AotCache.ensure) -----------------------
    def on_compile(self, name: str) -> None:
        """Count one program compile (before lower/compile); raise a
        scripted oom scheduled at this count. A compile-time
        RESOURCE_EXHAUSTED surfaces out of the dispatch that forced
        the compile, so the same supervise recovery path catches it."""
        from shadow_tpu.obs import trace as obstrace

        with self._lock:
            n = self._compiles
            self._compiles += 1
            hit = None
            if not self._oom_cleared:
                for ev in self._events:
                    if ev.kind == "oom" and ev.compile >= 0 and \
                            n >= ev.compile:
                        hit = ev
                        self.fired.append(
                            {"kind": "oom", "seam": "compile",
                             "compile": n, "program": name,
                             "error": ev.error})
                        break
        if hit is not None:
            obstrace.current().instant(
                "chaos.oom", "chaos", compile=n, program=name,
                error=hit.error)
            log.warning("chaos: scripted oom at compile %d (%s)", n,
                        name)
            raise ChaosError(
                f"{hit.error}: chaos: scripted oom at compile {n} "
                f"({name}; repeats until a degrade rung engages)")

    def on_degrade_rung(self, rung: str) -> None:
        """The degradation ladder engaged a rung: scripted ooms stop
        firing. Without this clear the drill could never converge —
        a real OOM clears because the rung genuinely shrank the
        footprint; the scripted one must honor the same contract."""
        with self._lock:
            if self._oom_cleared:
                return
            self._oom_cleared = True
            self.fired.append({"kind": "oom_cleared", "rung": rung})
        log.warning("chaos: scripted oom cleared by degrade rung %s",
                    rung)

    # -- checkpoint seam (supervise.Checkpointer.save) -----------------
    def on_checkpoint_saved(self, path: str) -> None:
        """Count one rotation save; corrupt the file on disk when an
        event is scheduled at this count (truncate mid-payload — the
        decoy a SIGKILL can leave). The RUN is untouched: the
        corruption is to the artifact, and the newest-readable
        rotation fallback must absorb it on resume."""
        import os

        from shadow_tpu.obs import trace as obstrace

        with self._lock:
            n = self._ck_saves
            self._ck_saves += 1
            hit = any(ev.kind == "checkpoint_corrupt" and
                      ev.entry == n for ev in self._events)
            if hit:
                self.fired.append({"kind": "checkpoint_corrupt",
                                   "entry": n, "path": path})
        if not hit:
            return
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.truncate(max(1, size // 3))
        obstrace.current().instant("chaos.checkpoint_corrupt",
                                   "chaos", entry=n, path=path)
        log.warning("chaos: rotation entry %d corrupted on disk "
                    "(truncated %s — the newest-readable fallback "
                    "must skip it on resume)", n, path)

    # -- compile-cache seam (aotcache.AotCache.store) ------------------
    def on_cache_store(self, key: str) -> bool:
        """Count one cache store; True = this store must fail (the
        cache degrades to an unpersisted fresh compile, loudly)."""
        from shadow_tpu.obs import trace as obstrace

        with self._lock:
            n = self._stores
            self._stores += 1
            hit = any(ev.kind == "cache_store_fail" and
                      ev.store == n for ev in self._events)
            if hit:
                self.fired.append({"kind": "cache_store_fail",
                                   "store": n, "key": key})
        if hit:
            obstrace.current().instant("chaos.cache_store_fail",
                                       "chaos", store=n, key=key)
            log.warning("chaos: cache store %d (key %s) refused by "
                        "schedule", n, key)
        return hit

    # -- server seam (shadow_tpu/serve/server.py scheduler loop) -------
    def on_server_tick(self) -> bool:
        """Count one campaign-server scheduler tick; True = a scripted
        ``server_crash`` fires here and the server must die the HARD
        way (its crash_fn defaults to os._exit — no drain, no journal
        flush beyond what append_line already fsync'd). The drill is
        the journal's crash-replay contract, not a graceful shutdown:
        the restarted server must requeue every non-terminal campaign
        and finish it bit-identical."""
        with self._lock:
            n = self._ticks
            self._ticks += 1
            hit = any(ev.kind == "server_crash" and ev.tick == n
                      for ev in self._events)
            if hit:
                self.fired.append({"kind": "server_crash", "tick": n})
        if hit:
            log.warning("chaos: scripted server crash at scheduler "
                        "tick %d", n)
        return hit


# -- module-global current injector ------------------------------------
# installed by DeviceRunner.__init__ for the run's lifetime (None when
# the config has no chaos schedule — schedules never leak across
# in-process runs); the checkpoint and cache seams read it here, the
# same ownership rule as obs.trace's current tracer.
_CURRENT: object = None


def current():
    return _CURRENT


def set_current(injector) -> None:
    global _CURRENT
    _CURRENT = injector


def from_config(xp) -> object:
    """The runner's injector factory from validated
    ``experimental.chaos`` (None without a schedule)."""
    events = getattr(xp, "chaos", None)
    if not events:
        return None
    return ChaosInjector(events_from_config(events))
