"""Persistent ahead-of-time compile cache for the device engine.

Every fresh process pays the engine's full XLA compile before the
first event executes — 40s+ at the bench headline rung (BENCH_r02:
41.4s compile vs 5.0s steady run) — and that cost is re-paid by
supervised restarts, hybrid failovers, ensemble campaigns, CI rungs,
and every bench iteration. Training/inference stacks solve exactly
this cold-start problem with a persistent executable cache; this
module is that cache for the simulation engine:

* the engine's jitted programs (``_run``, ``_run_ens``, and the
  profiling split programs) are lowered and compiled AHEAD OF TIME
  (``jax.jit(...).lower(args).compile()``), serialized via
  ``jax.experimental.serialize_executable`` (the ``jax.stages``
  ``Compiled`` round-trip), and written to a cache directory;
* entries are keyed by a **program fingerprint** composed of every
  input that shapes the traced program: the workload fingerprint
  (``capacity.app_fingerprint`` — app scalars + per-host arrays), all
  six capacity knobs, the exchange variant + mesh shape, the fault
  epoch count, the audit flag, the jax/jaxlib versions + backend
  platform, and a digest of the engine-side source modules — so any
  input that changes the traced program changes the key, and a stale
  entry can never be (mis)used;
* the cache is **corruption-tolerant**: an unreadable, truncated, or
  stale entry logs a warning, recompiles, and atomically overwrites
  the bad entry (``utils/artifacts.atomic_write``) — degradation is
  always to a fresh compile, never to a wrong trace;
* the cache is **bounded**: total entry bytes are capped
  (``experimental.compile_cache_cap_mb``) with LRU eviction — loads
  touch the entry mtime, stores evict the least-recently-used entries
  past the cap;
* hits/misses are **loud**: every ``ensure`` records an attribution
  event (lower/compile/serialize/load walls) that the runners surface
  through ``SimStats.compile_cache``.

Concurrent-writer safety rides the artifacts helper: tmp files carry
the writer's pid and land via ``os.replace``, so two processes racing
onto one entry each write a complete file and the loser's replace
simply lands second — readers always see a complete entry.

Backends whose PJRT client does not support executable serialization
(``serialize_executable`` raises) degrade to the plain jit path with
one warning; JAX's own persistent *tracing* cache
(``JAX_COMPILATION_CACHE_DIR`` / shadow_tpu/_jax.py) still covers
those environments.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import pickle
import threading
import time

from shadow_tpu.obs import trace as obstrace
from shadow_tpu.utils.slog import get_logger

log = get_logger("aotcache")

FORMAT = 2
ENTRY_SUFFIX = ".aotc"
DEFAULT_CAP_MB = 2048

# the engine-side source surface that shapes the traced programs: a
# code change in any of these must invalidate every cached executable
# (the fingerprint cannot see a rewritten flush or a new audit bit).
# Module paths, resolved lazily so importing this module stays cheap.
CODE_DIGEST_MODULES = (
    "shadow_tpu.device.engine",
    "shadow_tpu.device.apps",
    "shadow_tpu.device.netsem",
    "shadow_tpu.device.prng",
    # the two-level factored gather the traced program calls under
    # representation: hierarchical (compose order is trace semantics)
    "shadow_tpu.topology.hierarchy",
    "shadow_tpu.host.model_nic",
    # constant providers the trace bakes in: checksum fold constants
    # (CHK_*/MASK63), event kind ids (KIND_*), RNG purpose ids
    "shadow_tpu.utils.checksum",
    "shadow_tpu.core.event",
    "shadow_tpu.utils.rng",
)

# import-graph hook for the fingerprint-completeness pass
# (shadow_tpu/analyze/imports_audit.py): the walk starts at the trace
# roots, follows static imports, and requires every module it reaches
# to appear in CODE_DIGEST_MODULES — EXCEPT the declared boundary
# modules, whose trace-relevant outputs are fingerprinted BY VALUE
# elsewhere in the cache key (so their source need not be digested,
# and their own imports are not followed). Each boundary entry names
# where its value coverage lives; deleting a non-boundary module from
# CODE_DIGEST_MODULES fails the analyze CI rung loudly.
CODE_DIGEST_ROOTS = ("shadow_tpu.device.engine",)
CODE_DIGEST_BOUNDARY = {
    "shadow_tpu": "package namespace only (version/__init__ exports)",
    "shadow_tpu.device": "package namespace only",
    "shadow_tpu.topology":
        "package namespace only; builders never enter a traced "
        "program — the tables they produce join the cache key by "
        "value (world fingerprint + program_facts representation), "
        "and the traced gather itself is topology.hierarchy, digested",
    "shadow_tpu._jax":
        "import shim; jax/jaxlib versions join backend_signature",
    "shadow_tpu.simtime":
        "unit constants; the resolved values (lookahead, bootstrap, "
        "stops, MSS-derived app scalars) are fingerprinted by value "
        "via program_facts + app_fingerprint",
    "shadow_tpu.device.capacity":
        "its trace inputs (CAP/CAP2/CX, tp group split, exchange "
        "choice) are fingerprinted by value via program_facts",
    "shadow_tpu.models.tgen":
        "CPU-twin constants (CHUNK_PKTS) land in app scalars, "
        "fingerprinted by value via app_fingerprint",
    "shadow_tpu.models.tor":
        "CPU-twin constants land in app scalars, fingerprinted by "
        "value via app_fingerprint",
    "shadow_tpu.obs":
        "flight recorder: spans only read already-computed values "
        "(contract pinned by determinism_gate --telemetry)",
    "shadow_tpu.obs.trace":
        "flight recorder: spans only read already-computed values",
    "shadow_tpu.utils.slog": "logging only; no values enter a trace",
}

_code_digest_cache: str = ""


def _set_tracing_cache(enabled: bool) -> None:
    """Enable/disable JAX's persistent TRACING cache process-wide.

    The two caches do not compose on the CPU backend (verified
    empirically on jax 0.4.37): once any executable in the process
    came out of the tracing cache, later `serialize_executable` blobs
    (and loads) break with INTERNAL "Symbols not found" — the
    process-global JIT symbol state poisons the round-trip. So an
    enabled AOT cache turns the tracing cache OFF for the process
    (the engine executables land in THIS cache instead, which skips
    tracing too — strictly better), and a backend that turns out not
    to serialize turns it back ON so the documented fallback
    (JAX_COMPILATION_CACHE_DIR) still applies.

    jax latches `is_cache_used` per process at the first compile, so
    flipping the flag alone is not enough — reset_cache() drops the
    latch."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


_serialization_probe: bool | None = None


def serialization_supported() -> bool:
    """One cheap per-process probe: can this backend's PJRT client
    round a Compiled through serialize? Runs BEFORE the cache
    disables jax's tracing cache, so an unsupported backend (one whose
    PJRT client raises UNIMPLEMENTED) keeps the tracing cache as its
    persistence layer for the big engine compiles — not just for
    programs compiled after the first store failure. The probe
    compiles fresh (see _fresh_compile): a tracing-cache-hit
    executable would fail serialization for the wrong reason."""
    global _serialization_probe
    if _serialization_probe is None:
        try:
            import jax
            import jax.numpy as jnp
            from jax.experimental import serialize_executable as se

            with _fresh_compile():
                compiled = jax.jit(lambda x: x + 1).lower(
                    jnp.zeros((8,), jnp.int32)).compile()
            se.serialize(compiled)
            _serialization_probe = True
        except Exception as e:          # noqa: BLE001 — backend gap
            log.warning(
                "compile cache: executable serialization is "
                "unsupported on this backend (%s) — AOT entries "
                "disabled; JAX's built-in tracing cache remains the "
                "persistence layer (JAX_COMPILATION_CACHE_DIR)", e)
            _serialization_probe = False
    return _serialization_probe


@contextlib.contextmanager
def _fresh_compile():
    """Bypass JAX's persistent TRACING cache for one compile whose
    executable will be serialized (see _set_tracing_cache for why the
    caches must not mix). Standalone tooling (tpu_micro --variant 6)
    uses this; an enabled AotCache disables the tracing cache for the
    whole process instead."""
    import jax

    old = bool(jax.config.jax_enable_compilation_cache)
    _set_tracing_cache(False)
    try:
        yield
    finally:
        _set_tracing_cache(old)


def code_digest() -> str:
    """SHA-256 over the source of every program-shaping engine module
    (cached per process — the sources cannot change under a running
    interpreter)."""
    global _code_digest_cache
    if _code_digest_cache:
        return _code_digest_cache
    import importlib

    h = hashlib.sha256()
    for name in CODE_DIGEST_MODULES:
        mod = importlib.import_module(name)
        path = getattr(mod, "__file__", None)
        h.update(name.encode())
        if path and os.path.exists(path):
            with open(path, "rb") as f:
                h.update(f.read())
    _code_digest_cache = h.hexdigest()[:16]
    return _code_digest_cache


def backend_identity(devs) -> dict:
    """jax/jaxlib versions, platform, and device kinds for a device
    list — the ONE definition of "backend identity", shared by the
    cache key (backend_signature) and scripts/tune.py's plan records,
    so the two surfaces cannot drift on what identifies a backend."""
    import jax
    import jaxlib

    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": devs[0].platform,
        "device_kinds": sorted({d.device_kind for d in devs}),
    }


def backend_signature(mesh) -> dict:
    """The backend identity a serialized executable is only valid
    for, plus the mesh's device count and ordering (an executable
    compiled for devices [0..3] must not load onto a 1-device or a
    differently-ordered mesh; load() places it on exactly these)."""
    devs = list(mesh.devices.flat)
    sig = backend_identity(devs)
    sig["n_devices"] = len(devs)
    sig["device_ids"] = [int(d.id) for d in devs]
    return sig


def program_signature(engine, program: str) -> dict:
    """Every input that shapes `program`'s traced computation, as one
    JSON-able dict. The engine's ``program_facts`` carries the resolved
    compile-time surface (capacities, strategy flags, lookahead,
    epoch count, audit, ensemble width, ...); the workload fingerprint
    covers the app's scalars + per-host arrays; the backend signature
    and code digest cover everything outside the config."""
    from shadow_tpu.device.capacity import app_fingerprint

    sig = {
        "format": FORMAT,
        "program": str(program),
        "app": type(engine.app).__name__,
        "workload_fp": app_fingerprint(engine.app),
        "facts": dict(engine.program_facts),
        "backend": backend_signature(engine.mesh),
        "code": code_digest(),
    }
    if engine.config.model_bandwidth:
        # the fluid NIC bakes the per-host bandwidth vectors into the
        # trace as closure constants (engine.py bw_up_t/bw_down_t) —
        # unlike the latency/reliability tables, which ride the traced
        # world tuple — so under model_bandwidth they must key the
        # entry. Fault-free model-app runs skip the digest: the
        # vectors are unused there and would only cost spurious
        # misses on irrelevant bandwidth edits.
        import numpy as np

        h = hashlib.sha256()
        for arr in (engine.bw_up, engine.bw_down):
            h.update(np.ascontiguousarray(np.asarray(arr)).tobytes())
        sig["bw_digest"] = h.hexdigest()[:16]
    return sig


def program_key(engine, program: str) -> str:
    sig = program_signature(engine, program)
    return hashlib.sha256(
        json.dumps(sig, sort_keys=True).encode()).hexdigest()[:24]


class AotCache:
    """One persistent executable cache directory.

    The runners create ONE instance per run (from
    ``experimental.compile_cache``) and attach it to every engine they
    build — warm-up engines, re-planned engines, and resumed engines
    all consult the same cache, and ``report()`` aggregates the whole
    run's attribution (the loud hit/miss surface)."""

    def __init__(self, directory: str,
                 cap_bytes: int = DEFAULT_CAP_MB * (1 << 20)):
        self.directory = os.path.expanduser(directory)
        self.cap_bytes = int(cap_bytes)
        self.events: list[dict] = []
        # two independent degradations, so neither forfeits the
        # other's warm starts:
        # * unsupported  — the backend cannot serialize/deserialize
        #   executables at all: both load and store are off, jax's
        #   tracing cache stays on as the fallback;
        # * store_disabled — the DIRECTORY cannot be written
        #   (read-only shared cache, disk full): new entries are not
        #   stored, but EXISTING entries still load — a prepopulated
        #   read-only cache remains a warm-start source.
        self.unsupported = not serialization_supported()
        self.store_disabled = (False if self.unsupported
                               else not self._dir_writable())
        # background entry pre-reads (prefetch): key -> (thread,
        # slot). A plan/re-plan names the next program before its
        # first dispatch, so the entry's disk read + pickle parse can
        # overlap the state-transfer work instead of blocking load()
        self._prefetched: dict = {}
        if not self.unsupported:
            # executable serialization and jax's tracing cache do
            # not compose (see _set_tracing_cache) — whenever this
            # cache may LOAD entries, the tracing cache must be off,
            # or a tracing-cache-hit executable earlier in the
            # process poisons the deserialize. This also applies in
            # store_disabled mode (loads are the whole point there).
            # Named cost: programs OUTSIDE the AOT side table (the
            # heap builder, _probe, transfer broadcasts) lose cross-
            # process tracing-cache persistence; the engine's heavy
            # programs — the ones worth persisting — all live here.
            _set_tracing_cache(False)

    def _dir_writable(self) -> bool:
        """Probe the cache directory for writability NOW — before the
        constructor trades jax's tracing cache away for a cache that
        could never store anything (read-only home, full disk)."""
        probe = os.path.join(self.directory,
                             f".probe.{os.getpid()}.tmp")
        try:
            os.makedirs(self.directory, exist_ok=True)
            with open(probe, "wb"):
                pass
            os.unlink(probe)
            return True
        except OSError as e:
            log.warning(
                "compile cache: directory %s is not writable (%s) — "
                "new AOT entries disabled; existing entries still "
                "load, but fresh compiles are not persisted this "
                "run (the tracing cache must stay off while AOT "
                "entries load — the two layers do not compose)",
                self.directory, e)
            return False

    # -- entry I/O ----------------------------------------------------
    def entry_path(self, key: str) -> str:
        return os.path.join(self.directory, key + ENTRY_SUFFIX)

    def _read_entry(self, key: str, path: str) -> dict:
        """Read + structurally validate one entry file (raises on any
        problem). Shared by the synchronous load path and the
        prefetch thread, so the two can never disagree on what a
        valid entry is."""
        with open(path, "rb") as f:
            entry = pickle.load(f)
        if not isinstance(entry, dict) or \
                entry.get("format") != FORMAT or \
                entry.get("key") != key:
            raise ValueError(
                f"format {entry.get('format')!r} / key "
                f"{entry.get('key')!r} (want {FORMAT}/{key})")
        return entry

    def prefetch(self, key: str, program: str = "") -> bool:
        """Start a BACKGROUND read+parse of `key`'s entry so a later
        :meth:`load` finds it in memory (supervise.prefetch_programs
        — a plan or re-plan names the next program while the current
        segment's work still runs). Purely a wall-time optimization:
        the thread only reads bytes and validates structure; the
        deserialize into a live executable stays on the calling
        thread, and any prefetch failure silently falls back to the
        synchronous path. Returns True when a read was started."""
        if self.unsupported or key in self._prefetched:
            return False
        path = self.entry_path(key)
        if not os.path.exists(path):
            return False
        # bound the prediction set: a re-plan that supersedes an
        # unconsumed prefetch (repeated widen() cycles) must not pin
        # each superseded entry's multi-MB payload until process
        # exit — keep only the newest few, oldest first out
        while len(self._prefetched) >= 4:
            self._prefetched.pop(next(iter(self._prefetched)))
        slot: dict = {"entry": None, "dur_s": 0.0}

        def _read():
            t0 = time.perf_counter()
            try:
                slot["entry"] = self._read_entry(key, path)
            except Exception:   # noqa: BLE001 — load() retries + warns
                pass
            slot["dur_s"] = time.perf_counter() - t0

        th = threading.Thread(target=_read, daemon=True,
                              name=f"aot-prefetch-{key[:8]}")
        self._prefetched[key] = (th, slot)
        th.start()
        # the instant is recorded from the CALLING thread (the
        # tracer's attribution stacks are per-thread; a worker-thread
        # span would misattribute nothing but also belongs nowhere)
        obstrace.current().instant(
            f"compile.prefetch:{program or key[:8]}", "compile",
            key=key)
        log.info("compile cache: prefetching %s entry %s in the "
                 "background", program or "program",
                 self.entry_path(key))
        return True

    def _take_prefetched(self, key: str):
        """Collect a finished (or in-flight — joined; it is a local
        file read) prefetch for `key`, or None."""
        item = self._prefetched.pop(key, None)
        if item is None:
            return None
        th, slot = item
        th.join(timeout=60.0)
        if th.is_alive():       # a wedged filesystem: fall back
            return None
        if slot["entry"] is not None:
            log.info("compile cache: prefetched entry served for "
                     "%s (%.3fs background read)", key,
                     slot["dur_s"])
        return slot["entry"]

    def load(self, key: str):
        """Deserialize-and-load the cached executable for `key`, or
        None on a miss. ANY failure on an existing entry (truncated
        pickle, format drift, a backend that cannot load the blob) is
        a warned miss — the caller recompiles and the store path
        atomically overwrites the bad entry. A background
        :meth:`prefetch` of the same key feeds this path its already-
        parsed entry."""
        path = self.entry_path(key)
        entry = self._take_prefetched(key)
        if entry is None and not os.path.exists(path):
            return None
        try:
            if entry is None:
                entry = self._read_entry(key, path)
            import jax
            from jax.experimental import serialize_executable as se

            # load onto exactly the devices the executable was
            # compiled for: left to its default, deserialize assigns
            # EVERY device of the backend, and a 1-device program
            # loaded in an 8-device process then expects 8 shards
            by_id = {d.id: d for d in jax.devices()}
            loaded = se.deserialize_and_load(
                entry["payload"], entry["in_tree"], entry["out_tree"],
                execution_devices=[by_id[i]
                                   for i in entry["device_ids"]])
        except Exception as e:          # noqa: BLE001 — any bad entry
            log.warning(
                "compile cache: entry %s is unreadable/stale (%s) — "
                "recompiling and overwriting it", path, e)
            return None
        try:
            # LRU touch: loads refresh the entry's eviction clock
            os.utime(path, None)
        except OSError:
            pass
        return loaded

    def store(self, key: str, compiled, meta: dict) -> bool:
        """Serialize `compiled` (a jax.stages.Compiled) under `key`,
        atomically (tmp+rename via utils/artifacts — a mid-write kill
        or a concurrent writer can never leave a truncated entry),
        then evict LRU entries past the size cap."""
        from shadow_tpu.utils.artifacts import atomic_write

        from shadow_tpu.device import chaos as chaosmod

        inj = chaosmod.current()
        if inj is not None and inj.on_cache_store(key):
            # chaos seam (full-disk drill): this store is refused —
            # the run continues on the unpersisted fresh compile,
            # exactly the degradation contract a real write failure
            # gets below (store_disabled stays off: the scripted
            # failure is one store, not the directory)
            log.warning("compile cache: store of %s refused by the "
                        "chaos schedule — running on the unpersisted "
                        "fresh compile", key)
            return False

        try:
            from jax.experimental import serialize_executable as se

            payload, in_tree, out_tree = se.serialize(compiled)
        except Exception as e:          # noqa: BLE001 — backend gap
            self.unsupported = True
            # hand compile persistence back to jax's tracing cache —
            # the documented fallback for serialization-less backends
            _set_tracing_cache(True)
            log.warning(
                "compile cache: this backend cannot serialize "
                "executables (%s) — running without the AOT cache "
                "(JAX's built-in tracing cache re-enabled as the "
                "fallback; see JAX_COMPILATION_CACHE_DIR)", e)
            return False
        entry = {"format": FORMAT, "key": key, "meta": dict(meta),
                 "payload": payload, "in_tree": in_tree,
                 "out_tree": out_tree,
                 "device_ids": [int(d.id) for d in
                                compiled.runtime_executable()
                                .local_devices()]}
        path = self.entry_path(key)
        try:
            atomic_write(path, lambda f: pickle.dump(entry, f))
        except Exception as e:          # noqa: BLE001 — degrade, never crash
            # OSError: the directory turned unwritable after the
            # constructor probe (disk filled mid-run). Anything else
            # (a PyTreeDef that won't pickle on this jax version):
            # same remedy — stop STORING but keep LOADING, so valid
            # entries on disk still serve their warm starts. The
            # tracing cache stays OFF: re-enabling it mid-run would
            # poison every later AOT load in this process (the
            # non-compose rule), a worse trade than one run's
            # unpersisted fresh compiles. A cache-layer failure must
            # never abort the simulation.
            self.store_disabled = True
            log.warning("compile cache: could not write %s (%s) — "
                        "new entries disabled for this run (existing "
                        "entries still load)", path, e)
            return False
        self._evict()
        return True

    def _evict(self) -> None:
        """Drop least-recently-used entries until the directory fits
        the size cap. mtime is the LRU clock (stores write it, loads
        touch it); a racing sibling deleting the same file is fine."""
        try:
            names = os.listdir(self.directory)
        except OSError:
            return
        entries = []
        now = time.time()
        for n in names:
            p = os.path.join(self.directory, n)
            if not n.endswith(ENTRY_SUFFIX):
                # debris from a hard-killed writer (SIGKILL mid-write
                # skips atomic_write's cleanup): stale tmp files are
                # deleted outright — the size cap must bound what is
                # actually on disk, not just the finished entries
                if ".tmp" in n:
                    try:
                        if now - os.stat(p).st_mtime > 600:
                            os.unlink(p)
                    except OSError:
                        pass
                continue
            try:
                st = os.stat(p)
            except OSError:
                continue
            entries.append((st.st_mtime, st.st_size, p))
        total = sum(s for _, s, _ in entries)
        if total <= self.cap_bytes:
            return
        entries.sort()                  # oldest first
        # the newest entry is never evicted: a cap smaller than one
        # executable would otherwise delete the entry just stored and
        # leave the cache permanently cold
        if len(entries) > 1 and entries[-1][1] > self.cap_bytes:
            log.warning(
                "compile cache: one entry (%d MB) exceeds the "
                "compile_cache_cap_mb cap (%d MB) — raise the cap, "
                "or only this newest entry will survive",
                entries[-1][1] >> 20, self.cap_bytes >> 20)
        for _, size, p in entries[:-1]:
            if total <= self.cap_bytes:
                break
            try:
                os.unlink(p)
                total -= size
                log.info("compile cache: evicted %s (LRU, cap %d MB)",
                         p, self.cap_bytes >> 20)
            except OSError:
                pass

    # -- the engine hook ----------------------------------------------
    def ensure(self, engine, program: str, jit_fn, args):
        """Return a ready-to-call executable for `program`:

        * cache hit  -> the deserialized Compiled (no trace, no
          compile);
        * cache miss -> ``jit_fn.lower(*args).compile()`` timed in its
          two stages, stored for the next process, returned;
        * any cache-layer failure -> the original ``jit_fn`` (the
          plain lazy-jit path — correctness never depends on the
          cache).

        The attribution event lands in ``self.events`` either way."""
        ev = {"program": program, "hit": False,
              "lower_s": 0.0, "compile_s": 0.0, "load_s": 0.0,
              "serialize_s": 0.0}
        try:
            key = program_key(engine, program)
        except Exception as e:          # noqa: BLE001
            log.warning("compile cache: could not fingerprint %s "
                        "(%s); compiling without the cache",
                        program, e)
            ev["error"] = str(e)
            self.events.append(ev)
            return jit_fn
        ev["key"] = key
        if not self.unsupported:
            t0 = time.perf_counter()
            loaded = self.load(key)
            if loaded is not None:
                ev["hit"] = True
                ev["load_s"] = round(time.perf_counter() - t0, 3)
                self.events.append(ev)
                # flight-recorder attribution (shadow_tpu/obs): the
                # cache's walls are already measured, the tracer only
                # needs them on the run's timeline
                obstrace.current().record(
                    f"aot.load:{program}", "compile", ev["load_s"],
                    hit=True, key=key)
                log.info("compile cache HIT: %s <- %s (%.2fs load; "
                         "compile skipped)", program,
                         self.entry_path(key), ev["load_s"])
                return loaded
        # chaos seam (device/chaos.py `oom`): a scripted compile-time
        # RESOURCE_EXHAUSTED fires HERE — after the cache-hit return
        # (a hit compiles nothing), before lower/compile, and OUTSIDE
        # the lazy-jit fallback below (the fallback absorbs backend
        # quirks, not allocator failures) — so it surfaces out of the
        # dispatch that forced the compile, exactly like a real one
        from shadow_tpu.device import chaos as chaosmod

        inj = chaosmod.current()
        if inj is not None and hasattr(inj, "on_compile"):
            inj.on_compile(program)
        # a blob destined for the cache must come from a FRESH
        # compile (see _fresh_compile); when nothing will be stored
        # (unsupported backend, unwritable directory) keep JAX's
        # tracing cache in play so the compile persists SOMEWHERE
        will_store = not self.unsupported and not self.store_disabled
        try:
            ctx = (_fresh_compile() if will_store
                   else contextlib.nullcontext())
            with ctx:
                t0 = time.perf_counter()
                lowered = jit_fn.lower(*args)
                t1 = time.perf_counter()
                compiled = lowered.compile()
                t2 = time.perf_counter()
            ev["lower_s"] = round(t1 - t0, 3)
            ev["compile_s"] = round(t2 - t1, 3)
            tr = obstrace.current()
            # lower ended compile_s ago — placed before the compile
            # on the timeline, not overlapping it on one track
            tr.record(f"aot.lower:{program}", "compile",
                      ev["lower_s"], ago_s=ev["compile_s"],
                      hit=False)
            tr.record(f"aot.compile:{program}", "compile",
                      ev["compile_s"], hit=False, key=key)
        except Exception as e:          # noqa: BLE001
            # AOT lowering failed (exotic arg structure, backend
            # quirk): fall back to the lazy jit path, which compiles
            # the identical program on first call. The tracing cache
            # deliberately stays OFF — re-enabling it mid-run would
            # poison later AOT loads in this process (non-compose
            # rule), so this one program simply recompiles per
            # process until the quirk is fixed.
            log.warning("compile cache: AOT lower/compile of %s "
                        "failed (%s); falling back to lazy jit",
                        program, e)
            ev["error"] = str(e)
            self.events.append(ev)
            return jit_fn
        if will_store:
            t0 = time.perf_counter()
            try:
                stored = self.store(key, compiled, meta={
                    "program": program,
                    "signature": program_signature(engine, program)})
                if stored:
                    # self-validation: an entry that cannot
                    # round-trip (backend serialization gap our
                    # probe missed) must not greet the next process
                    # as a poisoned hit
                    if self.load(key) is None:
                        log.warning("compile cache: stored entry "
                                    "for %s failed its round-trip "
                                    "check — removing it", program)
                        try:
                            os.unlink(self.entry_path(key))
                        except OSError:
                            pass
                        stored = False
            except Exception as e:      # noqa: BLE001 — never abort a run
                log.warning("compile cache: storing %s failed (%s); "
                            "continuing with the fresh compile",
                            program, e)
                stored = False
            ev["serialize_s"] = round(time.perf_counter() - t0, 3)
            ev["stored"] = stored
            obstrace.current().record(
                f"aot.serialize:{program}", "compile",
                ev["serialize_s"], stored=stored)
        self.events.append(ev)
        log.info("compile cache MISS: %s (lower %.2fs + compile "
                 "%.2fs%s) -> %s", program, ev["lower_s"],
                 ev["compile_s"],
                 "" if ev.get("stored") else "; entry NOT stored",
                 self.entry_path(key))
        return compiled

    # -- attribution --------------------------------------------------
    def publish(self, stats) -> None:
        """The runners' one summary site: set
        ``stats.compile_cache`` to this run's report and log the loud
        hit/miss line (DeviceRunner and EnsembleRunner both call
        here, so the surface cannot drift between them)."""
        stats.compile_cache = rep = self.report()
        log.info("compile cache: %d hit(s), %d miss(es) "
                 "(%.1fs compiling, %.1fs loading) in %s",
                 rep["hits"], rep["misses"], rep["compile_s"],
                 rep["load_s"], rep["dir"])

    def report(self) -> dict:
        """The run's loud hit/miss surface (SimStats.compile_cache):
        per-program events plus the totals a record
        reader needs without walking the event list."""
        hits = sum(1 for e in self.events if e.get("hit"))
        misses = sum(1 for e in self.events
                     if not e.get("hit") and "error" not in e)
        return {
            "dir": self.directory,
            "cap_mb": self.cap_bytes >> 20,
            "unsupported": self.unsupported,
            "store_disabled": self.store_disabled,
            "hits": hits,
            "misses": misses,
            "compile_s": round(sum(e["lower_s"] + e["compile_s"]
                                   for e in self.events), 3),
            "load_s": round(sum(e["load_s"] for e in self.events), 3),
            "events": list(self.events),
        }


def default_dir() -> str:
    """Where ``compile_cache: auto`` keeps the AOT executables."""
    from shadow_tpu._jax import cache_root

    return os.path.join(cache_root(), "aot")


def resolve_cache(experimental) -> AotCache | None:
    """The runners' cache factory, from the validated
    ``experimental.compile_cache`` knob: ``off`` -> None, ``auto`` ->
    the ``aot`` subdirectory of the one compile-cache root
    (shadow_tpu._jax.cache_root), anything else is the
    (schema-validated) cache directory path."""
    mode = experimental.compile_cache
    if mode == "off":
        return None
    if mode == "auto":
        directory = default_dir()
    else:
        directory = mode
    cap = int(experimental.compile_cache_cap_mb) * (1 << 20)
    return AotCache(directory, cap_bytes=cap)
