"""Flight recorder: span-traced runs with per-phase wall attribution.

The ROADMAP's two biggest open levers — pipelined dispatch and
telemetry-driven auto-tuning — both need ONE missing input: where a
round's wall time goes. The signals exist (SimStats counters,
heartbeat log lines, OCC records, compile-cache attribution, watchdog
dumps) but on no common timeline. This module is that timeline: a
:class:`Tracer` records a span for each unit of work the run already
segments on — supervise.py segment advance, device round dispatch,
judge batching, exchange flush, capacity warm-up/re-plan, checkpoint
save/load, AOT cache lower/compile/serialize/load, retry/backoff
waits, SIGTERM drain — each tagged with its sim-time window,
wall-clock interval, and counters.

Three output surfaces (docs/observability.md):

* a streamed JSONL span log (``TRACE_<label>.jsonl``, one JSON object
  per completed span) written through the streamed-atomic path in
  utils/artifacts — `tail -f`-able mid-run, atomically placed at
  close, and the partial file survives a hang as the post-mortem;
* a Chrome-trace-event / Perfetto-loadable export
  (``TRACE_<label>.trace.json``, obs/perfetto.py);
* a ``METRICS_<label>.json`` summary with per-phase wall attribution
  (host_s / judge_s / dispatch.issue_s / dispatch.sync_s /
  exchange_s / checkpoint_s / retry_s, plus compile_s / plan_s) that
  scripts/trace_report.py consumes. ``host_s`` is the RESIDUAL — total
  tracer-lifetime wall minus every non-host measured bucket — i.e.
  exactly the host-side Python time no span claims, so the buckets
  always sum to the total by construction.

Every span is also a ``jax.profiler.TraceAnnotation``: under a running
profiler it appears on the trace's host plane, on the profiler's clock.

Modes (``experimental.telemetry``): ``off`` is a :class:`NullTracer`
(every call a no-op — zero per-round work of any kind); ``summary``
(the default) accumulates per-phase walls and a small recent-span
ring (for watchdog stall dumps) but stores no span list and writes no
files unless ``telemetry_path`` is set; ``trace`` additionally keeps
the span list (bounded; drops counted loudly) and writes all three
artifacts.

Hard contract: tracing never perturbs the simulation. Spans only READ
values the run already fetched (segment round counts, overflow dims,
``engine.effective``) — no tracer mode adds device work beyond what
the untraced run performs, and traces are bit-identical across
off/summary/trace (pinned by determinism_gate --telemetry).
"""

from __future__ import annotations

import json
import os
import sys
import time
from collections import deque
from typing import Optional

from shadow_tpu.utils.slog import get_logger

log = get_logger("obs")

FORMAT = 1
MODES = ("off", "summary", "trace")

# phase buckets for the METRICS wall attribution. "host" is the
# residual bucket (never directly attributed); spans may also carry
# free-form categories, which fold into "host" residual time.
# "dispatch.issue" (asynchronous enqueue cost) and "dispatch.sync"
# (blocking waits for device results) split the old conflated
# "dispatch" bucket so device-bound and sync-bound wall are finally
# distinguishable; "dispatch" and "exchange" stay as buckets because
# METRICS records that used them are still read (trace_report). The
# round's device stages are `engine.*` scopes in a profiler trace,
# not spans. "reshard" is the mesh-shrink failover's
# degradation cost (liveness probe + re-shard + re-place; the
# rebuild's compile wall lands in "compile" as ever), "chaos" marks
# scripted fault injections (instants — the faults themselves cost
# nothing), "failover" is the hybrid-rerun rung's own overhead
# (the rerun's inner spans keep their phases), and "degrade" marks
# the OOM degradation ladder's rung engagements (admission refusals
# and runtime rungs both land here).
PHASES = ("host", "judge", "dispatch", "dispatch.issue",
          "dispatch.sync", "exchange", "checkpoint",
          "retry", "compile", "plan", "reshard", "chaos",
          "failover", "degrade", "serve")

# recent-span ring size: what a watchdog stall dump embeds so a hang
# report shows what the run WAS doing, not just where it stopped
RECENT_SPANS = 64

# trace-mode span list cap: a runaway CPU run (one judge flush per
# round for hours) must not exhaust memory — past the cap spans still
# stream to the JSONL log and accumulate walls, only the in-memory
# list (the Perfetto export) stops growing, counted in `dropped`
MAX_SPANS = 200_000


class _NullSpan:
    """Reusable no-op span context (the off path allocates nothing)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def add(self, **kw):
        pass


_NULL_SPAN = _NullSpan()


class NullTracer:
    """telemetry: off — every call a no-op, zero allocations on the
    span path, no files, no recent ring."""

    mode = "off"

    def span(self, name, phase="host", sim_t0=-1, sim_t1=-1, **args):
        return _NULL_SPAN

    def instant(self, name, phase="host", sim_t0=-1, **args):
        pass

    def record(self, name, phase, dur_s, **args):
        pass

    def recent(self, n: int = RECENT_SPANS) -> list:
        return []

    def format_recent(self, n: int = RECENT_SPANS) -> str:
        return ""

    def phase_walls(self) -> dict:
        return {}

    def finalize(self, run_info=None, counters=None):
        return None


class _Span:
    """One in-flight span (context manager). ``add(**kw)`` attaches
    counters mid-flight; an exception inside the span is recorded as
    an ``error`` arg, never swallowed.

    Wall ATTRIBUTION is self-time: a span's bucket receives its gross
    duration minus every span/record completed inside it (the first
    dispatch segment contains the 40s XLA compile — double-counting
    both would make the phase walls sum past the total). The JSONL /
    Perfetto records keep the GROSS duration (that is what a timeline
    renders), with ``self_s`` added when nested time was carved out.

    The span is also a ``jax.profiler.TraceAnnotation`` of the same
    name, so it lands on the host plane of any running profiler trace,
    stamped by the profiler's clock beside the device's ops. jax is
    never imported for it: a process that has not loaded jax runs no
    profiler.
    """

    __slots__ = ("_tr", "name", "phase", "sim_t0", "sim_t1", "args",
                 "_start", "_child_s", "_ann")

    def __init__(self, tr, name, phase, sim_t0, sim_t1, args):
        self._tr = tr
        self.name = name
        self.phase = phase
        self.sim_t0 = sim_t0
        self.sim_t1 = sim_t1
        self.args = args
        self._child_s = 0.0

    def add(self, **kw):
        self.args.update(kw)

    def __enter__(self):
        self._tr._stack_of().append(self)
        jax = sys.modules.get("jax")
        self._ann = (jax.profiler.TraceAnnotation(self.name)
                     if jax is not None else None)
        if self._ann is not None:
            self._ann.__enter__()
        self._start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        end = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        stack = self._tr._stack_of()
        if stack and stack[-1] is self:
            stack.pop()
        if exc_type is not None:
            self.args["error"] = exc_type.__name__
        self._tr._record(self.name, self.phase, self._start, end,
                         self.sim_t0, self.sim_t1, self.args,
                         child_s=self._child_s)
        return False


class Tracer:
    """One run-wide flight recorder (modes ``summary`` / ``trace``).

    The Controller creates ONE instance per run and attaches it to the
    runner and the Manager; module-global :func:`current` serves the
    call sites with no plumbing path (aotcache, capacity). Wall
    stamps are offsets from construction
    (``perf_counter``), so the tracer's lifetime — not just the run()
    window — is the attribution total: pre-run work (capacity
    planning, the engine's first compile) lands inside it.
    """

    def __init__(self, mode: str = "summary", directory: str = "",
                 label: str = "run"):
        if mode not in ("summary", "trace"):
            raise ValueError(f"tracer mode {mode!r} is not "
                             "'summary' or 'trace'")
        self.mode = mode
        self.directory = directory
        self.label = label
        self.files: dict = {}
        self._t0 = time.perf_counter()
        self._walls: dict = {}
        self._span_counts: dict = {}
        self._spans: list = []
        self._recent: deque = deque(maxlen=RECENT_SPANS)
        self._dropped = 0
        self._stream = None
        self._closed = False
        self._summary: Optional[dict] = None
        # per-thread open-span stack for self-time attribution (spans
        # are recorded from the main advance loop; worker threads get
        # their own stack so interleavings cannot misattribute)
        import threading
        self._local = threading.local()

    def _stack_of(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    # -- recording ----------------------------------------------------
    def span(self, name: str, phase: str = "host",
             sim_t0: int = -1, sim_t1: int = -1, **args) -> _Span:
        """Open a span: ``with tracer.span("dispatch", "dispatch",
        sim_t0=t, sim_t1=nxt) as sp: ... sp.add(rounds=r)``."""
        return _Span(self, name, phase, int(sim_t0), int(sim_t1), args)

    def instant(self, name: str, phase: str = "host",
                sim_t0: int = -1, **args) -> None:
        """Zero-duration marker (preemption request, overflow, ...)."""
        now = time.perf_counter()
        self._record(name, phase, now, now, int(sim_t0), -1, args)

    def record(self, name: str, phase: str, dur_s: float,
               ago_s: float = 0.0, **args) -> None:
        """Retro-record an externally timed interval (the AOT cache's
        lower/compile/load walls are measured by the cache itself;
        the tracer only needs them on the timeline). ``ago_s`` shifts
        the interval's END back from now — a caller recording two
        consecutive stages after the fact places the earlier one
        before the later, so the exported timeline shows them in
        sequence instead of overlapping on one track."""
        end = time.perf_counter() - float(ago_s)
        self._record(name, phase, end - float(dur_s), end, -1, -1,
                     args)

    def _record(self, name, phase, start, end, sim_t0, sim_t1, args,
                child_s: float = 0.0):
        dur = end - start
        # the bucket receives SELF time; the enclosing open span (if
        # any) has this span's gross duration carved out of its own
        self_s = max(0.0, dur - child_s)
        self._walls[phase] = self._walls.get(phase, 0.0) + self_s
        self._span_counts[phase] = self._span_counts.get(phase, 0) + 1
        stack = self._stack_of()
        if stack:
            stack[-1]._child_s += dur
        rec = {"name": name, "phase": phase,
               "t0_s": round(start - self._t0, 6),
               "dur_s": round(dur, 6)}
        if child_s > 0:
            rec["self_s"] = round(self_s, 6)
        if sim_t0 >= 0:
            rec["sim_t0"] = int(sim_t0)
        if sim_t1 >= 0:
            rec["sim_t1"] = int(sim_t1)
        if args:
            rec["args"] = args
        self._recent.append(rec)
        if self.mode != "trace":
            return
        if len(self._spans) < MAX_SPANS:
            self._spans.append(rec)
        else:
            self._dropped += 1
        if self._stream is None:
            from shadow_tpu.utils.artifacts import StreamedLines

            try:
                self._stream = StreamedLines(
                    self._path("TRACE", ".jsonl"))
            except OSError as e:
                log.warning("telemetry: could not open the JSONL "
                            "stream (%s) — spans stay in memory only",
                            e)
                self._stream = False      # do not retry per span
        if self._stream:
            try:
                # default=str: span args are free-form kwargs from a
                # dozen call sites — a stray numpy scalar must
                # degrade to its string form, never to a TypeError
                # that aborts the simulation (the recorder's
                # never-break-the-run contract)
                self._stream.write_line(
                    json.dumps(rec, separators=(",", ":"),
                               default=str))
            except Exception as e:      # noqa: BLE001 — degrade, never crash
                # e.g. ValueError: write on a closed stream — a stray
                # span recorded after finalize must never crash
                log.warning("telemetry: JSONL stream failed (%s); "
                            "disabling it for this run", e)
                self._stream.abandon()
                self._stream = False

    # -- read surfaces ------------------------------------------------
    def recent(self, n: int = RECENT_SPANS) -> list:
        """Last completed spans, oldest first (watchdog stall dumps)."""
        out = list(self._recent)
        return out[-n:]

    def format_recent(self, n: int = RECENT_SPANS) -> str:
        """Human-readable recent-span block for a stall dump."""
        spans = self.recent(n)
        if not spans:
            return ""
        lines = [f"  last {len(spans)} completed span(s) "
                 "(flight recorder, oldest first):"]
        for r in spans:
            window = ""
            if "sim_t0" in r:
                window = (f" sim=({r['sim_t0']}"
                          f", {r.get('sim_t1', '?')}] ns")
            lines.append(
                f"    +{r['t0_s']:10.3f}s {r['dur_s']:8.3f}s "
                f"{r['phase']:10s} {r['name']}{window}")
        return "\n".join(lines)

    def phase_walls(self, total_wall_s: Optional[float] = None) -> dict:
        """Per-phase wall attribution: the six contract buckets plus
        compile_s/plan_s, with host_s the residual of the total (the
        tracer's lifetime unless given)."""
        total = (time.perf_counter() - self._t0
                 if total_wall_s is None else float(total_wall_s))
        out = {f"{p}_s": round(self._walls.get(p, 0.0), 3)
               for p in PHASES if p != "host"}
        # any free-form category's wall belongs to the residual too —
        # it was host-side work, just named
        attributed = sum(v for k, v in self._walls.items()
                         if k in PHASES and k != "host")
        out["host_s"] = round(max(0.0, total - attributed), 3)
        return out

    # -- output -------------------------------------------------------
    def _path(self, prefix: str, suffix: str) -> str:
        directory = (self.directory
                     or os.environ.get("SHADOW_TPU_OCC_DIR",
                                       "artifacts"))
        return os.path.join(directory, f"{prefix}_{self.label}{suffix}")

    def finalize(self, run_info: Optional[dict] = None,
                 counters: Optional[dict] = None) -> dict:
        """Close the recorder: land the JSONL stream, export the
        Perfetto trace, write the METRICS record, and return the
        summary dict (SimStats.telemetry). Idempotent — a second call
        returns the first's summary without rewriting files."""
        if self._closed:
            return self._summary
        self._closed = True
        total = time.perf_counter() - self._t0
        phases = self.phase_walls(total)
        dominant = max(phases, key=phases.get)
        summary = {
            "format": FORMAT,
            "mode": self.mode,
            "total_wall_s": round(total, 3),
            "phases": phases,
            "dominant_phase": dominant[:-2],
            "spans": sum(self._span_counts.values()),
            "span_counts": dict(sorted(self._span_counts.items())),
            "dropped_spans": self._dropped,
        }
        if run_info:
            summary["run"] = dict(run_info)
        if counters:
            summary["counters"] = dict(counters)
        # publish BEFORE the file writes: a failure below must leave
        # the idempotence path (and SimStats.telemetry) the summary,
        # not an AttributeError
        self._summary = summary
        if self._stream:
            try:
                self.files["jsonl"] = self._stream.close()
            except OSError as e:
                log.warning("telemetry: could not finalize the JSONL "
                            "log (%s); partial file kept at %s", e,
                            self._stream.partial)
            # spans recorded after finalize (a re-used runner, tests
            # driving the engine directly) still accumulate walls but
            # must not write to the landed file
            self._stream = False
        if self.mode == "trace":
            from shadow_tpu.obs import perfetto

            path = self._path("TRACE", ".trace.json")
            try:
                perfetto.export(self._spans, path, summary)
                self.files["perfetto"] = path
            except Exception as e:      # noqa: BLE001 — degrade, never crash
                log.warning("telemetry: could not write the Perfetto "
                            "trace %s: %s", path, e)
        # summary mode writes the METRICS record only when the config
        # names a destination — the default-on summary must not litter
        # artifacts/ on every test run; trace mode opted in explicitly
        if self.mode == "trace" or self.directory:
            from shadow_tpu.utils.artifacts import atomic_write_json

            path = self._path("METRICS", ".json")
            try:
                atomic_write_json({**summary, "files": self.files},
                                  path, default=str)
                self.files["metrics"] = path
            except Exception as e:      # noqa: BLE001 — degrade, never crash
                log.warning("telemetry: could not write the metrics "
                            "record %s: %s", path, e)
        summary["files"] = dict(self.files)
        if self._dropped:
            log.warning("telemetry: span list hit its %d-span cap — "
                        "%d span(s) streamed to the JSONL log only "
                        "(absent from the Perfetto export)",
                        MAX_SPANS, self._dropped)
        log.info("telemetry (%s): total %.2fs — %s; dominant phase: "
                 "%s%s", self.mode, total,
                 ", ".join(f"{k[:-2]} {v:.2f}s"
                           for k, v in sorted(
                               phases.items(), key=lambda kv: -kv[1])
                           if v > 0) or "no attributed walls",
                 summary["dominant_phase"],
                 f" -> {self.files}" if self.files else "")
        return summary


# -- module-global current tracer -------------------------------------
# set by the Controller for the run's lifetime; call sites without a
# plumbing path (aotcache.ensure, capacity record I/O)
# read it here. A fresh Controller overwrites it — the newest run owns
# the recorder, which is the right owner for every in-process caller.
_CURRENT: object = NullTracer()


def current():
    return _CURRENT


def set_current(tracer) -> None:
    global _CURRENT
    _CURRENT = tracer if tracer is not None else NullTracer()


def resolve_tracer(cfg, n_hosts: int = 0):
    """The Controller's tracer factory from the validated
    ``experimental.telemetry`` / ``telemetry_path`` knobs. The label
    (file stem) is ``<policy>_<n_hosts>`` — successive runs of one
    workload overwrite one record, like OCC records."""
    xp = cfg.experimental
    if xp.telemetry == "off":
        return NullTracer()
    label = f"{xp.scheduler_policy}_{n_hosts}"
    # artifacts_dir is the per-tenant namespacing seam (the campaign
    # server points it at <spool>/campaigns/<cid>/artifacts): an
    # explicit telemetry_path still wins, but a namespaced run lands
    # its METRICS/TRACE records inside its own directory instead of
    # racing other tenants on the shared label-keyed filenames
    directory = xp.telemetry_path or getattr(xp, "artifacts_dir", "")
    return Tracer(mode=xp.telemetry, directory=directory,
                  label=label)
