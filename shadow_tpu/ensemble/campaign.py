"""EnsembleRunner: R-replica simulation campaigns in one program.

The ensemble twin of device/runner.py's DeviceRunner: maps the config
to its vectorized device app, builds ONE engine whose program carries
a leading replica axis (vmapped outside the mesh shard axis), plans
capacities once from the worst-case replica, advances all replicas in
heartbeat/dispatch segments with per-replica heartbeat lines, and
emits an ``artifacts/ENSEMBLE_*.json`` campaign record with
per-replica checksums plus aggregate statistics.

Why one program: a seed/loss/fault sweep as N serial processes pays
the XLA compile and every dispatch N times; as one vmapped program it
pays them once, and the replica axis rides the vector units the small
per-host shapes leave idle. Replica *i* stays bit-identical to a
standalone run with replica *i*'s parameters (spec.py's contract), so
campaign aggregates are statistics over *real* runs, not
approximations.
"""

from __future__ import annotations

import os
import time
from typing import Optional

import numpy as np

from shadow_tpu import simtime
from shadow_tpu._jax import jax
from shadow_tpu.core.manager import SimStats
from shadow_tpu.device import capacity
from shadow_tpu.device.runner import DeviceRunner, NoDeviceTwin
from shadow_tpu.ensemble.spec import EnsembleWorlds, build_worlds
from shadow_tpu.utils.artifacts import atomic_write_json
from shadow_tpu.utils.slog import get_logger

log = get_logger("ensemble")

RECORD_FORMAT = 1
# per-replica per-host checksum lists stay inline below this host
# count; larger campaigns keep the sha256 digest only
CHK_INLINE_HOSTS = 64

_AGG_OPS = {
    "mean": np.mean,
    "min": np.min,
    "max": np.max,
    "p5": lambda v: np.percentile(v, 5),
    "p95": lambda v: np.percentile(v, 95),
}


def aggregate(values, which) -> dict:
    """Aggregate one per-replica metric vector with the configured
    statistics (mean/p5/p95/min/max)."""
    v = np.asarray(values, np.float64)
    return {k: float(_AGG_OPS[k](v)) for k in which}


class EnsembleRunner:
    """Runs the ``ensemble:`` campaign of a built simulation. Raises
    NoDeviceTwin when the config's apps have no fully-vectorized
    device twin — there is no hybrid fallback for campaigns (CPU host
    emulation cannot vmap), so the Controller surfaces that loudly
    instead of silently running one replica."""

    def __init__(self, sim, trace: Optional[list] = None, mesh=None):
        eopts = sim.cfg.ensemble
        if eopts is None:
            raise ValueError("EnsembleRunner needs an ensemble: "
                             "config block")
        if trace is not None:
            raise ValueError(
                "ensemble campaigns do not record python event "
                "traces; use the per-replica checksums in the "
                "ENSEMBLE record")
        if getattr(sim, "host_faults", None):
            raise ValueError(
                "ensemble: host_crash/host_restart faults are "
                "manager-side events — the campaign engine cannot "
                "run them (vary link faults via "
                "ensemble.fault_schedules instead)")
        # reuse DeviceRunner wholesale for the single-replica twin
        # mapping, knob plumbing, and engine construction — the
        # campaign engine is the same engine with ensemble worlds
        # (defer_engine: the standalone engine it would build is dead
        # weight here)
        self._base = DeviceRunner(sim, trace=None, mesh=mesh,
                                  defer_engine=True)
        self.app = self._base.app
        # the campaign engine consults the same AOT compile cache the
        # base runner resolved (one instance, one report)
        self.aot_cache = self._base.aot_cache
        self.sim = sim
        self.worlds: EnsembleWorlds = build_worlds(sim, eopts)
        self.engine = self._build_engine()
        self.replans = 0
        self.retries = 0
        self.reshards = 0
        self.degrades = 0
        self._planned = False
        # preflight admission verdict (capacity.admission_verdict),
        # set per run(); run() reads its replica_batch override and
        # the ENSEMBLE record carries it
        self.admission = None
        # nonzero = the OOM ladder may degrade this campaign to
        # sequential replica batches of this size (set per run();
        # zero while batching is impossible or already engaged)
        self._replica_batchable = 0
        # replica-index offset of the batch currently running, so
        # batched heartbeat lines keep campaign-global replica labels
        self._replica_offset = 0
        # chaos injection + shrink failover ride the base runner's
        # plumbing (one injector, one mesh owner); the shared advance
        # loop reads runner.chaos
        self.chaos = self._base.chaos
        self.occ_record: Optional[dict] = None
        self.record: Optional[dict] = None
        self.final_state: Optional[dict] = None
        # supervision plumbing (device/supervise.py), set per run();
        # campaign checkpoints carry the campaign stamp so standalone
        # runs refuse them
        self.checkpointer = None
        self.guard = None
        # wall-clock heartbeat staleness monitor (supervise.
        # HeartbeatMonitor), created per run() when
        # experimental.heartbeat_stale_after is set; the campaign
        # server's watchdog polls it cross-thread
        self.hb_monitor = None
        self._ck_extra_meta = {"campaign": self.worlds.campaign_fp,
                               "replicas": int(self.worlds.R)}
        # flight recorder (shadow_tpu/obs): attached by the
        # Controller; the shared advance loop records the spans
        self.tracer = None
        # ensemble-heartbeat rate mark: (wall, per-replica sent) at
        # the last heartbeat, for the pkts/s-since-last column
        self._hb_mark = None

    # ------------------------------------------------------------------
    @property
    def lookahead(self) -> int:
        """The campaign's shared lookahead window: the min over every
        replica's table (each replica's standalone floor is >= it, so
        it is conservative for all). determinism_gate --ensemble pins
        standalone comparison runs to this value via
        experimental.runahead."""
        xp = self.sim.cfg.experimental
        if xp.runahead is not None:
            return max(1, xp.runahead)
        return max(1, min(self.worlds.lookahead, self.sim.lookahead))

    def _build_engine(self):
        """The DeviceRunner's engine builder with the ensemble worlds
        attached: the engine swaps in replica 0's tables as its base
        world and additionally compiles the vmapped campaign program.
        One builder serves both runners — knob plumbing, outbox
        floors, and strategy tristates cannot drift apart."""
        return self._base._build_engine(
            ensemble=self.worlds,
            lookahead=self.lookahead,
            seed=int(self.worlds.seeds[0]))

    @property
    def _capacity_overrides(self) -> dict:
        return self._base._capacity_overrides

    @_capacity_overrides.setter
    def _capacity_overrides(self, value: dict) -> None:
        self._base._capacity_overrides = value

    def _shrink_to(self, alive, host_state: dict,
                   ensemble: bool = True):
        """The shrink failover's campaign path: mesh + capacity
        re-plan route through the base runner (the one owner of
        both), then the CAMPAIGN engine — vmapped replica axis
        outside the new, smaller mesh axis — rebuilds and the
        [R, ...] snapshot re-shards leaf-for-leaf. The replica axis
        survives intact: shrink is the one failover campaigns have
        (hybrid cannot vmap replicas). Transactional like the base
        runner's: a failed reshard rolls everything back so the
        escalation still sees the old-geometry engine."""
        from jax.sharding import Mesh

        from shadow_tpu.device import supervise
        from shadow_tpu.device.engine import AXIS
        from shadow_tpu.device.runner import DeviceRunner

        base = self._base
        rollback = (base._mesh, self.engine,
                    dict(base._capacity_overrides),
                    base._exchange_choice, base.strategy_plan)
        try:
            base._mesh = Mesh(np.array(list(alive)), (AXIS,))
            base._replan_for_shrink(
                len(alive), record=self.occ_record,
                per_iter=self.engine.effective["M_out"])
            self.engine = self._build_engine()
            supervise.prefetch_programs(self, ensemble=True)
            return DeviceRunner._place_resharded(self, host_state,
                                                 ensemble=True)
        except Exception:
            (base._mesh, self.engine, base._capacity_overrides,
             base._exchange_choice, base.strategy_plan) = rollback
            raise

    # ------------------------------------------------------------------
    def _worst_case_view(self, states) -> dict:
        """Reduce the [R, ...] occupancy/overflow leaves to the
        standalone shapes capacity.measure expects: elementwise MAX
        over the replica axis for high-water marks (the worst-case
        replica sizes the shared capacities), SUM for the loud
        overflow counters (any replica's loss fails the campaign)."""
        view = {}
        for k in ("occ_heap", "occ_ob", "occ_in", "occ_x",
                  "occ_trips", "occ_phases", "occ_iters", "occ_burst",
                  "occ_arrivals"):
            if k in states:
                view[k] = np.asarray(jax.device_get(states[k])).max(0)
        for k in ("overflow", "x_overflow"):
            view[k] = np.asarray(jax.device_get(states[k])).sum(0)
        return view

    def _plan_capacities(self, stop: int,
                         load_path: Optional[str] = None) -> None:
        """capacity_plan on the campaign: the warm-up slice runs the
        ENSEMBLE program, so the plan sizes every capacity from the
        worst-case replica's measured occupancy — one replica with a
        hot hub cannot overflow the others' tight plan."""
        xp = self.sim.cfg.experimental
        mode = xp.capacity_plan
        if load_path is None:
            load_path = xp.checkpoint_load
        if load_path:
            # same contract as DeviceRunner._plan_capacities: the
            # fingerprint pins the SAVING engine's capacities, so a
            # resume adopts them instead of re-planning (a fresh
            # warm-up could plan smaller sizes and reject a valid
            # campaign checkpoint — and would pay the warm-up compile
            # on every resume for nothing). ONE shared adopt path.
            self._base._adopt_checkpoint_caps(load_path)
            self.engine = self._build_engine()
            self._planned = True
            log.warning("capacity_plan: %s skipped — checkpoint_load "
                        "resumes the campaign with the saved "
                        "engine's capacities %s", mode,
                        self._capacity_overrides)
            return
        static_knobs = {k: getattr(self.engine.config, k)
                        for k in capacity.CAPACITY_KNOBS}
        if mode == "auto":
            warm = xp.capacity_warmup or max(1, stop // 8)
            warm = min(warm, stop)
            seg = xp.dispatch_segment
            states = self.engine.init_ensemble_state(self.sim.starts)
            for attempt in range(capacity.MAX_REPLANS + 1):
                t = 0
                dims = ()
                while t < warm:
                    nxt = min(warm, t + seg) if seg else warm
                    states, _ = self.engine.run_ensemble(
                        states, stop=nxt, final_stop=stop)
                    t = nxt
                    dims = capacity.overflow_dims(states)
                    if dims:
                        break
                if not dims:
                    break
                if attempt == capacity.MAX_REPLANS:
                    raise RuntimeError(
                        f"ensemble capacity warm-up still overflows "
                        f"after {capacity.MAX_REPLANS} doublings on "
                        f"{dims}")
                self._capacity_overrides = capacity.widen(
                    self._capacity_overrides, dims,
                    self.engine.effective)
                log.warning("ensemble capacity warm-up overflowed on "
                            "%s; retrying with %s", dims,
                            self._capacity_overrides)
                self.engine = self._build_engine()
                states = self.engine.init_ensemble_state(
                    self.sim.starts)
            record = capacity.measure(
                self.engine, self._worst_case_view(states),
                source=f"ensemble-warmup:{warm}ns")
        else:
            record = capacity.load_record(mode)
            want = {"app": type(self.app).__name__,
                    "app_fp": capacity.app_fingerprint(self.app),
                    "n_hosts": len(self.sim.hosts)}
            got = {k: record["workload"].get(k) for k in want}
            if got != want:
                raise ValueError(
                    f"occupancy record {mode} was measured on {got}; "
                    f"this campaign is {want} — re-measure with "
                    "capacity_plan: auto")
        # the worst-case view reduced occ_x over replicas, so the
        # auto choice (and the per-phase caps) cover every replica
        exchange = self._base._resolve_exchange(record,
                                                engine=self.engine)
        planned = capacity.plan(
            record,
            per_iter=self.engine.effective["M_out"],
            floor_iters=4 if self._base._burst > 1 else 8,
            n_shards=self.engine.n_shards,
            headroom=self._base._headroom(),
            exchange=exchange)
        record["planned"] = planned
        record["static"] = static_knobs
        self.occ_record = record
        self._capacity_overrides = dict(planned)
        self.engine = self._build_engine()
        self._planned = True
        # overlap the planned program's AOT entry read with the
        # ensemble init/load work that follows
        from shadow_tpu.device import supervise
        supervise.prefetch_programs(self, ensemble=True)
        log.info("ensemble capacity plan (%s, exchange %s): %s  "
                 "[measured %s]", mode, exchange, planned,
                 record["measured"])

    # ------------------------------------------------------------------
    def _emit_heartbeats(self, now: int, states) -> None:
        """Per-replica heartbeat lines at a segment boundary: replica
        totals from the device counters (the [R, H] arrays are a few
        KB — never the heaps). Each line carries the wall-clock
        pkts/s since the previous heartbeat and the campaign's
        cumulative retry/replan counts, so a stalled or thrashing
        replica is visible from the log stream alone."""
        from shadow_tpu.device.supervise import heartbeat_rates

        # getattr: obs tests drive this method on a bare stub runner
        mon = getattr(self, "hb_monitor", None)
        if mon is not None:
            mon.beat()
        H = len(self.sim.hosts)
        n_exec = np.asarray(jax.device_get(states["n_exec"]))[:, :H]
        n_sent = np.asarray(jax.device_get(states["n_sent"]))[:, :H]
        n_drop = np.asarray(jax.device_get(states["n_drop"]))[:, :H]
        n_deliv = np.asarray(jax.device_get(states["n_deliv"]))[:, :H]
        self._hb_mark, rates = heartbeat_rates(self._hb_mark,
                                               n_sent.sum(1))
        # live device memory, when the backend exposes allocator
        # stats (TPU/GPU); "n/a" on CPU or before the engine exists —
        # the operator can tell an approaching OOM from the log
        # stream alone
        eng = getattr(self, "engine", None)
        mem = eng.device_memory_stats() if eng is not None else None
        mem_s = (f"{capacity.fmt_bytes(mem[0])}/"
                 f"{capacity.fmt_bytes(mem[1])}"
                 if mem is not None else "n/a")
        for r in range(self.worlds.R):
            log.info("[ensemble-heartbeat] t=%s replica=%d events=%d "
                     "sent=%d dropped=%d delivered=%d pkts/s=%s "
                     "retries=%d replans=%d mem=%s",
                     simtime.format_time(now),
                     r + getattr(self, "_replica_offset", 0),
                     int(n_exec[r].sum()), int(n_sent[r].sum()),
                     int(n_drop[r].sum()), int(n_deliv[r].sum()),
                     rates[r], self.retries, self.replans, mem_s)

    # ------------------------------------------------------------------
    def record_path(self) -> str:
        """Canonical campaign record path (ensemble.record_path
        overrides; experimental.artifacts_dir namespaces the
        directory — the campaign server's per-tenant seam;
        SHADOW_TPU_OCC_DIR redirects the default artifacts dir, the
        same env tests already use to keep runs out of the repo)."""
        eopts = self.sim.cfg.ensemble
        if eopts.record_path:
            return eopts.record_path
        directory = (
            getattr(self.sim.cfg.experimental, "artifacts_dir", "")
            or os.environ.get("SHADOW_TPU_OCC_DIR", "artifacts"))
        return os.path.join(
            directory,
            f"ENSEMBLE_{type(self.app).__name__}"
            f"_{len(self.sim.hosts)}_{self.worlds.campaign_fp}.json")

    def _build_record(self, final: dict, rounds_r, wall: float,
                      ok: bool) -> dict:
        import hashlib

        H = len(self.sim.hosts)
        w = self.worlds
        eopts = self.sim.cfg.ensemble
        metrics = {
            "events_executed": final["n_exec"][:, :H].sum(1),
            "packets_sent": final["n_sent"][:, :H].sum(1),
            "packets_dropped": final["n_drop"][:, :H].sum(1),
            "packets_delivered": final["n_deliv"][:, :H].sum(1),
            "rounds": np.asarray(rounds_r),
        }
        replicas = []
        for r in range(w.R):
            chk = np.ascontiguousarray(final["chk"][r, :H])
            entry = dict(w.descriptors[r])
            entry.update({
                "events_executed": int(metrics["events_executed"][r]),
                "packets_sent": int(metrics["packets_sent"][r]),
                "packets_dropped": int(metrics["packets_dropped"][r]),
                "packets_delivered": int(
                    metrics["packets_delivered"][r]),
                "host_checksums_sha256": hashlib.sha256(
                    chk.tobytes()).hexdigest()[:16],
            })
            if H <= CHK_INLINE_HOSTS:
                entry["host_checksums"] = [int(c) for c in chk]
            replicas.append(entry)
        return {
            "format": RECORD_FORMAT,
            "campaign": w.campaign_fp,
            "workload": {
                "app": type(self.app).__name__,
                "n_hosts": H,
                "stop_time": int(self.sim.cfg.general.stop_time),
                "replicas": w.R,
                "lookahead": self.lookahead,
            },
            "vary": w.descriptors,
            "replicas": replicas,
            "aggregates": {
                name: aggregate(vals, eopts.aggregate)
                for name, vals in metrics.items()},
            "wall_s": round(wall, 3),
            "replans": self.replans,
            "ok": bool(ok),
        }

    # ------------------------------------------------------------------
    def _run_batched(self, t_start: int, pause: int, stop: int,
                     batch: int, tracer, resume=None):
        """Sequential replica batches: vmap over <= ``batch`` replicas
        at a time, then merge the per-batch host-side finals over the
        replica axis. Bit-identical to the full-R vmap — each
        replica's trace is a pure function of its own world row
        (spec.py's contract), and every batch keeps the FULL
        campaign's lookahead, so batch boundaries cannot move round
        boundaries. Engaged by ``ensemble.replica_batch``, a
        preflight admission override, or the OOM ladder's
        :class:`supervise.DegradeToReplicaBatch` rung. Returns
        ``(merged_final, combined AdvanceResult, per-replica
        rounds)``; the merged final is host-side (the point is never
        holding all R replicas of device state at once), which the
        downstream record/stats path consumes unchanged.

        Supervision: with ``checkpoint_every`` set each batch writes
        its OWN rotation series (``<save>.b<k>.t<ns>``, stamped with
        the batch's replica window) — every batch restarts sim time
        at 0, so a shared base would collide and cross-prune. A
        preemption drain saves the running batch's entry and stops
        the loop; the completed batches' finals are DISCARDED, and
        ``merged_final`` comes back None. ``resume=(path,
        replica_lo)`` replays batches before the stamped one fresh
        from t=0 (pure functions — bit-identical), loads the stamped
        batch from its entry, and runs the rest fresh, so the
        resumed campaign's record equals the uninterrupted one."""
        from shadow_tpu.device import checkpoint, supervise
        from shadow_tpu.ensemble import spec

        xp = self.sim.cfg.experimental
        w_full = self.worlds
        R = int(w_full.R)
        batch = max(1, min(int(batch), R))
        n_batches = -(-R // batch)
        log.warning(
            "replica batching: running %d replica(s) as %d "
            "sequential batch(es) of <= %d (one vmapped program per "
            "batch, finals merged — bit-identical to the full vmap)",
            R, n_batches, batch)
        # already batched: the ladder's replica-batch rung must not
        # re-trigger (an OOM inside a batch walks the next rung)
        self._replica_batchable = 0
        heaps = ("ht", "hk", "hm", "hv", "hw")
        b_resume = int(resume[1]) // batch if resume is not None else -1
        engine_full, finals, rounds_parts = self.engine, [], []
        ck_full = self.checkpointer
        combined = supervise.AdvanceResult()
        pl = combined.pipeline
        try:
            for b in range(n_batches):
                lo, hi = b * batch, min(R, (b + 1) * batch)
                part = spec.slice_worlds(w_full, lo, hi)
                self.worlds = part
                self._replica_offset = lo
                # per-replica heartbeat rate vectors change length
                # across batches — a stale mark would mis-zip
                self._hb_mark = None
                if xp.checkpoint_every:
                    self.checkpointer = supervise.Checkpointer(
                        f"{xp.checkpoint_save}.b{b}",
                        xp.checkpoint_every, xp.checkpoint_keep,
                        final_stop=stop,
                        extra_meta={**self._ck_extra_meta,
                                    "replica_lo": lo,
                                    "replica_hi": hi,
                                    "replica_batch": batch},
                        audit_enabled=xp.state_audit)
                with tracer.span("replica_batch", "host",
                                 sim_t0=t_start, lo=lo, hi=hi,
                                 batch_index=b):
                    self.engine = self._build_engine()
                    supervise.prefetch_programs(self, ensemble=True)
                    if b == b_resume:
                        states, t0 = checkpoint.load_state(
                            self.engine, self.sim.starts, resume[0],
                            final_stop=stop,
                            template=self.engine.init_ensemble_state(
                                self.sim.starts))
                        log.info("resumed replica batch %d "
                                 "(replicas [%d, %d)) from %s at "
                                 "t=%d ns", b, lo, hi, resume[0], t0)
                    else:
                        states = self.engine.init_ensemble_state(
                            self.sim.starts)
                        t0 = t_start
                    states, adv = supervise.advance(
                        self, states, t0, pause, stop,
                        ensemble=True)
                    if not adv.preempted:
                        finals.append(jax.device_get(
                            {k: v for k, v in states.items()
                             if k not in heaps}))
                combined.t_end = adv.t_end
                combined.retries += adv.retries
                combined.reshards += adv.reshards
                combined.degrades += adv.degrades
                combined.budget_hit |= adv.budget_hit
                combined.overflowed |= adv.overflowed
                # the batches run one after another: their dispatch
                # telemetry sums
                for k, v in adv.pipeline.items():
                    pl[k] = pl.get(k, 0) + v
                if adv.preempted:
                    # the drain already saved THIS batch's rotation
                    # entry; stop the loop — later batches never
                    # started, and the completed ones replay
                    # bit-identically on resume (pure functions of
                    # their world slices)
                    combined.preempted = True
                    combined.resume_path = adv.resume_path
                    break
                rounds_parts.append(np.broadcast_to(
                    np.asarray(adv.rounds), (hi - lo,)).copy())
        finally:
            self.worlds = w_full
            self._replica_offset = 0
            self.engine = engine_full
            self.checkpointer = ck_full
        for k in ("sync_wall_s", "advance_wall_s"):
            pl[k] = round(pl[k], 3)
        pl["replica_batches"] = int(n_batches)
        pl["replica_batch"] = int(batch)
        if isinstance(self.admission, dict):
            self.admission["replica_batch"] = int(batch)
        if combined.preempted:
            rounds_r = (np.concatenate(rounds_parts)
                        if rounds_parts else np.zeros(0, np.int64))
            combined.rounds = np.int64(
                rounds_r.max() if rounds_r.size else 0)
            return None, combined, rounds_r
        merged = {k: np.concatenate([f[k] for f in finals], axis=0)
                  for k in finals[0]}
        rounds_r = np.concatenate(rounds_parts)
        combined.rounds = np.int64(rounds_r.max())
        return merged, combined, rounds_r

    # ------------------------------------------------------------------
    def run(self, stop: int) -> SimStats:
        from shadow_tpu.device import checkpoint, supervise

        from shadow_tpu.obs import trace as obstrace

        xp = self.sim.cfg.experimental
        tracer = self.tracer or obstrace.current()
        self.replans = 0
        self.retries = 0
        self.reshards = 0
        self.degrades = 0
        self._hb_mark = None
        self._replica_offset = 0
        w = self.worlds
        if xp.checkpoint_save:
            checkpoint.probe_writable(xp.checkpoint_save)
        eopts = self.sim.cfg.ensemble
        knob_batch = int(getattr(eopts, "replica_batch", 0) or 0)
        load_path = ""
        resume_batch = None
        if xp.checkpoint_load:
            load_path = supervise.resolve_checkpoint(
                xp.checkpoint_load)
            meta = checkpoint.peek_meta(load_path)
            ens_meta = meta.get("ensemble") or {}
            camp = ens_meta.get("campaign")
            if camp is None:
                raise ValueError(
                    f"checkpoint {load_path} was saved by a "
                    "standalone run — an ensemble campaign cannot "
                    "resume it")
            if camp != w.campaign_fp:
                raise ValueError(
                    f"checkpoint {load_path} belongs to "
                    f"campaign {camp}; this config builds "
                    f"{w.campaign_fp} — the vary block or schedules "
                    "changed, so the saved replicas would diverge")
            saved_lo = ens_meta.get("replica_lo")
            if saved_lo is not None:
                # a replica-batch rotation entry: it stamps ONE
                # batch's sliced state, so only a campaign batched
                # the same way can place it
                saved_batch = int(ens_meta.get("replica_batch") or 0)
                if knob_batch != saved_batch:
                    have = (f"uses replica_batch: {knob_batch}"
                            if knob_batch else
                            "expects the full-R stacked state")
                    raise ValueError(
                        f"checkpoint {load_path} was saved by "
                        f"replica batch [{saved_lo}, "
                        f"{ens_meta.get('replica_hi')}) of a "
                        f"replica_batch={saved_batch} campaign — "
                        f"set ensemble.replica_batch: {saved_batch} "
                        f"to resume it (this config {have})")
                resume_batch = (load_path, int(saved_lo))
            elif knob_batch:
                raise ValueError(
                    f"checkpoint {load_path} stamps the full-R "
                    "stacked state — a replica_batch campaign "
                    "cannot resume it (drop ensemble.replica_batch "
                    "or resume without the checkpoint)")
            checkpoint.prevalidate_resume(
                load_path, stop,
                save_path=xp.checkpoint_save,
                save_time=xp.checkpoint_save_time)
            # a post-shrink campaign checkpoint stamps the shrunken
            # geometry: the base runner adopts the mesh (one adopt
            # path), then the CAMPAIGN engine rebuilds on it
            if self._base._adopt_checkpoint_geometry(load_path):
                self.engine = self._build_engine()
        # preflight admission (capacity.py): the campaign footprint —
        # per-replica state x R x two copies, exchange scratch —
        # against the per-device budget, BEFORE any compile (the
        # first compile happens lazily at the first dispatch, which
        # the capacity warm-up below would trigger). strict refuses
        # over-budget here; auto may pre-split the sweep into replica
        # batches.
        batch = knob_batch
        ck_on = bool(xp.checkpoint_save or xp.checkpoint_load
                     or xp.checkpoint_every)
        can_batch = w.R > 1 and not batch and not ck_on
        self.admission = capacity.admission_verdict(
            self.engine, xp, batchable=can_batch)
        adm_ov = self.admission.get("overrides") or {}
        if not batch and adm_ov.get("replica_batch"):
            batch = int(adm_ov["replica_batch"])
        # the OOM ladder may still degrade an unbatched campaign at
        # runtime (supervise.DegradeToReplicaBatch); a checkpointed
        # unbatched campaign stays unbatched — its checkpoints stamp
        # the full-R stacked state, which a mid-run batch switch
        # would orphan (explicit ensemble.replica_batch opts into
        # per-batch rotation series instead)
        self._replica_batchable = (max(1, w.R // 2)
                                   if can_batch and not batch else 0)
        if xp.capacity_plan != "static" and not self._planned:
            with tracer.span("capacity.plan", "plan",
                             mode=xp.capacity_plan, ensemble=True):
                self._plan_capacities(stop, load_path=load_path)
        if batch:
            # the whole point of batching is never materializing the
            # full-R state — _run_batched inits (or loads) each
            # batch's slice itself
            states = None
            t_start = 0
        elif load_path:
            with tracer.span("checkpoint.load", "checkpoint",
                             path=load_path):
                states, t_start = checkpoint.load_state(
                    self.engine, self.sim.starts, load_path,
                    final_stop=stop,
                    template=self.engine.init_ensemble_state(
                        self.sim.starts))
            log.info("resumed campaign checkpoint %s at t=%d ns",
                     load_path, t_start)
        else:
            states = self.engine.init_ensemble_state(self.sim.starts)
            t_start = 0
        pause = stop
        if xp.checkpoint_save:
            if xp.checkpoint_save_time:
                pause = min(stop, xp.checkpoint_save_time)
            if pause <= t_start:
                raise ValueError(
                    f"checkpoint_save_time {pause} ns is not after "
                    f"the campaign's start time {t_start} ns")
        self.checkpointer = None
        if xp.checkpoint_every and not batch:
            # batched campaigns rotate per-batch checkpointers inside
            # _run_batched (each batch restarts sim time at 0, so one
            # shared base would collide and cross-prune)
            self.checkpointer = supervise.Checkpointer(
                xp.checkpoint_save, xp.checkpoint_every,
                xp.checkpoint_keep, final_stop=stop,
                extra_meta=self._ck_extra_meta,
                audit_enabled=xp.state_audit)
        self.guard = supervise.make_guard(self.sim.cfg)
        self.hb_monitor = (
            supervise.HeartbeatMonitor(xp.heartbeat_stale_after)
            if getattr(xp, "heartbeat_stale_after", 0) else None)
        import contextlib
        t0 = time.perf_counter()
        rounds_r = None
        with (self.guard if self.guard is not None
              else contextlib.nullcontext()):
            if batch:
                states, adv, rounds_r = self._run_batched(
                    t_start, pause, stop, batch, tracer,
                    resume=resume_batch)
            else:
                try:
                    states, adv = supervise.advance(
                        self, states, t_start, pause, stop,
                        ensemble=True)
                except supervise.DegradeToReplicaBatch as dg:
                    # the ladder's replica-batch rung: the full-R
                    # vmap exhausted device memory deterministically
                    # — re-run the sweep from t=0 in sequential
                    # batches (bit-identical; no checkpointer exists
                    # on this path, so nothing was saved to rewind)
                    batch = dg.batch
                    states, adv, rounds_r = self._run_batched(
                        t_start, pause, stop, batch, tracer)
                    adv.degrades += 1   # the rung that engaged it
        if states is None:
            # batched campaign preempted mid-batch: there is no
            # merged final to record (and the completed batches'
            # finals were discarded — the resume replays them
            # bit-identically); surface the resumable outcome the
            # way a standalone preempted run does
            self.retries = adv.retries
            self.degrades = adv.degrades
            stats = SimStats()
            stats.end_time = adv.t_end
            stats.rounds = int(np.asarray(adv.rounds).max())
            stats.strategy_plan = self._base.strategy_plan
            if self.aot_cache is not None:
                self.aot_cache.publish(stats)
            stats.replans = self.replans
            stats.retries = adv.retries
            stats.reshards = adv.reshards
            stats.degrades = adv.degrades
            stats.admission = self.admission
            stats.preempted = True
            stats.resume_path = adv.resume_path
            stats.pipeline = adv.pipeline or None
            if self.hb_monitor is not None:
                stats.stale_heartbeats = self.hb_monitor.stale_events
            log.info("ensemble record not written (batched campaign "
                     "preempted; resume from %s)", adv.resume_path)
            return stats
        if rounds_r is None:
            rounds_r = np.broadcast_to(np.asarray(adv.rounds),
                                       (self.worlds.R,))
        t_end = adv.t_end
        budget_hit, overflowed = adv.budget_hit, adv.overflowed
        self.retries = adv.retries
        rounds = int(np.asarray(rounds_r).max())
        if xp.checkpoint_save and batch:
            # the merged final is host-side and heap-less — there is
            # no full-R stacked device state to save; the per-batch
            # rotation entries written during the run are the
            # campaign's checkpoints (schema.py requires
            # checkpoint_every alongside replica_batch+save for
            # exactly this reason)
            log.info("end-of-run campaign checkpoint skipped "
                     "(replica_batch: the rotation entries "
                     "%s.b<k>.t<ns> are the resumable artifacts)",
                     xp.checkpoint_save)
        elif xp.checkpoint_save:
            if budget_hit or overflowed:
                log.error("%s before the checkpoint boundary — NOT "
                          "saving %s",
                          "max_rounds exhausted" if budget_hit
                          else "capacity overflow (events lost)",
                          xp.checkpoint_save)
            elif adv.preempted:
                # the drain already saved the resume checkpoint
                pass
            else:
                with tracer.span("checkpoint.save", "checkpoint",
                                 sim_t0=t_end,
                                 path=xp.checkpoint_save):
                    checkpoint.save_state(
                        self.engine, states, xp.checkpoint_save,
                        t_end, final_stop=stop,
                        extra_meta=self._ck_extra_meta,
                        audit_meta=({"enabled": True, "violations": 0}
                                    if xp.state_audit else None))
                log.info("campaign checkpoint saved at t=%d ns -> %s",
                         t_end, xp.checkpoint_save)
        stat_keys = [k for k in states
                     if k not in ("ht", "hk", "hm", "hv", "hw")]
        with tracer.span("state.fetch", "host", sim_t0=t_end):
            final = {k: np.asarray(v) for k, v in jax.device_get(
                {k: states[k] for k in stat_keys}).items()}
        wall = time.perf_counter() - t0
        self.final_state = final
        H = len(self.sim.hosts)

        # `final` already holds every counter host-side — the
        # worst-case reduction reuses it rather than re-fetching the
        # same [R, ...] arrays from device
        occ = capacity.measure(self.engine,
                               self._worst_case_view(final),
                               source="ensemble-run")
        occ["workload"]["replicas"] = int(w.R)
        if self.occ_record is not None:
            self.occ_record["final_measured"] = occ["measured"]
            self.occ_record["effective"] = occ["effective"]
            self.occ_record["replans"] = self.replans
            self.occ_record["applied"] = dict(
                self._capacity_overrides)
        else:
            self.occ_record = occ

        overflow = int(final["overflow"][:, :H].sum())
        x_overflow = int(final["x_overflow"][:, :H].sum())
        ok = overflow == 0 and x_overflow == 0 and not budget_hit
        self.degrades = adv.degrades
        self.record = self._build_record(final, rounds_r, wall, ok)
        if self.admission is not None:
            # the preflight verdict (and any replica-batch split)
            # rides the campaign record
            self.record["admission"] = self.admission
        if batch:
            self.record["replica_batch"] = int(batch)
        if adv.degrades:
            self.record["degrades"] = int(adv.degrades)
        if adv.preempted:
            # a preempted campaign's counters cover only the executed
            # prefix — the resumed run writes the real record
            log.info("ensemble record not written (campaign "
                     "preempted; resume from %s)", adv.resume_path)
        else:
            path = self.record_path()
            try:
                atomic_write_json(self.record, path)
                log.info("ensemble record -> %s", path)
            except OSError as e:
                log.warning("could not write ensemble record %s: %s",
                            path, e)

        n_exec_total = int(final["n_exec"][:, :H].sum())
        log.info("ensemble perf: %d replicas, %d rounds in %.2fs "
                 "wall (%.0f events/s aggregate)", w.R, rounds, wall,
                 n_exec_total / wall if wall > 0 else 0.0)

        stats = SimStats()
        stats.end_time = t_end
        stats.rounds = int(rounds)
        stats.occupancy = self.occ_record
        # the campaign shares the base runner's plan adoption (the
        # one mutation site, before any engine was built)
        stats.strategy_plan = self._base.strategy_plan
        if self.aot_cache is not None:
            self.aot_cache.publish(stats)
        stats.replans = self.replans
        stats.retries = self.retries
        stats.reshards = adv.reshards
        stats.degrades = adv.degrades
        stats.admission = self.admission
        mem = self.engine.device_memory_stats()
        if mem is not None:
            stats.mem_bytes_in_use, stats.mem_budget = mem
        stats.preempted = adv.preempted
        stats.resume_path = adv.resume_path
        if self.hb_monitor is not None:
            stats.stale_heartbeats = self.hb_monitor.stale_events
        # campaigns ride the same advance loop as standalone runs
        # (supervise.advance is shared) — report its telemetry too
        stats.pipeline = adv.pipeline or None
        stats.ensemble = self.record
        # campaign totals (all replicas) — the aggregate view; the
        # per-replica breakdown lives in the record
        stats.events_executed = n_exec_total
        stats.packets_sent = int(final["n_sent"][:, :H].sum())
        stats.packets_dropped = int(final["n_drop"][:, :H].sum())
        stats.packets_delivered = int(final["n_deliv"][:, :H].sum())
        if overflow:
            stats.ok = False
            log.error("ensemble engine overflow: %d events lost — "
                      "raise experimental.event_capacity/"
                      "outbox_capacity, or set capacity_plan: auto",
                      overflow)
        if x_overflow:
            stats.ok = False
            log.error("ensemble exchange overflow: %d rows exceeded "
                      "the per-shard-pair capacity — raise "
                      "experimental.exchange_capacity or use "
                      "capacity_plan: auto", x_overflow)

        # replica 0's per-host results reflect onto the Host objects:
        # the determinism gate's signature path (and any tooling that
        # reads hosts) sees the base replica, which must bit-match a
        # standalone run with replica 0's parameters. A columnar build
        # adopts the row as plane columns instead — no host
        # materialization just to carry counters.
        plane = getattr(self.sim, "plane", None)
        if plane is not None:
            plane.adopt_final(final, replica=0)
        else:
            for h in self.sim.hosts:
                i = h.host_id
                h.events_executed = int(final["n_exec"][0, i])
                h.packets_sent = int(final["n_sent"][0, i])
                h.packets_dropped = int(final["n_drop"][0, i])
                h.packets_delivered = int(final["n_deliv"][0, i])
                h.trace_checksum = int(final["chk"][0, i])
        return stats
