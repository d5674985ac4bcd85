"""DeviceRunner: adapts the device engine to the Controller.

Selected by `experimental.scheduler_policy: tpu` — the device-mesh
scheduler policy slotting in beside the CPU thread policies, exactly as
the north-star design places it (a new policy alongside
src/main/core/scheduler's five).

Heterogeneity: client-LOCAL args (count/pause/retry) vary per host —
the device apps carry them as per-host arrays, covering the
tornettools shape (varied client behavior over a shared relay/server
fabric). Args that shape SHARED hosts' responses (tgen `size`, tor
`cells`) must stay uniform, and hosts must all belong to one model
family; mixed-family configs run hybrid (CPU host emulation + device
network judgments) via the NoDeviceTwin fallback.
"""

from __future__ import annotations

from typing import Optional

import numpy as np


from shadow_tpu._jax import jax
from shadow_tpu.core.manager import SimStats, resolve_host_ref
from shadow_tpu.obs import trace as obstrace
from shadow_tpu.device.apps import (
    DeviceApp,
    PholdDevice,
    TgenDevice,
    TorDevice,
)
from shadow_tpu.device.engine import AXIS, DeviceEngine, EngineConfig
from shadow_tpu.models.phold import PholdApp
from shadow_tpu.models.tgen import TgenClientApp, TgenServerApp
from shadow_tpu.models.tor import TorClientApp, TorRelayApp
from shadow_tpu.topology import hierarchy
from shadow_tpu.utils.slog import get_logger

log = get_logger("device")


def _tristate(value: str, true_word: str):
    """Strategy-knob mapping shared by every auto/<on>/<off> choice:
    'auto' -> None (engine picks by platform), `true_word` -> True,
    anything else (the schema-validated off word) -> False."""
    return None if value == "auto" else value == true_word


class NoDeviceTwin(ValueError):
    """The config's apps have no fully-vectorized device twin; the tpu
    policy falls back to hybrid execution (CPU host emulation + device
    network judgment, core/manager.py flush_judgments)."""


def _plane_twin(sim, plane) -> DeviceApp:
    """Device twin straight from the columnar host plane — no host
    materialization, no per-host iteration. Each group's ONE prototype
    app carries the parsed args; the twin's per-host arrays fill from
    group slices. Raises the exact errors the object path would, so
    the fallback story reads the same either way."""
    n_hosts = plane.n_hosts
    models = {g.model for g in plane.group_records}

    if models == {"phold"}:
        first = plane.group_records[0].prototype
        for g in plane.group_records[1:]:
            a = g.prototype
            if (a.msgload, a.size, a.selfloop) != (first.msgload,
                                                   first.size,
                                                   first.selfloop):
                raise ValueError("tpu policy: phold args must match "
                                 "across hosts")
        return PholdDevice(n_hosts_total=n_hosts, msgload=first.msgload,
                           size=first.size, selfloop=first.selfloop)

    # eligibility (host/plane.py COLUMNAR_MODELS) admits only phold
    # and tgen; a mixed phold+tgen set still lands here
    if models <= {"tgen_server", "tgen_client"}:
        client_groups = [g for g in plane.group_records
                         if g.model == "tgen_client"]
        if not client_groups:
            raise ValueError("tpu policy: tgen config has no clients")
        first = client_groups[0].prototype
        for g in client_groups:
            if g.prototype.size != first.size:
                raise ValueError(
                    "tpu policy: tgen client `size` must match across "
                    "hosts (it shapes the shared servers' responses); "
                    "count/pause/retry may vary")
        roles = np.zeros(n_hosts, np.int32)
        server_gid = np.zeros(n_hosts, np.int32)
        count = np.zeros(n_hosts, np.int32)
        pause = np.zeros(n_hosts, np.int64)
        retry = np.zeros(n_hosts, np.int64)
        for g in client_groups:
            sl = slice(g.base_id, g.base_id + g.count)
            a = g.prototype
            roles[sl] = 1
            count[sl] = a.count
            pause[sl] = a.pause_ns
            retry[sl] = a.retry_ns
            # same name-or-group rule as resolve_host_ref: an exact
            # host name pins every client in the group to one server;
            # a group name fans out by asker_id % group size
            sid = plane.names.get(a.server_name)
            if sid is not None:
                server_gid[sl] = sid
                continue
            members = (sim.groups or {}).get(a.server_name)
            if not members:
                raise ValueError(
                    f"tgen client on {plane.name_of(g.base_id)}: "
                    f"unknown server {a.server_name!r}")
            ids = np.arange(g.base_id, g.base_id + g.count,
                            dtype=np.int64)
            server_gid[sl] = (members[0]
                              + ids % len(members)).astype(np.int32)
        return TgenDevice(roles=roles, server_gid=server_gid,
                          size=first.size, count=count,
                          pause_ns=pause, retry_ns=retry)

    names = sorted(models)
    raise NoDeviceTwin(f"no device twin registered for {names}; "
                       "available: phold, tgen (server+client) — "
                       "running hybrid (CPU hosts + device net model)")


def device_twin(sim) -> DeviceApp:
    """Map the config's CPU model apps to their vectorized device twin.
    Supported: homogeneous phold; tgen server/client mixes (homogeneous
    client args)."""
    plane = getattr(sim, "plane", None)
    if plane is not None:
        return _plane_twin(sim, plane)
    if any(len(h.apps) > 1 for h in sim.hosts):
        raise NoDeviceTwin("tpu policy: multi-process hosts run hybrid")
    apps = [h.app for h in sim.hosts]
    n_hosts = len(sim.hosts)
    real = [a for a in apps if a is not None]
    if not real:
        raise NoDeviceTwin("tpu policy: no model apps configured")
    classes = {type(a) for a in real}

    if classes == {PholdApp}:
        first = real[0]
        for a in real:
            if (a.msgload, a.size, a.selfloop) != (first.msgload,
                                                   first.size,
                                                   first.selfloop):
                raise ValueError("tpu policy: phold args must match "
                                 "across hosts")
        return PholdDevice(n_hosts_total=n_hosts, msgload=first.msgload,
                           size=first.size, selfloop=first.selfloop)

    if classes <= {TgenServerApp, TgenClientApp}:
        name_to_id = {h.name: h.host_id for h in sim.hosts}
        roles = np.zeros(n_hosts, np.int32)
        server_gid = np.zeros(n_hosts, np.int32)
        clients = [a for a in real if isinstance(a, TgenClientApp)]
        if not clients:
            raise ValueError("tpu policy: tgen config has no clients")
        first = clients[0]
        # client-LOCAL args (count/pause/retry) vary per host; `size`
        # shapes the server's response and must stay uniform
        for c in clients:
            if c.size != first.size:
                raise ValueError(
                    "tpu policy: tgen client `size` must match across "
                    "hosts (it shapes the shared servers' responses); "
                    "count/pause/retry may vary")
        count = np.zeros(n_hosts, np.int32)
        pause = np.zeros(n_hosts, np.int64)
        retry = np.zeros(n_hosts, np.int64)
        for h in sim.hosts:
            if isinstance(h.app, TgenClientApp):
                roles[h.host_id] = 1
                count[h.host_id] = h.app.count
                pause[h.host_id] = h.app.pause_ns
                retry[h.host_id] = h.app.retry_ns
                try:
                    # same name-or-group rule as the CPU ctx.resolve
                    server_gid[h.host_id] = resolve_host_ref(
                        name_to_id, getattr(sim, "groups", None),
                        h.app.server_name, h.host_id)
                except KeyError:
                    raise ValueError(
                        f"tgen client on {h.name}: unknown server "
                        f"{h.app.server_name!r}") from None
        return TgenDevice(roles=roles, server_gid=server_gid,
                          size=first.size, count=count,
                          pause_ns=pause, retry_ns=retry)

    if classes <= {TorRelayApp, TorClientApp}:
        clients = [a for a in real if isinstance(a, TorClientApp)]
        if not clients:
            raise ValueError("tpu policy: tor config has no clients")
        first = clients[0]
        # `cells` shapes the exit relays' DATA service: uniform;
        # count/pause/retry are client-local and may vary
        for c in clients:
            if c.cells != first.cells:
                raise ValueError(
                    "tpu policy: tor client `cells` must match across "
                    "hosts (it shapes the exit relays' responses); "
                    "count/pause/retry may vary")
        roles = np.zeros(n_hosts, np.int32)
        count = np.zeros(n_hosts, np.int32)
        pause = np.zeros(n_hosts, np.int64)
        retry = np.zeros(n_hosts, np.int64)
        relay_gids = []
        for h in sim.hosts:
            if isinstance(h.app, TorClientApp):
                roles[h.host_id] = 1
                count[h.host_id] = h.app.count
                pause[h.host_id] = h.app.pause_ns
                retry[h.host_id] = h.app.retry_ns
            elif isinstance(h.app, TorRelayApp):
                relay_gids.append(h.host_id)
        if len(relay_gids) < 3:
            raise ValueError("tor model needs >= 3 relays")
        return TorDevice(roles=roles,
                         relay_gids=np.array(relay_gids, np.int64),
                         cells=first.cells, count=count,
                         pause_ns=pause, retry_ns=retry)

    names = sorted(c.__name__ for c in classes)
    raise NoDeviceTwin(f"no device twin registered for {names}; "
                       "available: phold, tgen (server+client), "
                       "tor (relay+client) — "
                       "running hybrid (CPU hosts + device net model)")


class DeviceRunner:
    def __init__(self, sim, trace: Optional[list] = None, mesh=None,
                 defer_engine: bool = False):
        if getattr(sim, "host_faults", None):
            # host crash/restart are manager-side events (processes
            # are killed and respawned) — the device engine has no
            # manager loop, so these configs run hybrid: CPU host
            # emulation with the batched device network judge, which
            # carries the same fault epoch table
            raise NoDeviceTwin(
                "host_crash/host_restart faults are manager-side "
                "events; running hybrid")
        self.app = device_twin(sim)     # raises NoDeviceTwin -> hybrid
        if trace is not None:
            raise ValueError(
                "the tpu policy does not record python event traces; "
                "use per-host trace checksums (Host.trace_checksum) for "
                "equivalence testing")
        self.sim = sim
        cfg = sim.cfg
        plane = getattr(sim, "plane", None)
        if (plane.any_pcap if plane is not None
                else any(h.pcap_directory for h in sim.hosts)):
            log.warning("tpu policy: pcap capture requires a CPU "
                        "scheduler policy (packets are device-resident "
                        "metadata here)")
        if mesh is None and cfg.experimental.mesh_shards:
            # experimental.mesh_shards: pin the mesh to the first N
            # devices (the chaos gate's uninterrupted M-shard
            # comparison runs; shrunken-geometry resumes on a
            # healthy pool) without XLA_FLAGS process-global
            # forcing. Resolved before plan adoption below — the
            # plan's applicability gates must see the mesh that
            # actually runs.
            from jax.sharding import Mesh
            n = cfg.experimental.mesh_shards
            devs = jax.devices()
            if n > len(devs):
                raise ValueError(
                    f"experimental.mesh_shards={n} but only "
                    f"{len(devs)} device(s) are available")
            mesh = Mesh(np.array(devs[:n]), (AXIS,))
        # strategy-plan adoption (shadow_tpu/tune/plan.py,
        # docs/autotune.md): under experimental.strategy_plan a
        # stored PLAN record for this workload fingerprint re-tunes
        # the config's execution knobs BEFORE anything below reads
        # them. Adoption changes wall time only — every plan-space
        # knob is bit-identity-pinned — and a fingerprint mismatch
        # refuses loudly inside adopt(). The provenance rides
        # SimStats.strategy_plan.
        from shadow_tpu.tune import plan as planmod
        self.strategy_plan = planmod.adopt(
            cfg, self.app, len(sim.hosts),
            n_shards=(mesh.devices.size if mesh is not None
                      else len(jax.devices())))
        # flow control blocks a host's pops when the outbox lacks a
        # full-burst (max_sends) of headroom; at OB == K that means one
        # event per phase, paying one collective exchange per event.
        # Give bursty apps 8 bursts of room unless the config asks for
        # more.
        bp = cfg.experimental.burst_pops
        if bp:
            # width override for on-chip tuning: lowering to 1 is
            # always safe (disables bursting); raising needs an app
            # that implements the burst contract (handle_burst +
            # burst_mask). Traces are P-invariant — per-host pop
            # order is (t, src, seq) regardless of lane width —
            # pinned by test_burst_width_identical_traces.
            if bp > 1 and getattr(self.app, "burst_pops", 1) <= 1:
                raise ValueError(
                    "experimental.burst_pops > 1 requires an app "
                    "with burst support (stateless-responder "
                    "contract); this app pops one event per "
                    "iteration")
            self.app.burst_pops = bp
        self._burst = max(1, getattr(self.app, "burst_pops", 1))
        self._mesh = mesh
        # deterministic chaos injection (device/chaos.py): installed
        # process-global for the run's lifetime — None without a
        # schedule, so schedules never leak across in-process runs
        from shadow_tpu.device import chaos as chaosmod
        self.chaos = chaosmod.from_config(cfg.experimental)
        chaosmod.set_current(self.chaos)
        # capacity overrides on top of the config's static knobs:
        # filled by the occupancy planner (capacity_plan: auto|path)
        # and widened by the overflow re-plan/retry loop
        self._capacity_overrides: dict = {}
        # `exchange: auto` resolution (capacity.choose_exchange over
        # the OCC record): None until a plan/record/checkpoint picks
        # a concrete variant; the engine builder falls back to
        # all_to_all meanwhile (warm-up slices, static plans)
        self._exchange_choice: str = ""
        # persistent AOT compile cache (device/aotcache.py): ONE
        # instance per run, attached to every engine this runner
        # builds — warm-up engines, re-planned engines, and resumed
        # engines all consult the same cache, and its report is the
        # run's loud hit/miss surface (SimStats.compile_cache)
        from shadow_tpu.device import aotcache
        self.aot_cache = aotcache.resolve_cache(cfg.experimental)
        # defer_engine: the EnsembleRunner reuses this class for twin
        # mapping + knob plumbing but builds ITS engine with the
        # stacked replica worlds — constructing a standalone engine
        # here too would be pure waste
        self.engine = None if defer_engine else self._build_engine()
        self.final_state: Optional[dict] = None
        self.occ_record: Optional[dict] = None
        self.replans = 0
        # supervision plumbing (device/supervise.py): the rotating
        # checkpoint writer and the SIGTERM/SIGINT drain guard, set up
        # per run() invocation; the shared advance loop reads them
        self.checkpointer = None
        self.guard = None
        # wall-clock heartbeat staleness monitor (supervise.
        # HeartbeatMonitor), created per run() when
        # experimental.heartbeat_stale_after is set; the campaign
        # server's watchdog polls it cross-thread
        self.hb_monitor = None
        self.retries = 0
        self.reshards = 0
        # OOM degradation-ladder rungs engaged (supervise.advance
        # walks the ladder; the heartbeat and SimStats report it)
        self.degrades = 0
        # preflight admission verdict (capacity.admission_verdict),
        # set per run(); SimStats.admission carries it
        self.admission = None
        # flight recorder (shadow_tpu/obs): the Controller attaches
        # its run-wide tracer; None (direct construction in tests)
        # falls through to the module-global current() in advance
        self.tracer = None
        # supervise-heartbeat rate mark: (wall, packets) at the last
        # heartbeat, for the pkts/s-since-last-heartbeat log column
        self._hb_mark = None
        # campaign checkpoint stamp (EnsembleRunner overrides)
        self._ck_extra_meta: Optional[dict] = None
        # set once _plan_capacities has sized the engine: run() skips
        # re-planning, so a caller may plan ahead of its timed window
        # and a re-used runner keeps its plan
        self._planned = False

    def _build_engine(self, ensemble=None,
                      lookahead: Optional[int] = None,
                      seed: Optional[int] = None) -> DeviceEngine:
        """Construct the engine from the config's static knobs plus
        any planner/retry capacity overrides (re-invoked by the
        re-plan loop; a capacity change recompiles the program).

        `ensemble`/`lookahead`/`seed` are the EnsembleRunner's
        overrides: with ensemble worlds the DeviceEngine constructor
        swaps in replica 0's tables itself, the campaign shares one
        conservative lookahead, and the engine seed is replica 0's —
        everything else (knob plumbing, outbox floors, strategy
        tristates) is identical, so campaigns reuse this one builder
        instead of copy-pasting it."""
        sim = self.sim
        cfg = sim.cfg
        xp = cfg.experimental
        per_iter = self.app.max_sends * self._burst + \
            self.app.max_timers
        # floor the outbox at 8 iterations per phase — 4 when bursts
        # drain backlogs P events at a time
        outbox = max(xp.outbox_capacity,
                     (4 if self._burst > 1 else 8) * per_iter)
        if outbox != xp.outbox_capacity and \
                "outbox_capacity" not in self._capacity_overrides:
            log.info("outbox_capacity raised %d -> %d (8 iterations "
                     "of %d lanes)",
                     xp.outbox_capacity, outbox, per_iter)
        knobs = {
            "event_capacity": xp.event_capacity,
            "outbox_capacity": outbox,
            "exchange_capacity": xp.exchange_capacity,
            "exchange_capacity2": xp.exchange_capacity2,
            "exchange_in_capacity": xp.exchange_in_capacity,
            "outbox_compact": xp.outbox_compact,
        }
        knobs.update(self._capacity_overrides)
        # exchange: auto resolves to whatever the planner (or an
        # adopted checkpoint) chose; before any record exists — the
        # warm-up slice, static plans — the direct all_to_all stands
        # in (it measures the occ_x pair matrix auto needs)
        exchange = xp.exchange
        if exchange == "auto":
            exchange = self._exchange_choice or "all_to_all"
        # link-fault epoch table (shadow_tpu/faults.py): the engine
        # carries the stacked [T,V,V] matrices and selects the active
        # epoch inside the jitted program; without faults it gets the
        # single base epoch and compiles identically to before
        ft = getattr(sim, "fault_table", None)
        latency_ns, reliability, epoch_times = hierarchy.world_tables(
            sim.topology, ft)
        engine = DeviceEngine(
            EngineConfig(
                n_hosts=len(sim.hosts),
                lookahead=(max(1, sim.lookahead)
                           if lookahead is None else lookahead),
                stop_time=cfg.general.stop_time,
                bootstrap_end=cfg.general.bootstrap_end_time,
                seed=cfg.general.seed if seed is None else seed,
                exchange=exchange,
                model_bandwidth=xp.model_bandwidth,
                count_paths=xp.count_paths,
                judge_hoist=_tristate(xp.judge_placement, "flush"),
                merge_global=_tristate(xp.merge_strategy, "global"),
                pop_onehot=_tristate(xp.pop_strategy, "onehot"),
                table_onehot=_tristate(xp.table_strategy, "onehot"),
                audit=xp.state_audit,
                **knobs,
            ),
            self.app,
            host_vertex=sim.netmodel.host_vertex.astype(np.int32),
            latency_ns=latency_ns,
            reliability=reliability,
            epoch_times=epoch_times,
            ensemble=ensemble,
            mesh=self._mesh,
            bw_up_bits=(sim.plane.bw_up_bits
                        if getattr(sim, "plane", None) is not None
                        else np.array([h.bw_up_bits
                                       for h in sim.hosts],
                                      dtype=np.int64)),
            bw_down_bits=(sim.plane.bw_down_bits
                          if getattr(sim, "plane", None) is not None
                          else np.array([h.bw_down_bits
                                         for h in sim.hosts],
                                        dtype=np.int64)),
        )
        # every engine this runner builds (static, warm-up, planned,
        # re-planned, resumed) shares the one AOT compile cache, so a
        # rebuild at previously-seen capacities starts warm
        engine.aot_cache = self.aot_cache
        return engine

    def _plan_capacities(self, stop: int,
                         load_path: Optional[str] = None) -> None:
        """capacity_plan: auto|<path> — size the engine's capacities
        from measured occupancy instead of the hand-tuned knobs.
        `auto` runs a short warm-up slice on the statically-sized
        engine (window clamping on the global stop, so the windows
        match the real run's prefix); a path consumes a previously
        written OCC record. Either way the planned engine's traces
        bit-match the static engine's whenever nothing overflows, and
        the overflow retry loop (supervise.advance) covers the
        undershoot case loudly. `load_path` is the rotation-resolved
        checkpoint_load path (run() resolves it once)."""
        from shadow_tpu.device import capacity

        xp = self.sim.cfg.experimental
        mode = xp.capacity_plan
        if load_path is None:
            load_path = xp.checkpoint_load
        if load_path:
            # the checkpoint fingerprint pins the saved engine's
            # capacities — a checkpoint written under a plan carries
            # the PLANNER's sizes, not the config's static knobs, so
            # re-planning (or building the static engine) would only
            # produce a loud fingerprint mismatch. Adopt the saved
            # capacities instead; an overflow past the resume point
            # still re-plans through the normal retry loop.
            self._adopt_checkpoint_caps(load_path)
            self.engine = self._build_engine()
            self._planned = True
            # the adopted capacities name the resume program: its AOT
            # entry read overlaps the checkpoint load that follows
            from shadow_tpu.device import supervise
            supervise.prefetch_programs(self)
            log.warning("capacity_plan: %s skipped — checkpoint_load "
                        "resumes with the saved engine's capacities "
                        "%s", mode, self._capacity_overrides)
            return
        # the record's audit baseline: what the config's static knobs
        # build, captured BEFORE any warm-up widen-retry rebuilds the
        # engine (else an overflowed warm-up reports the doubled
        # values as "static")
        static_knobs = {k: getattr(self.engine.config, k)
                        for k in capacity.CAPACITY_KNOBS}
        if mode == "auto":
            warm = xp.capacity_warmup or max(1, stop // 8)
            warm = min(warm, stop)
            # honor dispatch_segment here too: the warm-up is a real
            # device dispatch and obeys the same bound. Overflow is
            # checked at each boundary, so a bad static sizing
            # re-plans without finishing the slice first.
            seg = xp.dispatch_segment
            state = self.engine.init_state(self.sim.starts)
            for attempt in range(capacity.MAX_REPLANS + 1):
                t = 0
                dims = ()
                while t < warm:
                    nxt = min(warm, t + seg) if seg else warm
                    state, _ = self.engine.run(state, stop=nxt,
                                               final_stop=stop)
                    t = nxt
                    dims = capacity.overflow_dims(state)
                    if dims:
                        break
                if not dims:
                    break
                if attempt == capacity.MAX_REPLANS:
                    raise RuntimeError(
                        f"capacity warm-up still overflows after "
                        f"{capacity.MAX_REPLANS} doublings on {dims}")
                self._capacity_overrides = capacity.widen(
                    self._capacity_overrides, dims,
                    self.engine.effective)
                log.warning("capacity warm-up overflowed on %s; "
                            "retrying with %s", dims,
                            self._capacity_overrides)
                self.engine = self._build_engine()
                state = self.engine.init_state(self.sim.starts)
            record = capacity.measure(self.engine, state,
                                      source=f"warmup:{warm}ns")
        else:
            record = capacity.load_record(mode)
            want = {"app": type(self.app).__name__,
                    "app_fp": capacity.app_fingerprint(self.app),
                    "n_hosts": len(self.sim.hosts)}
            got = {k: record["workload"].get(k) for k in want}
            if got != want:
                raise ValueError(
                    f"occupancy record {mode} was measured on {got}; "
                    f"this simulation is {want} — re-measure with "
                    "capacity_plan: auto")
        exchange = self._resolve_exchange(record)
        planned = capacity.plan(
            record,
            per_iter=self.engine.effective["M_out"],
            floor_iters=4 if self._burst > 1 else 8,
            n_shards=self.engine.n_shards,
            headroom=self._headroom(),
            exchange=exchange)
        record["planned"] = planned
        record["static"] = static_knobs
        self.occ_record = record
        self._capacity_overrides = dict(planned)
        self.engine = self._build_engine()
        self._planned = True
        # the planned program is now named: overlap its AOT cache
        # entry read with the init_state / checkpoint-load work that
        # follows (supervise.prefetch_programs)
        from shadow_tpu.device import supervise
        supervise.prefetch_programs(self)
        log.info("capacity plan (%s, exchange %s, headroom %g): %s  "
                 "[measured %s]", mode, exchange, self._headroom(),
                 planned, record["measured"])

    def _headroom(self) -> float:
        """The capacity planner's pad factor: the tunable
        experimental.capacity_headroom when set, else the planner
        default. One accessor shared by the plan and the
        exchange-choice estimates so they can never pad
        differently."""
        from shadow_tpu.device import capacity

        return (self.sim.cfg.experimental.capacity_headroom
                or capacity.HEADROOM)

    def _adopt_checkpoint_caps(self, load_path: str) -> None:
        """Checkpoint resume under a capacity plan: adopt the SAVED
        engine's capacity knobs (the fingerprint pins them — a fresh
        plan would only produce a loud mismatch) and, under
        `exchange: auto`, the saved exchange schedule the caps were
        planned for. ONE adopt path for both runners — the campaign
        delegates here so standalone and ensemble resumes can never
        drift."""
        from shadow_tpu.device import checkpoint

        meta = checkpoint.peek_meta(load_path)
        caps = meta.get("capacities")
        if caps is None:
            # pre-"capacities" checkpoints: only the two
            # layout-determining knobs ride the fingerprint
            caps = {k: meta["fingerprint"][k]
                    for k in ("event_capacity", "outbox_capacity")}
        self._capacity_overrides = {k: int(v)
                                    for k, v in caps.items()}
        if self.sim.cfg.experimental.exchange == "auto":
            self._exchange_choice = meta.get("exchange",
                                             "all_to_all")

    def _adopt_checkpoint_geometry(self, load_path: str) -> bool:
        """A checkpoint written after a mesh-shrink failover stamps
        the shrunken geometry (checkpoint meta["geometry"]); loading
        it onto the full mesh would be a hard layout mismatch. Adopt
        instead: rebuild the mesh on the first ``n_shards`` available
        devices so the resume lands on the saved geometry — traces
        are mesh-placement-invariant, so WHICH devices is free.
        Returns whether the mesh changed (the EnsembleRunner rebuilds
        its campaign engine then). ONE adopt path for both runners,
        like _adopt_checkpoint_caps."""
        from shadow_tpu.device import checkpoint

        geom = checkpoint.peek_geometry(
            checkpoint.peek_meta(load_path))
        n = geom.get("n_shards")
        if n is None:
            return False
        n = int(n)
        cur = (self._mesh.devices.size if self._mesh is not None
               else len(jax.devices()))
        if n == cur:
            return False
        devs = (list(self._mesh.devices.flat)
                if self._mesh is not None else jax.devices())
        if n > len(devs):
            raise ValueError(
                f"checkpoint {load_path} was saved on {n} shard(s) "
                f"but only {len(devs)} device(s) are available — "
                "resume on a pool of at least the saved shard count")
        from jax.sharding import Mesh
        log.warning(
            "checkpoint %s was saved on %d shard(s) (this pool has "
            "%d) — rebuilding the mesh to the saved geometry for "
            "the resume", load_path, n, len(devs))
        self._mesh = Mesh(np.array(devs[:n]), (AXIS,))
        if self.engine is not None:
            self.engine = self._build_engine()
        return True

    def _replan_for_shrink(self, n_shards: int, record: dict = None,
                           per_iter: int = 0) -> None:
        """The exchange-geometry capacities were planned/auto-sized
        for the OLD shard count — fewer shards mean more hosts (and
        rows) per shard pair, so carrying them over would guarantee
        overflow re-plans. Drop them, re-resolve the exchange
        schedule for the new width (``exchange: auto``), and re-plan
        the caps from the measured occupancy record when one exists
        (capacity.pair_matrix degrades a mismatched-shape pair
        matrix to a safe scalar bound). Per-host capacities
        (event/outbox/IN/compact) are shard-independent and stay."""
        from shadow_tpu.device import capacity
        from shadow_tpu.tune import plan as planmod

        xp = self.sim.cfg.experimental
        for k in ("exchange_capacity", "exchange_capacity2"):
            # 0, not pop: a hand-set static knob was sized for the
            # dead geometry too — the override restores the engine's
            # own auto-sizing until the record-based plan below (if
            # any) supplies measured caps for the new width
            self._capacity_overrides[k] = 0
        record = record if record is not None else self.occ_record
        floor_iters = 4 if self._burst > 1 else 8
        # the EnsembleRunner passes its campaign engine's lane width
        # (the base runner's engine is deferred there)
        per_iter = per_iter or self.engine.effective["M_out"]
        exchange = xp.exchange
        if xp.exchange == "auto":
            if record is not None:
                choice, info = capacity.choose_exchange(
                    record, n_shards, per_iter=per_iter,
                    floor_iters=floor_iters,
                    headroom=self._headroom())
                record["exchange_auto"] = info
                exchange = self._exchange_choice = choice
                log.info("shrink re-plan: exchange auto -> %s at %d "
                         "shard(s)", choice, n_shards)
            else:
                exchange = self._exchange_choice = "all_to_all"
        if record is not None:
            planned = capacity.plan(
                record, per_iter=per_iter, floor_iters=floor_iters,
                n_shards=n_shards, headroom=self._headroom(),
                exchange=exchange)
            for k in ("exchange_capacity", "exchange_capacity2"):
                if planned[k]:
                    self._capacity_overrides[k] = planned[k]
            log.info("shrink re-plan at %d shard(s): %s", n_shards,
                     {k: v for k, v in self._capacity_overrides
                      .items() if k.startswith("exchange")})
        # the adopted strategy plan was validated against the old run
        # shape: re-run its applicability gates under the new shard
        # count and surface the knobs that no longer apply
        self.strategy_plan = planmod.revalidate_after_reshard(
            self.sim.cfg, self.strategy_plan, n_shards)

    def _shrink_to(self, alive, host_state: dict,
                   ensemble: bool = False):
        """Re-shard a host-side validated snapshot onto the surviving
        devices: new mesh, re-planned exchange capacities, rebuilt
        engine (warm through the shared AOT cache), and the snapshot
        re-padded to the new geometry (capacity.reshard_state) and
        re-placed with the new template's shardings. Returns the
        on-device state the advance loop continues from. The
        EnsembleRunner overrides this to rebuild its campaign
        engine; the mesh/override mutations stay here — one owner.

        Transactional: a failure anywhere rolls the mesh, engine,
        overrides, and plan provenance back to the pre-shrink
        state before re-raising — the escalation that follows
        persists the (old-geometry) snapshot through
        ``runner.engine``, so a half-committed shrink would stamp
        the NEW geometry over old-layout leaves and poison the
        failover checkpoint."""
        from jax.sharding import Mesh

        from shadow_tpu.device import supervise

        rollback = (self._mesh, self.engine,
                    dict(self._capacity_overrides),
                    self._exchange_choice, self.strategy_plan)
        try:
            self._mesh = Mesh(np.array(list(alive)), (AXIS,))
            self._replan_for_shrink(len(alive))
            self.engine = self._build_engine()
            supervise.prefetch_programs(self, ensemble=ensemble)
            return self._place_resharded(self, host_state, ensemble)
        except Exception:
            (self._mesh, self.engine, self._capacity_overrides,
             self._exchange_choice, self.strategy_plan) = rollback
            raise

    @staticmethod
    def _place_resharded(runner, host_state: dict, ensemble: bool):
        """Shared tail of the shrink: build the new engine's template
        (shapes + shardings + padding-row values), re-pad the
        snapshot onto it, and device_put. The template round-trips
        through the host once — the padding rows' contents (app init
        rows, heap fills) must be exactly what an uninterrupted run
        on the new mesh would hold, and init_state is their one
        source of truth."""
        from shadow_tpu.device import capacity

        engine = runner.engine
        template = (engine.init_ensemble_state(runner.sim.starts)
                    if ensemble else
                    engine.init_state(runner.sim.starts))
        new_host = capacity.reshard_state(
            host_state, len(runner.sim.hosts),
            jax.device_get(template))
        return capacity.transfer(engine, runner.sim.starts, new_host,
                                 template=template)

    def _resolve_exchange(self, record: dict, engine=None) -> str:
        """The exchange variant the planned engine will compile:
        the config's explicit choice, or — under `exchange: auto` —
        capacity.choose_exchange over the measured occ_x pair matrix
        (stamped into the record so the decision is auditable).
        Shared by DeviceRunner and EnsembleRunner (which passes its
        own campaign engine; this runner's may be deferred)."""
        from shadow_tpu.device import capacity

        engine = engine if engine is not None else self.engine
        xp = self.sim.cfg.experimental
        if xp.exchange != "auto":
            return xp.exchange
        choice, info = capacity.choose_exchange(
            record, engine.n_shards,
            per_iter=engine.effective["M_out"],
            floor_iters=4 if self._burst > 1 else 8,
            headroom=self._headroom())
        record["exchange_auto"] = info
        self._exchange_choice = choice
        if engine.n_shards > 1:
            log.info("exchange: auto -> %s (per-flush ICI row "
                     "estimates %s)", choice, info["estimates"])
        return choice

    def _emit_heartbeats(self, now: int, state) -> None:
        """Per-host [shadow-heartbeat] CSV lines from device counters
        at a run-segment boundary (tracker.c:418-560 format: same
        Tracker, same headers, counters device_get'd between
        segments). Interval attribution is window-granular: the
        segment pauses when the next event passes `now`, so events in
        [now, now+lookahead) of the last window are counted in THIS
        interval — up to one lookahead of skew vs the CPU tracker's
        exact per-tick attribution. Totals always agree.

        One aggregate ``[supervise-heartbeat]`` line rides along with
        the wall-clock pkts/s since the previous heartbeat and the
        cumulative retry/replan counts, so a stalling or thrashing
        run is visible from the log stream alone."""
        from shadow_tpu import simtime
        from shadow_tpu.device.supervise import heartbeat_rates
        from shadow_tpu.host.tracker import Tracker

        if self.hb_monitor is not None:
            self.hb_monitor.beat()
        n_exec = np.asarray(state["n_exec"])
        n_sent = np.asarray(state["n_sent"])
        n_drop = np.asarray(state["n_drop"])
        for h in self.sim.hosts:
            i = h.host_id
            if h.tracker is None:
                h.tracker = Tracker(
                    h.name, self.sim.cfg.general.heartbeat_interval)
            h.tracker.set_events_total(int(n_exec[i]))
            h.packets_sent = int(n_sent[i])
            h.packets_dropped = int(n_drop[i])
            h.tracker.heartbeat(now, h)
        H = len(self.sim.hosts)
        sent_total = int(n_sent[:H].sum())
        self._hb_mark, (rate,) = heartbeat_rates(self._hb_mark,
                                                 [sent_total])
        # live device memory, when the backend exposes allocator
        # stats (TPU/GPU); "n/a" on CPU — an approaching OOM is
        # visible from the log stream alone
        from shadow_tpu.device import capacity as capmod
        mem = self.engine.device_memory_stats()
        mem_s = (f"{capmod.fmt_bytes(mem[0])}/"
                 f"{capmod.fmt_bytes(mem[1])}"
                 if mem is not None else "n/a")
        log.info("[supervise-heartbeat] t=%s events=%d sent=%d "
                 "pkts/s=%s retries=%d replans=%d reshards=%d "
                 "mem=%s",
                 simtime.format_time(now), int(n_exec[:H].sum()),
                 sent_total, rate, self.retries, self.replans,
                 self.reshards, mem_s)

    def run(self, stop: int) -> SimStats:
        import time as _time

        from shadow_tpu.device import capacity, supervise

        xp = self.sim.cfg.experimental
        tracer = self.tracer or obstrace.current()
        self.replans = 0
        self.retries = 0
        self.reshards = 0
        self.degrades = 0
        self._hb_mark = None
        if xp.capacity_plan == "static":
            # a re-used runner must not merge this run's measurements
            # into a stale record from an earlier run (the merge
            # branch below is the with-a-plan-active path, and it
            # WRITES artifacts/OCC_*.json)
            self.occ_record = None
        if xp.checkpoint_save:
            from shadow_tpu.device import checkpoint
            checkpoint.probe_writable(xp.checkpoint_save)
        load_path = ""
        if xp.checkpoint_load:
            # rotation-aware resolution (a supervised run's base path
            # resolves to its newest readable rotation entry), then
            # pre-validate the resume parameters from the npz meta
            # alone — fail in milliseconds, not after the capacity
            # warm-up spends minutes compiling
            from shadow_tpu.device import checkpoint
            load_path = supervise.resolve_checkpoint(
                xp.checkpoint_load)
            checkpoint.prevalidate_resume(
                load_path, stop,
                save_path=xp.checkpoint_save,
                save_time=xp.checkpoint_save_time)
            # a post-shrink checkpoint stamps the shrunken geometry:
            # adopt it (rebuild the mesh + engine to match) BEFORE
            # planning/loading, so the resume lands on the saved
            # padded width instead of a loud layout mismatch
            self._adopt_checkpoint_geometry(load_path)
        # preflight admission (capacity.py): the modeled footprint —
        # two state copies, exchange scratch, world tables — against
        # the per-device budget, BEFORE any compile (the first
        # compile happens lazily at the first dispatch, which the
        # capacity warm-up below would trigger). strict refuses
        # over-budget with a readable diagnostic; auto admits loudly,
        # and the runtime degradation ladder backstops what the
        # model cannot see.
        self.admission = capacity.admission_verdict(self.engine, xp)
        if xp.capacity_plan != "static" and not self._planned:
            with tracer.span("capacity.plan", "plan",
                             mode=xp.capacity_plan):
                self._plan_capacities(stop, load_path=load_path)
        if load_path:
            from shadow_tpu.device import checkpoint
            with tracer.span("checkpoint.load", "checkpoint",
                             path=load_path):
                state, t_start = checkpoint.load_state(
                    self.engine, self.sim.starts, load_path,
                    final_stop=stop)
            if t_start >= stop:
                raise ValueError(
                    f"checkpoint_load: saved state pauses at "
                    f"{t_start} ns, at/after stop_time {stop} ns — "
                    f"nothing to resume")
            log.info("resumed checkpoint %s at t=%d ns",
                     load_path, t_start)
        else:
            state = self.engine.init_state(self.sim.starts)
            t_start = 0
        # with checkpoint_save, the run PAUSES at checkpoint_save_time
        # (0 = at stop_time) and writes the state there; window
        # clamping stays on the global stop either way, so the
        # paused+resumed pair bit-matches the uninterrupted run
        pause = stop
        if xp.checkpoint_save:
            if xp.checkpoint_save_time:
                pause = min(stop, xp.checkpoint_save_time)
            if pause <= t_start:
                raise ValueError(
                    f"checkpoint_save_time {pause} ns is not after "
                    f"the run's start time {t_start} ns")
        # supervision (device/supervise.py): the rotating checkpoint
        # writer and the SIGTERM/SIGINT drain guard — installed when
        # a checkpoint_save path exists AND the run has segment
        # boundaries for the drain to fire at (supervise.make_guard)
        self.checkpointer = None
        if xp.checkpoint_every:
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
        t0 = _time.perf_counter()
        # shared segmented advance (supervise.advance): heartbeat /
        # dispatch-segment / checkpoint boundaries, the overflow
        # re-plan loop, dispatch retry, audit validation, and the
        # preemption drain. A boundary that lands exactly on `pause`
        # still emits its heartbeat (an uninterrupted run would); only
        # the global end suppresses — resume restarts past the saved
        # t, so the pair emits each boundary exactly once
        with (self.guard if self.guard is not None
              else contextlib.nullcontext()):
            state, adv = supervise.advance(self, state, t_start,
                                           pause, stop)
        rounds, t_end = int(np.max(adv.rounds)), adv.t_end
        budget_hit, overflowed = adv.budget_hit, adv.overflowed
        self.retries = adv.retries
        if xp.checkpoint_save:
            if budget_hit or overflowed:
                # budget: the simulation stopped at an unknown
                # sim-time short of `pause`, so stamping `pause`
                # would let a resume skip unexecuted work. overflow:
                # the state has already dropped events, so a resumed
                # trace would silently replay the loss. Refuse both
                # loudly instead of leaving a valid-looking decoy.
                log.error("%s before the checkpoint boundary — NOT "
                          "saving %s",
                          "max_rounds exhausted" if budget_hit
                          else "capacity overflow (events lost)",
                          xp.checkpoint_save)
            elif adv.preempted:
                # the drain already saved the resume checkpoint
                # (adv.resume_path); a second, later-stamped save here
                # would shadow it with identical content
                pass
            else:
                from shadow_tpu.device import checkpoint
                with tracer.span("checkpoint.save", "checkpoint",
                                 sim_t0=t_end,
                                 path=xp.checkpoint_save):
                    checkpoint.save_state(
                        self.engine, state, xp.checkpoint_save, t_end,
                        final_stop=stop,
                        audit_meta=({"enabled": True, "violations": 0}
                                    if xp.state_audit else None))
                log.info("checkpoint saved at t=%d ns -> %s (run %s)",
                         t_end, xp.checkpoint_save,
                         "complete" if t_end >= stop else
                         "paused early; resume with checkpoint_load")
        # fetch ONLY the stats the controller needs — the [H,E] event
        # heaps are ~20 MB at the 10k rung (250 MB at tor_large); what
        # pulling them back would cost on the chip is not measured
        stat_keys = [k for k in state
                     if k not in ("ht", "hk", "hm", "hv", "hw")]
        with tracer.span("state.fetch", "host", sim_t0=t_end):
            final = jax.device_get({k: state[k] for k in stat_keys})
        wall = _time.perf_counter() - t0
        self.final_state = final
        H = len(self.sim.hosts)
        if "path_cnt" in final:
            # surface the device path histogram through the same API
            # the CPU engines populate (NetworkModel.path_packets)
            V = self.engine.n_vertices
            cnt = np.asarray(final["path_cnt"]).sum(0).reshape(V, V)
            nz = np.nonzero(cnt)
            self.sim.netmodel.record_paths(
                {(int(i), int(j)): int(cnt[i, j])
                 for i, j in zip(*nz)})
        n_exec_total = int(final["n_exec"][:H].sum())
        # perf-timer parity (USE_PERF_TIMERS round summaries): the
        # device program is one fused loop, so the breakdown is
        # per-run — rounds, wall, and throughput
        log.info("device perf: %d rounds in %.2fs wall "
                 "(%.0f rounds/s, %.0f events/s)", rounds,
                 wall, rounds / wall if wall > 0 else 0.0,
                 n_exec_total / wall if wall > 0 else 0.0)

        # occupancy record: measured high-water marks from the FULL
        # run alongside the capacities that held them; with a plan
        # active, merged into the planner's record and written to
        # artifacts/OCC_*.json for reuse (capacity_plan: <path>,
        # scripts/tune_10k.py sweep pruning)
        occ = capacity.measure(self.engine, state, source="run")
        if self.occ_record is not None:
            self.occ_record["final_measured"] = occ["measured"]
            self.occ_record["effective"] = occ["effective"]
            self.occ_record["replans"] = self.replans
            self.occ_record["applied"] = dict(self._capacity_overrides)
            if adv.preempted:
                # a preempted run's high-water marks cover only the
                # executed prefix — don't publish them as a workload
                # record the planner would size from
                log.info("occupancy record not written (run "
                         "preempted)")
            else:
                path = capacity.record_path(
                    self.engine,
                    directory=getattr(xp, "artifacts_dir", ""))
                try:
                    capacity.save_record(self.occ_record, path)
                    log.info("occupancy record -> %s", path)
                except OSError as e:
                    log.warning("could not write occupancy record "
                                "%s: %s", path, e)
        else:
            self.occ_record = occ

        stats = SimStats()
        stats.end_time = t_end
        stats.rounds = int(rounds)
        stats.occupancy = self.occ_record
        stats.strategy_plan = self.strategy_plan
        if self.aot_cache is not None:
            # loud hit/miss surface: the whole run's compile-cache
            # attribution (warm-up + planned + re-planned engines)
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
        # dispatch telemetry (supervise.advance): segments, sync
        # wall, advance wall — METRICS carries it and trace_report
        # prints the sync-wall line from it
        stats.pipeline = adv.pipeline or None
        stats.events_executed = n_exec_total
        stats.packets_sent = int(final["n_sent"][:H].sum())
        stats.packets_dropped = int(final["n_drop"][:H].sum())
        stats.packets_delivered = int(final["n_deliv"][:H].sum())
        overflow = int(final["overflow"][:H].sum())
        if overflow:
            stats.ok = False
            log.error("device engine overflow: %d events lost — raise "
                      "experimental.event_capacity/outbox_capacity, "
                      "or set capacity_plan: auto to size and retry "
                      "automatically", overflow)
        x_overflow = int(final["x_overflow"][:H].sum())
        if x_overflow:
            stats.ok = False
            log.error("exchange overflow: %d rows exceeded the per-"
                      "shard-pair capacity — raise experimental."
                      "exchange_capacity (or use exchange: all_gather "
                      "for hub-concentrated traffic, or "
                      "capacity_plan: auto)", x_overflow)

        # reflect per-host results back onto the Host objects — or,
        # for a columnar build, adopt them as plane columns: hosts
        # materialized later still read the real counters, and nothing
        # is materialized just to carry five ints
        plane = getattr(self.sim, "plane", None)
        if plane is not None:
            plane.adopt_final(final)
        else:
            for h in self.sim.hosts:
                i = h.host_id
                h.events_executed = int(final["n_exec"][i])
                h.packets_sent = int(final["n_sent"][i])
                h.packets_dropped = int(final["n_drop"][i])
                h.packets_delivered = int(final["n_deliv"][i])
                h.trace_checksum = int(final["chk"][i])
        return stats
