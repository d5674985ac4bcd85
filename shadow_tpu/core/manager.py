"""Manager: drives one machine's share of the simulation.

The round-loop owner, mirroring manager_run (src/main/core/manager.c:
615-649): given a time window [start, end) from the Controller, execute
every pending event below the barrier via the scheduler policy, then
report the earliest next event time for the Controller to open the next
window. Serial policies are drained centrally; threaded policies run
the round on their worker pool (each worker gets its own SimContext and
stats bucket, merged at finalize). Multi-manager distribution (stubbed
in the reference, controller.c:352-354) maps here to one Manager per
device-mesh slice.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Callable, Optional

from shadow_tpu import simtime
from shadow_tpu.core.event import (
    Event,
    KIND_BOOT,
    KIND_HOST_CRASH,
    KIND_HOST_RESTART,
    KIND_NIC_WAKE,
    KIND_PACKET,
    KIND_PACKET_READY,
    KIND_ROUTER_ARRIVAL,
    KIND_STOP,
    KIND_TCP_TIMER,
    KIND_TIMER,
)
from shadow_tpu.core.netmodel import NetworkModel
from shadow_tpu.core.scheduler.base import SchedulerPolicy
from shadow_tpu.core.worker import SimContext
from shadow_tpu.host.host import Host
from shadow_tpu.obs.trace import NullTracer
from shadow_tpu.utils import nprng
from shadow_tpu.utils.checksum import chk_mix
from shadow_tpu.utils.slog import get_logger, set_context, clear_context

log = get_logger("manager")


def resolve_host_ref(name_to_id: dict, groups: dict, name: str,
                     asker_id: int) -> int:
    """Hostname OR host-group reference -> host id. A `quantity: N`
    group named `g` expands to hosts g0..gN-1 (controller.py, which
    also records the explicit member list in BuiltSimulation.groups —
    no name-pattern guessing, so a group `web` never absorbs a
    sibling group `web2`). A bare group reference resolves to one
    member chosen deterministically by the asking host (asker_id
    modulo group size) so client fleets spread over server groups
    identically on the CPU and device engines."""
    hid = name_to_id.get(name)
    if hid is not None:
        return hid
    members = (groups or {}).get(name)
    if members:
        return members[asker_id % len(members)]
    raise KeyError(f"unknown host name {name!r}")


@dataclass
class SimStats:
    ok: bool = True
    end_time: int = 0
    events_executed: int = 0
    packets_sent: int = 0
    packets_delivered: int = 0
    packets_dropped: int = 0
    rounds: int = 0
    # device-engine occupancy telemetry (device/capacity.py record:
    # measured high-water marks + the capacities that held them);
    # None on CPU policies
    occupancy: Optional[dict] = None
    # capacity re-plan/retry cycles the run needed (0 = the plan held)
    replans: int = 0
    # supervised-run outcomes (device/supervise.py): transient device
    # dispatch retries the run absorbed; whether it was gracefully
    # preempted (SIGTERM/SIGINT drain — the run is INCOMPLETE and
    # resumable from resume_path, and the CLI exits EXIT_PREEMPTED)
    retries: int = 0
    preempted: bool = False
    resume_path: str = ""
    # mesh shrinks absorbed (failover: shrink, device/supervise.py):
    # the run lost device(s) mid-flight and continued on-device on
    # the surviving mesh — throughput degraded by the lost share,
    # results bit-identical
    reshards: int = 0
    # set when the tpu policy failed over to the hybrid backend
    # mid-run (the device checkpoint named here pins a device-side
    # resume; the hybrid results replayed from t=0)
    failover_checkpoint: str = ""
    # ensemble campaign record (shadow_tpu/ensemble/campaign.py):
    # per-replica results + aggregates; None outside ensemble runs.
    # The top-level counters above then hold CAMPAIGN totals (summed
    # over replicas)
    ensemble: Optional[dict] = None
    # AOT compile-cache attribution (device/aotcache.py report():
    # per-program hit/miss events + lower/compile/load walls); None
    # on CPU policies or with experimental.compile_cache: off
    compile_cache: Optional[dict] = None
    # flight-recorder summary (shadow_tpu/obs): per-phase wall
    # attribution (host_s/judge_s/dispatch_s/exchange_s/checkpoint_s/
    # retry_s/...), span counts, and the paths of any TRACE_*/
    # METRICS_* artifacts written. None with telemetry: off.
    telemetry: Optional[dict] = None
    # strategy-plan provenance (shadow_tpu/tune/plan.py adopt()):
    # which PLAN record steered this run's execution knobs, the
    # knobs actually applied, and the ones skipped (hand-set or
    # inapplicable). None when experimental.strategy_plan resolved
    # to nothing. Plans change wall time only, so provenance is what
    # keeps tuned and default runs honestly comparable.
    strategy_plan: Optional[dict] = None
    # segment dispatch telemetry (device/supervise.py advance): the
    # segments synced, the wall blocked in dispatch.sync, and the
    # advance loop's wall. None on CPU policies (no device dispatch
    # to report).
    pipeline: Optional[dict] = None
    # OOM degradation-ladder rungs engaged (device/supervise.py): a
    # deterministic RESOURCE_EXHAUSTED walked the ladder (replica
    # batching / dispatch segment) this many times —
    # each rung shrank the footprint and replayed bit-identically
    degrades: int = 0
    # preflight admission verdict (device/capacity.py
    # admission_verdict): mode, budget + source, modeled footprint,
    # action taken (admit/degrade/over/off/no-budget), and any
    # static overrides applied. None on CPU policies.
    admission: Optional[dict] = None
    # live device allocator stats at the end of the run, when the
    # backend exposes them (TPU/GPU memory_stats); -1 = unavailable
    # (CPU backends) — the heartbeat lines print "n/a" for the same
    # reason
    mem_bytes_in_use: int = -1
    mem_budget: int = -1
    # wall-clock heartbeat gaps that exceeded the configured
    # staleness threshold (experimental.heartbeat_stale_after x the
    # expected cadence; device/supervise.py HeartbeatMonitor). A
    # nonzero count means the run stalled between segment boundaries
    # — the campaign server's watchdog polls the same monitor live
    # to turn a wedged campaign into a supervised kill + requeue
    stale_heartbeats: int = 0

    def merge(self, other: "SimStats") -> None:
        self.events_executed += other.events_executed
        self.packets_sent += other.packets_sent
        self.packets_delivered += other.packets_delivered
        self.packets_dropped += other.packets_dropped

    def summary(self) -> str:
        return (f"{self.events_executed} events, "
                f"{self.packets_sent} packets sent "
                f"({self.packets_delivered} delivered, "
                f"{self.packets_dropped} dropped), "
                f"{self.rounds} rounds")


@dataclass
class NetOptions:
    """Per-host network-stack knobs plumbed from the config."""
    qdisc: str = "fifo"
    router_queue: str = "codel"
    router_static_capacity: int = 1024
    bootstrap_end: int = 0
    tcp_congestion: str = "reno"
    # defaults live in host/tcp.py (DEFAULT_RECV_WINDOW/SEND_BUFFER)
    tcp_recv_buffer: int = 0
    tcp_send_buffer: int = 0
    tcp_recv_autotune: bool = True
    tcp_send_autotune: bool = True

    def __post_init__(self):
        from shadow_tpu.host.tcp import (
            DEFAULT_RECV_WINDOW,
            DEFAULT_SEND_BUFFER,
        )
        self.tcp_recv_buffer = self.tcp_recv_buffer \
            or DEFAULT_RECV_WINDOW
        self.tcp_send_buffer = self.tcp_send_buffer \
            or DEFAULT_SEND_BUFFER


@dataclass
class Manager:
    hosts: list[Host]
    policy: SchedulerPolicy
    netmodel: NetworkModel
    seed: int
    stats: SimStats = field(default_factory=SimStats)
    trace: Optional[list] = None    # (time, dst, src, kind) if recording
    on_event_hook: Optional[Callable] = None
    net_opts: NetOptions = field(default_factory=NetOptions)
    groups: Optional[dict] = None   # group name -> [host ids]
    # hybrid mode: when set, packet judgments (drop roll + latency) are
    # deferred per round and computed on the device in one batch
    # (device/judge.py); None = judge synchronously on CPU
    net_judge: Optional[object] = None
    # flight recorder (shadow_tpu/obs): attached by the Controller;
    # directly-constructed Managers (tests) get the inert NullTracer,
    # so the flush path needs no None guards. Judge flushes record
    # spans here, and the round watchdog embeds the recent-span ring
    # in its stall dump.
    tracer: object = field(default_factory=NullTracer)

    def __post_init__(self):
        from shadow_tpu.host.netstack import HostNetStack

        self.rng_key = nprng.seed_key(self.seed)
        self._name_to_id = {h.name: h.host_id for h in self.hosts}
        # out-of-band TCP payload streams for managed processes,
        # keyed (src_host, src_port, dst_host, dst_port); the lock
        # covers create-vs-teardown races under threaded policies
        # (host-crash teardown runs on the crashed host's worker
        # while peers may be resolving channels concurrently)
        self._streams: dict[tuple, object] = {}
        self._streams_lock = threading.Lock()
        self._barrier = simtime.SIMTIME_INVALID
        self._trace_lock = threading.Lock()
        self._worker_stats: list[SimStats] = []
        # egress packets awaiting the batched device judgment:
        # (now, src_host, dst_host, pkt_seq, ev_seq, kind, data)
        self._pending: list[tuple] = []
        self._pending_lock = threading.Lock()
        self._last_hb_flush = simtime.SIMTIME_INVALID
        self._hb_interval = 0        # set by schedule_heartbeats
        self._hb_stop = 0
        self._ctx = SimContext(self, self.stats)
        no = self.net_opts
        for h in self.hosts:
            self.policy.add_host(h.host_id)
            h.net = HostNetStack(
                h, self, qdisc=no.qdisc, router_queue=no.router_queue,
                router_static_capacity=no.router_static_capacity,
                bootstrap_end=no.bootstrap_end,
                tcp_congestion=no.tcp_congestion,
                tcp_recv_buffer=no.tcp_recv_buffer,
                tcp_send_buffer=no.tcp_send_buffer,
                tcp_recv_autotune=no.tcp_recv_autotune,
                tcp_send_autotune=no.tcp_send_autotune)

    def resolve(self, name: str) -> int:
        if name not in self._name_to_id:
            raise KeyError(f"unknown host name {name!r}")
        return self._name_to_id[name]

    def resolve_ref(self, name: str, asker_id: int) -> int:
        return resolve_host_ref(self._name_to_id, self.groups, name,
                                asker_id)

    def stream_channel(self, key: tuple):
        """Byte channel for one TCP direction (host/descriptors.py)."""
        with self._streams_lock:
            ch = self._streams.get(key)
            if ch is None:
                from shadow_tpu.host.descriptors import StreamChannel
                ch = self._streams[key] = StreamChannel()
            return ch

    def push_event(self, ev: Event) -> None:
        self.policy.push(ev, self._barrier)

    def make_worker_state(self) -> tuple[SimContext, SimStats]:
        """Per-worker execution state for threaded policies."""
        stats = SimStats()
        self._worker_stats.append(stats)
        return SimContext(self, stats), stats

    def schedule_host_faults(self, host_faults: list[tuple]) -> None:
        """host_faults: [(time, host_id, kind)] from
        faults.resolve_host_faults — crash/restart events enter the
        queue before the first round, consuming event seqs exactly
        like boot/stop events (identically under every CPU policy, so
        traces stay policy-invariant)."""
        for t, host_id, kind in host_faults:
            h = self.hosts[host_id]
            self.push_event(Event(
                time=t, dst_host=host_id, src_host=host_id,
                seq=h.next_event_seq(),
                kind=(KIND_HOST_CRASH if kind == "host_crash"
                      else KIND_HOST_RESTART)))

    def _host_crash(self, ctx, host) -> None:
        """KIND_HOST_CRASH: the machine dies mid-run. Managed (real)
        processes are killed for real; model apps simply stop
        executing (their objects are replaced at restart). Pending
        events for the host are quarantined as they surface
        (execute_event), and the shared TCP payload channels the host
        participated in are dropped so surviving peers observe resets/
        timeouts through their own retry logic instead of reading a
        ghost's stream."""
        log.info("host %s crashed (fault injection)", host.name)
        for app in host.apps:
            if hasattr(app, "on_sim_end"):
                # ManagedProcess/PtraceProcess: kill the OS process
                app.on_sim_end(ctx)
        host.crashed = True
        # under threaded policies a peer draining in the same window
        # may interleave with this teardown by wall clock; the lock
        # makes the dict operations safe, and per-connection readers
        # tolerate a vanished channel as a reset (managed-TCP fault
        # scenarios wanting strict cross-run byte-level determinism
        # should run a serial policy, like threaded heartbeat
        # attribution already does)
        with self._streams_lock:
            for key in [k for k in self._streams
                        if k[0] == host.host_id
                        or k[2] == host.host_id]:
                del self._streams[key]
        # the pcap writer deliberately survives the crash: the capture
        # up to the outage is exactly the artifact a fault-injection
        # user inspects, and the restart re-attaches it (a fresh
        # HostNetStack would truncate the file)

    def _host_restart(self, ctx, host) -> None:
        """KIND_HOST_RESTART: respawn the configured processes from
        the factories captured at build time, on a FRESH network
        stack/CPU model — a rebooted machine keeps nothing but its
        disk (the per-host data dir). Boot events are pushed at the
        restart time (self-destined, so no causality bump) and the
        processes' original stop_times still apply when still in the
        future."""
        from shadow_tpu.core.event import KIND_TASK
        from shadow_tpu.host.cpu import Cpu
        from shadow_tpu.host.netstack import HostNetStack

        log.info("host %s restarting (fault injection; %d events "
                 "quarantined while down)", host.name,
                 host.events_quarantined)
        host.crashed = False
        old_pcap = host.net.pcap if host.net is not None else None
        no = self.net_opts
        pcap_dir, host.pcap_directory = host.pcap_directory, None
        try:
            host.net = HostNetStack(
                host, self, qdisc=no.qdisc,
                router_queue=no.router_queue,
                router_static_capacity=no.router_static_capacity,
                bootstrap_end=no.bootstrap_end,
                tcp_congestion=no.tcp_congestion,
                tcp_recv_buffer=no.tcp_recv_buffer,
                tcp_send_buffer=no.tcp_send_buffer,
                tcp_recv_autotune=no.tcp_recv_autotune,
                tcp_send_autotune=no.tcp_send_autotune)
        finally:
            host.pcap_directory = pcap_dir
        # re-attach the surviving capture (see _host_crash): the
        # constructor would have truncated the pre-crash file
        host.net.pcap = old_pcap
        if host.cpu is not None:
            host.cpu = Cpu()
        if host.model_nic is not None:
            host.model_nic = type(host.model_nic)(host.bw_up_bits,
                                                  host.bw_down_bits)
        # the heartbeat chain is self-rescheduling, so a tick that
        # surfaced during the outage was quarantined and the chain is
        # dead — re-seed it at the next interval boundary (the outage
        # shows as a gap, then ticks resume). ONLY dead chains: a
        # short outage whose next tick never surfaced while down
        # still has its live chain queued, and a second seed would
        # double every subsequent tick.
        if self._hb_interval and getattr(host, "_hb_dead", False):
            host._hb_dead = False
            nxt = (ctx.now // self._hb_interval + 1) * \
                self._hb_interval
            if nxt < self._hb_stop:
                self.push_event(Event(
                    time=nxt, dst_host=host.host_id,
                    src_host=host.host_id,
                    seq=host.next_event_seq(), kind=KIND_TASK,
                    task=self._make_hb_task(host)))
        if not host.respawn:
            log.warning("host %s restarted with no respawn factories "
                        "(nothing boots)", host.name)
            return
        host.apps = []
        host.app = None
        for proc_idx, (factory, start_time, stop_time, is_model) in \
                enumerate(host.respawn):
            if stop_time is not None and 0 <= stop_time <= ctx.now:
                # the process's configured life ended while the host
                # was down — it stays dead (a None placeholder keeps
                # later processes' BOOT/STOP indices aligned)
                host.apps.append(None)
                continue
            app = factory()
            host.apps.append(app)
            # mirror build()'s primary-app rule: the model app (at
            # most one) is always the packet/timer dispatch target
            if is_model or host.app is None:
                host.app = app
            # boot NOW only if the original start has passed; a
            # future start_time still has its original KIND_BOOT
            # event queued (it was never quarantined), and the
            # original KIND_STOP likewise fires on this new app —
            # pushing duplicates here would double-boot/-stop
            if start_time <= ctx.now:
                self.push_event(Event(
                    time=ctx.now, dst_host=host.host_id,
                    src_host=host.host_id,
                    seq=host.next_event_seq(),
                    kind=KIND_BOOT, data=(proc_idx,)))

    def boot_hosts(self, start_times: list[tuple]) -> None:
        """start_times: (host_id, start_time, stop_time|-1[, proc_idx])
        per process. Boot/stop events enter the queue before the first
        round (worker_bootHosts analogue, worker.c:581-591); the
        process index rides in the event data so multi-process hosts
        boot each process independently."""
        for entry in start_times:
            host_id, t_start, t_stop = entry[0], entry[1], entry[2]
            idx = entry[3] if len(entry) > 3 else 0
            h = self.hosts[host_id]
            self.push_event(Event(time=t_start, dst_host=host_id,
                                  src_host=host_id,
                                  seq=h.next_event_seq(),
                                  kind=KIND_BOOT, data=(idx,)))
            if t_stop is not None and t_stop >= 0:
                self.push_event(Event(time=t_stop, dst_host=host_id,
                                      src_host=host_id,
                                      seq=h.next_event_seq(),
                                      kind=KIND_STOP, data=(idx,)))

    def _apply_verdict(self, rec: tuple, delivered: bool,
                       deliver_time: int) -> None:
        """Single place where a judged packet becomes stats + an event
        (or a drop) — used by both the synchronous fallback and the
        batched device path, so their bookkeeping cannot diverge."""
        from shadow_tpu.routing.packet import PacketStatus

        _, src_h, dst_h, _, ev_seq, kind, data = rec
        host = self.hosts[src_h]
        host.packets_sent += 1
        pkt = data[0] if kind == KIND_ROUTER_ARRIVAL else None
        if not delivered:
            host.packets_dropped += 1
            if pkt is not None:
                pkt.add_status(PacketStatus.INET_DROPPED)
            return
        if pkt is not None:
            pkt.add_status(PacketStatus.INET_SENT)
        self.push_event(Event(time=int(deliver_time), dst_host=dst_h,
                              src_host=src_h, seq=ev_seq, kind=kind,
                              data=data))

    def defer_judgment(self, now: int, host, dst_host: int, pkt_seq: int,
                       ev_seq: int, kind: int, data: tuple) -> None:
        """Hybrid mode: queue one egress packet for the end-of-round
        device batch. The event seq was already consumed by the caller
        so later seq allocations are unaffected by the deferral.

        Self-destined packets are judged synchronously instead: they
        are exempt from the causality bump (SchedulerPolicy
        .apply_barrier), so one below the barrier must enter the queue
        NOW to run this round in per-host time order (possible when a
        runahead override exceeds the self-path latency). The verdict
        is a pure function of (seed, src, pkt_seq) either way, so sync
        and batched rolls agree bit-for-bit."""
        rec = (now, host.host_id, dst_host, pkt_seq, ev_seq, kind, data)
        if dst_host == host.host_id:
            v = self.netmodel.judge(now, host.host_id, dst_host, pkt_seq)
            self._apply_verdict(rec, v.delivered, v.deliver_time)
            return
        with self._pending_lock:
            self._pending.append(rec)

    def flush_judgments(self) -> None:
        """Judge every pending cross-host packet in one device batch
        and push the delivery events. Verdicts are bit-identical to the
        synchronous CPU path (same threefry chain, same latency
        matrices), so hybrid traces equal pure-CPU traces."""
        from collections import Counter

        import numpy as np

        with self._pending_lock:
            pending, self._pending = self._pending, []
        if not pending:
            return
        j = self.net_judge
        with self.tracer.span("judge.flush", "judge",
                              sim_t0=pending[0][0],
                              sim_t1=self._barrier,
                              pkts=len(pending)) as sp:
            if len(pending) < getattr(j, "min_batch", 0):
                # adaptive: a round this small never amortizes the
                # device dispatch — the synchronous CPU roll is
                # bit-identical (same threefry chain), so only the
                # wall clock changes
                for rec in pending:
                    v = self.netmodel.judge(rec[0], rec[1], rec[2],
                                            rec[3])
                    self._apply_verdict(rec, v.delivered,
                                        v.deliver_time)
                j.cpu_batches += 1
                j.cpu_packets += len(pending)
                nm = self.netmodel
                nm.record_paths(Counter(
                    (int(nm.host_vertex[r[1]]),
                     int(nm.host_vertex[r[2]])) for r in pending))
                sp.add(where="cpu")
                return
            now = np.fromiter((p[0] for p in pending), np.int64,
                              len(pending))
            src = np.fromiter((p[1] for p in pending), np.int32,
                              len(pending))
            dst = np.fromiter((p[2] for p in pending), np.int32,
                              len(pending))
            seq = np.fromiter((p[3] for p in pending), np.int32,
                              len(pending))
            delivered, deliver_time = self.net_judge.judge_batch(
                now, src, dst, seq)
            nm = self.netmodel
            nm.record_paths(Counter(
                (int(nm.host_vertex[r[1]]), int(nm.host_vertex[r[2]]))
                for r in pending))
            for i, rec in enumerate(pending):
                self._apply_verdict(rec, bool(delivered[i]),
                                    deliver_time[i])
            sp.add(where="device")

    def run_window(self, window_start: int, window_end: int) -> int:
        """Execute all events in [window_start, window_end); return the
        earliest remaining event time (scheduler_awaitNextRound).

        In hybrid mode the round's cross-host egress packets are judged
        in one device batch after the drain; every verdict lands at or
        after the barrier (cross-host events get the causality bump,
        self-destined ones were judged synchronously), so one flush per
        round suffices."""
        self._barrier = window_end
        if hasattr(self.policy, "run_parallel"):
            self.policy.run_parallel(self, window_end)
        else:
            while (ev := self.policy.pop(window_end)) is not None:
                self.execute_event(ev, self._ctx, self.stats)
        if self.net_judge is not None:
            self.flush_judgments()
        self.stats.rounds += 1
        return self.policy.next_event_time()

    def finalize(self) -> SimStats:
        for ws in self._worker_stats:
            self.stats.merge(ws)
        self._worker_stats.clear()
        # packet totals come from the per-host counters, which both the
        # raw-send path (worker.py) and the socket path (netstack.py)
        # maintain — the single source of truth
        self.stats.packets_sent = sum(h.packets_sent for h in self.hosts)
        self.stats.packets_dropped = sum(h.packets_dropped
                                         for h in self.hosts)
        self.stats.packets_delivered = sum(h.packets_delivered
                                           for h in self.hosts)
        if hasattr(self.policy, "shutdown"):
            self.policy.shutdown()
        for h in self.hosts:
            if h.net is not None and h.net.pcap is not None:
                h.net.pcap.close()
        return self.stats

    def _make_hb_task(self, host):
        """One host's self-rescheduling heartbeat task (shared by the
        initial seeding and the host_restart re-seed)."""
        from shadow_tpu.core.event import KIND_TASK

        interval, stop = self._hb_interval, self._hb_stop

        def task(ctx, ev):
            # hybrid: settle this round's pending drop verdicts so
            # the CSV counters match the pure-CPU oracle's interval
            # attribution (drop rolls are pure functions of
            # (seed, src, pkt_seq) — flushing mid-round is safe).
            # Serial policies only: under threaded policies a flush
            # from a worker would race other workers' counter
            # updates, and threaded heartbeat attribution is
            # unordered in pure-CPU mode anyway. One flush per
            # heartbeat tick, not per host.
            if (self.net_judge is not None
                    and not hasattr(self.policy, "run_parallel")
                    and self._last_hb_flush != ev.time):
                self._last_hb_flush = ev.time
                self.flush_judgments()
            host.tracker.heartbeat(ev.time, host)
            nxt = ev.time + interval
            if nxt < stop:
                self.push_event(Event(
                    time=nxt, dst_host=host.host_id,
                    src_host=host.host_id,
                    seq=host.next_event_seq(), kind=KIND_TASK,
                    task=task))
        # lets the quarantine path recognize a dead heartbeat chain
        # (the restart re-seed must not duplicate a chain whose next
        # tick survived the outage)
        task._hb_chain = True
        return task

    def schedule_heartbeats(self, interval: int, stop: int) -> None:
        """Per-host heartbeat chain (tracker_heartbeat, tracker.c:565)."""
        from shadow_tpu.core.event import KIND_TASK
        from shadow_tpu.host.tracker import Tracker

        self._hb_interval, self._hb_stop = interval, stop
        for h in self.hosts:
            h.tracker = Tracker(h.name, interval)
            self.push_event(Event(time=interval, dst_host=h.host_id,
                                  src_host=h.host_id,
                                  seq=h.next_event_seq(),
                                  kind=KIND_TASK,
                                  task=self._make_hb_task(h)))

    def dump_state(self) -> str:
        """Per-host / per-process diagnostic snapshot — what the round
        watchdog prints when a round stalls: executed/quarantined
        event counts, crash state, app types, and for managed (real)
        processes each thread's parked (blocked) syscall."""
        lines = []
        for h in self.hosts:
            apps = ",".join(type(a).__name__ for a in h.apps) or "-"
            lines.append(
                f"  host {h.name} (id {h.host_id}): "
                f"events={h.events_executed} "
                f"quarantined={h.events_quarantined} "
                f"crashed={h.crashed} apps=[{apps}]")
            for app in h.apps:
                threads = getattr(app, "threads", None)
                if not isinstance(threads, dict):
                    continue
                for vtid, th in threads.items():
                    parked = getattr(th, "parked", None)
                    if parked is None:
                        continue
                    from shadow_tpu.host.syscalls import NR_NAME
                    nr = parked[0] if parked else -1
                    lines.append(
                        f"    vtid {vtid}: blocked in syscall "
                        f"{NR_NAME.get(nr, nr)}")
        return "\n".join(lines)

    @staticmethod
    def _proc_of(host, ev: Event):
        """BOOT/STOP dispatch target: the process the event's index
        names (multi-process hosts), defaulting to the primary app."""
        if ev.data and host.apps:
            idx = ev.data[0]
            if 0 <= idx < len(host.apps):
                return host.apps[idx]
        return host.app

    def execute_event(self, ev: Event, ctx: SimContext,
                      stats: SimStats) -> None:
        """event_execute analogue (core/work/event.c:64): set the clock
        and host context, apply the CPU-delay model, dispatch by kind."""
        host = self.hosts[ev.dst_host]
        if host.crashed and ev.kind != KIND_HOST_RESTART:
            # quarantine: a crashed host executes nothing — events
            # surfacing for it while down are counted (packet kinds
            # also count as drops: the network lost them at the dead
            # NIC) and discarded. Per-host event order makes this
            # deterministic under every policy: the crash event at an
            # earlier (time, src, seq) key always runs first.
            host.events_quarantined += 1
            if ev.kind in (KIND_PACKET, KIND_PACKET_READY,
                           KIND_ROUTER_ARRIVAL):
                host.packets_dropped += ev.npkts
            if ev.task is not None and \
                    getattr(ev.task, "_hb_chain", False):
                # the self-rescheduling heartbeat tick died here —
                # _host_restart re-seeds exactly the dead chains
                host._hb_dead = True
            return
        if host.cpu is not None:
            host.cpu.update_time(ev.time)
            if host.cpu.is_blocked(ev.time):
                # defer delivery while the virtual CPU is busy
                # (event.c:70-87). Deferral times are forced strictly
                # increasing per host: precision rounding could
                # otherwise re-order two deferred events whose original
                # order the (time,dst,src,seq) key had fixed.
                new_time = ev.time + host.cpu.delay_until_ready(ev.time)
                floor = getattr(host, "_cpu_defer_floor", -1)
                new_time = max(new_time, floor + 1)
                host._cpu_defer_floor = new_time
                ev.time = new_time
                self.policy.push(ev, self._barrier)
                return
        ctx.now = ev.time
        ctx.host = host
        set_context(ev.time, host.name, host.host_id)
        try:
            host.events_executed += 1
            host.trace_checksum = chk_mix(host.trace_checksum, ev.time,
                                          ev.src_host, ev.kind, ev.seq)
            if host.tracker is not None:
                host.tracker.on_event()
            stats.events_executed += 1
            if self.trace is not None:
                with self._trace_lock:
                    self.trace.append((ev.time, ev.dst_host, ev.src_host,
                                       ev.kind))
            if self.on_event_hook is not None:
                self.on_event_hook(ev)
            app = host.app
            if ev.task is not None:
                ev.execute(ctx)
            elif ev.kind in (KIND_ROUTER_ARRIVAL, KIND_NIC_WAKE,
                             KIND_TCP_TIMER):
                host.net.handle_event(ev, ev.time, ctx)
            elif ev.kind == KIND_PACKET:
                nic = host.model_nic
                if nic is not None:
                    # model-NIC RX stage: CoDel may drop; otherwise the
                    # payload re-fires as KIND_PACKET_READY after the
                    # download-bandwidth serialization. Pushed without
                    # the causality bump: it is this host's own future
                    # (the device engine inserts into the local heap
                    # the same way).
                    size = ev.data[0] if ev.data else 0
                    deliver = nic.rx_deliver(ev.time, size)
                    if deliver < 0:
                        host.packets_dropped += 1
                    else:
                        self.policy.push(
                            Event(time=deliver, dst_host=ev.dst_host,
                                  src_host=ev.src_host, seq=ev.seq,
                                  kind=KIND_PACKET_READY, data=ev.data,
                                  npkts=ev.npkts),
                            simtime.SIMTIME_INVALID)
                else:
                    host.packets_delivered += ev.npkts
                    if app is not None:
                        size = ev.data[0] if ev.data else 0
                        app.on_packet(ctx, ev.src_host, size,
                                      ev.data[1:])
            elif ev.kind == KIND_PACKET_READY:
                host.packets_delivered += ev.npkts
                if app is not None:
                    size = ev.data[0] if ev.data else 0
                    app.on_packet(ctx, ev.src_host, size, ev.data[1:])
            elif ev.kind == KIND_TIMER:
                if app is not None:
                    app.on_timer(ctx, ev.data)
            elif ev.kind == KIND_BOOT:
                target = self._proc_of(host, ev)
                if target is not None:
                    target.boot(ctx)
            elif ev.kind == KIND_STOP:
                target = self._proc_of(host, ev)
                if target is not None:
                    target.on_stop(ctx)
            elif ev.kind == KIND_HOST_CRASH:
                self._host_crash(ctx, host)
            elif ev.kind == KIND_HOST_RESTART:
                self._host_restart(ctx, host)
        finally:
            clear_context()


class RoundWatchdog:
    """Wall-clock stall detector for the scheduling round loop
    (experimental.round_watchdog, seconds; 0 = off).

    A wedged host-side call — a blocking open the emulation missed, a
    managed process spinning off-channel — used to hang the whole
    simulator forever with zero diagnostics. The watchdog samples a
    cheap progress signal (rounds + per-host executed-event counters)
    from a daemon thread; when NOTHING moves for `interval` wall
    seconds it dumps per-host/per-process state (Manager.dump_state:
    current blocked syscall, quarantine counts) plus the flight
    recorder's last completed spans (shadow_tpu/obs — what the run
    was DOING when it froze) and aborts the run with a diagnostic
    instead of hanging.

    `on_stall(dump)` is injectable for tests; the default logs the
    dump, marks stats not-ok, and interrupts the main thread.
    `dump_path` (experimental.round_watchdog_dump) additionally
    persists the dump to a file via the atomic tmp+rename helper —
    written BEFORE on_stall runs, so even a custom handler (or a
    truncated log) leaves the post-mortem on disk."""

    def __init__(self, manager: Manager, interval_s: float,
                 on_stall=None, dump_path: str = ""):
        if interval_s <= 0:
            raise ValueError("round_watchdog interval must be > 0")
        self._m = manager
        self.interval = float(interval_s)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.on_stall = on_stall or self._default_stall
        self.dump_path = dump_path
        self.fired = False

    def _progress(self) -> tuple:
        m = self._m
        return (m.stats.rounds,
                sum(h.events_executed for h in m.hosts),
                sum(h.events_quarantined for h in m.hosts))

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._run, name="round-watchdog", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)

    def _run(self) -> None:
        import time as _time

        poll = max(0.05, min(self.interval / 4.0, 1.0))
        last = self._progress()
        last_t = _time.monotonic()
        while not self._stop.wait(poll):
            cur = self._progress()
            if cur != last:
                last, last_t = cur, _time.monotonic()
                continue
            if _time.monotonic() - last_t >= self.interval:
                self.fired = True
                dump = self._m.dump_state()
                # the flight recorder's recent-span ring shows what
                # the run WAS doing (last dispatches, judge flushes,
                # checkpoints), not just where it stopped — embedded
                # in both the log dump and the on-disk post-mortem
                tracer = getattr(self._m, "tracer", None)
                recent = (tracer.format_recent()
                          if tracer is not None else "")
                if recent:
                    dump = f"{dump}\n{recent}"
                if self.dump_path:
                    try:
                        from shadow_tpu.utils.artifacts import \
                            atomic_write_text
                        atomic_write_text(
                            f"round watchdog stall dump (no progress "
                            f"for {self.interval:.0f}s wall)\n"
                            f"{dump}\n", self.dump_path)
                        log.info("watchdog stall dump -> %s",
                                 self.dump_path)
                    except OSError as e:
                        log.warning("could not write watchdog dump "
                                    "%s: %s", self.dump_path, e)
                self.on_stall(dump)
                return

    def _default_stall(self, dump: str) -> None:
        import signal

        log.error(
            "round watchdog: no scheduling progress for %.0fs wall — "
            "aborting with per-host state:\n%s", self.interval, dump)
        self._m.stats.ok = False
        # a REAL signal to the main thread: pthread_kill delivers
        # SIGINT so a main thread wedged inside a blocking C call
        # (the exact class this watchdog exists for) takes EINTR and
        # raises KeyboardInterrupt; interrupt_main() would only set a
        # flag checked between bytecodes, which such a thread never
        # reaches
        try:
            signal.pthread_kill(threading.main_thread().ident,
                                signal.SIGINT)
        except (ValueError, ProcessLookupError, RuntimeError, OSError):
            import _thread
            _thread.interrupt_main()
