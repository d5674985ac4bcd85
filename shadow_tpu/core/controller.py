"""Controller: global clock windows and simulation lifecycle.

Mirrors controller_run (src/main/core/controller.c:79-424): load the
topology, register hosts (attachment + per-host RNG + app processes),
compute the conservative lookahead window ("min time jump" = minimum
path latency, controller.c:125-153), then advance the simulation in
rounds [start, start + lookahead) until stop_time, asking the
Manager(s) for the earliest next event between rounds.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import Optional

import numpy as np

from shadow_tpu import simtime
from shadow_tpu.config.schema import ConfigOptions
from shadow_tpu.core.manager import Manager, SimStats
from shadow_tpu.core.netmodel import NetworkModel
from shadow_tpu.core.scheduler import make_policy
from shadow_tpu.host.host import Host
from shadow_tpu.models import is_model_path, make_app
from shadow_tpu.topology.attach import Attacher, HostAttachment
from shadow_tpu.topology.graph import Topology
from shadow_tpu.utils.rng import SeededRandom
from shadow_tpu.utils.slog import get_logger

log = get_logger("controller")


def load_topology(cfg: ConfigOptions) -> Topology:
    net = cfg.network
    rep = net.representation
    if net.graph_type == "1_gbit_switch":
        return Topology.builtin_1_gbit_switch(representation=rep)
    if net.graph_type == "gml":
        if net.graph_inline:
            return Topology.from_gml(net.graph_inline,
                                     net.use_shortest_path,
                                     representation=rep)
        if net.graph_file:
            with open(net.graph_file) as f:
                return Topology.from_gml(f.read(), net.use_shortest_path,
                                         representation=rep)
        raise ValueError("network.graph.type=gml needs file.path or inline")
    if net.graph_type == "star_clusters":
        from shadow_tpu.topology.generate import generate_star_clusters
        return generate_star_clusters(net.graph_params,
                                      net.use_shortest_path,
                                      representation=rep)
    raise ValueError(f"unknown graph type {net.graph_type!r}")


@dataclass
class BuiltSimulation:
    """Everything instantiated from a config, pre-run."""
    cfg: ConfigOptions
    topology: Topology
    hosts: list[Host]
    netmodel: NetworkModel
    starts: list[tuple[int, int, int]]   # (host_id, start, stop|-1)
    lookahead: int
    dns: object = None
    groups: dict = None                  # group name -> [host ids]
    runtime: object = None               # ManagedRuntime if real procs
    # fault injection (shadow_tpu/faults.py): the compiled link-fault
    # epoch table (None without link faults) and the validated
    # [(time, host_id, kind)] host crash/restart schedule
    fault_table: object = None
    host_faults: list = None
    # columnar builds only (host/plane.py): the HostPlane whose columns
    # DeviceRunner consumes directly; `hosts` is then a LazyHostList
    # view over it
    plane: object = None


# log one [build-heartbeat] line per this many hosts (only for builds
# big enough that silence reads as a hang)
_HEARTBEAT_MIN_HOSTS = 50_000


def _heartbeat(t_start: float, done: int, total: int) -> None:
    elapsed = time.monotonic() - t_start
    rate = done / elapsed if elapsed > 0 else 0.0
    eta = (total - done) / rate if rate > 0 else 0.0
    log.info("[build-heartbeat] %d/%d hosts in %.1fs "
             "(%.0f hosts/s, ETA %.1fs)", done, total, elapsed,
             rate, eta)


def build(cfg: ConfigOptions) -> BuiltSimulation:
    """Instantiate a config: columnar fast path (host/plane.py) for
    pure model-app device-policy runs, the per-host object loop for
    everything else. Both paths produce bit-identical simulations —
    the plane is a representation change, not a semantic one."""
    from shadow_tpu import faults as faultmod
    from shadow_tpu.host import plane as planemod
    from shadow_tpu.routing.dns import Dns

    topology = load_topology(cfg)
    # link faults compile into the epoch table HERE, at load time,
    # exactly like the base all-pairs matrices; host faults resolve
    # against the built host names further down
    link_events, host_events = faultmod.split_events(cfg.network.faults)
    fault_table = faultmod.compile_link_faults(topology, link_events)
    dns = Dns()
    reason = planemod.object_build_reason(cfg, topology)
    if reason is None:
        return _build_columnar(cfg, topology, dns, fault_table,
                               host_events)
    if cfg.ensemble is not None or \
            cfg.experimental.scheduler_policy == "tpu":
        # device policies WANT the fast path; a quiet fallback would
        # read as "columnar is slow" instead of "columnar was refused"
        log.warning("[host-plane] falling back to the object build: "
                    "%s", reason)
    return _build_objects(cfg, topology, dns, fault_table, host_events)


def _lookahead(cfg: ConfigOptions, netmodel: NetworkModel) -> int:
    # the lookahead window must be a static floor over every fault
    # epoch (netmodel.min_latency_ns is fault-aware) — all backends
    # consume this one value, so window sequences stay identical
    return (cfg.experimental.runahead
            if cfg.experimental.runahead is not None
            else netmodel.min_latency_ns)


def _build_columnar(cfg: ConfigOptions, topology: Topology, dns,
                    fault_table, host_events) -> BuiltSimulation:
    """O(groups) vectorized build: every per-host quantity is an array
    fill (strided arange attachment, broadcast bandwidths, one DNS
    block per group); Host objects materialize lazily off the plane."""
    from shadow_tpu import faults as faultmod
    from shadow_tpu.host import plane as planemod
    from shadow_tpu.models import make_app

    n_total = cfg.total_hosts()
    t_start = time.monotonic()
    records: list[planemod.PlaneGroup] = []
    groups: dict[str, range] = {}
    v_parts, d_parts, u_parts, ip_parts = [], [], [], []
    t0_parts, t1_parts = [], []
    base = 0
    for group in cfg.hosts:
        q = group.quantity
        if group.network_node_stride > 0:
            stride_base = topology.vertex_index_for_id(
                group.network_node_id)
            last = stride_base + (q - 1) * group.network_node_stride
            if last >= topology.n_vertices:
                raise ValueError(
                    f"hosts.{group.name}: network_node_stride walks "
                    f"past the topology (host {q - 1} "
                    f"would attach at vertex {last}, the graph has "
                    f"{topology.n_vertices})")
            v = stride_base + np.arange(q, dtype=np.int64) * \
                group.network_node_stride
        elif group.network_node_id is not None:
            v = np.full(q, topology.vertex_index_for_id(
                group.network_node_id), dtype=np.int64)
        else:
            # eligibility guarantees a 1-vertex graph here
            v = np.zeros(q, dtype=np.int64)
        d_parts.append(np.full(q, group.bandwidth_down, dtype=np.int64)
                       if group.bandwidth_down is not None
                       else topology.bw_down_bits[v].astype(np.int64))
        u_parts.append(np.full(q, group.bandwidth_up, dtype=np.int64)
                       if group.bandwidth_up is not None
                       else topology.bw_up_bits[v].astype(np.int64))
        v_parts.append(v)
        ip_parts.append(dns.register_block(base, group.name, q))
        proc = group.processes[0]
        stop = proc.stop_time if proc.stop_time is not None else -1
        records.append(planemod.PlaneGroup(
            name=group.name, base_id=base, count=q,
            pcap_directory=group.pcap_directory,
            path=proc.path, args=proc.args,
            start_time=proc.start_time, stop_time=stop,
            model=proc.path[len("model:"):],
            prototype=make_app(proc.path, proc.args, base, n_total)))
        groups[group.name] = range(base, base + q)
        t0_parts.append(np.full(q, proc.start_time, dtype=np.int64))
        t1_parts.append(np.full(q, stop, dtype=np.int64))
        base += q
        if n_total >= _HEARTBEAT_MIN_HOSTS:
            _heartbeat(t_start, base, n_total)

    def _cat(parts):
        return parts[0] if len(parts) == 1 else np.concatenate(parts)

    starts = planemod.StartColumns(_cat(t0_parts), _cat(t1_parts))
    plane = planemod.HostPlane(cfg, records, _cat(v_parts),
                               _cat(d_parts), _cat(u_parts),
                               _cat(ip_parts), starts)
    netmodel = NetworkModel(
        topology=topology,
        host_vertex=plane.vertex,
        seed=cfg.general.seed,
        bootstrap_end=cfg.general.bootstrap_end_time,
        faults=fault_table,
    )
    host_faults = faultmod.resolve_host_faults(host_events, plane.names)
    log.info("[host-plane] columnar build: %d hosts in %d groups, "
             "%.2fs", n_total, len(records),
             time.monotonic() - t_start)
    return BuiltSimulation(cfg=cfg, topology=topology,
                           hosts=planemod.LazyHostList(plane),
                           netmodel=netmodel, starts=starts,
                           lookahead=_lookahead(cfg, netmodel),
                           dns=dns, runtime=None, groups=groups,
                           fault_table=fault_table,
                           host_faults=host_faults, plane=plane)


def _build_objects(cfg: ConfigOptions, topology: Topology, dns,
                   fault_table, host_events) -> BuiltSimulation:
    from shadow_tpu import faults as faultmod
    from shadow_tpu.host.cpu import Cpu
    from shadow_tpu.routing.address import Address

    root_rng = SeededRandom(cfg.general.seed)
    attacher = Attacher(topology, root_rng.child("attach"))

    hosts: list[Host] = []
    starts: list[tuple[int, int, int]] = []
    groups: dict[str, list[int]] = {}
    runtime = None
    n_total = cfg.total_hosts()
    t_start = time.monotonic()
    beat_every = max(10_000, n_total // 20)
    for group in cfg.hosts:
        # network_node_stride: host i of the group attaches at vertex
        # index base + i*stride — resolved ONCE per group (the id
        # lookup is an O(V) scan; a million strided hosts must not
        # pay it a million times)
        stride_base = None
        if group.network_node_stride > 0:
            stride_base = topology.vertex_index_for_id(
                group.network_node_id)
            last = stride_base + \
                (group.quantity - 1) * group.network_node_stride
            if last >= topology.n_vertices:
                raise ValueError(
                    f"hosts.{group.name}: network_node_stride walks "
                    f"past the topology (host {group.quantity - 1} "
                    f"would attach at vertex {last}, the graph has "
                    f"{topology.n_vertices})")
        members = groups.setdefault(group.name, [])
        # bulk DNS for model-only groups: one vectorized block
        # allocation instead of `quantity` Address constructions and
        # 3x that many dict inserts (hint-less groups only — a
        # requested IP needs the scalar path's validity checks)
        block_ips = None
        if group.quantity > 1 and not group.ip_address_hint and \
                all(is_model_path(p.path) for p in group.processes):
            block_ips = dns.register_block(len(hosts), group.name,
                                           group.quantity)
        for i in range(group.quantity):
            name = group.name if group.quantity == 1 else f"{group.name}{i}"
            host_id = len(hosts)
            members.append(host_id)
            if stride_base is not None:
                v = stride_base + i * group.network_node_stride
                att = HostAttachment(
                    vertex=v,
                    bw_down_bits=(group.bandwidth_down
                                  if group.bandwidth_down is not None
                                  else int(topology.bw_down_bits[v])),
                    bw_up_bits=(group.bandwidth_up
                                if group.bandwidth_up is not None
                                else int(topology.bw_up_bits[v])))
            else:
                att = attacher.attach(
                    network_node_id=group.network_node_id,
                    ip_hint=group.ip_address_hint,
                    city_hint=group.city_code_hint,
                    country_hint=group.country_code_hint,
                    bw_down_override=group.bandwidth_down,
                    bw_up_override=group.bandwidth_up,
                )
            host = Host(host_id=host_id, name=name, vertex=att.vertex,
                        bw_down_bits=att.bw_down_bits,
                        bw_up_bits=att.bw_up_bits,
                        rng=root_rng.child(f"host:{name}"),
                        pcap_directory=group.pcap_directory)
            host.cpu = Cpu()
            if cfg.experimental.model_bandwidth:
                from shadow_tpu.host.model_nic import ModelNic
                host.model_nic = ModelNic(att.bw_up_bits,
                                          att.bw_down_bits)
            if block_ips is not None:
                host.address = Address(host_id=host_id, name=name,
                                       ip=int(block_ips[i]))
            else:
                host.address = dns.register(
                    host_id, name, requested_ip=group.ip_address_hint)
            host.ip = host.address.ip_str
            for proc in group.processes:
                for _ in range(proc.quantity):
                    app = None
                    factory = None   # respawn closure (host_restart)
                    if is_model_path(proc.path):
                        # packet/timer events dispatch to the host's
                        # single model app; real processes are driven
                        # by their syscalls instead, so any number of
                        # those can share the host
                        if any(not hasattr(a, "vpid")
                               for a in host.apps):
                            raise ValueError(
                                f"host {name}: at most one model app "
                                "per host (any number of real "
                                "processes)")
                        app = make_app(proc.path, proc.args,
                                       host_id, n_total)
                        factory = (lambda p=proc.path, a=proc.args,
                                   hid=host_id, n=n_total:
                                   make_app(p, a, hid, n))
                    else:
                        # real executable under syscall interposition
                        import shutil

                        from shadow_tpu.host.process import (
                            ManagedProcess,
                            ManagedRuntime,
                        )
                        if runtime is None:
                            runtime = ManagedRuntime(
                                dns, cfg.general.data_directory,
                                cfg.general.seed,
                                spin_max=cfg.experimental
                                .preload_spin_max)
                        path = proc.path
                        if "/" not in path:
                            path = shutil.which(path) or path
                        path = os.path.abspath(path)
                        if not os.path.exists(path):
                            raise ValueError(
                                f"process executable not found: "
                                f"{proc.path!r}")
                        from shadow_tpu.host.process import \
                            elf_is_static
                        use_ptrace = \
                            cfg.experimental.interpose_method == \
                            "ptrace"
                        if not use_ptrace and elf_is_static(path):
                            # LD_PRELOAD cannot enter a static binary;
                            # the ptrace backend interposes it fully
                            # (every syscall traps, vDSO patched)
                            log.info("%s is statically linked: using "
                                     "the ptrace backend (the preload "
                                     "shim cannot load)", path)
                            use_ptrace = True
                        if use_ptrace:
                            from shadow_tpu.host.ptrace import (
                                PtraceProcess,
                            )
                            app = PtraceProcess(
                                runtime, path, proc.args,
                                proc.environment)
                            factory = (lambda cls=PtraceProcess,
                                       rt=runtime, p=path,
                                       a=proc.args,
                                       e=proc.environment:
                                       cls(rt, p, a, e))
                        else:
                            app = ManagedProcess(
                                runtime, path, proc.args,
                                proc.environment)
                            factory = (lambda cls=ManagedProcess,
                                       rt=runtime, p=path,
                                       a=proc.args,
                                       e=proc.environment:
                                       cls(rt, p, a, e))
                    proc_idx = len(host.apps)
                    host.apps.append(app)
                    if host.respawn is None:
                        host.respawn = []
                    host.respawn.append(
                        (factory, proc.start_time,
                         proc.stop_time if proc.stop_time is not None
                         else -1,
                         is_model_path(proc.path)))
                    # the model app (at most one) is ALWAYS the
                    # packet/timer dispatch target, regardless of its
                    # position in the process list; otherwise the
                    # first process stands in
                    if is_model_path(proc.path) or host.app is None:
                        host.app = app
                    starts.append((host_id, proc.start_time,
                                   proc.stop_time
                                   if proc.stop_time is not None else -1,
                                   proc_idx))
            hosts.append(host)
            if n_total >= _HEARTBEAT_MIN_HOSTS and \
                    len(hosts) % beat_every == 0:
                _heartbeat(t_start, len(hosts), n_total)

    netmodel = NetworkModel(
        topology=topology,
        host_vertex=np.array([h.vertex for h in hosts], dtype=np.int64),
        seed=cfg.general.seed,
        bootstrap_end=cfg.general.bootstrap_end_time,
        faults=fault_table,
    )
    host_faults = faultmod.resolve_host_faults(
        host_events, {h.name: h.host_id for h in hosts})
    lookahead = _lookahead(cfg, netmodel)
    if runtime is not None:
        # managed processes resolve names against this file
        # (dns.c's /etc/hosts-style emission)
        os.makedirs(cfg.general.data_directory, exist_ok=True)
        dns.write_hosts_file(os.path.join(cfg.general.data_directory,
                                          "etc_hosts"))
    return BuiltSimulation(cfg=cfg, topology=topology, hosts=hosts,
                           netmodel=netmodel, starts=starts,
                           lookahead=lookahead, dns=dns, runtime=runtime,
                           groups=groups, fault_table=fault_table,
                           host_faults=host_faults)


class Controller:
    def __init__(self, cfg: ConfigOptions, trace: Optional[list] = None,
                 tracer=None):
        self.cfg = cfg
        # flight recorder (shadow_tpu/obs): ONE per run, attached to
        # whichever executor this config resolves to and published as
        # the module-global current() for call sites with no plumbing
        # path (aotcache.ensure, capacity record I/O).
        # A nested run (the hybrid failover rerun) receives its
        # parent's tracer instead, so the rerun's spans land in the
        # SAME trace under the parent's `failover` span — the parent
        # finalizes, the child must not. Resolved BEFORE build so the
        # boot wall lands in the trace's `plan` phase.
        from shadow_tpu.obs import trace as obstrace
        self._owns_tracer = tracer is None
        self.tracer = (tracer if tracer is not None
                       else obstrace.resolve_tracer(cfg,
                                                    cfg.total_hosts()))
        obstrace.set_current(self.tracer)
        with self.tracer.span("build", "plan",
                              n_hosts=cfg.total_hosts()):
            self.sim = build(cfg)
        policy_name = cfg.experimental.scheduler_policy
        self.runner = None
        self.manager = None
        net_judge = None
        if cfg.ensemble is not None:
            # R-replica campaign in one vmapped device program
            # (shadow_tpu/ensemble/). No hybrid fallback: CPU host
            # emulation cannot vmap, so a config whose apps lack a
            # device twin fails loudly rather than silently running
            # one replica.
            from shadow_tpu.device.runner import NoDeviceTwin
            from shadow_tpu.ensemble.campaign import EnsembleRunner
            try:
                self.runner = EnsembleRunner(self.sim, trace=trace)
                self.runner.tracer = self.tracer
                return
            except NoDeviceTwin as e:
                raise ValueError(
                    "ensemble: the config's apps have no fully-"
                    f"vectorized device twin ({e}) — campaigns "
                    "cannot fall back to hybrid CPU emulation; run "
                    "the replicas as separate processes instead"
                ) from e
        if policy_name == "tpu":
            from shadow_tpu.device.runner import DeviceRunner, NoDeviceTwin
            try:
                self.runner = DeviceRunner(self.sim, trace=trace)
                self.runner.tracer = self.tracer
                return
            except NoDeviceTwin as e:
                log.info("tpu policy -> hybrid: %s", e)
                if cfg.experimental.capacity_plan != "static":
                    # the schema rejects capacity_plan on CPU policies
                    # for exactly this silent-ignore hazard; the
                    # fallback must not hide it either
                    log.warning(
                        "capacity_plan: %s ignored — the hybrid "
                        "fallback's CPU host emulation has no static "
                        "capacities to plan",
                        cfg.experimental.capacity_plan)
                if cfg.experimental.chaos:
                    # the schema's fail-fast rule for fault schedules
                    # must survive the fallback too: a chaos drill
                    # that silently injects nothing would read as a
                    # green failover test that drilled nothing
                    log.warning(
                        "experimental.chaos ignored — the hybrid "
                        "fallback has no device dispatch/checkpoint/"
                        "cache seams to inject at; this run drills "
                        "NOTHING")
                if cfg.experimental.mesh_shards:
                    log.warning(
                        "experimental.mesh_shards=%d ignored — the "
                        "hybrid fallback's CPU host emulation has "
                        "no device mesh to pin",
                        cfg.experimental.mesh_shards)
                policy_name = "hybrid"
        self.strategy_plan = None
        if policy_name == "hybrid":
            # strategy-plan adoption for the hybrid path
            # (tune/plan.py): the judge batching knob is the plan
            # space's hybrid member, so hybrid runs need an adoption
            # path too. The plan identity is the device twin's
            # workload fingerprint — a config without one (the
            # NoDeviceTwin fallback's usual cause) has no plan to
            # match and skips with a log line. policy="hybrid" makes
            # the gates see the policy actually running, not the
            # config's pre-fallback `tpu`.
            if cfg.experimental.strategy_plan != "off":
                from shadow_tpu.device.runner import (
                    NoDeviceTwin,
                    device_twin,
                )
                from shadow_tpu.tune import plan as planmod
                try:
                    twin = device_twin(self.sim)
                    self.strategy_plan = planmod.adopt(
                        cfg, twin, len(self.sim.hosts),
                        policy="hybrid")
                except NoDeviceTwin as e:
                    log.info("strategy_plan: no device twin to "
                             "fingerprint this workload (%s) — no "
                             "plan adopted", e)
            # CPU host emulation + batched device network judgment
            # (worker.c:520-579's hot path on the accelerator)
            from shadow_tpu.device.judge import DeviceJudge
            net_judge = DeviceJudge(
                self.sim.topology,
                self.sim.netmodel.host_vertex,
                cfg.general.seed,
                bootstrap_end=cfg.general.bootstrap_end_time,
                min_batch=cfg.experimental.hybrid_judge_min_batch,
                fault_table=self.sim.fault_table)
            policy_name = cfg.experimental.hybrid_cpu_policy
        if self.sim.plane is not None:
            # a CPU-policy backend reached a columnar sim (the
            # NoDeviceTwin hybrid fallback): the Manager touches every
            # host per event, so lazy materialization buys nothing —
            # materialize the whole table once, up front
            log.info("[host-plane] CPU backend %r: materializing all "
                     "%d hosts", policy_name, len(self.sim.hosts))
            self.sim.hosts = list(self.sim.hosts)
        from shadow_tpu.core.manager import NetOptions
        self.manager = Manager(
            tracer=self.tracer,
            hosts=self.sim.hosts,
            policy=make_policy(policy_name,
                               n_workers=(cfg.experimental.workers
                                          or cfg.general.parallelism),
                               parallelism=cfg.general.parallelism,
                               pin_cpus=cfg.experimental
                               .use_cpu_pinning),
            netmodel=self.sim.netmodel,
            seed=cfg.general.seed,
            trace=trace,
            groups=self.sim.groups,
            net_judge=net_judge,
            net_opts=NetOptions(
                qdisc=cfg.experimental.interface_qdisc,
                router_queue=cfg.experimental.router_queue,
                router_static_capacity=cfg.experimental
                .router_static_capacity,
                bootstrap_end=cfg.general.bootstrap_end_time,
                tcp_congestion=cfg.experimental.tcp_congestion,
                tcp_recv_buffer=cfg.experimental.socket_recv_buffer,
                tcp_send_buffer=cfg.experimental.socket_send_buffer,
                tcp_recv_autotune=cfg.experimental
                .socket_recv_autotune,
                tcp_send_autotune=cfg.experimental
                .socket_send_autotune,
            ),
        )

    def _failover_run(self, exc) -> SimStats:
        """The failover ladder's hybrid rung (failover: hybrid, or
        shrink when no shrink was possible) — finish the run on the
        hybrid backend (CPU host emulation + device network judge)
        instead of aborting. CPU host state cannot be rebuilt from
        device arrays, so the hybrid run replays from t=0; the last
        validated device checkpoint stays on disk to pin a
        device-side resume once the accelerator returns. Determinism
        makes the replayed results bit-identical to what the device
        run would have produced. The rerun shares THIS run's flight
        recorder under a `failover` span, so the whole incident —
        device prefix, escalation, hybrid replay — reads off one
        timeline."""
        import copy

        if exc.checkpoint_path is None:
            # the ONE diagnostic for the persist failure: the
            # escalation could save no state at all, so the hybrid
            # rerun has no device-side resume point — previously this
            # path silently dropped the failover and re-raised
            log.error(
                "DEVICE FAILOVER: %s — no device checkpoint could be "
                "persisted (%s); re-running on the hybrid backend "
                "from t=0 with NO device-side resume point.", exc,
                exc.persist_error or "unknown persist error")
        else:
            log.error(
                "DEVICE FAILOVER: %s — re-running on the hybrid "
                "backend from t=0 (device state is not importable "
                "into CPU hosts; the prefix up to t=%d ns is "
                "replayed). The validated device checkpoint %s "
                "remains for a device-side resume.", exc,
                exc.sim_time, exc.checkpoint_path or "<none>")
        cfg2 = copy.deepcopy(self.cfg)
        xp = cfg2.experimental
        xp.scheduler_policy = "hybrid"
        # supervision/planning/chaos knobs are device-only; the schema
        # would reject them on a CPU policy, and the hybrid replay
        # must not try to checkpoint, re-plan, or re-inject
        xp.checkpoint_save = ""
        xp.checkpoint_save_time = 0
        xp.checkpoint_load = ""
        xp.checkpoint_every = 0
        xp.capacity_plan = "static"
        xp.capacity_warmup = 0
        xp.state_audit = False
        xp.dispatch_retries = 0
        xp.failover = "abort"
        xp.chaos = []
        xp.mesh_shards = 0
        with self.tracer.span("failover.hybrid_rerun", "failover",
                              sim_t0=exc.sim_time,
                              checkpoint=exc.checkpoint_path or "",
                              error=str(exc)[:200]):
            inner = Controller(cfg2, tracer=self.tracer)
            stats = inner.run()
        stats.failover_checkpoint = exc.checkpoint_path or ""
        # reflect the replayed per-host results onto THIS sim's hosts:
        # anything reading c.sim.hosts after the run (the determinism
        # gate's signature path, summary tooling) must see the real
        # counters, not the abandoned device run's zeros
        for mine, theirs in zip(self.sim.hosts, inner.sim.hosts):
            mine.events_executed = theirs.events_executed
            mine.packets_sent = theirs.packets_sent
            mine.packets_dropped = theirs.packets_dropped
            mine.packets_delivered = theirs.packets_delivered
            mine.trace_checksum = theirs.trace_checksum
        return stats

    def run(self) -> SimStats:
        """Run to stop_time. The flight recorder finalizes on EVERY
        exit path — success, failover, or a raised error — so a
        failed run still leaves its trace artifacts (the post-mortem
        is most valuable exactly then), and the summary lands on
        SimStats.telemetry for tooling."""
        stats = None
        try:
            stats = self._run_inner()
            return stats
        finally:
            counters = None
            if stats is not None:
                counters = {"events": stats.events_executed,
                            "packets": stats.packets_sent,
                            "rounds": stats.rounds,
                            "retries": stats.retries,
                            "replans": stats.replans}
                if stats.reshards:
                    # the shrink's degradation cost is a first-class
                    # observable: the count rides the METRICS
                    # counters, the wall rides the reshard phase
                    counters["reshards"] = stats.reshards
                if stats.pipeline:
                    # the METRICS record's dispatch block: segments,
                    # sync wall and advance wall
                    counters["pipeline"] = dict(stats.pipeline)
            # a nested run (the hybrid failover rerun shares its
            # parent's tracer) must NOT finalize: the parent closes
            # the recorder once for the whole incident timeline and
            # publishes the combined summary onto these stats
            if self._owns_tracer:
                summary = self.tracer.finalize(
                    run_info={
                        "policy": self.cfg.experimental
                        .scheduler_policy,
                        "n_hosts": len(self.sim.hosts),
                        "stop_time": int(self.cfg.general.stop_time),
                        "seed": int(self.cfg.general.seed),
                        "representation": self.sim.topology
                        .representation},
                    counters=counters)
                if stats is not None and summary is not None and \
                        stats.telemetry is None:
                    stats.telemetry = summary

    def _run_inner(self) -> SimStats:
        cfg = self.cfg
        stop = cfg.general.stop_time
        if self.runner is not None:
            from shadow_tpu.device.supervise import DeviceFailover
            try:
                stats = self.runner.run(stop)
            except DeviceFailover as e:
                return self._failover_run(e)
            if stats.preempted:
                log.warning(
                    "run preempted at %s: resume checkpoint %s "
                    "(set experimental.checkpoint_load to continue)",
                    simtime.format_time(stats.end_time),
                    stats.resume_path)
            if stats.retries:
                log.warning("run absorbed %d transient device "
                            "dispatch retr%s", stats.retries,
                            "y" if stats.retries == 1 else "ies")
            if stats.reshards:
                log.warning(
                    "run absorbed %d mesh shrink(s): device loss "
                    "survived on-device — the mesh now runs %d "
                    "shard(s), results bit-identical, throughput "
                    "degraded by the lost share", stats.reshards,
                    self.runner.engine.n_shards)
            if stats.ensemble is not None:
                rec = stats.ensemble
                log.info(
                    "ensemble campaign %s: %d replicas, "
                    "packets_sent aggregates %s",
                    rec["campaign"], rec["workload"]["replicas"],
                    {k: round(v, 1) for k, v in
                     rec["aggregates"]["packets_sent"].items()})
            occ = stats.occupancy
            if occ is not None and "planned" in occ:
                # one-line audit of the adaptive plan: what it chose
                # vs the static knobs, and whether it held first try
                log.info(
                    "capacity plan (%s): %s  [static %s, %d replan%s]",
                    cfg.experimental.capacity_plan, occ["planned"],
                    occ["static"], stats.replans,
                    "" if stats.replans == 1 else "s")
            return stats

        m = self.manager
        m.boot_hosts(self.sim.starts)
        if self.sim.host_faults:
            m.schedule_host_faults(self.sim.host_faults)
        if cfg.general.heartbeat_interval:
            m.schedule_heartbeats(cfg.general.heartbeat_interval, stop)
        lookahead = max(1, self.sim.lookahead)
        log.info("starting: %d hosts, stop=%s, lookahead=%s",
                 len(self.sim.hosts), simtime.format_time(stop),
                 simtime.format_time(lookahead))

        watchdog = None
        if cfg.experimental.round_watchdog:
            from shadow_tpu.core.manager import RoundWatchdog
            watchdog = RoundWatchdog(
                m, cfg.experimental.round_watchdog,
                dump_path=cfg.experimental.round_watchdog_dump)
            watchdog.start()
        try:
            next_time = m.policy.next_event_time()
            while next_time < stop:
                window_end = min(next_time + lookahead, stop)
                next_time = m.run_window(next_time, window_end)

            if self.sim.runtime is not None:
                # kill surviving managed processes (forked children
                # die with their parents), release the arena. Inside
                # the watchdog's try: its SIGINT may land just after
                # the loop exits (progress resumed between the sample
                # and the signal), and that window must surface the
                # same diagnostic, not a bare ^C traceback mid-
                # teardown
                ctx = m._ctx
                ctx.now = stop
                for h in m.hosts:
                    for app in (h.apps or [h.app]):
                        if app is not None and \
                                hasattr(app, "on_sim_end"):
                            ctx.host = h
                            app.on_sim_end(ctx)
                self.sim.runtime.close()
        except KeyboardInterrupt:
            if watchdog is None or not watchdog.fired:
                raise
            # the watchdog aborted a stalled round: surface a
            # diagnostic error, not a bare ^C traceback
            raise RuntimeError(
                "simulation aborted by the round watchdog (no "
                "scheduling progress for "
                f"{cfg.experimental.round_watchdog}s wall — see the "
                "per-host state dump in the log)") from None
        finally:
            if watchdog is not None:
                watchdog.stop()
        m.finalize()
        m.stats.end_time = stop
        m.stats.strategy_plan = self.strategy_plan
        if m.net_judge is not None:
            j = m.net_judge
            log.info("hybrid perf: %d packets judged on device in %d "
                     "batches (%.1f pkts/batch); %d packets in %d "
                     "sub-threshold rounds stayed on the CPU "
                     "(min_batch=%d)", j.packets, j.batches,
                     j.packets / j.batches if j.batches else 0.0,
                     j.cpu_packets, j.cpu_batches, j.min_batch)
        return m.stats
