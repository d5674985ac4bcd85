"""Configuration schema.

YAML-compatible with the reference's config format (reference
src/main/core/support/configuration.rs:27-760 and
docs/shadow_config_spec.md): sections `general`, `network`, `experimental`,
and `hosts.<name>` with nested `processes`. New TPU-specific knobs live
under `experimental` (the reference's escape-hatch section) so existing
configs parse unchanged.
"""

from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass, field
from typing import Any, Optional

from shadow_tpu.config.units import (
    parse_bandwidth_bits,
    parse_time_ns,
    parse_size_bytes,
)

LOG_LEVELS = ("error", "warning", "info", "debug", "trace")

# Scheduler policies: the five CPU policies of the reference
# (scheduler_policy_type.h:26, configuration.rs:575) plus the new `tpu`
# policy that runs the network model on device.
SCHEDULER_POLICIES = (
    "host",          # thread-per-host set, per-host queues (host_single)
    "steal",         # work stealing (host_steal)
    "thread",        # thread_single
    "threadXthread",  # thread_perthread
    "threadXhost",   # thread_perhost
    "serial",        # single-threaded reference oracle (new)
    "tpu",           # JAX device engine; falls back to hybrid when the
                     # apps have no vectorized twin (new)
    "hybrid",        # CPU host emulation + device network judgment (new)
)

INTERPOSE_METHODS = ("preload", "ptrace", "model")


def _check_keys(section: str, d: dict, allowed: set[str]) -> None:
    """Reject unknown keys, like the reference's serde
    `deny_unknown_fields` on every config struct — a typo'd option must
    fail loudly, not silently keep its default."""
    unknown = set(d) - allowed
    if unknown:
        raise ValueError(
            f"unknown key(s) in {section}: {sorted(unknown)} "
            f"(allowed: {sorted(allowed)})"
        )


def _check_choice(section: str, name: str, value: str, choices) -> None:
    if value not in choices:
        raise ValueError(
            f"{section}.{name}={value!r} is not one of {list(choices)}"
        )


def _keyword_or_path(name: str, value, keywords: tuple,
                     path_hint: str, json_record: bool = False,
                     bool_words: tuple = ()) -> str:
    """The ONE keyword-vs-path validation for experimental knobs that
    accept a mode keyword OR a filesystem path (capacity_plan,
    compile_cache, strategy_plan — a new such knob joins here, not as
    a fourth copy of the typo-rejection logic): normalize YAML 1.1
    bare ``on``/``off`` booleans back to the knob's keywords
    (`bool_words` = (off_word, on_word)), reject non-string scalars
    with the knob's own message (never a TypeError from a path check),
    pass keywords through, and require anything else to LOOK like the
    kind of path the knob documents — ``.json`` record paths
    (`json_record`) or directory-ish paths (a separator or a leading
    ``./``/``~``/``/``). A typo'd keyword must fail at config load,
    not minutes later as a raw FileNotFoundError deep inside the
    run."""
    if bool_words and isinstance(value, bool):
        value = bool_words[1] if value else bool_words[0]
    kws = " / ".join(repr(k) for k in keywords)
    if not isinstance(value, str):
        raise ValueError(
            f"experimental.{name}: {value!r} is neither {kws} nor "
            f"{path_hint}")
    if value in keywords:
        return value
    looks_like_path = (value.endswith(".json") if json_record else
                       (os.sep in value
                        or value.startswith((".", "~", "/"))))
    if not looks_like_path:
        raise ValueError(
            f"experimental.{name}: {value!r} is neither {kws} nor "
            f"{path_hint}")
    return value


@dataclass
class ProcessOptions:
    """One virtual process (configuration.rs:478-503)."""

    path: str
    args: Any = ""
    environment: str = ""
    quantity: int = 1
    start_time: int = 0            # sim ns
    stop_time: Optional[int] = None

    @classmethod
    def from_dict(cls, d: dict) -> "ProcessOptions":
        _check_keys("process", d, {"path", "args", "environment", "quantity",
                                   "start_time", "stop_time"})
        return cls(
            path=d["path"],
            args=d.get("args", ""),
            environment=d.get("environment", ""),
            quantity=int(d.get("quantity", 1)),
            start_time=parse_time_ns(d.get("start_time", 0)),
            stop_time=(parse_time_ns(d["stop_time"])
                       if d.get("stop_time") is not None else None),
        )


@dataclass
class HostOptions:
    """One host group (configuration.rs:505+)."""

    name: str = ""
    quantity: int = 1
    bandwidth_down: Optional[int] = None   # bits/s; default from topology vertex
    bandwidth_up: Optional[int] = None
    network_node_id: Optional[int] = None  # pin to a topology vertex id
    # with network_node_id: host i of the group attaches at vertex
    # network_node_id + i * stride — O(1) placement for generated
    # million-vertex topologies (no per-host vertex scan)
    network_node_stride: int = 0
    ip_address_hint: Optional[str] = None
    country_code_hint: Optional[str] = None
    city_code_hint: Optional[str] = None
    log_level: Optional[str] = None
    pcap_directory: Optional[str] = None
    options: dict = field(default_factory=dict)
    processes: list[ProcessOptions] = field(default_factory=list)

    @classmethod
    def from_dict(cls, name: str, d: dict) -> "HostOptions":
        _check_keys(f"hosts.{name}", d, {
            "quantity", "bandwidth_down", "bandwidth_up", "network_node_id",
            "network_node_stride",
            "ip_address_hint", "ip_addr", "country_code_hint",
            "city_code_hint", "log_level", "pcap_directory", "options",
            "processes",
        })
        stride = int(d.get("network_node_stride", 0))
        if stride < 0:
            raise ValueError(
                f"hosts.{name}: network_node_stride must be >= 0")
        if stride > 0 and d.get("network_node_id") is None:
            raise ValueError(
                f"hosts.{name}: network_node_stride needs "
                "network_node_id (the stride's base vertex)")
        return cls(
            name=name,
            quantity=int(d.get("quantity", 1)),
            network_node_id=(int(d["network_node_id"])
                             if d.get("network_node_id") is not None
                             else None),
            network_node_stride=stride,
            bandwidth_down=(parse_bandwidth_bits(d["bandwidth_down"])
                            if d.get("bandwidth_down") is not None else None),
            bandwidth_up=(parse_bandwidth_bits(d["bandwidth_up"])
                          if d.get("bandwidth_up") is not None else None),
            ip_address_hint=d.get("ip_address_hint") or d.get("ip_addr"),
            country_code_hint=d.get("country_code_hint"),
            city_code_hint=d.get("city_code_hint"),
            log_level=d.get("log_level"),
            pcap_directory=d.get("pcap_directory"),
            options=dict(d.get("options", {})),
            processes=[ProcessOptions.from_dict(p)
                       for p in d.get("processes", [])],
        )


@dataclass
class GeneralOptions:
    """`general` section (configuration.rs:129-195)."""

    stop_time: int = 0                      # sim ns; required in practice
    seed: int = 1
    parallelism: int = 0                    # 0 => use all cores/devices
    bootstrap_end_time: int = 0             # unlimited bandwidth until here
    log_level: str = "info"
    heartbeat_interval: Optional[int] = None
    data_directory: str = "shadow.data"
    template_directory: Optional[str] = None
    progress: bool = False
    model_unblocked_syscall_latency: bool = False

    @classmethod
    def from_dict(cls, d: dict) -> "GeneralOptions":
        _check_keys("general", d, {
            "stop_time", "seed", "parallelism", "bootstrap_end_time",
            "log_level", "heartbeat_interval", "data_directory",
            "template_directory", "progress",
            "model_unblocked_syscall_latency",
        })
        return cls(
            stop_time=parse_time_ns(d.get("stop_time", 0)),
            seed=int(d.get("seed", 1)),
            parallelism=int(d.get("parallelism", 0)),
            bootstrap_end_time=parse_time_ns(d.get("bootstrap_end_time", 0)),
            log_level=d.get("log_level", "info"),
            heartbeat_interval=(parse_time_ns(d["heartbeat_interval"])
                                if d.get("heartbeat_interval") is not None
                                else None),
            data_directory=d.get("data_directory", "shadow.data"),
            template_directory=d.get("template_directory"),
            progress=bool(d.get("progress", False)),
            model_unblocked_syscall_latency=bool(
                d.get("model_unblocked_syscall_latency", False)),
        )


def _fault_from_dict(i: int, d: dict):
    """One `network.faults` entry -> a validated FaultEvent
    (shadow_tpu/faults.py). Structural validation happens here at
    config load; topology-dependent checks (the edge exists, down/up
    pairing, host names) happen at build time when the graph and host
    list exist."""
    from shadow_tpu.faults import (
        FAULT_KINDS,
        FaultEvent,
        HOST_KINDS,
        LINK_KINDS,
    )

    section = f"network.faults[{i}]"
    if not isinstance(d, dict):
        raise ValueError(f"{section} must be a mapping")
    _check_keys(section, d, {"kind", "time", "source", "target",
                             "duration", "latency_multiplier",
                             "extra_packet_loss", "host"})
    kind = d.get("kind")
    if kind not in FAULT_KINDS:
        raise ValueError(
            f"{section}.kind={kind!r} is not one of {list(FAULT_KINDS)}")
    if "time" not in d:
        raise ValueError(f"{section}: missing required key 'time'")
    if kind in LINK_KINDS:
        if d.get("source") is None or d.get("target") is None:
            raise ValueError(
                f"{section}: {kind} needs 'source' and 'target' "
                "topology vertex ids")
        if d.get("host") is not None:
            raise ValueError(
                f"{section}: 'host' is only valid for "
                f"{list(HOST_KINDS)}")
    else:
        if not d.get("host"):
            raise ValueError(
                f"{section}: {kind} needs 'host' (a configured host "
                "name, group-expanded like client0)")
        for bad in ("source", "target", "duration",
                    "latency_multiplier", "extra_packet_loss"):
            if d.get(bad) is not None:
                raise ValueError(
                    f"{section}: {bad!r} is only valid for link "
                    "faults")
    if kind != "degrade":
        for bad in ("duration", "latency_multiplier",
                    "extra_packet_loss"):
            if d.get(bad) is not None:
                raise ValueError(
                    f"{section}: {bad!r} is only valid for degrade")
    return FaultEvent(
        kind=kind,
        time=parse_time_ns(d["time"]),
        source=int(d["source"]) if d.get("source") is not None else -1,
        target=int(d["target"]) if d.get("target") is not None else -1,
        duration=(parse_time_ns(d["duration"])
                  if d.get("duration") is not None else 0),
        latency_multiplier=float(d.get("latency_multiplier", 1.0)),
        extra_packet_loss=float(d.get("extra_packet_loss", 0.0)),
        host=str(d.get("host", "")),
    )


@dataclass
class NetworkOptions:
    """`network` section (configuration.rs:199-213).

    graph.type is "gml" (with `file.path` or `inline`) or the builtin
    "1_gbit_switch" (configuration.rs:732-760). `faults` is the
    deterministic fault-injection schedule (shadow_tpu/faults.py):
    timed link_down/link_up/degrade edge events compiled into an
    epoch table at load, plus manager-side host_crash/host_restart.
    """

    graph_type: str = "1_gbit_switch"
    graph_file: Optional[str] = None
    graph_inline: Optional[str] = None
    # generator knobs (graph.type: star_clusters — topology/generate.py)
    graph_params: dict = field(default_factory=dict)
    use_shortest_path: bool = True
    # network.topology.representation: dense | hierarchical | auto —
    # how the all-pairs tables are stored (topology/graph.py; see
    # docs/topology.md). dense is the exact [V,V] baseline;
    # hierarchical factors through clusters (O(C^2 + V), required
    # beyond ~100k hosts) and REFUSES non-factorable graphs; auto
    # tries hierarchical and falls back to dense with a log line.
    representation: str = "dense"
    faults: list = field(default_factory=list)

    @classmethod
    def from_dict(cls, d: dict) -> "NetworkOptions":
        _check_keys("network", d, {"graph", "use_shortest_path",
                                   "topology", "faults"})
        graph = d.get("graph", {}) or {}
        _check_keys("network.graph", graph, {
            "type", "file", "inline",
            # star_clusters generator surface
            "clusters", "spokes_per_cluster", "hub_latency",
            "access_latency", "hub_packet_loss", "access_packet_loss",
            "bandwidth_down", "bandwidth_up"})
        gtype = graph.get("type", "1_gbit_switch")
        gfile = None
        if isinstance(graph.get("file"), dict):
            gfile = graph["file"].get("path")
        elif isinstance(graph.get("file"), str):
            gfile = graph["file"]
        params = {k: graph[k] for k in (
            "clusters", "spokes_per_cluster", "hub_latency",
            "access_latency", "hub_packet_loss", "access_packet_loss",
            "bandwidth_down", "bandwidth_up") if k in graph}
        if params and gtype != "star_clusters":
            raise ValueError(
                "network.graph: generator keys "
                f"{sorted(params)} are only valid with "
                "type: star_clusters")
        topo = d.get("topology", {}) or {}
        _check_keys("network.topology", topo, {"representation"})
        rep = str(topo.get("representation", "dense"))
        if rep not in ("dense", "hierarchical", "auto"):
            raise ValueError(
                "network.topology.representation must be dense, "
                f"hierarchical or auto (got {rep!r})")
        raw_faults = d.get("faults") or []
        if not isinstance(raw_faults, list):
            raise ValueError("network.faults must be a list of fault "
                             "events")
        return cls(
            graph_type=gtype,
            graph_file=gfile,
            graph_inline=graph.get("inline"),
            graph_params=params,
            use_shortest_path=bool(d.get("use_shortest_path", True)),
            representation=rep,
            faults=[_fault_from_dict(i, f)
                    for i, f in enumerate(raw_faults)],
        )


@dataclass
class ExperimentalOptions:
    """`experimental` escape hatches (configuration.rs:230-392) plus the
    TPU engine's capacity/layout knobs (new)."""

    interpose_method: str = "model"
    # default flips to "tpu" once a config opts in; serial is the safe
    # universal default (the device engine requires jax devices)
    scheduler_policy: str = "serial"
    runahead: Optional[int] = None          # override lookahead window, ns
    use_cpu_pinning: bool = True
    # worker CONTEXTS for threaded policies; 0 = one per LP. When
    # workers > general.parallelism, the LogicalProcessors layer
    # multiplexes them (logical_processor.rs analogue)
    workers: int = 0
    use_memory_manager: bool = True
    use_seccomp: bool = True
    use_shim_syscall_handler: bool = True
    preload_spin_max: int = 8096
    interface_qdisc: str = "fifo"           # fifo | roundrobin
    interface_buffer: int = 1024 * 1024     # bytes
    socket_recv_buffer: int = 174760
    socket_send_buffer: int = 131072
    socket_recv_autotune: bool = True
    socket_send_autotune: bool = True
    tcp_congestion: str = "reno"            # tcp_cong.h algorithm name
    router_queue: str = "codel"             # codel | single | static
    router_static_capacity: int = 1024      # packets, for `static` queue
    # bandwidth + CoDel for RAW model-app sends (the socket path always
    # models bandwidth): the vectorizable fluid NIC that exists on both
    # the CPU and device engines (host/model_nic.py)
    model_bandwidth: bool = False
    # per-path packet counters (topology_incrementPathPacketCounter):
    # tracked by the CPU NetworkModel always; on the device engine
    # this opts into the flush-time [V,V] histogram (V^2 <= 65536)
    count_paths: bool = False

    # --- TPU engine knobs (new; absent from the reference) ---
    event_capacity: int = 64        # device event slots per host
    outbox_capacity: int = 32       # device packet sends per host per round
    # cross-shard exchange schedule: "all_to_all" (direct per-pair
    # buffers), "all_gather" (replicate whole outboxes; hub-heavy
    # traffic), "two_phase" (hierarchical intra-group then
    # inter-group schedule with aggregated per-phase buffers; skewed
    # sparse traffic), or "auto" (pick per workload from the measured
    # occupancy record — needs capacity_plan auto/<path> on a
    # multi-chip mesh, otherwise resolves to all_to_all). Traces are
    # bit-identical across variants (docs/exchange.md).
    exchange: str = "all_to_all"
    exchange_capacity: int = 0      # per shard-pair rows; 0 = auto-size
    # two_phase phase-2 (inter-group forward) buffer rows; 0 =
    # auto-size. Ignored by the other exchange variants.
    exchange_capacity2: int = 0
    # per-host arrivals accepted per flush (the merge-sort width is
    # event_capacity + this, so it is a first-order term of flush
    # cost); 0 = event_capacity. Too small fails LOUDLY via the
    # overflow counter — size it to the worst per-window fan-in
    # (e.g. every client of one server requesting in the same window)
    exchange_in_capacity: int = 0
    # per-host outbox rows surviving to the flush's global sort (the
    # outbox is mostly empty; compaction shrinks the flat sort from
    # H*outbox to H*this). 0 = off; too small fails loudly
    # (x_overflow). Size to the busiest host's sends+timers per phase.
    outbox_compact: int = 0
    # occupancy-driven capacity planning (device/capacity.py):
    # "static" keeps the hand-tuned knobs above; "auto" measures a
    # short warm-up slice and sizes every capacity from its occupancy
    # high-water marks; any other value is a path to a previously
    # written artifacts/OCC_*.json record. Non-static runs also
    # re-plan with doubled headroom and retry from the last
    # known-good state on a loud capacity overflow instead of
    # failing the run. Traces are bit-identical across capacity
    # choices whenever nothing overflows (tests pin it).
    capacity_plan: str = "static"
    # warm-up slice length for capacity_plan: auto (sim time;
    # 0 = stop_time / 8). It must reach real traffic — a slice that
    # ends before the first client start_time measures only boot.
    capacity_warmup: int = 0
    # network-judgment placement on the device engine: "auto" judges
    # the phase's outbox at flush on TPU (fewer ops in the pop loop)
    # and in-step on CPU; "flush"/"step" pin it. Bit-identical traces
    # either way.
    judge_placement: str = "auto"   # auto | flush | step
    # flush merge strategy: "global" regroups arrivals and re-sorts
    # the heaps in ONE double sort over [outbox | heap] rows keyed by
    # (dst host, time, src/seq) — no gathers, the right trade on TPU
    # where takes cost ~10 ms and multi-operand sorts ~3 ms; "window"
    # is the flat-sort + per-host window + row-merge path (the right
    # trade on one CPU core). "auto" picks by platform. Bit-identical
    # traces either way.
    merge_strategy: str = "auto"    # auto | global | window
    # pop head reads on the device engine: "onehot" replaces the pop
    # loop's take_along_axis head reads with one-hot masked
    # reductions (no gathers — the same trade as merge_strategy:
    # global, applied to the pop side); "gather" keeps
    # take_along_axis (cheaper on one CPU core). "auto" picks by
    # platform. Bit-identical traces either way.
    pop_strategy: str = "auto"      # auto | onehot | gather
    # the judge's topology lookups on the device engine: "onehot"
    # resolves each send's path latency and reliability by
    # compare-selects over host_vertex's runs (no gather; one fault
    # epoch, dense tables, no model_bandwidth, V*V <= 128 and at most
    # 128 host-vertex runs, else the gathers stay), "gather" keeps
    # indexed lookups (cheaper on one CPU core). "auto" picks by
    # platform. Bit-identical traces either way.
    table_strategy: str = "auto"    # auto | onehot | gather
    # burst-pop lane width override (0 = the app's own declaration):
    # burst apps (tgen servers, tor relays) pop up to this many
    # consecutive in-window packet events per iteration, one send
    # lane each. Traces are width-invariant; the knob trades
    # per-iteration vector width (nearly free on TPU) against
    # iteration count (the serial cost). 1 disables bursting.
    burst_pops: int = 0
    # max simulated time per device dispatch (ns; 0 = unbounded):
    # long runs split into several invocations of the one compiled
    # program with identical traces (window clamping stays on the
    # global stop).
    dispatch_segment: int = 0
    # device-state checkpoint / resume (device/checkpoint.py; the
    # reference has no checkpoint at all — SURVEY §5). checkpoint_save
    # writes the full simulation state at checkpoint_save_time
    # (0 = at stop_time) and pauses the run there; checkpoint_load
    # resumes a saved state and runs on to stop_time. A paused+resumed
    # pair bit-matches the uninterrupted run (window clamping stays on
    # the global stop — the heartbeat-segmentation contract).
    checkpoint_save: str = ""
    checkpoint_save_time: int = 0
    checkpoint_load: str = ""
    # --- supervised runs (device/supervise.py) ---
    # periodic validated checkpointing: every `checkpoint_every` sim
    # ns of progress the run writes a rotating checkpoint
    # (<checkpoint_save>.t<ns>, atomic tmp+rename, last
    # `checkpoint_keep` retained), validated by the fingerprint/meta
    # machinery plus the state_audit health word when enabled — so a
    # corrupted checkpoint is never the one a crash-restart resumes
    # from. 0 = off (the end-of-run checkpoint_save semantics are
    # unchanged). checkpoint_load accepts the base path and resolves
    # to the newest readable rotation entry.
    checkpoint_every: int = 0
    checkpoint_keep: int = 3
    # compile the on-device invariant audit (engine.py AUD_* bits:
    # heap order, clock monotonicity, counter non-negativity, packet
    # conservation across exchange) into the round program. Cheap
    # (reductions + one scalar collective per round); off by default
    # — the un-audited program is byte-identical to before.
    state_audit: bool = False
    # persistent AOT compile cache (device/aotcache.py): "auto"
    # serializes the engine's compiled executables under the aot/
    # subdirectory of the one cache root ($JAX_COMPILATION_CACHE_DIR,
    # else <checkout>/.cache/jax; shadow_tpu/_jax.py cache_root)
    # keyed by the full program fingerprint, so repeat processes
    # (supervised restarts, failover re-runs, ensemble campaigns, CI
    # rungs, benchmark iterations) skip the XLA compile; "off" disables;
    # any other value is the cache DIRECTORY path (it must look like
    # a path — contain a separator or start with ./ ~ / — so a
    # typo'd keyword fails at load, like capacity_plan). A cache hit
    # is bit-identical to a fresh compile, and an unreadable/stale
    # entry recompiles loudly (determinism_gate --compile-cache pins
    # both). Backends without executable serialization fall back to
    # JAX's built-in tracing cache (JAX_COMPILATION_CACHE_DIR).
    compile_cache: str = "auto"
    # total size cap for the cache directory, in MB; least-recently-
    # used entries are evicted past it
    compile_cache_cap_mb: int = 2048
    # transient-dispatch recovery: a device error matching the
    # transient markers (RESOURCE_EXHAUSTED, device unavailable, ...)
    # retries the failed segment from the last validated state up to
    # `dispatch_retries` CONSECUTIVE times (the counter resets when a
    # segment completes) with capped exponential backoff
    # (`dispatch_retry_backoff` seconds base, doubling, 30 s cap).
    dispatch_retries: int = 0
    dispatch_retry_backoff: float = 0.5
    # after exhausting retries — the failover LADDER
    # (docs/operations.md#failover): "abort" fails the run; "shrink"
    # probes the mesh for dead devices, re-shards the last validated
    # state onto the M survivors, re-plans exchange capacities for
    # the new geometry, and continues ON-DEVICE at M/N throughput
    # (bit-identical to the uninterrupted run — the mesh-shape
    # determinism contract), escalating to the hybrid rung only when
    # no shrink is possible (no dead device found, no survivor, or
    # the state is unrecoverable); "hybrid" saves the last validated
    # state to <checkpoint_save>.failover (kept for a device-side
    # resume) and re-runs on the hybrid backend with a loud
    # diagnostic — CPU host state is rebuilt from t=0 (device arrays
    # are not importable into CPU hosts), so the run finishes at the
    # cost of replaying the lost prefix. Ensemble campaigns may use
    # "shrink" (the replica axis vmaps outside the mesh axis and
    # survives intact); "hybrid" stays rejected for them (CPU host
    # emulation cannot vmap replicas).
    failover: str = "abort"
    # deterministic chaos injection (device/chaos.py,
    # docs/operations.md#chaos): a list of scripted fault points —
    # device_loss / dispatch_error at the k-th dispatch issue,
    # checkpoint_corrupt after the k-th rotation save,
    # cache_store_fail at the k-th cache store — fired at
    # deterministic seam counters so the same schedule reproduces
    # the identical run, failures included. This is how the failover
    # ladder is drilled in CI (determinism_gate --chaos) without
    # real hardware dying on cue.
    chaos: list = field(default_factory=list)
    # pin the device mesh to the first N available devices (0 = all):
    # the chaos gate's uninterrupted M-shard comparison runs, and any
    # workload that wants a submesh (a shrunken-geometry resume on a
    # healthy pool, capacity experiments), build their mesh here
    # instead of via XLA_FLAGS process-global forcing.
    mesh_shards: int = 0
    mesh_axis: str = "hosts"
    device_batch_rounds: int = 64   # rounds fused into one device while_loop
    # hybrid mode: which CPU policy drives host emulation while the
    # network model runs on device
    hybrid_cpu_policy: str = "serial"
    # adaptive judge: rounds with fewer pending packets than this are
    # judged synchronously on the CPU (the cost of one device
    # dispatch on the chip is not measured; a CPU judgment costs
    # ~10 us/pkt, so small batches may not pay for the trip).
    # 0 = always device.
    hybrid_judge_min_batch: int = 192
    # wall-clock round watchdog (core/manager.py RoundWatchdog),
    # seconds; 0 = off. If a scheduling round makes no progress for
    # this long, dump per-host/per-process state (current blocked
    # syscall, quarantine counts) and abort with a diagnostic instead
    # of hanging forever. CPU policies only (the device engine's
    # rounds are bounded by max_rounds). Size the interval ABOVE any
    # legitimate in-round pause — in particular hybrid mode's first
    # device flush includes its XLA compile (tens of seconds), during
    # which no event executes.
    round_watchdog: int = 0
    # where the watchdog ALSO writes its per-host/per-process stall
    # dump (atomic tmp+rename) when it fires — log lines scroll away
    # or get truncated by supervisors; the file survives for
    # post-mortem. "" = log only.
    round_watchdog_dump: str = ""
    # flight recorder (shadow_tpu/obs, docs/observability.md): "off"
    # records nothing (zero per-round work), "summary" (default)
    # accumulates per-phase wall attribution into SimStats.telemetry
    # (plus a recent-span ring for watchdog stall dumps), "trace"
    # additionally streams a JSONL span log and writes a
    # Perfetto-loadable TRACE_*.trace.json + METRICS_*.json record.
    # Tracing never perturbs the simulation: traces are bit-identical
    # across all three modes (determinism_gate --telemetry pins it).
    telemetry: str = "summary"
    # output DIRECTORY for the telemetry artifacts ("" = the
    # artifacts dir, honoring $SHADOW_TPU_OCC_DIR like OCC/ENSEMBLE
    # records). Setting it also makes `summary` mode write its
    # METRICS_*.json (by default only `trace` writes files).
    telemetry_path: str = ""
    # per-run artifacts DIRECTORY override for every record the run
    # writes by label/fingerprint-derived name — OCC occupancy
    # records, ENSEMBLE campaign records, METRICS/TRACE telemetry
    # ("" = "artifacts", honoring $SHADOW_TPU_OCC_DIR; an explicit
    # telemetry_path / ensemble.record_path still wins for its own
    # artifact). This is the multi-tenant namespacing seam: two
    # concurrent runs of the same workload derive the SAME canonical
    # filenames, so the campaign server points each tenant at
    # <spool>/campaigns/<cid>/artifacts and they can never clobber
    # each other's records.
    artifacts_dir: str = ""
    # loud wall-clock staleness detection on the supervise/ensemble
    # heartbeat cadence (device/supervise.py HeartbeatMonitor): a
    # gap wider than this many times the expected cadence (EWMA of
    # healthy gaps) warns loudly and counts into
    # SimStats.stale_heartbeats; the campaign server's watchdog
    # polls the same monitor to turn a wedged campaign into a
    # supervised kill + requeue instead of a wedged slot. 0 = off.
    heartbeat_stale_after: int = 0
    # telemetry-driven strategy plans (shadow_tpu/tune/,
    # docs/autotune.md): "off" ignores stored plans; "auto" adopts
    # the workload's PLAN_<app>_<H>_<fp>.json record (written by
    # scripts/tune.py next to the OCC records) when one exists; any
    # other value is an explicit plan path (must end in .json — a
    # typo'd keyword fails at load, like capacity_plan) whose
    # workload fingerprint must match this simulation (loud mismatch
    # refusal, never a silently wrong plan). Adoption changes WALL
    # time only: every knob in the plan space is individually
    # bit-identity-pinned, so a tuned run's traces equal the
    # default-knob run's (determinism_gate --tuned pins the
    # composition).
    strategy_plan: str = "off"
    # capacity-plan headroom factor override for capacity.plan's pad
    # rule (planned = ceil(measured * headroom) + slack): 0 keeps the
    # planner default (capacity.HEADROOM, 1.5). A tunable trade:
    # more headroom buys fewer overflow re-plans at the cost of
    # wider sorts and more ICI padding. Requires capacity_plan
    # auto/<path> (there is nothing to pad on a static run).
    capacity_headroom: float = 0.0
    # preflight resource admission (device/capacity.py footprint +
    # admission_verdict; docs/operations.md#admission): before any
    # compile, both runners estimate the per-device byte footprint
    # and compare it to the per-device budget. "auto" (default)
    # admits, statically degrades (ensemble replica batching), or
    # admits loudly over budget — the runtime
    # degradation ladder is the backstop; "strict" refuses an
    # over-budget config with a readable diagnostic; "off" skips.
    admission: str = "auto"
    # per-device memory budget in bytes (size suffixes accepted:
    # "7.5 GiB") for backends that report none (cpu meshes). A
    # backend-reported bytes_limit wins when
    # present. 0 = no budget: admission auto skips, strict refuses.
    device_memory_budget: int = 0

    @classmethod
    def from_dict(cls, d: dict) -> "ExperimentalOptions":
        _check_keys("experimental", d,
                    {f.name for f in dataclasses.fields(cls)})
        out = cls()
        for f in dataclasses.fields(cls):
            if f.name in d:
                v = d[f.name]
                if f.name in ("runahead", "dispatch_segment",
                              "checkpoint_save_time",
                              "checkpoint_every",
                              "capacity_warmup"):
                    v = parse_time_ns(v)
                elif f.name in ("interface_buffer", "socket_recv_buffer",
                                "socket_send_buffer",
                                "device_memory_budget"):
                    v = parse_size_bytes(v)
                elif f.type == "int":
                    v = int(v)
                elif f.type == "float":
                    v = float(v)
                elif f.type == "bool":
                    v = bool(v)
                setattr(out, f.name, v)
        _check_choice("experimental", "scheduler_policy",
                      out.scheduler_policy, SCHEDULER_POLICIES)
        _check_choice("experimental", "interpose_method",
                      out.interpose_method, INTERPOSE_METHODS)
        _check_choice("experimental", "interface_qdisc",
                      out.interface_qdisc, ("fifo", "roundrobin"))
        _check_choice("experimental", "router_queue",
                      out.router_queue, ("codel", "single", "static"))
        _check_choice("experimental", "exchange",
                      out.exchange, ("all_gather", "all_to_all",
                                     "two_phase", "auto"))
        _check_choice("experimental", "judge_placement",
                      out.judge_placement, ("auto", "flush", "step"))
        _check_choice("experimental", "merge_strategy",
                      out.merge_strategy, ("auto", "global", "window"))
        _check_choice("experimental", "pop_strategy",
                      out.pop_strategy, ("auto", "onehot", "gather"))
        _check_choice("experimental", "table_strategy",
                      out.table_strategy, ("auto", "onehot", "gather"))
        if isinstance(out.telemetry, bool):
            # YAML 1.1 reads bare `off`/`on` as booleans — map them
            # back to the knob's keywords (the compile_cache rule);
            # `on` means the default-on mode, summary
            out.telemetry = "summary" if out.telemetry else "off"
        from shadow_tpu.obs.trace import MODES as TELEMETRY_MODES
        _check_choice("experimental", "telemetry",
                      out.telemetry, TELEMETRY_MODES)
        if not isinstance(out.telemetry_path, str):
            raise ValueError(
                f"experimental.telemetry_path: {out.telemetry_path!r} "
                "must be a directory path string")
        if not isinstance(out.artifacts_dir, str):
            raise ValueError(
                f"experimental.artifacts_dir: {out.artifacts_dir!r} "
                "must be a directory path string")
        if out.heartbeat_stale_after < 0:
            raise ValueError(
                "experimental.heartbeat_stale_after must be >= 0 "
                "(0 = staleness detection off; k = warn when a "
                "heartbeat gap exceeds k x the expected cadence)")
        from shadow_tpu.host.tcp import CONGESTION_ALGORITHMS
        _check_choice("experimental", "tcp_congestion",
                      out.tcp_congestion,
                      sorted(CONGESTION_ALGORITHMS))
        _check_choice("experimental", "hybrid_cpu_policy",
                      out.hybrid_cpu_policy,
                      [p for p in SCHEDULER_POLICIES
                       if p not in ("tpu", "hybrid")])
        if out.checkpoint_save_time and not out.checkpoint_save:
            raise ValueError(
                "experimental.checkpoint_save_time is set but "
                "checkpoint_save (the output path) is not — the "
                "pause time would be silently ignored")
        if out.capacity_plan != "static" and \
                out.scheduler_policy != "tpu":
            raise ValueError(
                "experimental.capacity_plan: occupancy-driven "
                "capacity planning sizes the DEVICE engine's buffers "
                "and requires scheduler_policy: tpu (CPU policies "
                "have no static capacities to plan)")
        if out.capacity_warmup < 0:
            raise ValueError(
                "experimental.capacity_warmup must be >= 0")
        # record paths always end in .json (capacity.record_path
        # writes OCC_*.json); the shared helper owns the typo
        # rejection
        out.capacity_plan = _keyword_or_path(
            "capacity_plan", out.capacity_plan, ("static", "auto"),
            "a path to a saved OCC_*.json occupancy record",
            json_record=True)
        if out.capacity_warmup and out.capacity_plan != "auto":
            raise ValueError(
                "experimental.capacity_warmup is set but "
                f"capacity_plan is {out.capacity_plan!r} — the "
                "warm-up slice only runs under capacity_plan: auto, "
                "so the knob would be silently ignored")
        # cache directories always look like paths — anything else
        # ("atuo", a bare number) is a typo'd mode that would
        # otherwise silently become a directory named after the typo;
        # YAML 1.1 bare off/on booleans normalize to the keywords
        out.compile_cache = _keyword_or_path(
            "compile_cache", out.compile_cache, ("auto", "off"),
            "a cache directory path (paths must contain a separator "
            "or start with './', '~', or '/')",
            bool_words=("off", "auto"))
        # strategy plans are .json records next to the OCC records
        # (tune/plan.py); same bool normalization as compile_cache
        out.strategy_plan = _keyword_or_path(
            "strategy_plan", out.strategy_plan, ("auto", "off"),
            "a path to a saved PLAN_*.json strategy record",
            json_record=True, bool_words=("off", "auto"))
        if out.capacity_headroom and out.capacity_headroom < 1.0:
            raise ValueError(
                "experimental.capacity_headroom must be 0 (planner "
                "default) or >= 1.0 — padding below the measured "
                "high-water mark would guarantee overflow re-plans")
        if out.capacity_headroom and out.capacity_plan == "static":
            raise ValueError(
                "experimental.capacity_headroom is set but "
                "capacity_plan is 'static' — the headroom factor "
                "only shapes planned capacities, so the knob would "
                "be silently ignored")
        if out.compile_cache_cap_mb < 1:
            raise ValueError(
                "experimental.compile_cache_cap_mb must be >= 1")
        if (out.checkpoint_save or out.checkpoint_load) and \
                out.scheduler_policy != "tpu":
            raise ValueError(
                "experimental.checkpoint_save/load: device-state "
                "checkpointing requires scheduler_policy: tpu (CPU "
                "policies execute managed OS processes, whose state "
                "is not checkpointable — the reference has the same "
                "limitation, i.e. no checkpoint at all)")
        _check_choice("experimental", "failover", out.failover,
                      ("abort", "shrink", "hybrid"))
        if isinstance(out.admission, bool):
            # YAML 1.1 reads bare `off`/`on` as booleans — map them
            # back to the knob's keywords (the telemetry rule); `on`
            # means the default-on mode, auto
            out.admission = "auto" if out.admission else "off"
        _check_choice("experimental", "admission", out.admission,
                      ("auto", "off", "strict"))
        if out.admission == "strict" and \
                out.scheduler_policy != "tpu":
            raise ValueError(
                "experimental.admission: strict gates DEVICE engine "
                "footprints and requires scheduler_policy: tpu (CPU "
                "policies have no device budget to admit against)")
        if out.device_memory_budget and out.scheduler_policy != "tpu":
            raise ValueError(
                "experimental.device_memory_budget bounds the DEVICE "
                "engine's footprint and requires scheduler_policy: "
                "tpu")
        if out.chaos:
            # the injector owns its schedule format — validate every
            # entry at load (the network.faults rule: a typo'd
            # schedule fails in milliseconds, never as a run that
            # silently injects nothing)
            from shadow_tpu.device.chaos import events_from_config
            out.chaos = events_from_config(out.chaos)
            if out.scheduler_policy != "tpu":
                raise ValueError(
                    "experimental.chaos injects faults at the DEVICE "
                    "supervise/engine seams and requires "
                    "scheduler_policy: tpu")
        if out.mesh_shards and out.scheduler_policy != "tpu":
            raise ValueError(
                "experimental.mesh_shards pins the DEVICE mesh and "
                "requires scheduler_policy: tpu (CPU policies have "
                "no mesh to pin)")
        if out.checkpoint_every:
            if not out.checkpoint_save:
                raise ValueError(
                    "experimental.checkpoint_every is set but "
                    "checkpoint_save (the rotation base path) is not "
                    "— periodic checkpoints would have nowhere to go")
            if out.checkpoint_save_time:
                raise ValueError(
                    "experimental.checkpoint_every cannot combine "
                    "with checkpoint_save_time: periodic supervision "
                    "runs to stop_time writing rotating checkpoints, "
                    "while checkpoint_save_time pauses the run at one "
                    "boundary — pick one")
        if out.state_audit and out.scheduler_policy != "tpu":
            raise ValueError(
                "experimental.state_audit compiles the invariant "
                "audit into the DEVICE round program and requires "
                "scheduler_policy: tpu")
        if (out.dispatch_retries or out.failover != "abort") and \
                out.scheduler_policy != "tpu":
            raise ValueError(
                "experimental.dispatch_retries/failover supervise "
                "DEVICE dispatches and require scheduler_policy: tpu")
        if out.dispatch_retry_backoff < 0:
            raise ValueError(
                "experimental.dispatch_retry_backoff must be >= 0")
        if out.model_bandwidth and out.judge_placement == "flush":
            raise ValueError(
                "experimental.judge_placement: flush cannot combine "
                "with model_bandwidth (the fluid NIC's tx/rx state "
                "is sequential per event; judgment stays in-step)")
        for name, minimum in (("event_capacity", 2),
                              ("dispatch_segment", 0),
                              ("checkpoint_save_time", 0),
                              ("checkpoint_every", 0),
                              ("checkpoint_keep", 1),
                              ("dispatch_retries", 0),
                              ("mesh_shards", 0),
                              ("outbox_capacity", 1),
                              ("exchange_capacity", 0),
                              ("exchange_capacity2", 0),
                              ("exchange_in_capacity", 0),
                              ("outbox_compact", 0),
                              ("burst_pops", 0),
                              ("device_batch_rounds", 1),
                              ("hybrid_judge_min_batch", 0),
                              ("round_watchdog", 0),
                              ("preload_spin_max", 0),
                              ("device_memory_budget", 0)):
            if getattr(out, name) < minimum:
                raise ValueError(
                    f"experimental.{name} must be >= {minimum}")
        if out.burst_pops > 32:
            raise ValueError(
                "experimental.burst_pops must be <= 32 (the per-lane "
                "checksum fold unrolls P-wide in the compiled step)")
        if out.burst_pops > 1 and out.model_bandwidth:
            raise ValueError(
                "experimental.burst_pops > 1 cannot combine with "
                "model_bandwidth (the fluid NIC's tx/rx state is "
                "sequential per event — the engine would silently "
                "degrade the requested width to 1)")
        return out


# ensemble vary axes: per-replica values that change array VALUES on
# device (seeds, topology tables, epoch times) — never shapes. Axes
# that would change shapes (host counts, capacities, stop_time) are
# deliberately not offered.
ENSEMBLE_VARY_AXES = ("seed", "latency_scale", "packet_loss_delta",
                      "fault_schedule")
ENSEMBLE_AGGREGATES = ("mean", "p5", "p95", "min", "max")


@dataclass
class EnsembleOptions:
    """`ensemble` section (new; no reference analogue): run R
    independent replicas of the device-twin workload in ONE compiled
    program (shadow_tpu/ensemble/), varying only array values per
    replica. Replica i is bit-identical to a standalone run with
    replica i's parameters (the campaign determinism contract,
    enforced by determinism_gate.py --ensemble)."""

    replicas: int = 1
    vary: dict = field(default_factory=dict)
    # named alternative link-fault schedules for vary.fault_schedule
    # (each a list of validated FaultEvents; "base" = the config's
    # network.faults schedule, "none" = fault-free)
    fault_schedules: dict = field(default_factory=dict)
    aggregate: tuple = ENSEMBLE_AGGREGATES
    record_path: str = ""        # "" = artifacts/ENSEMBLE_*.json
    # sequential replica batching (the ensembles' out-of-memory
    # story, and the degradation ladder's rung 2): 0 = the full
    # R-replica vmap in one program; k = run ceil(R/k) sequential
    # batches of <= k replicas each and merge the results — pinned
    # bit-identical to the full vmap (each replica's trace is the
    # standalone program's regardless of which batch carries it,
    # determinism_gate --degrade). Combines with supervised
    # checkpointing via checkpoint_save + checkpoint_every only:
    # each batch writes its own rotation series
    # (<save>.b<k>.t<ns>, stamped with the batch's replica window)
    # and a preempted campaign resumes by replaying completed
    # batches fresh (pure functions — bit-identical) and loading
    # the stamped batch's entry. checkpoint_save_time is rejected
    # (batches replay the full time range, so there is no single
    # campaign pause point).
    replica_batch: int = 0

    @classmethod
    def from_dict(cls, d: dict) -> "EnsembleOptions":
        from shadow_tpu.faults import LINK_KINDS

        _check_keys("ensemble", d, {"replicas", "vary",
                                    "fault_schedules", "aggregate",
                                    "record_path", "replica_batch"})
        if "replicas" not in d:
            raise ValueError("ensemble: missing required key "
                             "'replicas'")
        replicas = int(d["replicas"])
        if replicas < 1:
            raise ValueError("ensemble.replicas must be >= 1")
        raw_vary = d.get("vary") or {}
        if not isinstance(raw_vary, dict):
            raise ValueError("ensemble.vary must be a mapping of "
                             "axis -> per-replica value list")
        _check_keys("ensemble.vary", raw_vary, set(ENSEMBLE_VARY_AXES))
        if replicas > 1 and not raw_vary:
            raise ValueError(
                "ensemble: replicas > 1 with an empty vary block "
                "would run identical replicas — declare at least one "
                f"vary axis ({list(ENSEMBLE_VARY_AXES)})")
        vary: dict = {}
        for axis, vals in raw_vary.items():
            if not isinstance(vals, list) or len(vals) != replicas:
                raise ValueError(
                    f"ensemble.vary.{axis} must list exactly one "
                    f"value per replica ({replicas})")
            if axis == "seed":
                vary[axis] = [int(v) for v in vals]
            elif axis == "latency_scale":
                vary[axis] = [float(v) for v in vals]
                if any(v <= 0 for v in vary[axis]):
                    raise ValueError(
                        "ensemble.vary.latency_scale values must be "
                        "> 0")
            elif axis == "packet_loss_delta":
                vary[axis] = [float(v) for v in vals]
                if any(not (0.0 <= v <= 1.0) for v in vary[axis]):
                    raise ValueError(
                        "ensemble.vary.packet_loss_delta values must "
                        "be in [0, 1]")
            else:                        # fault_schedule
                vary[axis] = [str(v) for v in vals]
        raw_scheds = d.get("fault_schedules") or {}
        if not isinstance(raw_scheds, dict):
            raise ValueError("ensemble.fault_schedules must be a "
                             "mapping of name -> fault event list")
        schedules: dict = {}
        for name, evs in raw_scheds.items():
            if name in ("base", "none"):
                raise ValueError(
                    f"ensemble.fault_schedules: {name!r} is reserved "
                    "('base' = network.faults, 'none' = fault-free)")
            if not isinstance(evs, list):
                raise ValueError(
                    f"ensemble.fault_schedules.{name} must be a list "
                    "of fault events")
            events = [_fault_from_dict(i, e) for i, e in enumerate(evs)]
            bad = [e.kind for e in events if e.kind not in LINK_KINDS]
            if bad:
                raise ValueError(
                    f"ensemble.fault_schedules.{name}: {bad} are "
                    "manager-side host faults — ensemble campaigns "
                    "run on the device engine and only vary link "
                    f"faults ({list(LINK_KINDS)})")
            schedules[name] = events
        for name in vary.get("fault_schedule", ()):
            if name not in ("base", "none") and name not in schedules:
                raise ValueError(
                    f"ensemble.vary.fault_schedule names unknown "
                    f"schedule {name!r} (declare it under "
                    "ensemble.fault_schedules, or use 'base'/'none')")
        agg = d.get("aggregate")
        if agg is None:
            aggregate = ENSEMBLE_AGGREGATES
        else:
            if not isinstance(agg, list) or not agg:
                raise ValueError("ensemble.aggregate must be a "
                                 "non-empty list")
            for a in agg:
                _check_choice("ensemble", "aggregate", a,
                              ENSEMBLE_AGGREGATES)
            aggregate = tuple(agg)
        replica_batch = int(d.get("replica_batch", 0) or 0)
        if replica_batch < 0 or replica_batch > replicas:
            raise ValueError(
                f"ensemble.replica_batch must be in [0, replicas="
                f"{replicas}] (0 = full vmap; k = sequential batches "
                "of <= k replicas)")
        return cls(replicas=replicas, vary=vary,
                   fault_schedules=schedules, aggregate=aggregate,
                   record_path=str(d.get("record_path", "") or ""),
                   replica_batch=replica_batch)


@dataclass
class ConfigOptions:
    general: GeneralOptions = field(default_factory=GeneralOptions)
    network: NetworkOptions = field(default_factory=NetworkOptions)
    experimental: ExperimentalOptions = field(default_factory=ExperimentalOptions)
    hosts: list[HostOptions] = field(default_factory=list)
    ensemble: Optional[EnsembleOptions] = None

    @classmethod
    def from_dict(cls, d: dict) -> "ConfigOptions":
        _check_keys("config", d, {"general", "network", "experimental",
                                  "hosts", "host_option_defaults",
                                  "host_defaults", "ensemble"})
        hosts = [HostOptions.from_dict(name, hd or {})
                 for name, hd in (d.get("hosts", {}) or {}).items()]
        ensemble = (EnsembleOptions.from_dict(d["ensemble"])
                    if d.get("ensemble") else None)
        out = cls(
            general=GeneralOptions.from_dict(d.get("general", {}) or {}),
            network=NetworkOptions.from_dict(d.get("network", {}) or {}),
            experimental=ExperimentalOptions.from_dict(
                d.get("experimental", {}) or {}),
            hosts=hosts,
            ensemble=ensemble,
        )
        if ensemble is not None and \
                out.experimental.scheduler_policy != "tpu":
            raise ValueError(
                "ensemble: multi-replica campaigns run as one vmapped "
                "device program and require "
                "experimental.scheduler_policy: tpu (run replicas as "
                "separate processes on CPU policies)")
        if ensemble is not None and \
                out.experimental.failover == "hybrid":
            raise ValueError(
                "ensemble: experimental.failover: hybrid is not "
                "available for campaigns (CPU host emulation cannot "
                "vmap replicas) — use failover: shrink (campaigns "
                "survive device loss on-device; the replica axis "
                "vmaps outside the mesh axis), or let exhausted "
                "retries fail loudly with the last validated "
                "checkpoint on disk")
        if ensemble is not None and ensemble.replica_batch and \
                out.experimental.checkpoint_save_time:
            raise ValueError(
                "ensemble.replica_batch cannot combine with "
                "checkpoint_save_time: every sequential batch replays "
                "the full time range, so there is no single campaign "
                "pause point to save at — use checkpoint_every for "
                "supervised/preemptible batched campaigns")
        if ensemble is not None and ensemble.replica_batch and \
                out.experimental.checkpoint_save and \
                not out.experimental.checkpoint_every:
            raise ValueError(
                "ensemble.replica_batch with checkpoint_save needs "
                "checkpoint_every: a batched campaign never "
                "materializes the full-R stacked state, so the only "
                "checkpoints it can write are the per-batch rotation "
                "entries (<save>.b<k>.t<ns>) the supervised drain "
                "produces — without checkpoint_every the end-of-run "
                "save would be silently skipped")
        if out.experimental.heartbeat_stale_after and \
                not out.general.heartbeat_interval:
            raise ValueError(
                "experimental.heartbeat_stale_after is set but "
                "general.heartbeat_interval is 0 — staleness is "
                "measured on the [supervise-heartbeat] boundaries, "
                "so without a heartbeat cadence the knob would be "
                "silently ignored")
        return out

    def total_hosts(self) -> int:
        return sum(h.quantity for h in self.hosts)
