"""Device engine: prng bit-identity and trace equivalence vs the CPU
serial oracle — the core correctness argument of the TPU design."""

import numpy as np
import pytest

from shadow_tpu.config import load_config_str
from shadow_tpu.core.controller import Controller
from shadow_tpu.utils import nprng
from shadow_tpu.utils.rng import PURPOSE_APP, PURPOSE_PACKET_DROP


def test_device_prng_matches_numpy():
    from shadow_tpu.device import prng as dprng
    from shadow_tpu._jax import jnp
    seed = 42
    ids = np.array([0, 3, 17, 1000], dtype=np.uint32)
    seqs = np.array([0, 100, 2**20, 7], dtype=np.uint32)
    jk = dprng.chain_key(dprng.seed_key(seed), PURPOSE_PACKET_DROP,
                         jnp.asarray(ids), jnp.asarray(seqs))
    ju = np.asarray(dprng.uniform01(jk))
    nu = nprng.packet_uniform(seed, PURPOSE_PACKET_DROP, ids, seqs)
    np.testing.assert_array_equal(ju, nu)
    jb = np.asarray(dprng.random_bits32(dprng.chain_key(
        dprng.seed_key(seed), PURPOSE_APP, jnp.asarray(ids),
        jnp.asarray(seqs))))
    k = nprng.fold_in(nprng.fold_in(nprng.fold_in(
        nprng.seed_key(seed), PURPOSE_APP), ids), seqs)
    np.testing.assert_array_equal(jb, nprng.random_bits32(k))


def test_chain_key_vmaps_through_barriers():
    """The ensemble program vmaps chain_key's optimization_barriers;
    jax batches them natively, bit-identically to the unbatched form."""
    from shadow_tpu.device import prng as dprng
    from shadow_tpu._jax import jax, jnp
    ids = jnp.asarray(np.array([0, 3, 17, 1000], dtype=np.uint32))
    seqs = jnp.asarray(np.array([0, 100, 2**20, 7], dtype=np.uint32))
    seeds = [dprng.seed_key(s) for s in (1, 42)]
    k1 = jnp.stack([jnp.asarray(s[0]) for s in seeds])
    k2 = jnp.stack([jnp.asarray(s[1]) for s in seeds])
    got = jax.vmap(lambda a, b: dprng.uniform01(dprng.chain_key(
        (a, b), PURPOSE_PACKET_DROP, ids, seqs)))(k1, k2)
    for r, s in enumerate((1, 42)):
        np.testing.assert_array_equal(
            np.asarray(got[r]),
            nprng.packet_uniform(s, PURPOSE_PACKET_DROP,
                                 np.asarray(ids), np.asarray(seqs)))


PHOLD_YAML = """
general:
  stop_time: 2s
  seed: {seed}
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 bandwidth_down "100 Mbit" bandwidth_up "100 Mbit" ]
        node [ id 1 bandwidth_down "100 Mbit" bandwidth_up "100 Mbit" ]
        edge [ source 0 target 0 latency "30 ms" packet_loss {loss} ]
        edge [ source 0 target 1 latency "10 ms" packet_loss {loss} ]
        edge [ source 1 target 1 latency "30 ms" packet_loss {loss} ]
      ]
experimental:
  scheduler_policy: {policy}
  event_capacity: 64
  outbox_capacity: 16
hosts:
  left:
    quantity: {q}
    network_node_id: 0
    processes:
    - path: model:phold
      args: msgload={msgload}
      start_time: 100ms
  right:
    quantity: {q}
    network_node_id: 1
    processes:
    - path: model:phold
      args: msgload={msgload}
      start_time: 150ms
"""


def _run(policy, seed=5, loss=0.0, q=8, msgload=2):
    yaml = PHOLD_YAML.format(policy=policy, seed=seed, loss=loss, q=q,
                             msgload=msgload)
    c = Controller(load_config_str(yaml))
    stats = c.run()
    hosts = c.sim.hosts
    return stats, hosts


@pytest.mark.parametrize("loss,msgload", [(0.0, 2), (0.1, 2), (0.0, 1)])
def test_device_matches_serial_oracle(loss, msgload):
    s_stats, s_hosts = _run("serial", loss=loss, msgload=msgload)
    d_stats, d_hosts = _run("tpu", loss=loss, msgload=msgload)
    assert d_stats.ok
    assert s_stats.events_executed == d_stats.events_executed
    assert s_stats.packets_sent == d_stats.packets_sent
    assert s_stats.packets_dropped == d_stats.packets_dropped
    assert s_stats.packets_delivered == d_stats.packets_delivered
    for sh, dh in zip(s_hosts, d_hosts):
        assert sh.events_executed == dh.events_executed, sh.name
        assert sh.trace_checksum == dh.trace_checksum, sh.name


def test_device_in_window_self_packets_match_oracle():
    # runahead larger than the self-path latency: self packets deliver
    # inside the window and must execute in-window, in timestamp order
    yaml = """
general: {{stop_time: 1s, seed: 4}}
network:
  graph:
    type: gml
    inline: |
      graph [ directed 0
        node [ id 0 bandwidth_down "1 Gbit" bandwidth_up "1 Gbit" ]
        edge [ source 0 target 0 latency "10 ms" packet_loss 0.0 ] ]
experimental:
  scheduler_policy: {policy}
  runahead: 100 ms
hosts:
  peer:
    quantity: 4
    network_node_id: 0
    processes:
    - path: model:phold
      args: msgload=2 selfloop=1
      start_time: 5ms
"""
    s = Controller(load_config_str(yaml.format(policy="serial")))
    s_stats = s.run()
    d = Controller(load_config_str(yaml.format(policy="tpu")))
    d_stats = d.run()
    assert d_stats.ok
    assert s_stats.events_executed == d_stats.events_executed
    assert s_stats.rounds == d_stats.rounds
    for sh, dh in zip(s.sim.hosts, d.sim.hosts):
        assert sh.trace_checksum == dh.trace_checksum, sh.name


def test_threaded_policy_propagates_app_errors():
    yaml = """
general: {stop_time: 1s, seed: 1}
network: {graph: {type: 1_gbit_switch}}
experimental: {scheduler_policy: host, runahead: 10 ms}
hosts:
  client:
    processes:
    - path: model:tgen_client
      args: server=nonexistent
      start_time: 1ms
"""
    c = Controller(load_config_str(yaml))
    with pytest.raises(RuntimeError, match="worker thread failed"):
        c.run()


def test_exchange_modes_identical_traces():
    """all_to_all exchanges only each shard pair's rows; all_gather
    replicates everything. Same rows, same deterministic arrival order
    -> bit-identical traces on the 8-device mesh."""
    yaml = PHOLD_YAML.format(policy="tpu", seed=6, loss=0.05, q=8,
                             msgload=2)
    out = {}
    for mode in ("all_gather", "all_to_all"):
        c = Controller(load_config_str(
            yaml.replace("experimental:",
                         f"experimental:\n  exchange: {mode}")))
        stats = c.run()
        assert stats.ok, mode
        out[mode] = [h.trace_checksum for h in c.sim.hosts]
    assert out["all_gather"] == out["all_to_all"]


def test_exchange_capacity_overflow_detected():
    """A deliberately tiny per-pair capacity must fail the run loudly
    (overflow counted per source host), never silently drop rows."""
    yaml = PHOLD_YAML.format(policy="tpu", seed=6, loss=0.0, q=8,
                             msgload=4)
    c = Controller(load_config_str(
        yaml.replace("experimental:",
                     "experimental:\n  exchange_capacity: 1")))
    stats = c.run()
    assert not stats.ok


def test_dispatch_segment_trace_invariant():
    """Bounding the sim-time of each device dispatch splits one run
    into several invocations of the same compiled program; window
    clamping stays on the global stop, so the trace must be
    bit-identical."""
    base = PHOLD_YAML.format(policy="tpu", seed=5, loss=0.1, q=8,
                             msgload=2)
    seg = base.replace("experimental:",
                       "experimental:\n  dispatch_segment: 300ms")
    outs = []
    for yaml in (base, seg):
        c = Controller(load_config_str(yaml))
        stats = c.run()
        assert stats.ok
        outs.append((stats.events_executed, stats.packets_sent,
                     [h.trace_checksum for h in c.sim.hosts]))
    assert outs[0] == outs[1]


def test_judge_placement_identical_traces_phold():
    """Hoisted vs in-step judgment on the multi-send-lane phold app
    (K > 1, no trains): bit-identical traces and stats."""
    outs = {}
    for placement in ("step", "flush"):
        yaml = PHOLD_YAML.format(policy="tpu", seed=7, loss=0.1, q=8,
                                 msgload=3)
        yaml = yaml.replace(
            "experimental:",
            f"experimental:\n  judge_placement: {placement}")
        c = Controller(load_config_str(yaml))
        stats = c.run()
        assert stats.ok, placement
        outs[placement] = (stats.events_executed, stats.packets_sent,
                           stats.packets_dropped,
                           [h.trace_checksum for h in c.sim.hosts])
    assert outs["step"] == outs["flush"]


def test_merge_strategy_identical_traces_phold():
    """Gatherless global double-sort merge vs the flat-sort + window
    merge: same arrival sets, same (time, src, seq) per-host order,
    bit-identical traces — on lossy multi-lane phold over the
    8-device mesh (exercises the all_to_all pack + self-shard bypass
    feeding the global merge)."""
    outs = {}
    for strategy in ("window", "global"):
        yaml = PHOLD_YAML.format(policy="tpu", seed=7, loss=0.1, q=8,
                                 msgload=3)
        yaml = yaml.replace(
            "experimental:",
            f"experimental:\n  merge_strategy: {strategy}")
        c = Controller(load_config_str(yaml))
        stats = c.run()
        assert stats.ok, strategy
        outs[strategy] = (stats.events_executed, stats.packets_sent,
                          stats.packets_dropped,
                          [h.trace_checksum for h in c.sim.hosts])
    assert outs["window"] == outs["global"]


def test_tpu_default_knobs_identical_traces():
    """The combination production TPU actually runs — judgment
    hoisted to flush, the global double-sort merge, one-hot pop reads
    and compare-select table lookups together (_judge_outbox rewrites
    ob t/m/v, then _ob_rows re-reads them) — pinned against the
    CPU-default combination."""
    outs = {}
    for extra in ("  judge_placement: step\n  merge_strategy: window\n"
                  "  pop_strategy: gather\n  table_strategy: gather",
                  "  judge_placement: flush\n  merge_strategy: global\n"
                  "  pop_strategy: onehot\n  table_strategy: onehot"):
        yaml = PHOLD_YAML.format(policy="tpu", seed=7, loss=0.1, q=8,
                                 msgload=3)
        yaml = yaml.replace("experimental:",
                            "experimental:\n" + extra)
        c = Controller(load_config_str(yaml))
        stats = c.run()
        assert stats.ok, extra
        outs[extra] = (stats.events_executed, stats.packets_sent,
                       stats.packets_dropped,
                       [h.trace_checksum for h in c.sim.hosts])
    a, b = outs.values()
    assert a == b


@pytest.mark.parametrize("placement,merge", [("flush", "global"),
                                             ("step", "window")])
def test_round_program_names_its_stages(placement, merge):
    """The optimized HLO of the dispatched round program names each
    op's stage in its metadata: on the TPU path (judge hoisted to the
    flush, global merge) and with both off (in-step judge, window
    merge), over the 8-device mesh (so the exchange is there too)."""
    yaml = PHOLD_YAML.format(policy="tpu", seed=7, loss=0.1, q=4,
                             msgload=2)
    yaml = yaml.replace("experimental:",
                        f"experimental:\n  judge_placement: {placement}"
                        f"\n  merge_strategy: {merge}")
    c = Controller(load_config_str(yaml))
    assert c.run().ok
    eng = c.runner.engine
    hoisted = placement == "flush"
    assert eng.program_facts["judge_hoist"] is hoisted
    assert eng.program_facts["merge_global"] is (merge == "global")
    text = eng.program_text("run")
    for scope in ("engine.pop", "engine.judge", "engine.flush",
                  "engine.exchange", "engine.merge"):
        assert f"/{scope}/" in text, scope
    # the in-step judge sits inside the pop loop; the hoisted one in
    # the flush
    outer = "engine.flush" if hoisted else "engine.pop"
    assert f"{outer}/engine.judge/" in text or \
        f"{outer}/while/body/engine.judge/" in text
    # a program never dispatched has no text
    assert eng.program_text("flush") is None


def test_pop_strategy_identical_traces_phold():
    """One-hot masked-reduction head reads vs take_along_axis: the
    pop loop must yield the same event order (and thus bit-identical
    traces) on lossy multi-lane phold over the 8-device mesh."""
    outs = {}
    for strategy in ("gather", "onehot"):
        yaml = PHOLD_YAML.format(policy="tpu", seed=7, loss=0.1, q=8,
                                 msgload=3)
        yaml = yaml.replace(
            "experimental:",
            f"experimental:\n  pop_strategy: {strategy}")
        c = Controller(load_config_str(yaml))
        stats = c.run()
        assert stats.ok, strategy
        outs[strategy] = (stats.events_executed, stats.packets_sent,
                          stats.packets_dropped,
                          [h.trace_checksum for h in c.sim.hosts])
    assert outs["gather"] == outs["onehot"]


def test_merge_strategy_identical_traces_all_gather():
    """The all_gather exchange fallback under the global merge:
    every shard replicates raw outbox rows and keeps its own via the
    destination mask; traces must match the window path."""
    outs = {}
    for strategy in ("window", "global"):
        yaml = PHOLD_YAML.format(policy="tpu", seed=3, loss=0.05, q=8,
                                 msgload=2)
        yaml = yaml.replace(
            "experimental:",
            "experimental:\n  exchange: all_gather\n"
            f"  merge_strategy: {strategy}")
        c = Controller(load_config_str(yaml))
        stats = c.run()
        assert stats.ok, strategy
        outs[strategy] = (stats.events_executed, stats.packets_sent,
                          stats.packets_dropped,
                          [h.trace_checksum for h in c.sim.hosts])
    assert outs["window"] == outs["global"]


def _groups_yaml(groups, app, stop="2s", seed=7, loss=0.1, V=3):
    """A lossy V-vertex graph and one host group per (name, vertex,
    quantity, processes line) entry, in order: host ids follow the
    groups, so the groups lay out host_vertex's runs."""
    nodes = "\n".join(
        f'        node [ id {v} bandwidth_down "1 Gbit" '
        f'bandwidth_up "1 Gbit" ]' for v in range(V))
    edges = "\n".join(
        f"        edge [ source {a} target {b} "
        f'latency "{10 + 7 * a + 3 * b} ms" packet_loss {loss} ]'
        for a in range(V) for b in range(a, V))
    hosts = "".join(
        f"  {name}:\n    quantity: {q}\n    network_node_id: {v}\n"
        f"    processes:\n    - {proc}\n"
        for name, v, q, proc in groups)
    return (f"general: {{stop_time: {stop}, seed: {seed}}}\n"
            "network:\n  graph:\n    type: gml\n    inline: |\n"
            f"      graph [ directed 0\n{nodes}\n{edges}\n      ]\n"
            f"experimental:\n  scheduler_policy: tpu\n{app}"
            f"hosts:\n{hosts}")


def _table_case(case):
    """(yaml, host-vertex runs the one-hot program unrolls; 0 where
    the lookups must fall back to the gathers)."""
    if case == "phold_lossy":
        return PHOLD_YAML.format(policy="tpu", seed=7, loss=0.1, q=8,
                                 msgload=3), 2
    if case == "tgen_groups":
        # vertex 0 holds three runs that are not contiguous (the
        # server, a client group, the padding); 9 hosts pad to 16
        # over the 8-device mesh: runs 0 | 1 1 1 | 0 0 | 2 2 2 | 0 x7
        client = ("{path: model:tgen_client, args: server=server "
                  "size=60KiB count=2 pause=100ms retry=300ms, "
                  "start_time: 100ms}")
        groups = [("server", 0, 1,
                   "{path: model:tgen_server, start_time: 10ms}"),
                  ("ca", 1, 3, client), ("cb", 0, 2, client),
                  ("cc", 2, 3, client)]
        return _groups_yaml(groups, "  event_capacity: 192\n"
                            "  outbox_capacity: 256\n",
                            stop="1500ms", loss=0.05), 5
    # more than 128 runs: 130 one-host groups alternate between two
    # vertices (131 runs with the padding)
    phold = "{path: model:phold, args: msgload=1, start_time: 100ms}"
    groups = [(f"h{i:03d}", i % 2, 1, phold) for i in range(130)]
    return _groups_yaml(groups, "  event_capacity: 32\n"
                        "  outbox_capacity: 8\n", stop="600ms",
                        V=2), 0


@pytest.mark.parametrize("case", ["phold_lossy", "tgen_groups",
                                  "many_runs"])
def test_table_strategy_identical_traces(case):
    """Compare-select topology lookups vs indexed gathers (lossy, so
    the reliability feeds real drop rolls) over the 8-device mesh:
    selection is exact, traces must bit-match. The one-hot program
    unrolls host_vertex's runs; past 128 it keeps the gathers."""
    yaml0, runs = _table_case(case)
    outs = {}
    for strategy in ("gather", "onehot"):
        yaml = yaml0.replace(
            "experimental:",
            "experimental:\n  judge_placement: flush\n"
            f"  table_strategy: {strategy}")
        c = Controller(load_config_str(yaml))
        stats = c.run()
        assert stats.ok, strategy
        facts = c.runner.engine.program_facts
        on = strategy == "onehot" and runs > 0
        assert facts["table_onehot"] is on
        assert facts["vertex_runs"] == (runs if on else 0)
        assert c.runner.engine.effective["vertex_runs"] == \
            facts["vertex_runs"]
        outs[strategy] = (stats.events_executed, stats.packets_sent,
                          stats.packets_dropped,
                          [h.trace_checksum for h in c.sim.hosts])
    assert outs["gather"][1] > 0
    assert outs["gather"] == outs["onehot"]


def _judge_gathers(hlo_text):
    """Gather instructions of an optimized HLO text whose op_name lies
    under engine.judge; a fused gather without metadata takes the
    op_name of the instruction that calls its computation."""
    import re

    comp_of, op_of, callers, gathers = {}, {}, {}, []
    comp = None
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$", line)
        if head and " = " not in line:
            comp = head.group(1)
            continue
        m = re.match(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ", line)
        if not m:
            continue
        name = m.group(1)
        comp_of[name] = comp
        op = re.search(r'op_name="([^"]*)"', line)
        op_of[name] = op.group(1) if op else None
        for called in re.findall(
                r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", line):
            callers.setdefault(called, name)
        if re.search(r"\bgather\(", line):
            gathers.append(name)

    def op_name(name, depth=0):
        if op_of.get(name) or depth > 32:
            return op_of.get(name) or ""
        caller = callers.get(comp_of.get(name))
        return op_name(caller, depth + 1) if caller else ""

    return [g for g in gathers if "engine.judge" in op_name(g)]


@pytest.mark.parametrize("placement", ["flush", "step"])
def test_table_onehot_judge_makes_no_gathers(placement):
    """With table_strategy: onehot the dispatched round program's
    judge (hoisted to the flush, or in the pop loop's step) holds no
    gather; with the gathers it holds some."""
    found = {}
    for strategy in ("gather", "onehot"):
        yaml = PHOLD_YAML.format(policy="tpu", seed=7, loss=0.1, q=8,
                                 msgload=3)
        yaml = yaml.replace(
            "experimental:",
            f"experimental:\n  judge_placement: {placement}\n"
            f"  table_strategy: {strategy}")
        c = Controller(load_config_str(yaml))
        assert c.run().ok
        eng = c.runner.engine
        assert eng.program_facts["table_onehot"] is (strategy == "onehot")
        assert eng.program_facts["vertex_runs"] == \
            (2 if strategy == "onehot" else 0)
        found[strategy] = _judge_gathers(eng.program_text("run"))
    assert found["gather"]
    assert found["onehot"] == []


@pytest.mark.parametrize("case", ["legal", "cpu_auto",
                                  "model_bandwidth", "fault_epochs",
                                  "hierarchical", "wide_graph"])
def test_table_onehot_legality(case):
    """Outside the compare-select lookups' legality the engine builds
    the gathers' program (table_onehot false, vertex_runs 0), and on
    the CPU `auto` resolves to the gathers."""
    from shadow_tpu.device.apps import PholdDevice
    from shadow_tpu.device.engine import DeviceEngine, EngineConfig

    H, V = 8, (12 if case == "wide_graph" else 2)
    hv = (np.arange(H) % V).astype(np.int32)
    lat = np.full((V, V), 1_000_000, np.int64)
    rel = np.full((V, V), 0.9, np.float32)
    kw = {}
    if case == "fault_epochs":
        lat, rel = np.stack([lat, lat]), np.stack([rel, rel])
        kw["epoch_times"] = np.array([0, 5_000_000], np.int64)
    if case == "hierarchical":
        one = np.full(V, 1_000_000, np.int64)
        lat = (np.zeros((1, 1), np.int64), np.zeros(V, np.int32), one,
               one)
        rel = (np.ones((1, 1), np.float32), np.zeros(V, np.int32),
               np.full(V, 0.9, np.float32), np.ones(V, np.float32))
    eng = DeviceEngine(
        EngineConfig(n_hosts=H, event_capacity=8, outbox_capacity=8,
                     lookahead=1_000_000, stop_time=10_000_000,
                     model_bandwidth=case == "model_bandwidth",
                     table_onehot=None if case == "cpu_auto" else True),
        PholdDevice(n_hosts_total=H, msgload=2), hv, lat, rel, **kw)
    on = case == "legal"
    assert eng.program_facts["table_onehot"] is on
    assert eng.program_facts["vertex_runs"] == (H if on else 0)
    assert eng.effective["table_onehot"] is on


def test_table_onehot_ensemble_replicas_match_single_worlds():
    """The vmapped ensemble program reads each replica's own latency
    and reliability tables through the run table: replicas with
    different tables and seeds equal single-world runs of the gather
    program, over the 8-device mesh with padded hosts."""
    from shadow_tpu import simtime
    from shadow_tpu.device.apps import PholdDevice
    from shadow_tpu.device.engine import DeviceEngine, EngineConfig
    from shadow_tpu.ensemble.spec import seed_key_np

    H, seeds = 12, (3, 11)
    hv = np.array([0, 0, 1, 1, 1, 0, 2, 2, 0, 1, 1, 2], np.int32)
    lat = np.stack([np.array([[10, 20, 30], [20, 12, 25], [30, 25, 14]]),
                    np.array([[11, 40, 22], [40, 9, 31], [22, 31, 16]])]
                   ).astype(np.int32) * 1_000_000
    rel = np.stack([np.full((3, 3), 0.9), np.full((3, 3), 0.8)]) \
        .astype(np.float32)
    rel[1, 0, 0] = 1.0

    class Worlds:
        R = 2
        latency, reliability = lat, rel
        epoch_times = np.zeros((2, 1), np.int64)
        seed_k1 = np.array([seed_key_np(s)[0] for s in seeds], np.uint32)
        seed_k2 = np.array([seed_key_np(s)[1] for s in seeds], np.uint32)

    starts = [(h, simtime.from_millis(1 + h), -1) for h in range(H)]

    def engine(seed, onehot, **kw):
        return DeviceEngine(
            EngineConfig(n_hosts=H, event_capacity=16,
                         outbox_capacity=8, lookahead=9_000_000,
                         stop_time=simtime.from_millis(300), seed=seed,
                         exchange="all_to_all", table_onehot=onehot,
                         judge_hoist=True),
            PholdDevice(n_hosts_total=H, msgload=2, size=64),
            host_vertex=hv, **kw)

    ens = engine(seeds[0], True, latency_ns=lat[0], reliability=rel[0],
                 ensemble=Worlds)
    assert ens.program_facts["table_onehot"] is True
    # seven runs of hosts, then the padding (vertex 0) up to 16
    assert ens.program_facts["vertex_runs"] == 8
    got, _ = ens.run_ensemble(ens.init_ensemble_state(starts))
    for r, seed in enumerate(seeds):
        one = engine(seed, False, latency_ns=lat[r], reliability=rel[r])
        want, _ = one.run(one.init_state(starts))
        for k in ("chk", "n_exec", "n_sent", "n_drop"):
            assert (np.asarray(got[k])[r] == np.asarray(want[k])).all(), \
                (r, k)
        assert int(np.asarray(want["n_drop"]).sum()) > 0


TOR_GROUPS = [
    ("relay", 0, 8, "{path: model:tor_relay, start_time: 100ms}"),
    ("client", 1, 16, "{path: model:tor_client, args: cells=48 "
     "count=2 pause=500ms retry=2s, start_time: 1s}")]


def _payload_case(case):
    """(yaml, mesh shards) of a merge_payload parity case."""
    if case == "phold_lossy":
        # one shard: the merge row is [heap | arrivals], E + IN wide
        return PHOLD_YAML.format(policy="tpu", seed=7, loss=0.1, q=8,
                                 msgload=3).replace(
            "experimental:", "experimental:\n  mesh_shards: 1"), 1
    if case == "phold_all_to_all":
        # the all_to_all self-shard bypass adds a second arrival
        # block: the row is E + 2*IN wide
        return PHOLD_YAML.format(policy="tpu", seed=3, loss=0.05, q=8,
                                 msgload=3).replace(
            "experimental:", "experimental:\n  exchange: all_to_all"), 8
    if case == "phold_two_phase":
        # relayed forwards reach the post-exchange block keyed to
        # other shards' hosts
        return PHOLD_YAML.format(policy="tpu", seed=5, loss=0.05, q=8,
                                 msgload=3).replace(
            "experimental:", "experimental:\n  exchange: two_phase"), 8
    if case == "phold_all_gather":
        # every shard's rows and keys replicated, then windowed
        return PHOLD_YAML.format(policy="tpu", seed=11, loss=0.05, q=8,
                                 msgload=3).replace(
            "experimental:", "experimental:\n  exchange: all_gather"), 8
    if case == "tgen_burst":
        yaml, _ = _table_case("tgen_groups")
        return yaml, 8
    # onion trains forwarded across hops, relay burst pops
    return _groups_yaml(TOR_GROUPS, "  event_capacity: 96\n"
                        "  outbox_capacity: 48\n", stop="4s",
                        loss=0.05), 8


def _payload_controller(monkeypatch, yaml, payload):
    """A window-merge Controller whose engines pin
    EngineConfig.merge_payload (the engine picks it by platform; no
    config option sets it)."""
    import functools

    from shadow_tpu.device import runner
    from shadow_tpu.device.engine import EngineConfig

    monkeypatch.setattr(runner, "EngineConfig", functools.partial(
        EngineConfig, merge_payload=payload))
    return Controller(load_config_str(yaml.replace(
        "experimental:", "experimental:\n  merge_strategy: window")))


@pytest.mark.parametrize("case", ["phold_lossy", "phold_all_to_all",
                                  "phold_two_phase", "phold_all_gather",
                                  "tgen_burst", "tor"])
def test_merge_payload_identical_traces(monkeypatch, case):
    """The window merge carrying its payload through its sorts (the
    filler-sorted arrival windows, the row sort) vs recovering it with
    gathers (seg_take, take_along_axis): identical stats, traces and
    windowed arrival rows, on one shard and over the 8-device mesh
    (the all_to_all self-shard bypass and post-exchange block, the
    two-phase route's relayed forwards, the all_gather replicas)."""
    yaml, shards = _payload_case(case)
    outs = {}
    for payload in ("gather", "sort"):
        c = _payload_controller(monkeypatch, yaml, payload)
        stats = c.run()
        assert stats.ok, payload
        eng = c.runner.engine
        assert eng.program_facts["merge_global"] is False
        assert eng.program_facts["merge_payload"] == payload
        assert eng.n_shards == shards
        final = c.runner.final_state
        outs[payload] = (stats.events_executed, stats.packets_sent,
                         stats.packets_dropped,
                         [h.trace_checksum for h in c.sim.hosts],
                         int(np.asarray(final["occ_arrivals"]).sum()))
    assert outs["gather"][2] > 0
    assert outs["gather"][4] > 0
    assert outs["gather"] == outs["sort"]


@pytest.mark.parametrize("case", ["tor", "tgen_burst"])
def test_merge_payload_live_heap_slots_equal(monkeypatch, case):
    """Round by round on one shard, the two payload recoveries leave
    every live heap slot (ht < INF) equal in all five columns, and the
    same head cursors: on lossy Tor (train masks in w) and tgen (its
    32-packet trains set w's top bit)."""
    yaml = _payload_case(case)[0].replace(
        "experimental:", "experimental:\n  mesh_shards: 1")
    runs = []
    for payload in ("gather", "sort"):
        c = _payload_controller(monkeypatch, yaml, payload)
        eng = c.runner.engine
        assert eng.program_facts["merge_payload"] == payload
        runs.append((eng, eng.init_state(c.sim.starts),
                     eng.host_vertex_device(), eng.world()))
    INF = 1 << 62
    stop = runs[0][0].config.stop_time
    trains = top_bit = padded = rounds = 0
    while True:
        ht, head = np.asarray(runs[0][1]["ht"]), \
            np.asarray(runs[0][1]["head"])
        nxt = int(np.take_along_axis(ht, head[:, None].clip(
            max=ht.shape[1] - 1), 1).min())
        if nxt >= stop:
            break
        win_end = np.int64(nxt + runs[0][0].config.lookahead)
        sts = []
        for i, (eng, st, hv, world) in enumerate(runs):
            st, _ = eng._round_step(st, win_end, hv, world)
            runs[i] = (eng, st, hv, world)
            sts.append({k: np.asarray(st[k])
                        for k in ("ht", "hk", "hm", "hv", "hw", "head")})
        a, b = sts
        live = a["ht"] < INF
        assert (live == (b["ht"] < INF)).all()
        for k in ("ht", "hk", "hm", "hv", "hw"):
            assert (a[k][live] == b[k][live]).all(), (rounds, k)
        assert (a["head"] == b["head"]).all()
        trains += int((a["hw"][live] != 0).sum())
        top_bit += int(((a["hw"][live] >> 31) & 1).sum())
        padded += int((~live).sum())
        rounds += 1
    assert int(np.asarray(runs[1][1]["overflow"]).sum()) == 0
    assert rounds > 20 and trains > 0 and padded > 0
    assert top_bit > 0 or case == "tor"


def _window_rows(rng, span, arrivals, top_host):
    """Unsorted flat rows for the windowing unit test: `arrivals[g]`
    rows keyed to global host g, each with a distinct okey (one row of
    `top_host` with the largest, span - 1), plus empty (IMAX) rows;
    returns (ukey, rows of the XF fields)."""
    from shadow_tpu.device.engine import IMAX, INF, XF

    n = int(sum(arrivals))
    okey = rng.choice(span - 1, n, replace=False)
    dst = np.repeat(np.arange(len(arrivals)), arrivals)
    okey[np.flatnonzero(dst == top_host)[0]] = span - 1
    ukey = np.concatenate([dst * span + okey,
                           np.full(n // 2 + 3, IMAX)]).astype(np.int64)
    order = rng.permutation(ukey.size)
    ukey = ukey[order]
    rows = {f: rng.integers(0, 1 << 62, ukey.size).astype(np.int64)
            for f in XF}
    rows["t"] = np.where(ukey < IMAX, rows["t"] >> 2, INF)
    return ukey, rows


@pytest.mark.parametrize("overflow", [False, True])
def test_sorted_windows_match_seg_take(overflow):
    """The windowing alone: sorted_windows against seg_take through
    the (key, iota) sort, for shard 1 of 3 — empty hosts, a host with
    exactly IN arrivals, rows keyed to the other shards' hosts, a real
    okey of SPAN - 1 — bit-identical. With one host past IN the
    overflow count is equal, and every host before it still is."""
    from shadow_tpu._jax import jnp
    from jax import lax

    from shadow_tpu.device.engine import merge_cols, seg_take, \
        sorted_windows

    rng = np.random.default_rng(29)
    S, H_loc, OB, IN = 3, 16, 8, 6
    arrivals = rng.integers(0, IN, S * H_loc)
    arrivals[H_loc + 2:H_loc + 5] = 0           # empty hosts
    arrivals[H_loc + 7] = IN                    # exactly full
    arrivals[H_loc + 9] = 2                     # takes okey SPAN - 1
    if overflow:
        arrivals[H_loc + 11] = IN + 3
    span = S * H_loc * OB
    ukey, rows = _window_rows(rng, span, arrivals, H_loc + 9)
    ukey = jnp.asarray(ukey)
    rows = {f: jnp.asarray(v) for f, v in rows.items()}
    lo = jnp.int64(H_loc)
    skey, perm = lax.sort((ukey, jnp.arange(ukey.shape[0],
                                            dtype=jnp.int64)),
                          num_keys=1)
    edges = jnp.searchsorted(skey, (lo + jnp.arange(H_loc + 1)) * span)
    starts, counts = edges[:-1], edges[1:] - edges[:-1]
    assert (np.asarray(counts) == arrivals[H_loc:2 * H_loc]).all()
    want = merge_cols(seg_take(perm, rows, starts, counts, IN))
    got = sorted_windows(ukey, merge_cols(rows), counts, lo, span, IN)
    over = np.maximum(0, np.asarray(counts) - IN)
    assert over.sum() == (3 if overflow else 0)
    ok = slice(0, 11) if overflow else slice(None)
    for w, g in zip(want, got):
        w, g = np.asarray(w), np.asarray(g)
        assert w.shape == g.shape == (H_loc, IN)
        assert (w[ok] == g[ok]).all()
    filled = np.asarray(want[0]) < (1 << 62)
    assert filled[7].all() and not filled[2:5].any()


def test_outbox_compact_global_identical_traces():
    """Gatherless compaction on the GLOBAL merge path (lane sort +
    static slice): with a width that fits the real per-host fan-out,
    traces must bit-match the uncompacted global merge — on the
    8-device mesh over both exchanges (all_to_all self-shard rows and
    the all_gather replication, whose ICI volume compaction cuts)."""
    for exchange in ("all_to_all", "all_gather"):
        outs = {}
        for cx in (0, 12):
            yaml = PHOLD_YAML.format(policy="tpu", seed=7, loss=0.1,
                                     q=8, msgload=3)
            yaml = yaml.replace(
                "experimental:",
                f"experimental:\n  exchange: {exchange}\n"
                f"  merge_strategy: global\n  outbox_compact: {cx}")
            c = Controller(load_config_str(yaml))
            stats = c.run()
            assert stats.ok, (exchange, cx)
            outs[cx] = (stats.events_executed, stats.packets_sent,
                        stats.packets_dropped,
                        [h.trace_checksum for h in c.sim.hosts])
        assert outs[0] == outs[12], exchange


def test_outbox_compact_global_overflow_detected():
    """A compaction width smaller than a host's real per-phase
    fan-out must fail LOUDLY (x_overflow), never silently drop."""
    yaml = PHOLD_YAML.format(policy="tpu", seed=7, loss=0.1, q=8,
                             msgload=3)
    yaml = yaml.replace(
        "experimental:",
        "experimental:\n  merge_strategy: global\n"
        "  outbox_compact: 1")
    c = Controller(load_config_str(yaml))
    stats = c.run()
    assert not stats.ok


def test_merge_global_overflow_detected():
    """Hub skew under the global merge: 999 clients hammering one
    server must fail LOUDLY at small event_capacity (rank-based
    overflow, same contract as the window path's arrival-window
    overflow) and, once the knob is raised, bit-match the window
    path."""
    yaml = HUB_YAML.format(exchange="all_to_all", ecap=64).replace(
        "experimental:", "experimental:\n  merge_strategy: global")
    c = Controller(load_config_str(yaml))
    stats = c.run()
    assert not stats.ok

    out = {}
    for strategy in ("window", "global"):
        yaml = HUB_YAML.format(exchange="all_to_all",
                               ecap=1024).replace(
            "experimental:",
            f"experimental:\n  merge_strategy: {strategy}")
        c = Controller(load_config_str(yaml))
        stats = c.run()
        assert stats.ok, strategy
        out[strategy] = [h.trace_checksum for h in c.sim.hosts]
    assert out["window"] == out["global"]


def test_device_deterministic_across_runs():
    _, h1 = _run("tpu", seed=9)
    _, h2 = _run("tpu", seed=9)
    assert [h.trace_checksum for h in h1] == \
        [h.trace_checksum for h in h2]
    _, h3 = _run("tpu", seed=10)
    assert [h.trace_checksum for h in h1] != \
        [h.trace_checksum for h in h3]


def test_device_app_state_matches_cpu():
    from shadow_tpu.core.controller import Controller as C
    yaml = PHOLD_YAML.format(policy="serial", seed=3, loss=0.05, q=4,
                             msgload=1)
    c = C(load_config_str(yaml))
    c.run()
    cpu_recv = [h.app.received for h in c.sim.hosts]

    yaml = PHOLD_YAML.format(policy="tpu", seed=3, loss=0.05, q=4,
                             msgload=1)
    c2 = C(load_config_str(yaml))
    c2.run()
    dev_recv = list(np.asarray(
        c2.runner.final_state["app"][:len(c2.sim.hosts), 0]))
    assert cpu_recv == dev_recv


def test_path_packet_counters_match_oracle():
    """topology_incrementPathPacketCounter parity (ref topology.c:1983):
    the device's flush-time [V,V] histogram equals the CPU oracle's
    per-path judged-packet counts — drop-rolled packets included."""
    from shadow_tpu.config import load_config_str

    def run(policy):
        yaml = PHOLD_YAML.format(policy=policy, seed=5, loss=0.1, q=8,
                                 msgload=2)
        yaml += "\n"
        cfg = load_config_str(
            yaml, overrides=["experimental.count_paths=true"])
        c = Controller(cfg)
        stats = c.run()
        assert stats.ok
        return dict(c.sim.netmodel.path_packets)

    s = run("serial")
    d = run("tpu")
    assert s and sum(s.values()) > 200
    assert s == d


HUB_YAML = """
general:
  stop_time: 4s
  seed: 11
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 bandwidth_down "1 Gbit" bandwidth_up "1 Gbit" ]
        node [ id 1 bandwidth_down "1 Gbit" bandwidth_up "1 Gbit" ]
        edge [ source 0 target 0 latency "5 ms" packet_loss 0.001 ]
        edge [ source 0 target 1 latency "20 ms" packet_loss 0.001 ]
        edge [ source 1 target 1 latency "5 ms" packet_loss 0.001 ]
      ]
experimental:
  scheduler_policy: tpu
  exchange: {exchange}
  event_capacity: {ecap}
hosts:
  server_hub:
    network_node_id: 0
    processes: [{{path: model:tgen_server, start_time: 1s}}]
  clients:
    quantity: 999
    network_node_id: 1
    processes:
    - {{path: model:tgen_client, args: server=server_hub size=4KiB count=1, start_time: 2s}}
"""


def test_hub_skew_exchange(caplog):
    """SURVEY hard-part #2 at skew: 999 clients all hammering ONE
    server shard (maximum (src,dst)-pair concentration). With default
    capacities the run must FAIL LOUDLY (the hub's per-flush arrival
    window overflows; no silent loss). With event_capacity raised,
    the auto-sized all_to_all CAP must hold — zero x_overflow — and
    bit-match the all_gather oracle on the same config."""
    import logging

    # 1: default capacities -> loud failure with the capacity knob
    # named in the error (never a wrong answer)
    c = Controller(load_config_str(
        HUB_YAML.format(exchange="all_to_all", ecap=64)))
    with caplog.at_level(logging.ERROR):
        stats = c.run()
    assert not stats.ok
    assert any("capacity" in r.message for r in caplog.records)

    # 2: the documented knob fixes it; auto CAP holds at full skew
    out = {}
    for mode in ("all_to_all", "all_gather"):
        c = Controller(load_config_str(
            HUB_YAML.format(exchange=mode, ecap=1024)))
        stats = c.run()
        assert stats.ok, mode
        x_of = int(np.asarray(
            c.runner.final_state["x_overflow"]).sum())
        assert x_of == 0, mode
        assert stats.packets_sent > 999     # requests + responses
        out[mode] = [h.trace_checksum for h in c.sim.hosts]
    assert out["all_to_all"] == out["all_gather"]


def test_self_shard_rows_bypass_exchange_capacity():
    """ADVICE r3 #4: self-shard rows (timers, local sends) never
    enter the all_to_all pack — a fully shard-local workload runs
    with exchange_capacity=1 and zero x_overflow (it used to consume
    CAP and overflow)."""
    yaml = """
general:
  stop_time: 4s
  seed: 2
network:
  graph:
    type: gml
    inline: |
      graph [ directed 0
        node [ id 0 bandwidth_down "1 Gbit" bandwidth_up "1 Gbit" ]
        edge [ source 0 target 0 latency "10 ms" packet_loss 0.01 ]
      ]
experimental:
  scheduler_policy: tpu
  exchange: all_to_all
  exchange_capacity: 1
hosts:
"""
    # 8 adjacent (server, client) pairs -> 16 hosts over the 8-device
    # mesh (H_loc=2): every pair is shard-local, all traffic self-shard
    for i in range(8):
        yaml += f"""  server{i}:
    network_node_id: 0
    processes: [{{path: model:tgen_server, start_time: 10ms}}]
  client{i}:
    network_node_id: 0
    processes:
    - {{path: model:tgen_client, args: server=server{i} size=64KiB count=2 pause=100ms, start_time: 100ms}}
"""
    c = Controller(load_config_str(yaml))
    stats = c.run()
    assert stats.ok
    assert int(np.asarray(c.runner.final_state["x_overflow"]).sum()) \
        == 0
    assert stats.packets_sent > 0
    # and the serial oracle agrees bit-for-bit
    c2 = Controller(load_config_str(
        yaml.replace("scheduler_policy: tpu",
                     "scheduler_policy: serial")))
    s2 = c2.run()
    assert s2.ok
    assert [h.trace_checksum for h in c2.sim.hosts] == \
        [h.trace_checksum for h in c.sim.hosts]


@pytest.mark.parametrize("app", ["phold", "tgen", "tor"])
def test_round_program_names_the_app_stage(app):
    """The app's handler (and the draws that feed it) runs under its
    own scope inside the pop loop: the optimized round program has ops
    whose innermost scope is `engine.app`, for every device app, and
    the app's events still equal the serial policy's."""
    from perfbench import stages

    if app == "tor":
        relay = "{path: model:tor_relay, start_time: 100ms}"
        client = ("{path: model:tor_client, args: cells=40 count=2 "
                  "pause=200ms retry=1s, start_time: 200ms}")
        groups = [("relay", 0, 4, relay), ("client", 1, 6, client)]
    elif app == "tgen":
        client = ("{path: model:tgen_client, args: server=server "
                  "size=60KiB count=2 pause=100ms retry=300ms, "
                  "start_time: 100ms}")
        groups = [("server", 0, 1,
                   "{path: model:tgen_server, start_time: 10ms}"),
                  ("client", 1, 4, client)]
    else:
        groups = [("h", 0, 6, "{path: model:phold, args: msgload=2, "
                               "start_time: 100ms}")]
    yaml = _groups_yaml(groups, "  event_capacity: 64\n"
                        "  outbox_capacity: 32\n", stop="1500ms", V=2)
    c = Controller(load_config_str(yaml))
    assert c.run().ok
    by_stage = stages.stage_map(c.runner.engine.program_text("run"))
    assert "engine.app" in by_stage.values()
    assert "engine.pop" in by_stage.values()
    serial = Controller(load_config_str(yaml.replace(
        "scheduler_policy: tpu", "scheduler_policy: serial")))
    serial.run()
    assert [h.trace_checksum for h in c.sim.hosts] == \
        [h.trace_checksum for h in serial.sim.hosts]
