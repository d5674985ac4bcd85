"""Occupancy telemetry + adaptive capacity planner (device/capacity.py).

Three contracts:
* the engine's occ_* high-water marks equal brute-force occupancies
  replayed from the serial oracle's event trace (same window loop,
  pure Python);
* a planner-sized engine produces bit-identical per-host trace
  checksums to the statically-sized engine (capacities are purely a
  performance lever while nothing overflows);
* a plan that undershoots (warm-up slice ends before real traffic)
  trips the loud overflow counters, re-plans with doubled headroom,
  and COMPLETES with the static run's trace instead of failing.
"""

import json
import os

import numpy as np
import pytest

from shadow_tpu.config import load_config_str
from shadow_tpu.config.loader import load_config
from shadow_tpu.core.controller import Controller
from shadow_tpu.core.event import KIND_BOOT, KIND_PACKET
from shadow_tpu.device import capacity

PHOLD_YAML = """
general:
  stop_time: {stop}
  seed: 9
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 bandwidth_down "100 Mbit" bandwidth_up "100 Mbit" ]
        node [ id 1 bandwidth_down "100 Mbit" bandwidth_up "100 Mbit" ]
        edge [ source 0 target 0 latency "30 ms" packet_loss 0.0 ]
        edge [ source 0 target 1 latency "10 ms" packet_loss 0.0 ]
        edge [ source 1 target 1 latency "30 ms" packet_loss 0.0 ]
      ]
experimental:
  scheduler_policy: {policy}
  event_capacity: 64
  outbox_capacity: 16
{extra}hosts:
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


def _cfg(policy, stop="1s", q=3, msgload=2, extra=""):
    return load_config_str(PHOLD_YAML.format(
        policy=policy, stop=stop, q=q, msgload=msgload, extra=extra))


def _checksums(hosts):
    return [h.trace_checksum for h in hosts]


# ---------------------------------------------------------------------
# (a) telemetry vs brute force from the serial oracle's trace
# ---------------------------------------------------------------------

def _replay_windows(boots, packets, H, L, stop, msgload, H_loc, S,
                    split_in=False):
    """Replay the device engine's window loop in pure Python from the
    oracle's event times: windows open at the global min pending time,
    close at min(nxt + lookahead, stop); events with time < win_end
    pop (emitting their sends), packets sent in the window arrive at
    its flush. Returns per-host/per-pair occupancy high-water marks —
    what the engine's reduction-only occ_* telemetry must equal."""
    live = [(t, h, msgload) for t, h in boots]   # (exec_t, host, sends)
    pkts = sorted(packets)                        # by send_time
    ip = 0
    occ_heap, occ_in, occ_ob = [0] * H, [0] * H, [0] * H
    occ_x = np.zeros((S, S), dtype=int)
    trips_max, phases, arrivals = 0, 0, 0
    iters = np.zeros(S, dtype=int)
    while live:
        nxt = min(t for t, _, _ in live)
        if nxt >= stop:
            break
        win_end = min(nxt + L, stop)
        popped = [e for e in live if e[0] < win_end]
        live = [e for e in live if e[0] >= win_end]
        ob, per_exec = [0] * H, [0] * H
        # the windowed all_to_all path accepts self-shard and remote
        # arrivals as two separate IN-wide blocks, so its occ_in is
        # the per-block max; single-shard (and the global merge)
        # windows them jointly
        inn, inn_far = [0] * H, [0] * H
        for _, h, k in popped:
            ob[h] += k
            per_exec[h] += 1
        x = np.zeros((S, S), dtype=int)
        while ip < len(pkts) and pkts[ip][0] < win_end:
            send_t, exec_t, src, dst = pkts[ip]
            ip += 1
            assert send_t >= nxt, "arrival from a pre-window send"
            live.append((exec_t, dst, 1))
            arrivals += 1
            if split_in and src // H_loc != dst // H_loc:
                inn_far[dst] += 1
            else:
                inn[dst] += 1
            if src // H_loc != dst // H_loc:
                x[src // H_loc][dst // H_loc] += 1
        heap_now = [0] * H
        for _, h, _ in live:
            heap_now[h] += 1
        for h in range(H):
            occ_ob[h] = max(occ_ob[h], ob[h])
            occ_in[h] = max(occ_in[h], inn[h], inn_far[h])
            occ_heap[h] = max(occ_heap[h], heap_now[h])
        occ_x = np.maximum(occ_x, x)
        trips_max = max(trips_max, max(per_exec))
        # each shard's pop loop runs until ITS busiest host is done
        for s in range(S):
            iters[s] += max(per_exec[s * H_loc:(s + 1) * H_loc],
                            default=0)
        phases += 1
    # the oracle trace is taken from a LONGER run so sends still in
    # flight at `stop` are visible (they ride the exchange and sit in
    # heaps without ever executing); anything left over must be sends
    # from events at/after `stop` — outside the replayed run entirely
    assert all(p[0] >= stop for p in pkts[ip:]), \
        "trace packets the replay never delivered"
    return dict(heap=occ_heap, inn=occ_in, ob=occ_ob, x=occ_x,
                trips=trips_max, phases=phases, iters=iters,
                arrivals=arrivals)


@pytest.mark.parametrize("merge", [
    "auto",
    # the global-merge path measures occ_in/occ_heap with different
    # arithmetic (searchsorted segments); covered outside tier-1
    pytest.param("global", marks=pytest.mark.slow),
])
def test_occupancy_marks_match_trace_brute_force(merge):
    msgload, q = 2, 3
    trace = []
    # the oracle runs PAST the device stop: events before `stop` are
    # identical (DES prefix determinism), and the longer trace also
    # shows packets sent before `stop` that deliver after it — the
    # device ships and heap-inserts those without executing them, so
    # the replay must see them to match occ_in/occ_x/occ_heap
    s = Controller(_cfg("serial", stop="1200ms", q=q,
                        msgload=msgload), trace=trace)
    s.run()

    d = Controller(_cfg("tpu", q=q, msgload=msgload,
                        extra=f"  merge_strategy: {merge}\n"))
    stats = d.run()
    assert stats.ok
    eng = d.runner.engine
    H = len(d.sim.hosts)
    L = max(1, d.sim.lookahead)
    stop = d.cfg.general.stop_time

    vertex = np.asarray(d.sim.netmodel.host_vertex)
    lat = np.asarray(d.sim.topology.latency_ns)
    boots = [(t, h) for h, t, *_ in d.sim.starts]
    packets = []
    for t, dst, src, kind in trace:
        if kind == KIND_PACKET:
            send_t = t - int(lat[vertex[src], vertex[dst]])
            packets.append((send_t, t, src, dst))
        else:
            assert kind == KIND_BOOT, f"unexpected kind {kind}"

    ref = _replay_windows(boots, packets, H, L, stop, msgload,
                          eng.H_loc, eng.n_shards,
                          split_in=(eng.n_shards > 1
                                    and merge != "global"))

    final = d.runner.final_state
    np.testing.assert_array_equal(
        np.asarray(final["occ_heap"])[:H], ref["heap"])
    np.testing.assert_array_equal(
        np.asarray(final["occ_in"])[:H], ref["inn"])
    np.testing.assert_array_equal(
        np.asarray(final["occ_ob"])[:H], ref["ob"])
    if merge != "global":
        # the global merge sorts all rows jointly — there is no
        # per-shard-pair exchange, so occ_x legitimately stays 0
        np.testing.assert_array_equal(np.asarray(final["occ_x"]),
                                      ref["x"])
    assert int(np.asarray(final["occ_phases"]).max()) == ref["phases"]
    # the pop loop runs one iteration per runnable event per host
    # (burst_pops=1 here); dirty-slot stalls could only add iterations
    trips = int(np.asarray(final["occ_trips"]).max())
    assert trips >= ref["trips"]
    # one phase per window (asserted above), so each shard's pop loop
    # ran as many iterations as its busiest host popped events
    np.testing.assert_array_equal(np.asarray(final["occ_iters"]),
                                  ref["iters"])
    assert stats.occupancy["measured"]["pop_iters"] == \
        ref["iters"].max()
    assert stats.occupancy is not None
    assert stats.occupancy["measured"]["heap_rows_max"] == \
        max(ref["heap"])
    # the window merge counts the real rows it windowed (every arrival,
    # since none overflowed); the global merge's state has no counter
    if eng.program_facts["merge_global"]:
        assert "arrivals_windowed" not in stats.occupancy["measured"]
    else:
        assert int(np.asarray(final["occ_arrivals"]).sum()) == \
            ref["arrivals"] > 0
        assert stats.occupancy["measured"]["arrivals_windowed"] == \
            ref["arrivals"]


# ---------------------------------------------------------------------
# planner pure functions
# ---------------------------------------------------------------------

def test_pop_iterations_count_a_hosts_events_in_one_window():
    """A host holding k events in one window costs the pop loop k
    iterations (one event per host per iteration): after one round
    occ_iters is k, with one flush, however many other hosts pop."""
    import jax

    from shadow_tpu.device.engine import INF

    k = 5
    c = Controller(_cfg("tpu", q=3, extra="  mesh_shards: 1\n"))
    eng = c.runner.engine
    st = eng.init_state(c.sim.starts)
    boot = 100_000_000                       # the left group's start
    ht, hk, hm, hw = (np.array(st[f]) for f in ("ht", "hk", "hm", "hw"))
    assert ht[0, 0] == boot and hm[0, 0] >> 32 == KIND_BOOT
    # host 0 also holds k-1 packets from host 1 just after its boot
    for j in range(1, k):
        ht[0, j] = boot + j
        hk[0, j] = (1 << 32) | j
        hm[0, j] = (KIND_PACKET << 32) | 512
        hw[0, j] = 1
    assert (ht[0, k:] == INF).all()
    for f, a in (("ht", ht), ("hk", hk), ("hm", hm), ("hw", hw)):
        st[f] = jax.device_put(a, st[f].sharding)
    win_end = boot + eng.config.lookahead
    out, _ = eng._round_step(st, np.int64(win_end),
                             eng.host_vertex_device(), eng.world())
    q = 3                                    # left hosts boot in window
    assert int(np.asarray(out["occ_iters"])[0]) == k
    assert int(np.asarray(out["occ_phases"])[0]) == 1
    assert int(np.asarray(out["n_exec"]).sum()) == q + k - 1


def test_plan_sizes_from_measurements():
    record = {"measured": {
        "heap_rows_max": 20, "outbox_rows_max": 6,
        "arrivals_per_flush_max": 10, "exchange_rows_max": 4,
        "pop_trips_max": 5, "phases": 100,
        "overflow": 0, "x_overflow": 0}}
    p = capacity.plan(record, per_iter=3, floor_iters=4, n_shards=4)
    assert p["event_capacity"] == 32            # ceil(20*1.5)+2
    assert p["exchange_in_capacity"] == 17      # ceil(10*1.5)+2
    assert p["outbox_capacity"] == 10 * 3       # ceil(5*1.5)+2 iters
    assert p["outbox_compact"] == 11            # ceil(6*1.5)+2 < 3/4*30
    assert p["exchange_capacity"] == 8          # ceil(4*1.5)+2
    # single shard: the exchange axis keeps the engine's auto-sizing
    p1 = capacity.plan(record, per_iter=3, n_shards=1)
    assert p1["exchange_capacity"] == 0
    # a compaction width near the outbox width stops paying for itself
    record["measured"]["outbox_rows_max"] = 25
    p2 = capacity.plan(record, per_iter=3, floor_iters=4, n_shards=1)
    assert p2["outbox_compact"] == 0


def test_plan_prefers_full_run_maxima():
    """A saved record carries warm-up (`measured`) and full-run
    (`final_measured`) maxima; plan() sizes from the elementwise max
    so a capacity_plan: <path> replay covers steady state."""
    record = {
        "measured": {
            "heap_rows_max": 20, "outbox_rows_max": 6,
            "arrivals_per_flush_max": 10, "exchange_rows_max": 4,
            "pop_trips_max": 5, "phases": 100,
            "overflow": 0, "x_overflow": 0},
        "final_measured": {
            "heap_rows_max": 90, "outbox_rows_max": 3,
            "arrivals_per_flush_max": 10, "exchange_rows_max": 4,
            "pop_trips_max": 5, "phases": 400,
            "overflow": 0, "x_overflow": 0},
    }
    p = capacity.plan(record, per_iter=3, floor_iters=4, n_shards=1)
    assert p["event_capacity"] == 137           # ceil(90*1.5)+2
    assert p["outbox_compact"] == 11            # max(6,3) -> 6


def test_widen_doubles_offending_dimension():
    eff = {"E": 16, "IN": 8, "CAP": 32, "CX": 8, "OB": 24,
           "B": 4, "M_out": 6, "n_shards": 2}
    out = capacity.widen({}, ("event_capacity",
                              "exchange_in_capacity"), eff)
    assert out == {"event_capacity": 32, "exchange_in_capacity": 16}
    out = capacity.widen(out, ("event_capacity",), eff)
    assert out["event_capacity"] == 64          # doubles the override
    out = capacity.widen({}, ("exchange_capacity",
                              "outbox_compact"), eff)
    assert out["exchange_capacity"] == 64
    assert out["outbox_compact"] == 16          # 2*CX, still < OB
    # a compaction width that cannot double under OB turns off
    out = capacity.widen({}, ("outbox_compact",),
                         dict(eff, CX=16, OB=24))
    assert out["outbox_compact"] == 0


def test_record_roundtrip_and_validation(tmp_path):
    rec = {"format": capacity.FORMAT, "measured": {"heap_rows_max": 3},
           "workload": {"app": "X", "n_hosts": 4}}
    path = str(tmp_path / "OCC_X_4.json")
    capacity.save_record(rec, path)
    assert capacity.load_record(path) == rec
    with open(path, "w") as f:
        json.dump({"format": 999}, f)
    with pytest.raises(ValueError, match="format"):
        capacity.load_record(path)


def test_grow_heaps_pads_and_refuses_shrink():
    INF = np.int64(1) << np.int64(62)
    st = {k: np.arange(6, dtype=np.int64).reshape(2, 3)
          for k in ("ht", "hk", "hm", "hv", "hw")}
    out = capacity.grow_heaps(st, 5)
    assert out["ht"].shape == (2, 5)
    assert (out["ht"][:, 3:] == INF).all()
    assert (out["hm"][:, 3:] == 0).all()
    np.testing.assert_array_equal(out["hk"][:, :3], st["hk"])
    assert capacity.grow_heaps(st, 3) is not st  # no-op copy
    with pytest.raises(ValueError, match="shrink"):
        capacity.grow_heaps(st, 2)


# ---------------------------------------------------------------------
# (b) planner-sized runs are bit-identical to static runs
# ---------------------------------------------------------------------

def test_planned_phold_trace_bit_identical(tmp_path, monkeypatch):
    monkeypatch.setenv("SHADOW_TPU_OCC_DIR", str(tmp_path))
    s = Controller(_cfg("tpu"))
    s_stats = s.run()
    assert s_stats.ok

    # warm-up must reach steady state for a first-try plan (the
    # default stop/8 = 125ms sees little more than the 100ms boots)
    p = Controller(_cfg(
        "tpu",
        extra="  capacity_plan: auto\n  capacity_warmup: 600ms\n"))
    p_stats = p.run()
    assert p_stats.ok
    assert p_stats.replans == 0          # warm-up covered steady state
    assert _checksums(p.sim.hosts) == _checksums(s.sim.hosts)
    assert p_stats.events_executed == s_stats.events_executed
    assert p_stats.packets_sent == s_stats.packets_sent

    # the plan actually tightened something vs the static knobs
    planned = p_stats.occupancy["planned"]
    static = p_stats.occupancy["static"]
    assert planned != static
    assert planned["event_capacity"] < 64

    # the OCC record landed and replays through capacity_plan: <path>
    files = [f for f in os.listdir(tmp_path) if f.startswith("OCC_")]
    assert len(files) == 1
    path = os.path.join(str(tmp_path), files[0])
    r = Controller(_cfg("tpu", extra=f"  capacity_plan: {path}\n"))
    r_stats = r.run()
    assert r_stats.ok
    assert _checksums(r.sim.hosts) == _checksums(s.sim.hosts)


@pytest.mark.slow
@pytest.mark.parametrize("example,stop,warmup", [
    ("examples/tgen_100.yaml", "4s", "3s"),
    ("examples/phold.yaml", "1s", "500ms"),
])
def test_planned_example_trace_bit_identical(example, stop, warmup,
                                             tmp_path, monkeypatch):
    monkeypatch.setenv("SHADOW_TPU_OCC_DIR", str(tmp_path))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, example)
    s = Controller(load_config(path, overrides=[
        f"general.stop_time={stop}"]))
    s_stats = s.run()
    assert s_stats.ok

    p = Controller(load_config(path, overrides=[
        f"general.stop_time={stop}",
        "experimental.capacity_plan=auto",
        f"experimental.capacity_warmup={warmup}"]))
    p_stats = p.run()
    assert p_stats.ok
    assert _checksums(p.sim.hosts) == _checksums(s.sim.hosts)
    assert p_stats.events_executed == s_stats.events_executed
    rec = p_stats.occupancy
    assert rec["measured"]["overflow"] == 0
    assert rec["planned"].keys() == rec["static"].keys()


# ---------------------------------------------------------------------
# (c) a bad plan overflows loudly, re-plans, and completes
# ---------------------------------------------------------------------

def test_forced_overflow_replans_and_completes(tmp_path, monkeypatch):
    monkeypatch.setenv("SHADOW_TPU_OCC_DIR", str(tmp_path))
    # default q/msgload on purpose: the static engine here has the
    # same shapes as the other tests', so its compile is a cache hit
    s = Controller(_cfg("tpu"))
    s_stats = s.run()
    assert s_stats.ok

    # warm-up ends at 50ms — before the first boot at 100ms — so the
    # plan is sized on an EMPTY slice (floors only) and the real run
    # must overflow; the retry loop re-plans and still bit-matches
    f = Controller(_cfg(
        "tpu",
        extra="  capacity_plan: auto\n  capacity_warmup: 50ms\n"))
    f_stats = f.run()
    assert f_stats.ok, "re-plan/retry loop failed to absorb overflow"
    assert f_stats.replans >= 1
    assert _checksums(f.sim.hosts) == _checksums(s.sim.hosts)
    assert f_stats.events_executed == s_stats.events_executed
    assert f_stats.packets_sent == s_stats.packets_sent
    assert f_stats.packets_sent > 0
    rec = f_stats.occupancy
    assert rec["replans"] == f_stats.replans
    # the final (widened) capacities held: counters clean at the end
    assert rec["final_measured"]["overflow"] == 0
    assert rec["final_measured"]["x_overflow"] == 0


def test_static_overflow_refuses_checkpoint(tmp_path):
    """A static run that overflows (events lost) must not leave a
    valid-looking checkpoint behind — a resume from it would silently
    replay the loss (same refusal as the max_rounds budget path)."""
    ck = str(tmp_path / "state.npz")
    cfg = _cfg("tpu", extra=f"  checkpoint_save: {ck}\n")
    cfg.experimental.event_capacity = 2
    stats = Controller(cfg).run()
    assert not stats.ok
    assert not os.path.exists(ck)


def test_warmup_without_auto_rejected():
    with pytest.raises(ValueError, match="capacity_warmup"):
        _cfg("tpu", extra="  capacity_warmup: 50ms\n")


def test_capacity_plan_requires_tpu_policy():
    with pytest.raises(ValueError, match="capacity_plan"):
        _cfg("serial", extra="  capacity_plan: auto\n")
