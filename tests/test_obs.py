"""Flight recorder (shadow_tpu/obs): tracer mechanics, the Perfetto
export format, per-phase wall attribution, the streamed JSONL
artifact, trace_report aggregation, watchdog span embedding, and the
end-to-end bit-identity contract (telemetry off == summary == trace).
"""

import json
import logging
import os
import time

import pytest

from shadow_tpu.obs.trace import (
    NullTracer,
    PHASES,
    RECENT_SPANS,
    Tracer,
    current,
    set_current,
)


# ---------------------------------------------------------------------
# schema
# ---------------------------------------------------------------------

def test_schema_validates_telemetry():
    from shadow_tpu.config.schema import ExperimentalOptions

    out = ExperimentalOptions.from_dict({})
    assert out.telemetry == "summary"
    assert out.telemetry_path == ""
    out = ExperimentalOptions.from_dict({"telemetry": "trace",
                                         "telemetry_path": "/tmp/x"})
    assert out.telemetry == "trace"
    with pytest.raises(ValueError, match="telemetry"):
        ExperimentalOptions.from_dict({"telemetry": "verbose"})


# ---------------------------------------------------------------------
# tracer mechanics
# ---------------------------------------------------------------------

def test_span_walls_and_recent():
    tr = Tracer(mode="summary")
    with tr.span("dispatch", "dispatch", sim_t0=0, sim_t1=100) as sp:
        sp.add(rounds=3)
        time.sleep(0.01)
    tr.instant("preempt.request", "checkpoint", sim_t0=50)
    walls = tr.phase_walls(total_wall_s=1.0)
    assert walls["dispatch_s"] >= 0.01
    assert walls["checkpoint_s"] == 0.0
    # host is the residual of the given total
    assert walls["host_s"] == pytest.approx(
        1.0 - sum(v for k, v in walls.items() if k != "host_s"),
        abs=1e-6)
    recent = tr.recent()
    assert [r["name"] for r in recent] == ["dispatch",
                                           "preempt.request"]
    assert recent[0]["args"]["rounds"] == 3
    assert recent[0]["sim_t0"] == 0 and recent[0]["sim_t1"] == 100
    text = tr.format_recent()
    assert "dispatch" in text and "preempt.request" in text


def test_self_time_attribution():
    # a nested record (the AOT compile inside the first dispatch)
    # must not be double-counted: the outer span's bucket gets only
    # its self time, so the buckets sum to at most the elapsed wall
    tr = Tracer(mode="summary")
    with tr.span("dispatch", "dispatch"):
        time.sleep(0.06)                     # "the compile elapses
        tr.record("aot.compile:run", "compile", 0.05)  # in here"
        with tr.span("inner.save", "checkpoint"):
            time.sleep(0.02)
    walls = tr._walls
    assert walls["compile"] == pytest.approx(0.05, abs=0.01)
    assert walls["checkpoint"] >= 0.02
    # the dispatch bucket got gross - (compile + checkpoint), NOT
    # the gross ~0.08s
    assert walls["dispatch"] < walls["compile"] + walls["checkpoint"]
    # the record keeps the GROSS duration plus self_s
    rec = tr.recent()[-1]
    assert rec["name"] == "dispatch"
    assert rec["dur_s"] >= 0.08
    assert rec["self_s"] == pytest.approx(
        rec["dur_s"] - 0.05 - walls["checkpoint"], abs=0.01)


def test_span_error_tagged_and_reraised():
    tr = Tracer(mode="summary")
    with pytest.raises(RuntimeError):
        with tr.span("dispatch", "dispatch"):
            raise RuntimeError("transient")
    rec = tr.recent()[-1]
    assert rec["args"]["error"] == "RuntimeError"


def test_recent_ring_bounded():
    tr = Tracer(mode="summary")
    for i in range(RECENT_SPANS + 10):
        tr.instant(f"tick{i}", "host")
    recent = tr.recent()
    assert len(recent) == RECENT_SPANS
    assert recent[-1]["name"] == f"tick{RECENT_SPANS + 9}"


def test_null_tracer_is_inert(tmp_path):
    tr = NullTracer()
    with tr.span("x", "dispatch") as sp:
        sp.add(rounds=1)
    tr.instant("y")
    tr.record("z", "compile", 1.0)
    assert tr.recent() == []
    assert tr.phase_walls() == {}
    assert tr.finalize() is None


def test_spans_land_in_a_profiler_trace(tmp_path):
    """Every recorder span is also a jax.profiler TraceAnnotation: under
    a running profiler it shows on the trace's host plane, by name and
    nested as recorded; the off tracer's spans add nothing."""
    import glob

    import jax
    from jax.profiler import ProfileData

    tr = Tracer(mode="summary")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("dispatch.sync", "dispatch.sync"):
            with tr.span("heartbeat"):
                time.sleep(0.002)
        with NullTracer().span("null.span", "dispatch"):
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                      recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events.setdefault(e.name, []).append(
                        (e.start_ns, e.end_ns))
    assert len(events["dispatch.sync"]) == len(events["heartbeat"]) == 1
    (s0, s1), (h0, h1) = events["dispatch.sync"][0], events["heartbeat"][0]
    assert s0 <= h0 < h1 <= s1
    assert "null.span" not in events
    # the recorder's own record is unchanged
    assert [r["name"] for r in tr.recent()] == ["heartbeat",
                                                 "dispatch.sync"]


def test_current_tracer_swap():
    tr = Tracer(mode="summary")
    old = current()
    try:
        set_current(tr)
        assert current() is tr
        set_current(None)
        assert isinstance(current(), NullTracer)
    finally:
        set_current(old)


# ---------------------------------------------------------------------
# artifacts: JSONL stream, Perfetto export, METRICS record
# ---------------------------------------------------------------------

def test_trace_mode_writes_all_artifacts(tmp_path):
    tr = Tracer(mode="trace", directory=str(tmp_path), label="t_9")
    with tr.span("dispatch", "dispatch", sim_t0=0, sim_t1=10):
        time.sleep(0.002)
    tr.instant("occ.save", "plan", path="x.json")
    summary = tr.finalize(run_info={"policy": "tpu"},
                          counters={"events": 5})
    # idempotent
    assert tr.finalize() is summary

    jsonl = tmp_path / "TRACE_t_9.jsonl"
    assert jsonl.exists()
    recs = [json.loads(ln) for ln in
            jsonl.read_text().strip().splitlines()]
    assert [r["name"] for r in recs] == ["dispatch", "occ.save"]
    assert not list(tmp_path.glob("*.partial"))

    trace = json.loads((tmp_path / "TRACE_t_9.trace.json")
                       .read_text())
    evs = trace["traceEvents"]
    names = {e["name"] for e in evs}
    assert "dispatch" in names and "occ.save" in names
    # every phase has a named swimlane
    lanes = {e["args"]["name"] for e in evs
             if e["name"] == "thread_name"}
    assert set(PHASES) <= lanes
    x = [e for e in evs if e["name"] == "dispatch"][0]
    assert x["ph"] == "X" and x["dur"] > 0
    assert x["args"]["sim_t1_ns"] == 10
    i = [e for e in evs if e["name"] == "occ.save"][0]
    assert i["ph"] == "i"

    metrics = json.loads((tmp_path / "METRICS_t_9.json").read_text())
    assert metrics["run"]["policy"] == "tpu"
    assert metrics["counters"]["events"] == 5
    # the per-phase walls sum to the recorded total (the acceptance
    # contract, exact by the residual construction)
    assert sum(metrics["phases"].values()) == pytest.approx(
        metrics["total_wall_s"], rel=0.01, abs=0.01)
    assert metrics["files"]["jsonl"].endswith("TRACE_t_9.jsonl")


def test_summary_mode_writes_metrics_only_with_path(tmp_path):
    tr = Tracer(mode="summary", directory=str(tmp_path), label="s_1")
    tr.instant("x", "host")
    tr.finalize()
    assert (tmp_path / "METRICS_s_1.json").exists()
    assert not (tmp_path / "TRACE_s_1.jsonl").exists()
    assert not (tmp_path / "TRACE_s_1.trace.json").exists()


def test_streamed_lines_atomic_placement(tmp_path):
    from shadow_tpu.utils.artifacts import StreamedLines

    path = str(tmp_path / "log.jsonl")
    s = StreamedLines(path, flush_every=1)
    s.write_line('{"a":1}')
    assert not os.path.exists(path)          # still streaming
    assert os.path.exists(s.partial)
    assert open(s.partial).read() == '{"a":1}\n'
    assert s.close() == path
    assert open(path).read() == '{"a":1}\n'
    assert not os.path.exists(s.partial)

    s2 = StreamedLines(path + "2")
    s2.write_line("x")
    kept = s2.abandon()                      # error path keeps it
    assert os.path.exists(kept)


def test_non_serializable_args_degrade_not_crash(tmp_path):
    # span args are free-form kwargs from a dozen call sites; a
    # stray numpy scalar must degrade to its string form on every
    # write path, never abort the run (the recorder's contract)
    import numpy as np

    tr = Tracer(mode="trace", directory=str(tmp_path), label="np_1")
    with tr.span("dispatch", "dispatch", weird=np.int64(7),
                 arr=np.arange(2)):
        pass
    summary = tr.finalize()
    assert summary["spans"] == 1
    for name in ("TRACE_np_1.jsonl", "TRACE_np_1.trace.json",
                 "METRICS_np_1.json"):
        assert (tmp_path / name).exists(), name
    rec = json.loads((tmp_path / "TRACE_np_1.jsonl").read_text())
    assert rec["args"]["weird"] == "7"          # default=str form

    # finalize stays idempotent even if a later call races a failure
    assert tr.finalize() is summary


# ---------------------------------------------------------------------
# trace_report
# ---------------------------------------------------------------------

def test_trace_report_from_metrics_and_jsonl(tmp_path, capsys):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import trace_report

    tr = Tracer(mode="trace", directory=str(tmp_path), label="r_3")
    with tr.span("dispatch", "dispatch", sim_t0=0, sim_t1=10):
        time.sleep(0.002)
    time.sleep(0.05)
    tr.record("aot.compile:run", "compile", 0.04)
    tr.finalize()

    m = trace_report.load_metrics(str(tmp_path / "METRICS_r_3.json"))
    trace_report.print_report(m)
    out = capsys.readouterr().out
    assert "dominant phase:" in out and "compile" in out

    m2 = trace_report.load_metrics(str(tmp_path / "TRACE_r_3.jsonl"))
    assert m2["spans"] == 2
    assert m2["phases"]["compile_s"] == pytest.approx(0.04, abs=0.01)
    # jsonl aggregation keeps the sum-to-total contract too
    assert sum(m2["phases"].values()) == pytest.approx(
        m2["total_wall_s"], rel=0.01, abs=0.01)
    trace_report.print_report(m2, top=2)
    out = capsys.readouterr().out
    assert "slowest" in out


# ---------------------------------------------------------------------
# watchdog embedding
# ---------------------------------------------------------------------

def test_watchdog_dump_embeds_recent_spans(tmp_path):
    from shadow_tpu.core.manager import RoundWatchdog, SimStats

    tr = Tracer(mode="summary")
    with tr.span("dispatch", "dispatch", sim_t0=0, sim_t1=7):
        pass

    class StubManager:
        stats = SimStats()
        hosts = []
        tracer = tr

        def dump_state(self):
            return "  host web0: events=3"

    dumps = []
    dump_path = str(tmp_path / "stall.txt")
    wd = RoundWatchdog(StubManager(), 0.15, on_stall=dumps.append,
                       dump_path=dump_path)
    wd.start()
    deadline = time.monotonic() + 10
    while not wd.fired and time.monotonic() < deadline:
        time.sleep(0.02)
    wd.stop()
    assert wd.fired
    assert "host web0" in dumps[0]
    assert "completed span(s)" in dumps[0]
    assert "dispatch" in dumps[0]
    on_disk = open(dump_path).read()
    assert "dispatch" in on_disk


# ---------------------------------------------------------------------
# end-to-end: bit-identity across modes + artifacts from a real run
# ---------------------------------------------------------------------

E2E_YAML = """
general:
  stop_time: 2s
  seed: 3
  data_directory: {data}
experimental:
  scheduler_policy: tpu
  telemetry: {mode}
  telemetry_path: {tel}
hosts:
  server:
    processes:
    - {{path: model:tgen_server, start_time: 100ms}}
  client:
    quantity: 2
    processes:
    - {{path: model:tgen_client, args: server=server size=4KiB
        count=3 pause=100ms, start_time: 200ms}}
"""


def _e2e(tmp_path, mode):
    from shadow_tpu.config import load_config_str
    from shadow_tpu.core.controller import Controller

    tel = tmp_path / f"tel_{mode}"
    cfg = load_config_str(E2E_YAML.format(
        mode=mode, tel=tel, data=tmp_path / mode / "shadow.data"))
    c = Controller(cfg)
    stats = c.run()
    assert stats.ok
    return stats, [h.trace_checksum for h in c.sim.hosts], tel


def test_e2e_modes_bit_identical_and_trace_artifacts(tmp_path):
    s_off, chk_off, _ = _e2e(tmp_path, "off")
    s_sum, chk_sum, _ = _e2e(tmp_path, "summary")
    s_tr, chk_tr, tel = _e2e(tmp_path, "trace")
    # the hard contract: tracing never perturbs the simulation
    assert chk_off == chk_sum == chk_tr
    assert s_off.telemetry is None
    assert s_sum.telemetry is not None
    assert set(s_sum.telemetry["phases"]) == {
        f"{p}_s" for p in PHASES}
    # trace artifacts exist and the walls sum to the total
    mfiles = list(tel.glob("METRICS_*.json"))
    tfiles = list(tel.glob("TRACE_*.trace.json"))
    jfiles = list(tel.glob("TRACE_*.jsonl"))
    assert mfiles and tfiles and jfiles
    m = json.loads(mfiles[0].read_text())
    assert sum(m["phases"].values()) == pytest.approx(
        m["total_wall_s"], rel=0.1)
    # the dispatch spans carry sim windows covering the run — split
    # since PR 11 into the asynchronous issue and the blocking sync,
    # which must pair up over identical windows
    recs = [json.loads(ln) for ln in
            jfiles[0].read_text().strip().splitlines()]
    issue = [r for r in recs if r["name"] == "dispatch.issue"]
    sync = [r for r in recs if r["name"] == "dispatch.sync"]
    assert issue and sync and sync[-1]["sim_t1"] == 2 * 10**9
    assert [(r["sim_t0"], r["sim_t1"]) for r in issue] == \
        [(r["sim_t0"], r["sim_t1"]) for r in sync]
    # and SimStats carries the same summary the file holds
    assert s_tr.telemetry["phases"] == m["phases"]


def test_failover_rerun_lands_in_same_trace(tmp_path, monkeypatch):
    """Satellite: the hybrid failover rerun shares its parent's
    flight recorder — its spans land in the SAME trace under a
    `failover` phase, and the METRICS walls still sum to total (the
    host bucket is the residual by construction, so the failover
    span's self-time must not double-count the inner run's spans)."""
    import shadow_tpu.device.engine as eng
    from shadow_tpu.config import load_config_str
    from shadow_tpu.core.controller import Controller

    def dead(self, state, stop=None, final_stop=None):
        raise RuntimeError("UNAVAILABLE: device went away")

    monkeypatch.setattr(eng.DeviceEngine, "run", dead)
    tel = tmp_path / "tel_failover"
    cfg = load_config_str(E2E_YAML.format(
        mode="trace", tel=tel, data=tmp_path / "fo" / "shadow.data"))
    cfg.experimental.failover = "hybrid"
    cfg.experimental.dispatch_segment = 500_000_000
    stats = Controller(cfg).run()
    assert stats.ok
    summary = stats.telemetry
    assert summary is not None
    # ONE finalized recorder for the whole incident: the rerun did
    # not write its own METRICS/TRACE set
    mfiles = list(tel.glob("METRICS_*.json"))
    jfiles = list(tel.glob("TRACE_*.jsonl"))
    assert len(mfiles) == 1 and len(jfiles) == 1
    recs = [json.loads(ln) for ln in
            jfiles[0].read_text().strip().splitlines()]
    fo = [r for r in recs if r["phase"] == "failover"]
    assert fo and fo[0]["name"] == "failover.hybrid_rerun"
    # the hybrid rerun's own spans (judge flushes, at minimum) are in
    # the SAME stream, after the device prefix's dispatch spans
    assert any(r["phase"] == "judge" for r in recs)
    assert any(r["name"] == "dispatch.issue" for r in recs)
    # walls still sum to total (host is the residual)
    assert sum(summary["phases"].values()) == pytest.approx(
        summary["total_wall_s"], rel=0.1)
    assert summary["span_counts"].get("failover", 0) >= 1


def test_ensemble_heartbeat_rate_columns(caplog):
    # satellite: per-replica [ensemble-heartbeat] lines carry a
    # pkts/s-since-last-heartbeat rate and cumulative retry/replan
    # counts (stub runner — the line format is the contract)
    from types import SimpleNamespace

    import numpy as np

    from shadow_tpu.ensemble.campaign import EnsembleRunner

    r = SimpleNamespace(
        sim=SimpleNamespace(hosts=[SimpleNamespace(host_id=0),
                                   SimpleNamespace(host_id=1)]),
        worlds=SimpleNamespace(R=2),
        retries=1, replans=2, _hb_mark=None)
    states = {k: np.arange(4).reshape(2, 2)
              for k in ("n_exec", "n_sent", "n_drop", "n_deliv")}
    with caplog.at_level(logging.INFO):
        EnsembleRunner._emit_heartbeats(r, 10**9, states)
        EnsembleRunner._emit_heartbeats(r, 2 * 10**9, states)
    lines = [m for m in caplog.messages
             if "[ensemble-heartbeat]" in m]
    assert len(lines) == 4                   # 2 replicas x 2 beats
    assert "pkts/s=n/a" in lines[0]          # no previous mark
    assert "retries=1" in lines[0] and "replans=2" in lines[0]
    assert "replica=1" in lines[1]
    # the second beat rates against the first (0 new packets -> 0)
    assert "pkts/s=0" in lines[2]


def test_supervise_heartbeat_line(tmp_path, caplog):
    # satellite: the aggregate [supervise-heartbeat] line carries a
    # pkts/s rate and cumulative retry/replan counts
    from shadow_tpu.config import load_config_str
    from shadow_tpu.core.controller import Controller

    cfg = load_config_str(E2E_YAML.format(
        mode="summary", tel=tmp_path / "tel",
        data=tmp_path / "hb" / "shadow.data"))
    cfg.general.heartbeat_interval = 5 * 10**8
    with caplog.at_level(logging.INFO):
        stats = Controller(cfg).run()
    assert stats.ok
    lines = [r.getMessage() for r in caplog.records
             if "[supervise-heartbeat]" in r.getMessage()]
    assert lines, "no supervise heartbeat lines"
    assert "pkts/s=n/a" in lines[0]          # no previous mark yet
    for ln in lines:
        assert "retries=0" in ln and "replans=0" in ln
    if len(lines) > 1:
        assert "pkts/s=n/a" not in lines[1]


def _dispatch_metrics(sync_s, advance_s, segments=8):
    """A METRICS record carrying the advance loop's dispatch block."""
    return {"format": 1, "mode": "summary", "total_wall_s": 4.0,
            "phases": {"dispatch.sync_s": sync_s, "host_s": 1.0},
            "dominant_phase": "dispatch.sync", "spans": 17,
            "counters": {"packets": 1000,
                         "pipeline": {"segments": segments,
                                      "sync_wall_s": sync_s,
                                      "advance_wall_s": advance_s}}}


def test_trace_report_prints_dispatch_block(tmp_path, capsys):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import trace_report

    p = tmp_path / "METRICS_d.json"
    p.write_text(json.dumps(_dispatch_metrics(2.5, 3.25)))
    trace_report.print_report(trace_report.load_metrics(str(p)))
    out = capsys.readouterr().out
    assert ("dispatch: 8 segment(s); sync 2.500s of 3.250s advance "
            "wall") in out
    assert "depth" not in out and "overlap" not in out


def test_trace_report_compare_prints_sync_wall(tmp_path, capsys):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import trace_report

    pa, pb = tmp_path / "METRICS_a.json", tmp_path / "METRICS_b.json"
    pa.write_text(json.dumps(_dispatch_metrics(2.5, 3.25)))
    pb.write_text(json.dumps(_dispatch_metrics(1.0, 2.0, 4)))
    trace_report.print_compare(trace_report.load_metrics(str(pa)),
                               trace_report.load_metrics(str(pb)),
                               str(pa), str(pb))
    out = capsys.readouterr().out
    assert "sync wall: A 2.500s -> B 1.000s" in out
    assert "depth" not in out and "overlap" not in out
