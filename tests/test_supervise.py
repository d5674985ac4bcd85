"""Supervised runs (device/supervise.py + experimental.state_audit).

The supervision layer's three guarantees, pinned:
* periodic validated checkpoints rotate (last-K, atomic) and a resume
  from the rotation bit-matches the uninterrupted run;
* SIGTERM drains gracefully — the in-flight segment finishes, a
  resume checkpoint lands, stats mark the run preempted — and the
  resumed run is bit-identical;
* transient dispatch errors retry from the last validated state, and
  exhausted retries fail over to the hybrid backend instead of
  aborting.
Plus the health-word audit: clean runs stay bit-identical with it on,
corrupted states are named, and with supervision disabled the
compiled device program is unchanged (no audit leaves, identical
lowering).
"""

import glob
import json
import os
import signal

import numpy as np
import pytest

from shadow_tpu.config import load_config_str
from shadow_tpu.core.controller import Controller
from shadow_tpu.device import supervise

YAML = """
general:
  stop_time: 800ms
  seed: 9
network:
  graph:
    type: 1_gbit_switch
experimental:
  scheduler_policy: tpu
  event_capacity: 48
{extra}
hosts:
  left:
    quantity: 3
    processes:
    - {{path: model:phold, args: msgload=2, start_time: 10ms}}
  right:
    quantity: 3
    processes:
    - {{path: model:phold, args: msgload=2, start_time: 10ms}}
"""


def _run(extra=""):
    c = Controller(load_config_str(YAML.format(extra=extra)))
    stats = c.run()
    return stats, c


def _sig(stats, c):
    return (stats.events_executed, stats.packets_sent,
            stats.packets_dropped, stats.packets_delivered,
            [(h.name, h.trace_checksum) for h in c.sim.hosts])


# ---------------------------------------------------------------------------
# atomic artifact writes (utils/artifacts.py)
# ---------------------------------------------------------------------------

def test_atomic_write_json_lands_whole_or_not_at_all(tmp_path):
    from shadow_tpu.utils.artifacts import atomic_write_json

    path = str(tmp_path / "sub" / "rec.json")
    atomic_write_json({"a": 1, "b": [2, 3]}, path)
    with open(path) as f:
        assert json.load(f) == {"a": 1, "b": [2, 3]}
    # no tmp debris after a successful write
    assert os.listdir(os.path.dirname(path)) == ["rec.json"]

    # a failing serialization leaves nothing behind (not even a tmp)
    with pytest.raises(TypeError):
        atomic_write_json({"bad": object()}, str(tmp_path / "x.json"))
    assert not glob.glob(str(tmp_path / "x.json*"))


# ---------------------------------------------------------------------------
# checkpoint_load rotation resolution
# ---------------------------------------------------------------------------

def test_resolve_checkpoint_skips_corrupt_newest(tmp_path):
    base = str(tmp_path / "ck.npz")
    good = f"{base}.t{500:015d}"
    bad = f"{base}.t{900:015d}"
    meta = {"format": 1, "sim_time": 500, "final_stop": 0,
            "fingerprint": {}, "keys": []}
    with open(good, "wb") as f:
        np.savez_compressed(f, __meta__=json.dumps(meta))
    # the newest entry is a truncated decoy — exactly what a SIGKILL
    # mid-write used to leave; the resolver must fall back
    with open(bad, "wb") as f:
        f.write(b"PK\x03\x04 not really an npz")
    assert supervise.resolve_checkpoint(base) == good
    # a concrete existing file always wins
    assert supervise.resolve_checkpoint(good) == good
    with pytest.raises(ValueError, match="nothing to resume"):
        supervise.resolve_checkpoint(str(tmp_path / "absent.npz"))


# ---------------------------------------------------------------------------
# rotation + graceful preemption + resume bit-identity (tier-1 fast path;
# the full mid-campaign preemption of examples/ensemble_seed_sweep.yaml is
# the slow gate test below)
# ---------------------------------------------------------------------------

def test_rotation_prune_preempt_and_resume_bitmatch(tmp_path,
                                                    monkeypatch):
    full_stats, full_c = _run()
    assert full_stats.ok
    ref = _sig(full_stats, full_c)

    # supervised run, SIGTERM raised synchronously after the second
    # dispatch segment completes — the guard drains at the next
    # boundary, so the preemption point is deterministic
    base = str(tmp_path / "ck.npz")
    import shadow_tpu.device.engine as eng
    orig = eng.DeviceEngine.run
    calls = {"n": 0}

    def poking(self, state, stop=None, final_stop=None):
        out = orig(self, state, stop=stop, final_stop=final_stop)
        calls["n"] += 1
        if calls["n"] == 3:
            signal.raise_signal(signal.SIGTERM)
        return out

    monkeypatch.setattr(eng.DeviceEngine, "run", poking)
    pre_stats, _ = _run(
        f"  checkpoint_save: {base}\n"
        f"  checkpoint_every: 200ms\n"
        f"  checkpoint_keep: 2\n"
        f"  state_audit: true")
    monkeypatch.setattr(eng.DeviceEngine, "run", orig)
    assert pre_stats.preempted
    assert pre_stats.end_time == 600_000_000  # drained at boundary 3
    assert pre_stats.resume_path
    assert os.path.exists(pre_stats.resume_path)
    # rotation pruned to checkpoint_keep entries, newest retained
    rot = supervise.rotation_entries(base)
    assert len(rot) == 2
    assert rot[-1][1] == pre_stats.resume_path
    # the preempted run stopped early: strictly less work than full
    assert pre_stats.events_executed < full_stats.events_executed
    # the rotation entries carry the validation stamp
    from shadow_tpu.device import checkpoint
    assert checkpoint.peek_meta(rot[-1][1])["audit"] == {
        "enabled": True, "violations": 0}

    # resume from the BASE path (rotation-resolved), audit off — the
    # audit leaves are auxiliary and must not pin the resume
    res_stats, res_c = _run(f"  checkpoint_load: {base}")
    assert res_stats.ok and not res_stats.preempted
    assert _sig(res_stats, res_c) == ref

    # resume with audit ON from the same checkpoint: the reseeded
    # conservation ledger must stay clean to the end
    res2_stats, res2_c = _run(
        f"  checkpoint_load: {base}\n  state_audit: true")
    assert res2_stats.ok
    assert _sig(res2_stats, res2_c) == ref


# ---------------------------------------------------------------------------
# health-word audit
# ---------------------------------------------------------------------------

def test_audit_trace_invariant_and_leaves(tmp_path):
    s_off, c_off = _run()
    s_on, c_on = _run("  state_audit: true")
    assert _sig(s_off, c_off) == _sig(s_on, c_on)
    # audited run: leaves present, word clean
    state = c_on.runner.final_state
    assert int(np.asarray(state["aud"]).max()) == 0
    assert "aud_tx" in state
    # un-audited run: no audit leaves anywhere in the state
    assert not any(k.startswith("aud") for k in c_off.runner.final_state)


def test_audit_detects_corrupted_state():
    import jax
    import jax.numpy as jnp

    _, c = _run("  state_audit: true")
    r = c.runner
    state = r.engine.init_state(r.sim.starts)
    bad = np.array(jax.device_get(state["n_sent"]))
    bad[0] = -7
    state["n_sent"] = jax.device_put(jnp.asarray(bad),
                                     state["n_sent"].sharding)
    state, _ = r.engine.run(state, stop=200_000_000,
                            final_stop=800_000_000)
    aud = np.asarray(jax.device_get(state["aud"]))
    assert aud.any()
    word = int(np.bitwise_or.reduce(aud, axis=None))
    assert "counter-negativity" in supervise.decode_audit(word)
    with pytest.raises(supervise.AuditFailure,
                       match="counter-negativity"):
        supervise.check_audit(state, where="unit test")


def test_supervision_knobs_do_not_change_program(tmp_path):
    """With the audit off, none of the supervision knobs (periodic
    checkpoints, retries, failover) may leak into the compiled device
    program — they are host-side orchestration. Pinned by comparing
    the lowered program text."""
    import jax.numpy as jnp

    _, plain = _run()
    base = str(tmp_path / "ck.npz")
    _, sup = _run(
        f"  checkpoint_save: {base}\n"
        f"  checkpoint_every: 200ms\n"
        f"  dispatch_retries: 3\n"
        f"  failover: hybrid")

    def lowered(c):
        e = c.runner.engine
        state = e.init_state(c.sim.starts)
        import jax
        from jax.sharding import NamedSharding
        repl = NamedSharding(e.mesh, e._repl_spec)
        hv = jax.device_put(jnp.asarray(e.host_vertex), repl)
        return e._run.lower(state, hv, e.world(), jnp.int64(100),
                            jnp.int64(100)).as_text()

    assert lowered(plain) == lowered(sup)


# ---------------------------------------------------------------------------
# dispatch retry + failover
# ---------------------------------------------------------------------------

def test_transient_dispatch_retry_bitmatch(monkeypatch):
    full_stats, full_c = _run()
    ref = _sig(full_stats, full_c)

    import shadow_tpu.device.engine as eng
    orig = eng.DeviceEngine.run
    calls = {"n": 0}

    def flaky(self, state, stop=None, final_stop=None):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("RESOURCE_EXHAUSTED: injected")
        return orig(self, state, stop=stop, final_stop=final_stop)

    monkeypatch.setattr(eng.DeviceEngine, "run", flaky)
    stats, c = _run("  dispatch_retries: 2\n"
                    "  dispatch_retry_backoff: 0.0\n"
                    "  dispatch_segment: 200ms")
    assert stats.ok
    assert stats.retries == 1
    assert _sig(stats, c) == ref

    # the retry budget is per segment (CONSECUTIVE failures): two
    # unrelated incidents in different segments each recover under
    # dispatch_retries: 1 — they must not pool into exhaustion
    calls["n"] = 0

    def flaky_twice(self, state, stop=None, final_stop=None):
        calls["n"] += 1
        if calls["n"] in (2, 5):
            raise RuntimeError("UNAVAILABLE: injected hiccup")
        return orig(self, state, stop=stop, final_stop=final_stop)

    monkeypatch.setattr(eng.DeviceEngine, "run", flaky_twice)
    stats2, c2 = _run("  dispatch_retries: 1\n"
                      "  dispatch_retry_backoff: 0.0\n"
                      "  dispatch_segment: 200ms")
    assert stats2.ok
    assert stats2.retries == 2
    assert _sig(stats2, c2) == ref

    # a non-transient error is NOT retried
    def broken(self, state, stop=None, final_stop=None):
        raise RuntimeError("XlaRuntimeError: INVALID_ARGUMENT: bug")

    monkeypatch.setattr(eng.DeviceEngine, "run", broken)
    with pytest.raises(RuntimeError, match="INVALID_ARGUMENT"):
        _run("  dispatch_retries: 5\n"
             "  dispatch_retry_backoff: 0.0")


def test_failover_to_hybrid_finishes_the_run(monkeypatch, tmp_path,
                                             caplog):
    import logging

    ref_stats, ref_c = _run()
    ref = _sig(ref_stats, ref_c)

    import shadow_tpu.device.engine as eng

    def dead(self, state, stop=None, final_stop=None):
        raise RuntimeError("UNAVAILABLE: device went away")

    monkeypatch.setattr(eng.DeviceEngine, "run", dead)
    with caplog.at_level(logging.ERROR):
        stats, c = _run(
            f"  failover: hybrid\n"
            f"  checkpoint_save: {tmp_path / 'fo.npz'}\n"
            f"  dispatch_segment: 200ms")
    assert stats.ok
    assert stats.failover_checkpoint
    assert os.path.exists(stats.failover_checkpoint)
    assert any("DEVICE FAILOVER" in r.getMessage()
               for r in caplog.records)
    assert _sig(stats, c) == ref


def test_no_guard_without_drain_boundaries(tmp_path):
    """checkpoint_save alone (no checkpoint_every / dispatch_segment
    / heartbeat) runs as ONE dispatch segment — no boundary a drain
    could fire at. The guard must NOT install: swallowing SIGTERM
    while promising a drain that can never happen would be strictly
    worse than the default signal disposition."""
    ck = str(tmp_path / "solo.npz")
    stats, c = _run(f"  checkpoint_save: {ck}")
    assert stats.ok
    assert c.runner.guard is None
    # with a boundary source, the guard installs
    stats2, c2 = _run(f"  checkpoint_save: {ck}2\n"
                      f"  dispatch_segment: 400ms")
    assert stats2.ok
    assert c2.runner.guard is not None


# ---------------------------------------------------------------------------
# round-watchdog stall dump (direct unit test of the dump path)
# ---------------------------------------------------------------------------

def test_watchdog_writes_stall_dump_file(tmp_path):
    import time

    from shadow_tpu.core.manager import RoundWatchdog

    cfg = load_config_str(YAML.format(extra="").replace(
        "scheduler_policy: tpu", "scheduler_policy: serial"))
    c = Controller(cfg)          # built, never run: zero progress
    dump_path = str(tmp_path / "stall" / "dump.txt")
    captured = []
    wd = RoundWatchdog(c.manager, 0.1, on_stall=captured.append,
                       dump_path=dump_path)
    wd.start()
    try:
        deadline = time.monotonic() + 5
        while not wd.fired and time.monotonic() < deadline:
            time.sleep(0.02)
    finally:
        wd.stop()
    assert wd.fired
    assert captured and "host left0" in captured[0]
    with open(dump_path) as f:
        text = f.read()
    assert "no progress" in text and "host left0" in text


# ---------------------------------------------------------------------------
# schema validation of the new knobs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("extra,match", [
    ("  checkpoint_every: 100ms", "checkpoint_save"),
    ("  checkpoint_save: /tmp/x.npz\n  checkpoint_every: 100ms\n"
     "  checkpoint_save_time: 1s", "cannot combine"),
    ("  checkpoint_save: /tmp/x.npz\n  checkpoint_every: 100ms\n"
     "  checkpoint_keep: 0", "checkpoint_keep"),
    ("  dispatch_retries: -1", "dispatch_retries"),
    ("  failover: sideways", "failover"),
])
def test_schema_rejects_bad_supervision_knobs(extra, match):
    with pytest.raises(ValueError, match=match):
        load_config_str(YAML.format(extra=extra))


def test_schema_rejects_supervision_on_cpu_policies():
    serial = YAML.replace("scheduler_policy: tpu",
                          "scheduler_policy: serial")
    for extra, match in (("  state_audit: true", "state_audit"),
                         ("  dispatch_retries: 2", "dispatch_retries"),
                         ("  failover: hybrid", "failover")):
        with pytest.raises(ValueError, match=match):
            load_config_str(serial.format(extra=extra))


def test_schema_rejects_hybrid_failover_for_campaigns():
    yaml = YAML.format(extra="  failover: hybrid") + """
ensemble:
  replicas: 2
  vary:
    seed: [1, 2]
"""
    with pytest.raises(ValueError, match="failover"):
        load_config_str(yaml)


# ---------------------------------------------------------------------------
# full mid-campaign preemption of the example sweep (the CI rung, run
# here end-to-end through the gate script)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_ensemble_preemption_gate_slow():
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts",
                                      "determinism_gate.py"),
         os.path.join(repo, "examples", "ensemble_seed_sweep.yaml"),
         "--preempt", "--ensemble"],
        cwd=repo, capture_output=True, text=True, timeout=1800)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "preemption OK" in r.stdout


# ---------------------------------------------------------------------------
# the segmented advance loop: issue -> sync -> boundary work, serially
# ---------------------------------------------------------------------------

SEGMENTED = ("  dispatch_segment: 100ms\n"
             "  state_audit: true")


def test_segmented_advance_bitmatches_unsegmented():
    ref_stats, ref_c = _run()
    stats, c = _run(SEGMENTED)
    assert stats.ok
    assert _sig(stats, c) == _sig(ref_stats, ref_c)
    p = stats.pipeline
    # 800ms / 100ms segments, each issued and synced once
    assert p["segments"] == 8
    assert stats.telemetry["span_counts"]["dispatch.sync"] == 8
    assert stats.telemetry["span_counts"]["dispatch.issue"] == 8
    # the sync wall is measured, not the whole advance: issue
    # enqueues and boundary work are not blocking waits
    assert 0.0 < p["sync_wall_s"] <= p["advance_wall_s"]
    assert set(p) == {"segments", "sync_wall_s", "advance_wall_s"}


def test_forced_overflow_mid_run_replays_and_bitmatches(
        tmp_path, monkeypatch):
    monkeypatch.setenv("SHADOW_TPU_OCC_DIR", str(tmp_path))
    ref_stats, ref_c = _run("  dispatch_segment: 100ms")
    assert ref_stats.ok
    ref = _sig(ref_stats, ref_c)

    # the warm-up slice ends before the phold boots at 10ms, so the
    # plan is sized on an empty slice (floors only) and a real
    # segment must overflow — the re-plan replays from the last
    # validated boundary
    stats, c = _run("  dispatch_segment: 100ms\n"
                    "  capacity_plan: auto\n"
                    "  capacity_warmup: 5ms")
    assert stats.ok, "re-plan/retry failed to absorb the overflow"
    assert stats.replans >= 1
    assert _sig(stats, c) == ref
    # the overflowing segment was synced, then replayed
    assert stats.pipeline["segments"] > 8


def test_transient_error_respects_consecutive_budget(monkeypatch):
    ref_stats, ref_c = _run()
    ref = _sig(ref_stats, ref_c)

    import shadow_tpu.device.engine as eng
    orig = eng.DeviceEngine.run
    calls = {"n": 0}

    def flaky(self, state, stop=None, final_stop=None):
        calls["n"] += 1
        if calls["n"] == 4:     # a mid-run issue, 3 segments done
            raise RuntimeError("RESOURCE_EXHAUSTED: injected")
        return orig(self, state, stop=stop, final_stop=final_stop)

    monkeypatch.setattr(eng.DeviceEngine, "run", flaky)
    stats, c = _run(SEGMENTED +
                    "\n  dispatch_retries: 2"
                    "\n  dispatch_retry_backoff: 0.0")
    assert stats.ok
    assert stats.retries == 1
    assert _sig(stats, c) == ref

    # CONSECUTIVE-failure budget: two hiccups in different segments
    # each recover under dispatch_retries: 1 — a segment that syncs
    # clean resets the count
    calls["n"] = 0

    def flaky_twice(self, state, stop=None, final_stop=None):
        calls["n"] += 1
        if calls["n"] in (3, 9):
            raise RuntimeError("UNAVAILABLE: injected hiccup")
        return orig(self, state, stop=stop, final_stop=final_stop)

    monkeypatch.setattr(eng.DeviceEngine, "run", flaky_twice)
    stats2, c2 = _run(SEGMENTED +
                      "\n  dispatch_retries: 1"
                      "\n  dispatch_retry_backoff: 0.0")
    assert stats2.ok
    assert stats2.retries == 2
    assert _sig(stats2, c2) == ref

    # a genuinely dead device exhausts the budget: no segment ever
    # syncs clean, so the failures stay consecutive and the error
    # surfaces after dispatch_retries replays
    def dead(self, state, stop=None, final_stop=None):
        raise RuntimeError("UNAVAILABLE: device went away")

    monkeypatch.setattr(eng.DeviceEngine, "run", dead)
    with pytest.raises(RuntimeError, match="UNAVAILABLE"):
        _run(SEGMENTED +
             "\n  dispatch_retries: 2"
             "\n  dispatch_retry_backoff: 0.0")


def test_preempt_between_segments_resumes_bitidentical(
        tmp_path, monkeypatch):
    full_stats, full_c = _run()
    assert full_stats.ok
    ref = _sig(full_stats, full_c)

    # SIGTERM raised synchronously inside the third dispatch: the
    # segment still syncs and finishes its boundary work, then the
    # loop saves the resume checkpoint before issuing the next
    base = str(tmp_path / "ck.npz")
    import shadow_tpu.device.engine as eng
    orig = eng.DeviceEngine.run
    calls = {"n": 0}

    def poking(self, state, stop=None, final_stop=None):
        out = orig(self, state, stop=stop, final_stop=final_stop)
        calls["n"] += 1
        if calls["n"] == 3:
            signal.raise_signal(signal.SIGTERM)
        return out

    monkeypatch.setattr(eng.DeviceEngine, "run", poking)
    pre_stats, _ = _run(
        SEGMENTED +
        f"\n  checkpoint_save: {base}"
        f"\n  checkpoint_every: 200ms"
        f"\n  checkpoint_keep: 3")
    assert pre_stats.preempted
    assert pre_stats.resume_path
    assert os.path.exists(pre_stats.resume_path)
    # the signalled segment was synced, not thrown away
    assert pre_stats.pipeline["segments"] == 3
    assert pre_stats.events_executed < full_stats.events_executed

    monkeypatch.setattr(eng.DeviceEngine, "run", orig)
    # the resume is bit-identical unsegmented and unaudited...
    res1_stats, res1_c = _run(f"  checkpoint_load: {base}")
    assert res1_stats.ok and not res1_stats.preempted
    assert _sig(res1_stats, res1_c) == ref
    # ...and segmented with the audit on — segmentation and audit
    # are host orchestration, never part of the checkpoint contract
    res2_stats, res2_c = _run(SEGMENTED +
                              f"\n  checkpoint_load: {base}")
    assert res2_stats.ok
    assert _sig(res2_stats, res2_c) == ref


def test_preempt_before_first_segment_saves_at_start(tmp_path):
    # a drain requested before anything was issued saves the start
    # state and dispatches nothing
    base = str(tmp_path / "ck.npz")
    c = Controller(load_config_str(YAML.format(
        extra=SEGMENTED + f"\n  checkpoint_save: {base}")))
    runner = c.runner
    runner.guard = supervise.PreemptionGuard()
    runner.guard.request()
    state = runner.engine.init_state(runner.sim.starts)
    out, res = supervise.advance(runner, state, 0, 800_000_000,
                                 800_000_000)
    assert res.preempted and res.t_end == 0
    assert res.resume_path == base and os.path.exists(base)
    assert res.pipeline["segments"] == 0
    assert int(np.max(res.rounds)) == 0
    assert out is state


def test_schema_rejects_pipeline_depth_as_unknown():
    for policy in ("tpu", "serial"):
        with pytest.raises(ValueError,
                           match="unknown key.*pipeline_depth"):
            load_config_str(YAML.format(
                extra="  pipeline_depth: 1").replace(
                    "scheduler_policy: tpu",
                    f"scheduler_policy: {policy}"))


def test_plan_roundtrips_dispatch_segment_and_refuses_pipeline_depth(
        tmp_path, monkeypatch):
    monkeypatch.setenv("SHADOW_TPU_OCC_DIR", str(tmp_path))
    from shadow_tpu.core.controller import build
    from shadow_tpu.device.runner import device_twin
    from shadow_tpu.tune import plan as planmod
    from shadow_tpu.tune import space

    cfg = load_config_str(YAML.format(extra=""))
    assert "pipeline_depth" not in space.KNOB_BY_NAME
    with pytest.raises(ValueError,
                       match="unknown knob 'pipeline_depth'"):
        space.apply_assignment(cfg, {"pipeline_depth": 2})
    assert space.apply_assignment(
        cfg, {"dispatch_segment": "100000000"}) == {
            "dispatch_segment": 100_000_000}

    # plan adoption round-trips the knob and stays bit-identical; a
    # knob the plan space no longer has is skipped, never applied
    ref_stats, ref_c = _run()
    sim = build(load_config_str(YAML.format(extra="")))
    twin, H = device_twin(sim), len(sim.hosts)
    path = str(tmp_path / "PLAN_seg.json")
    planmod.save_plan(
        {"format": planmod.FORMAT,
         "workload": {**planmod.workload_stamp(twin, H),
                      "stop_time": 800_000_000, "seed": 9},
         "default": {},
         "knobs": {"dispatch_segment": 100_000_000,
                   "pipeline_depth": 2},
         "score": {"pkts_per_s": 1.0}}, path)
    stats, c = _run(f"  strategy_plan: {path}")
    assert stats.ok
    assert c.sim.cfg.experimental.dispatch_segment == 100_000_000
    assert stats.strategy_plan["knobs"] == {
        "dispatch_segment": 100_000_000}
    assert "unknown knob" in stats.strategy_plan["skipped"][
        "pipeline_depth"]
    assert stats.pipeline["segments"] == 8
    assert _sig(stats, c) == _sig(ref_stats, ref_c)
