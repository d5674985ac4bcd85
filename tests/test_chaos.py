"""Elastic mesh-shrink failover + the deterministic chaos injector
(device/chaos.py, failover: shrink, capacity.reshard_state).

The contract under test: losing 1 of N mesh devices mid-run costs
1/N of throughput, never the run or the trace — a scripted device
loss exhausts retries, the last validated state re-shards onto the
survivors (new padded width, re-planned exchange capacities, warm
engine rebuild), and the continuation is bit-identical to both the
uninterrupted M-shard run and the serial oracle. Checkpoints written
after the shrink stamp the new geometry and resume on it
automatically. Campaigns get the same ladder (the replica axis vmaps
outside the mesh axis). Every injected fault fires at a
deterministic seam counter, so runs reproduce byte for byte,
failures included.
"""

import logging
import os

import numpy as np
import pytest

from shadow_tpu.config import load_config_str
from shadow_tpu.core.controller import Controller
from shadow_tpu.device import chaos as chaosmod
from shadow_tpu.device import checkpoint, supervise

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

SHRINK = """  mesh_shards: 4
  dispatch_segment: 200ms
  state_audit: true
  failover: shrink
  dispatch_retries: 1
  dispatch_retry_backoff: 0.0
  chaos:
  - {kind: device_loss, segment: 2, shard: 1}
"""


def _run(extra=""):
    c = Controller(load_config_str(YAML.format(extra=extra)))
    stats = c.run()
    return stats, c


def _sig(stats, c):
    return (stats.events_executed, stats.packets_sent,
            stats.packets_dropped, stats.packets_delivered,
            [(h.name, h.trace_checksum) for h in c.sim.hosts])


@pytest.fixture(scope="module")
def ref():
    """The uninterrupted reference signature, computed ONCE on a
    3-shard mesh: per-host signatures are invariant across mesh
    shape, segmentation cadence and audit (the determinism
    contract, pinned elsewhere), so every recovery test
    in this module compares against this one run."""
    stats, c = _run("  mesh_shards: 3\n"
                    "  dispatch_segment: 200ms\n"
                    "  state_audit: true")
    assert stats.ok
    return _sig(stats, c)


# ---------------------------------------------------------------------------
# schema validation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("extra,match", [
    ("  chaos:\n  - {kind: sideways, segment: 1}", "kind"),
    ("  chaos:\n  - {kind: device_loss, segment: 1}", "shard"),
    ("  chaos:\n  - {kind: dispatch_error}", "segment"),
    ("  chaos:\n  - {kind: checkpoint_corrupt}", "entry"),
    ("  chaos:\n  - {kind: cache_store_fail}", "store"),
    ("  chaos:\n  - {kind: cache_store_fail, store: 0, shard: 1}",
     "not valid"),
    ("  mesh_shards: -1", "mesh_shards"),
])
def test_schema_rejects_bad_chaos_knobs(extra, match):
    with pytest.raises(ValueError, match=match):
        load_config_str(YAML.format(extra=extra))


def test_schema_rejects_chaos_on_cpu_policies():
    serial = YAML.replace("scheduler_policy: tpu",
                          "scheduler_policy: serial")
    for extra, match in (
            ("  chaos:\n  - {kind: cache_store_fail, store: 0}",
             "chaos"),
            ("  mesh_shards: 2", "mesh_shards")):
        with pytest.raises(ValueError, match=match):
            load_config_str(serial.format(extra=extra))


def test_schema_allows_shrink_for_campaigns_rejects_hybrid(tmp_path):
    ens = ENS.format(rec=tmp_path / "ENSEMBLE.json")
    cfg = load_config_str(YAML.format(extra="  failover: shrink")
                          + ens)
    assert cfg.experimental.failover == "shrink"
    with pytest.raises(ValueError, match="shrink"):
        load_config_str(YAML.format(extra="  failover: hybrid") + ens)


# ---------------------------------------------------------------------------
# the tentpole: scripted device loss -> 4 -> 3 shrink, bit-identical
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("segment", ["200ms", "100ms"])
def test_shrink_bitmatches_uninterrupted_3_shard_run(ref, segment):
    # the shrink replays from the last validated boundary whatever
    # the segment cadence: every segment before the loss was synced
    # and validated on the live mesh
    stats, c = _run(SHRINK.replace("dispatch_segment: 200ms",
                                   f"dispatch_segment: {segment}"))
    assert stats.ok
    assert stats.reshards == 1
    assert stats.retries >= 1
    assert c.runner.engine.n_shards == 3
    assert _sig(stats, c) == ref
    # the injector's ledger names what fired, deterministically
    assert [f["kind"] for f in c.runner.chaos.fired] == ["device_loss"]
    # the audited run kept a zero health word across the reshard
    assert int(np.asarray(c.runner.final_state["aud"]).max()) == 0


def test_shrink_checkpoints_stamp_geometry_and_resume(tmp_path,
                                                     ref):
    base = str(tmp_path / "ck.npz")
    stats, c = _run(SHRINK + f"  checkpoint_save: {base}\n"
                             f"  checkpoint_every: 200ms\n"
                             f"  checkpoint_keep: 8")
    assert stats.ok and stats.reshards == 1
    entries = supervise.rotation_entries(base)
    post = [(t, p) for t, p in entries if t < 800_000_000]
    assert post, "no rotation entry before stop"
    t_last, p_last = post[-1]
    geom = checkpoint.peek_geometry(checkpoint.peek_meta(p_last))
    # a post-shrink checkpoint stamps the SHRUNKEN geometry
    assert geom == {"n_shards": 3, "h_pad": 6, "h_loc": 2}

    # resume on the full (8-device conftest) pool: the runner must
    # adopt the saved 3-shard geometry from the stamp and bit-match
    res_stats, res_c = _run(f"  checkpoint_load: {p_last}\n"
                            f"  dispatch_segment: 200ms")
    assert res_stats.ok
    assert res_c.runner.engine.n_shards == 3
    assert _sig(res_stats, res_c) == ref


def test_geometry_mismatch_message_is_readable(tmp_path):
    """Satellite: the shard-geometry fields live in readable
    __meta__ keys, so a direct cross-geometry load names the shard
    counts instead of an opaque fingerprint diff."""
    base = str(tmp_path / "geo.npz")
    stats, c = _run("  mesh_shards: 4\n"
                    f"  checkpoint_save: {base}\n"
                    "  checkpoint_save_time: 400ms")
    assert stats.ok
    meta = checkpoint.peek_meta(base)
    assert meta["geometry"] == {"n_shards": 4, "h_pad": 8,
                               "h_loc": 2}
    # build (never run) a 2-shard engine and load the 4-shard
    # checkpoint directly: the refusal must name the shard counts
    cfg2 = load_config_str(YAML.format(extra="  mesh_shards: 2"))
    c2 = Controller(cfg2)
    with pytest.raises(ValueError,
                       match=r"saved on 4 shard\(s\).*loading on 2"):
        checkpoint.load_state(c2.runner.engine, c2.sim.starts, base,
                              final_stop=800_000_000)


def test_reshard_state_rejects_unregistered_leaves():
    _, c = _run("  mesh_shards: 2")
    from shadow_tpu._jax import jax
    from shadow_tpu.device import capacity

    r = c.runner
    state = jax.device_get(r.engine.init_state(r.sim.starts))
    template = dict(state)
    template["mystery"] = np.zeros(8)
    bad = dict(state)
    bad["mystery"] = np.zeros(8)
    with pytest.raises(ValueError, match="mystery"):
        capacity.reshard_state(bad, 6, template)
    # a snapshot carrying a non-auxiliary leaf the target lacks is
    # equally loud
    with pytest.raises(ValueError, match="mystery"):
        capacity.reshard_state(bad, 6, state)


# ---------------------------------------------------------------------------
# the other chaos kinds
# ---------------------------------------------------------------------------

def test_one_shot_dispatch_error_retries_bitmatch(ref):
    stats, c = _run(
        "  dispatch_segment: 200ms\n"
        "  dispatch_retries: 2\n"
        "  dispatch_retry_backoff: 0.0\n"
        "  chaos:\n"
        "  - {kind: dispatch_error, segment: 1, "
        "error: RESOURCE_EXHAUSTED}")
    assert stats.ok
    assert stats.retries == 1 and stats.reshards == 0
    assert _sig(stats, c) == ref

    # a non-transient scripted class is never retried
    with pytest.raises(chaosmod.ChaosError, match="INVALID_ARGUMENT"):
        _run("  dispatch_segment: 200ms\n"
             "  dispatch_retries: 5\n"
             "  chaos:\n"
             "  - {kind: dispatch_error, segment: 1, "
             "error: INVALID_ARGUMENT}")


def test_checkpoint_corrupt_engages_newest_readable(tmp_path):
    base = str(tmp_path / "rot.npz")
    # 3 rotation saves (200ms cadence, stop 800ms => t=200/400/600);
    # the schedule corrupts the LAST one
    stats, _ = _run(f"  checkpoint_save: {base}\n"
                    f"  checkpoint_every: 200ms\n"
                    f"  checkpoint_keep: 8\n"
                    f"  dispatch_segment: 200ms\n"
                    f"  chaos:\n"
                    f"  - {{kind: checkpoint_corrupt, entry: 2}}")
    assert stats.ok
    entries = supervise.rotation_entries(base)
    newest = entries[-1][1]
    # the end-of-run base save would win resolution; drop it to
    # simulate the crash the rotation exists for
    os.unlink(base)
    resolved = supervise.resolve_checkpoint(base)
    assert resolved != newest
    assert resolved == entries[-2][1]
    with pytest.raises(Exception):
        checkpoint.peek_meta(newest)


def test_cache_store_fail_degrades_loudly(tmp_path, caplog):
    # a fresh cache directory: the session-shared test cache would
    # serve a HIT and no store (the drilled seam) would ever fire
    with caplog.at_level(logging.WARNING):
        stats, c = _run("  chaos:\n"
                        "  - {kind: cache_store_fail, store: 0}\n"
                        f"  compile_cache: {tmp_path / 'aot'}")
    assert stats.ok
    inj = c.runner.chaos
    rep = stats.compile_cache or {}
    if rep.get("unsupported"):
        pytest.skip("backend has no executable serialization — no "
                    "store seam to drill")
    assert [f["kind"] for f in inj.fired] == ["cache_store_fail"]
    assert any("refused by the chaos schedule" in r.getMessage()
               for r in caplog.records)


def test_injector_not_leaked_across_runs():
    stats, c = _run("  chaos:\n"
                    "  - {kind: cache_store_fail, store: 999}")
    assert c.runner.chaos is not None
    _run("")
    assert chaosmod.current() is None


# ---------------------------------------------------------------------------
# ensemble campaigns shrink too (their first working failover)
# ---------------------------------------------------------------------------

ENS = """
ensemble:
  replicas: 2
  vary:
    seed: [9, 11]
  record_path: {rec}
"""


def test_ensemble_campaign_shrinks_bitmatch(tmp_path):
    def run_ens(extra):
        ens = ENS.format(rec=tmp_path / "ENSEMBLE.json")
        c = Controller(load_config_str(YAML.format(extra=extra)
                                       + ens))
        stats = c.run()
        f = c.runner.final_state
        return stats, c, {k: np.asarray(f[k])
                          for k in ("chk", "n_exec", "n_sent",
                                    "n_drop", "n_deliv")}

    ref_stats, _, ref = run_ens("  mesh_shards: 3\n"
                                "  dispatch_segment: 200ms\n"
                                "  state_audit: true")
    assert ref_stats.ok
    stats, c, f = run_ens(SHRINK)
    assert stats.ok
    assert stats.reshards == 1
    assert c.runner.engine.n_shards == 3
    H = 6
    for k in ref:
        assert np.array_equal(ref[k][:, :H], f[k][:, :H]), k


# ---------------------------------------------------------------------------
# satellite: persist failure during escalation still fails over, with
# ONE diagnostic naming the persist error
# ---------------------------------------------------------------------------

def test_failover_persist_failure_still_runs_hybrid(monkeypatch,
                                                    caplog, ref):
    import shadow_tpu.device.engine as eng

    def dead(self, state, stop=None, final_stop=None):
        raise RuntimeError("UNAVAILABLE: device went away")

    def unsavable(engine, state, path, sim_time, **kw):
        raise OSError("disk full: injected persist failure")

    monkeypatch.setattr(eng.DeviceEngine, "run", dead)
    monkeypatch.setattr(checkpoint, "save_state", unsavable)
    with caplog.at_level(logging.ERROR):
        stats, c = _run("  failover: hybrid\n"
                        "  dispatch_segment: 200ms")
    assert stats.ok
    # no state made it to disk: the stat says so explicitly
    assert stats.failover_checkpoint == ""
    assert _sig(stats, c) == ref
    diags = [r.getMessage() for r in caplog.records
             if "DEVICE FAILOVER" in r.getMessage()]
    assert len(diags) == 1, diags
    assert "injected persist failure" in diags[0]
    assert "NO device-side resume point" in diags[0]


def test_failed_reshard_rolls_back_before_escalating(monkeypatch,
                                                     tmp_path, ref):
    """A shrink that dies mid-reshard must roll the runner back to
    the OLD mesh/engine before escalating: the escalation persists
    the (old-geometry) snapshot through runner.engine, so a
    half-committed shrink would stamp the new geometry over
    old-layout leaves and poison the failover checkpoint."""
    from shadow_tpu.device import capacity

    def broken_reshard(host_state, n_hosts, template_host):
        raise RuntimeError("injected reshard failure")

    monkeypatch.setattr(capacity, "reshard_state", broken_reshard)
    base = str(tmp_path / "fo.npz")
    stats, c = _run(SHRINK + f"  checkpoint_save: {base}\n")
    # shrink failed -> the ladder's hybrid rung finished the run
    assert stats.ok
    assert stats.reshards == 0
    assert _sig(stats, c) == ref
    assert stats.failover_checkpoint
    geom = checkpoint.peek_geometry(
        checkpoint.peek_meta(stats.failover_checkpoint))
    # the failover checkpoint carries the ORIGINAL 4-shard geometry,
    # matching its leaves — not the half-committed 3-shard mesh
    assert geom["n_shards"] == 4


def test_shrink_escalates_to_hybrid_when_nothing_dead(monkeypatch,
                                                      caplog, ref):
    """The ladder: failover: shrink with a dispatch failure no
    liveness probe can attribute (every device answers) must fall
    through to the hybrid rung, not abort."""
    import shadow_tpu.device.engine as eng

    def dead(self, state, stop=None, final_stop=None):
        raise RuntimeError("UNAVAILABLE: flaky fabric, no dead chip")

    monkeypatch.setattr(eng.DeviceEngine, "run", dead)
    with caplog.at_level(logging.ERROR):
        stats, c = _run("  failover: shrink\n"
                        "  dispatch_segment: 200ms")
    assert stats.ok
    assert _sig(stats, c) == ref
    assert any("cannot be attributed" in r.getMessage()
               for r in caplog.records)
    assert any("DEVICE FAILOVER" in r.getMessage()
               for r in caplog.records)
