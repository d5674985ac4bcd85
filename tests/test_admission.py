"""Preflight resource admission + the OOM degradation ladder
(device/capacity.py footprint/admission_verdict +
device/supervise.py recover_oom + ensemble replica batches).

The contract under test: a run must never OOM blind. Before any
compile, both runners estimate the per-device byte footprint and
compare it to the budget — `admission: strict` refuses over-budget
configs with a readable diagnostic, `auto` statically degrades
(ensemble replica batches) or admits loudly. At runtime, a
deterministic RESOURCE_EXHAUSTED walks a degradation ladder (split
the ensemble into sequential replica batches -> halve the dispatch
segment -> failover) instead
of draining dispatch_retries, and every rung is bit-identical to
the undegraded run. The footprint model itself is kept honest
against live device bytes within capacity.FOOTPRINT_TOLERANCE.
"""

import gc
import json
import logging

import numpy as np
import pytest

from shadow_tpu.config import load_config_str
from shadow_tpu.core.controller import Controller
from shadow_tpu.device import capacity
from shadow_tpu.device.runner import DeviceRunner
from shadow_tpu.ensemble.campaign import EnsembleRunner

YAML = """
general:
  stop_time: 800ms
  seed: 9
  heartbeat_interval: 200ms
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

ENS = """
ensemble:
  replicas: 2
  vary:
    seed: [9, 11]
  record_path: {rec}
"""

# every OOM-ladder run segments so rungs have boundaries to engage at
OOM_BASE = ("  dispatch_segment: 200ms\n"
            "  state_audit: true\n"
            "  dispatch_retries: 1\n"
            "  dispatch_retry_backoff: 0.0\n")


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
    """The undegraded reference: signature + stats + controller (its
    engine feeds the footprint computations below)."""
    stats, c = _run("  dispatch_segment: 200ms\n  state_audit: true")
    assert stats.ok
    return _sig(stats, c), stats, c


@pytest.fixture(scope="module")
def ens_full(tmp_path_factory):
    """The full-vmap 2-replica campaign every batched/degraded
    campaign must bit-match."""
    rec = tmp_path_factory.mktemp("ens_full") / "ENSEMBLE.json"
    c = Controller(load_config_str(
        YAML.format(extra="  dispatch_segment: 200ms")
        + ENS.format(rec=rec)))
    stats = c.run()
    assert stats.ok
    f = c.runner.final_state
    return {k: np.asarray(f[k])
            for k in ("chk", "n_exec", "n_sent", "n_drop", "n_deliv")}


# ---------------------------------------------------------------------------
# schema validation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("extra,match", [
    ("  admission: sideways", "admission"),
    ("  device_memory_budget: -4", "device_memory_budget"),
])
def test_schema_rejects_bad_admission_knobs(extra, match):
    with pytest.raises(ValueError, match=match):
        load_config_str(YAML.format(extra=extra))


def test_schema_rejects_admission_knobs_on_cpu_policies():
    serial = YAML.replace("scheduler_policy: tpu",
                          "scheduler_policy: serial")
    for extra, match in (
            ("  admission: strict", "scheduler_policy"),
            ("  device_memory_budget: 1GiB", "device_memory_budget")):
        with pytest.raises(ValueError, match=match):
            load_config_str(serial.format(extra=extra))


def test_schema_parses_budget_sizes_and_admission_choices():
    cfg = load_config_str(YAML.format(
        extra="  device_memory_budget: 8GiB\n  admission: off"))
    assert cfg.experimental.device_memory_budget == 8 * (1 << 30)
    assert cfg.experimental.admission == "off"
    # default: auto, no budget
    cfg = load_config_str(YAML.format(extra=""))
    assert cfg.experimental.admission == "auto"
    assert cfg.experimental.device_memory_budget == 0


def test_schema_bounds_replica_batch(tmp_path):
    ens = ENS.format(rec=tmp_path / "ENSEMBLE.json")
    for batch in (-1, 3):     # campaign has 2 replicas
        with pytest.raises(ValueError, match="replica_batch"):
            load_config_str(
                YAML.format(extra="")
                + ens + f"  replica_batch: {batch}\n")
    cfg = load_config_str(YAML.format(extra="")
                          + ens + "  replica_batch: 1\n")
    assert cfg.ensemble.replica_batch == 1


def test_schema_replica_batch_checkpoint_contract(tmp_path):
    ens = ENS.format(rec=tmp_path / "ENSEMBLE.json")
    # per-batch rotation checkpoints (<save>.b<k>.t<ns>) made batched
    # campaigns preemptible, so save + every is now a valid combo
    cfg = load_config_str(
        YAML.format(
            extra=f"  checkpoint_save: {tmp_path / 'ck.npz'}\n"
                  "  checkpoint_every: 200ms")
        + ens + "  replica_batch: 1\n")
    assert cfg.ensemble.replica_batch == 1
    # but a batched campaign still has no single pause point, so the
    # one-shot save-at-time form stays rejected
    with pytest.raises(ValueError, match="replica_batch"):
        load_config_str(
            YAML.format(
                extra=f"  checkpoint_save: {tmp_path / 'ck.npz'}\n"
                      "  checkpoint_save_time: 200ms")
            + ens + "  replica_batch: 1\n")
    # and save without a rotation cadence can never write anything
    with pytest.raises(ValueError, match="checkpoint_every"):
        load_config_str(
            YAML.format(
                extra=f"  checkpoint_save: {tmp_path / 'ck.npz'}")
            + ens + "  replica_batch: 1\n")


# ---------------------------------------------------------------------------
# preflight admission: strict refusal / auto verdicts
# ---------------------------------------------------------------------------

def test_strict_refusal_is_readable_and_precedes_compile(tmp_path):
    # a private cold AOT cache: if anything compiled before the
    # refusal, an entry would land here
    aot = tmp_path / "aot"
    with pytest.raises(ValueError, match=r"admission: needs .* per "
                                         r"device, budget 4\.0 KiB "
                                         r"\(config\)") as ei:
        _run("  admission: strict\n"
             "  device_memory_budget: 4KiB\n"
             f"  compile_cache: {aot}")
    # the diagnostic must name the levers, not just the numbers
    assert "ensemble.replicas" in str(ei.value)
    assert not aot.is_dir() or not list(aot.iterdir())


def test_strict_without_any_budget_refuses():
    # CPU backends report no bytes_limit; strict must not silently
    # admit just because there is nothing to compare against
    with pytest.raises(ValueError, match="budget"):
        _run("  admission: strict")


def test_auto_without_budget_skips_loudly(ref):
    _, stats, _ = ref
    adm = stats.admission
    assert adm is not None and adm["action"] == "no-budget"
    assert adm["budget"] == 0 and adm["overrides"] == {}


def test_auto_admits_within_budget():
    stats, c = _run("  device_memory_budget: 1GiB")
    assert stats.ok
    adm = stats.admission
    assert adm["action"] == "admit" and adm["fits"]
    assert adm["budget_source"] == "config"
    assert adm["estimate"]["per_device"] <= adm["budget"]


def test_auto_over_budget_admits_loudly_and_runs(ref):
    sig_ref, _, _ = ref
    stats, c = _run("  dispatch_segment: 200ms\n"
                    "  state_audit: true\n"
                    "  device_memory_budget: 4KiB")
    assert stats.ok
    adm = stats.admission
    assert adm["action"] == "over" and not adm["fits"]
    assert _sig(stats, c) == sig_ref


# ---------------------------------------------------------------------------
# the runtime ladder: deterministic OOM degrades instead of aborting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("retries", [1, 3])
def test_deterministic_oom_halves_dispatch_segment(ref, retries):
    sig_ref, _, _ = ref
    # a scripted RESOURCE_EXHAUSTED that REPEATS until a rung engages:
    # the first one charges one ordinary retry, the second
    # consecutive identical one routes to the ladder whatever budget
    # is left. No ensemble: the rung halves the dispatch segment and
    # replays
    stats, c = _run(OOM_BASE.replace("dispatch_retries: 1",
                                     f"dispatch_retries: {retries}") +
                    "  chaos:\n"
                    "  - {kind: oom, segment: 1}")
    assert stats.ok
    assert stats.degrades == 1
    assert stats.retries == 1      # the ladder walk charged nothing
    assert _sig(stats, c) == sig_ref
    kinds = [f["kind"] for f in c.runner.chaos.fired]
    assert "oom" in kinds and "oom_cleared" in kinds
    cleared = [f for f in c.runner.chaos.fired
               if f["kind"] == "oom_cleared"]
    assert cleared[0]["rung"] == "dispatch_segment 200000000->100000000"


def test_compile_seam_oom_walks_ladder(tmp_path, ref):
    sig_ref, _, _ = ref
    # a COLD private cache so the compile actually runs (a warm hit
    # compiles nothing and the seam never fires)
    stats, c = _run(OOM_BASE +
                    f"  compile_cache: {tmp_path / 'aot'}\n"
                    "  chaos:\n"
                    "  - {kind: oom, compile: 0}")
    assert stats.ok
    assert stats.degrades == 1
    assert stats.retries <= 1
    assert _sig(stats, c) == sig_ref
    fired = c.runner.chaos.fired
    assert any(f.get("seam") == "compile" for f in fired
               if f["kind"] == "oom")


# ---------------------------------------------------------------------------
# ensemble replica batches: configured and ladder-driven
# ---------------------------------------------------------------------------

def test_replica_batch_config_bitmatches_full_vmap(tmp_path, ens_full):
    rec = tmp_path / "ENSEMBLE.json"
    c = Controller(load_config_str(
        YAML.format(extra="  dispatch_segment: 200ms")
        + ENS.format(rec=rec) + "  replica_batch: 1\n"))
    stats = c.run()
    assert stats.ok
    f = c.runner.final_state
    for k, want in ens_full.items():
        assert np.array_equal(np.asarray(f[k]), want), k
    assert stats.pipeline["replica_batches"] == 2
    assert stats.pipeline["replica_batch"] == 1
    record = json.loads(rec.read_text())
    assert record["replica_batch"] == 1
    assert record["admission"]["replica_batch"] == 1


def test_oom_walks_replica_batch_rung_bitmatch(tmp_path, ens_full):
    # ensemble: the ladder's replica-batch rung re-runs the
    # campaign as sequential batches — bit-identical to the full vmap
    rec = tmp_path / "ENSEMBLE.json"
    c = Controller(load_config_str(
        YAML.format(extra=OOM_BASE +
                    "  chaos:\n"
                    "  - {kind: oom, segment: 1}")
        + ENS.format(rec=rec)))
    stats = c.run()
    assert stats.ok
    assert stats.degrades >= 1
    f = c.runner.final_state
    for k, want in ens_full.items():
        assert np.array_equal(np.asarray(f[k]), want), k
    assert stats.pipeline["replica_batches"] == 2
    cleared = [f for f in c.runner.chaos.fired
               if f["kind"] == "oom_cleared"]
    assert cleared and "replica" in cleared[0]["rung"]


def test_replica_batches_sum_dispatch_telemetry(tmp_path, monkeypatch):
    # the batches run one after another: the campaign's dispatch
    # block is the sum of each batch's advance telemetry
    from shadow_tpu.device import supervise

    per_batch = []
    orig = supervise.advance

    def spy(*a, **kw):
        state, adv = orig(*a, **kw)
        per_batch.append(dict(adv.pipeline))
        return state, adv

    monkeypatch.setattr(supervise, "advance", spy)
    c = Controller(load_config_str(
        YAML.format(extra="  dispatch_segment: 200ms")
        + ENS.format(rec=tmp_path / "ENSEMBLE.json")
        + "  replica_batch: 1\n"))
    stats = c.run()
    assert stats.ok
    assert len(per_batch) == 2
    p = stats.pipeline
    assert p["segments"] == sum(b["segments"] for b in per_batch) == 8
    for k in ("sync_wall_s", "advance_wall_s"):
        assert p[k] == round(sum(b[k] for b in per_batch), 3)
    assert 0.0 < p["sync_wall_s"] <= p["advance_wall_s"]


# ---------------------------------------------------------------------------
# estimator honesty: footprint() vs live device bytes mid-run
# ---------------------------------------------------------------------------

def _spy_live(monkeypatch, cls):
    """Sample engine.live_bytes() at every heartbeat boundary (the
    template heartbeats every 200ms), when the run's state actually
    sits on the devices."""
    samples = []
    orig = cls._emit_heartbeats

    def probe(self, now, state):
        samples.append(self.engine.live_bytes())
        return orig(self, now, state)

    monkeypatch.setattr(cls, "_emit_heartbeats", probe)
    return samples


def _honest(samples, engine):
    assert samples
    live = max(samples)
    est = capacity.footprint(engine)["per_device"]
    tol = capacity.FOOTPRINT_TOLERANCE
    assert live <= est * tol, (live, est)   # never a blind underestimate
    assert est <= live * tol, (live, est)   # never uselessly conservative


@pytest.mark.parametrize("extra", [
    "  dispatch_segment: 200ms",
    "  dispatch_segment: 200ms\n  state_audit: true",
])
def test_footprint_honest_standalone(monkeypatch, extra):
    gc.collect()
    samples = _spy_live(monkeypatch, DeviceRunner)
    stats, c = _run(extra)
    assert stats.ok
    _honest(samples, c.runner.engine)


def test_footprint_counts_two_state_copies(ref):
    # the segment in flight plus the last validated snapshot: the
    # model holds exactly two copies of the state
    _, _, c_ref = ref
    est = capacity.footprint(c_ref.runner.engine)
    assert est["copies"] == 2 and est["replicas"] == 1
    assert est["per_device"] == (2 * est["state_bytes"]
                                 + est["scratch_bytes"]
                                 + est["world_bytes"])
    assert set(est) == {"representation", "per_device", "total",
                        "state_bytes", "scratch_bytes", "world_bytes",
                        "copies", "replicas", "n_devices"}


def test_footprint_honest_ensemble(monkeypatch, tmp_path):
    gc.collect()
    samples = _spy_live(monkeypatch, EnsembleRunner)
    c = Controller(load_config_str(
        YAML.format(extra="  dispatch_segment: 200ms")
        + ENS.format(rec=tmp_path / "ENSEMBLE.json")))
    stats = c.run()
    assert stats.ok
    _honest(samples, c.runner.engine)


# ---------------------------------------------------------------------------
# memory observability: heartbeat column + SimStats fields
# ---------------------------------------------------------------------------

def test_heartbeats_and_stats_report_memory(caplog):
    with caplog.at_level(logging.INFO):
        stats, c = _run("  dispatch_segment: 200ms")
    assert stats.ok
    hb = [r.getMessage() for r in caplog.records
          if "[supervise-heartbeat]" in r.getMessage()]
    assert hb and all("mem=" in line for line in hb)
    mem = c.runner.engine.device_memory_stats()
    if mem is None:
        # CPU backends expose no allocator stats: the column reads
        # n/a and the stats fields hold the -1 sentinel
        assert all("mem=n/a" in line for line in hb)
        assert stats.mem_bytes_in_use == -1
        assert stats.mem_budget == -1
    else:
        assert stats.mem_bytes_in_use > 0
        assert stats.mem_budget > 0


def test_ensemble_heartbeats_report_memory(caplog, tmp_path):
    with caplog.at_level(logging.INFO):
        c = Controller(load_config_str(
            YAML.format(extra="  dispatch_segment: 200ms")
            + ENS.format(rec=tmp_path / "ENSEMBLE.json")))
        stats = c.run()
    assert stats.ok
    hb = [r.getMessage() for r in caplog.records
          if "[ensemble-heartbeat]" in r.getMessage()]
    assert hb and all("mem=" in line for line in hb)
