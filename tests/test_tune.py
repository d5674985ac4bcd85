"""Strategy autotuner (shadow_tpu/tune/, docs/autotune.md).

Fast tier-1 coverage of the plan space, the PLAN record lifecycle
(save / load / fingerprint verification), adoption through the
DeviceRunner (provenance, hand-set-wins, loud mismatch refusal, and
the bit-identity contract: an adopted plan changes wall time only),
the trial harness on a tiny workload, and trace_report --compare.
The full search loop and the composed-adversarial gate run in
scripts/determinism_gate.py --tuned (CI) and are exercised here on a
tiny budget as a slow test.
"""

import json
import os
import sys

import pytest

from shadow_tpu import simtime
from shadow_tpu.config import load_config_str
from shadow_tpu.config.schema import ExperimentalOptions
from shadow_tpu.core.controller import Controller, build
from shadow_tpu.device.runner import device_twin
from shadow_tpu.tune import plan as planmod
from shadow_tpu.tune import space

TGEN_SMALL = """
general:
  stop_time: {stop}
  seed: 3
network:
  graph:
    type: 1_gbit_switch
experimental:
  scheduler_policy: tpu
{extra}hosts:
  server:
    quantity: 2
    processes:
    - path: model:tgen_server
      start_time: 100ms
  client:
    quantity: 14
    processes:
    - path: model:tgen_client
      args: server=server size=4KiB count=3 pause=100ms
      start_time: 200ms
"""


def _cfg(stop="2s", extra=""):
    return load_config_str(TGEN_SMALL.format(stop=stop, extra=extra))


def _sig(c):
    return [(h.name, h.trace_checksum, h.events_executed,
             h.packets_sent, h.packets_dropped, h.packets_delivered)
            for h in c.sim.hosts]


# ---------------------------------------------------------------------
# schema: the shared keyword-vs-path helper across all three knobs
# ---------------------------------------------------------------------

def test_schema_strategy_plan_keyword_or_path():
    assert ExperimentalOptions.from_dict({}).strategy_plan == "off"
    for ok in ("auto", "off", "artifacts/PLAN_x.json",
               "./rel/PLAN.json"):
        assert ExperimentalOptions.from_dict(
            {"strategy_plan": ok}).strategy_plan == ok
    # YAML 1.1 bare on/off arrive as booleans
    assert ExperimentalOptions.from_dict(
        {"strategy_plan": False}).strategy_plan == "off"
    assert ExperimentalOptions.from_dict(
        {"strategy_plan": True}).strategy_plan == "auto"
    for bad in ("atuo", "on_", "plan.txt", 3, ["x"]):
        with pytest.raises(ValueError, match="strategy_plan"):
            ExperimentalOptions.from_dict({"strategy_plan": bad})


def test_schema_shared_helper_still_rejects_siblings():
    """The refactor onto one helper must keep the siblings' loud
    typo rejection (capacity_plan record paths, compile_cache dir
    paths) intact."""
    with pytest.raises(ValueError, match="capacity_plan"):
        ExperimentalOptions.from_dict(
            {"capacity_plan": "atuo", "scheduler_policy": "tpu"})
    with pytest.raises(ValueError, match="compile_cache"):
        ExperimentalOptions.from_dict({"compile_cache": "atuo"})
    with pytest.raises(ValueError, match="compile_cache"):
        ExperimentalOptions.from_dict({"compile_cache": 3})
    assert ExperimentalOptions.from_dict(
        {"compile_cache": False}).compile_cache == "off"


def test_schema_capacity_headroom():
    ok = ExperimentalOptions.from_dict(
        {"capacity_headroom": 1.25, "capacity_plan": "auto",
         "scheduler_policy": "tpu"})
    assert ok.capacity_headroom == 1.25
    with pytest.raises(ValueError, match="capacity_headroom"):
        ExperimentalOptions.from_dict(
            {"capacity_headroom": 0.5, "capacity_plan": "auto",
             "scheduler_policy": "tpu"})
    with pytest.raises(ValueError, match="capacity_headroom"):
        ExperimentalOptions.from_dict({"capacity_headroom": 1.5})


# ---------------------------------------------------------------------
# the plan space
# ---------------------------------------------------------------------

def test_space_gates_by_policy_and_mesh():
    cfg = _cfg()
    ctx = space.context(cfg, n_shards=1)
    names = [k.name for k in space.applicable(cfg, ctx)]
    assert "dispatch_segment" in names
    assert "exchange" not in names          # one shard
    assert "hybrid_judge_min_batch" not in names    # tpu policy
    assert "capacity_headroom" not in names  # capacity_plan static
    ctx8 = space.context(cfg, n_shards=8)
    assert "exchange" in [k.name for k in space.applicable(cfg, ctx8)]
    cfg.experimental.scheduler_policy = "hybrid"
    ctxh = space.context(cfg, n_shards=8)
    names_h = [k.name for k in space.applicable(cfg, ctxh)]
    assert names_h == ["hybrid_judge_min_batch"]


def test_space_candidates_and_order():
    cfg = _cfg(extra="  capacity_plan: auto\n")
    ctx = space.context(cfg, n_shards=4)
    knobs = space.applicable(cfg, ctx)
    # free runtime knobs precede reshaping ones (descent order)
    reshapes = [k.reshapes for k in knobs]
    assert reshapes == sorted(reshapes)
    seg = space.KNOB_BY_NAME["dispatch_segment"]
    cands = seg.candidates(cfg, ctx)
    assert len(cands) == len(set(cands))
    assert cands[0] == cfg.experimental.dispatch_segment
    exch = space.KNOB_BY_NAME["exchange"]
    assert set(exch.candidates(cfg, ctx)) == {
        "all_to_all", "all_gather", "two_phase"}
    assert "auto" not in exch.candidates(cfg, ctx)


def test_apply_assignment_validates():
    cfg = _cfg()
    applied = space.apply_assignment(
        cfg, {"dispatch_segment": "500000000"})
    assert applied == {"dispatch_segment": 500000000}
    assert cfg.experimental.dispatch_segment == 500000000
    with pytest.raises(ValueError, match="unknown knob"):
        space.apply_assignment(cfg, {"event_capacity": 4})
    # "auto" round-trips as a VALUE (an `exchange: auto` config's
    # baseline mirrors it) but is never a searched candidate
    assert space.apply_assignment(
        cfg, {"exchange": "auto"}) == {"exchange": "auto"}
    with pytest.raises(ValueError, match="exchange"):
        space.apply_assignment(cfg, {"exchange": "alltoall"})
    with pytest.raises(ValueError, match="dispatch_segment"):
        space.apply_assignment(cfg, {"dispatch_segment": -5})
    with pytest.raises(ValueError, match="capacity_headroom"):
        space.apply_assignment(cfg, {"capacity_headroom": 0.3})


# ---------------------------------------------------------------------
# PLAN records: path, round trip, verification
# ---------------------------------------------------------------------

def _twin(cfg):
    sim = build(cfg)
    return device_twin(sim), len(sim.hosts)


def _record(twin, n_hosts, knobs):
    return {"format": planmod.FORMAT,
            "workload": {**planmod.workload_stamp(twin, n_hosts),
                         "stop_time": 2_000_000_000, "seed": 3},
            "default": {}, "knobs": dict(knobs),
            "score": {"pkts_per_s": 1.0}}


def test_plan_path_is_fingerprint_keyed(tmp_path, monkeypatch):
    monkeypatch.setenv("SHADOW_TPU_OCC_DIR", str(tmp_path))
    twin, H = _twin(_cfg())
    p = planmod.plan_path(twin, H)
    assert p.startswith(str(tmp_path))
    assert f"PLAN_TgenDevice_{H}_" in p and p.endswith(".json")
    # a different traffic shape fingerprints to a different file
    twin2, H2 = _twin(load_config_str(TGEN_SMALL.format(
        stop="2s", extra="").replace("count=3", "count=5")))
    assert planmod.plan_path(twin2, H2) != p


def test_plan_roundtrip_and_validation(tmp_path):
    twin, H = _twin(_cfg())
    rec = _record(twin, H, {"dispatch_segment": 250_000_000})
    path = str(tmp_path / "PLAN_t.json")
    planmod.save_plan(rec, path)
    back = planmod.load_plan(path)
    assert back["knobs"] == {"dispatch_segment": 250_000_000}
    planmod.verify_workload(back, twin, H)
    with pytest.raises(ValueError, match="tuned for"):
        planmod.verify_workload(back, twin, H + 1)
    bad = dict(rec, format=99)
    planmod.save_plan(bad, path)
    with pytest.raises(ValueError, match="format"):
        planmod.load_plan(path)
    (tmp_path / "PLAN_m.json").write_text(json.dumps(
        {"format": planmod.FORMAT, "knobs": {}}))
    with pytest.raises(ValueError, match="workload"):
        planmod.load_plan(str(tmp_path / "PLAN_m.json"))


def test_resolve_plan_modes(tmp_path, monkeypatch):
    monkeypatch.setenv("SHADOW_TPU_OCC_DIR", str(tmp_path))
    twin, H = _twin(_cfg())
    assert planmod.resolve_plan("off", twin, H) == (None, "")
    # auto with no canonical record: silent no-op
    assert planmod.resolve_plan("auto", twin, H) == (None, "")
    # an explicit missing path is a loud error
    with pytest.raises(ValueError, match="does not exist"):
        planmod.resolve_plan(str(tmp_path / "nope.json"), twin, H)
    canon = planmod.plan_path(twin, H)
    planmod.save_plan(_record(twin, H, {"dispatch_segment": 1}),
                      canon)
    rec, path = planmod.resolve_plan("auto", twin, H)
    assert path == canon and rec["knobs"] == {"dispatch_segment": 1}


# ---------------------------------------------------------------------
# adoption through the runner: provenance + bit-identity
# ---------------------------------------------------------------------

def test_adopted_plan_is_bit_identical_with_provenance(tmp_path):
    twin, H = _twin(_cfg())
    path = str(tmp_path / "PLAN_t.json")
    planmod.save_plan(
        _record(twin, H, {"dispatch_segment": 500_000_000}), path)

    c_def = Controller(_cfg())
    s_def = c_def.run()
    assert s_def.ok and s_def.strategy_plan is None

    c_tuned = Controller(_cfg(
        extra=f"  strategy_plan: {path}\n"))
    s_tuned = c_tuned.run()
    assert s_tuned.ok
    assert _sig(c_tuned) == _sig(c_def)
    prov = s_tuned.strategy_plan
    assert prov["path"] == path
    assert prov["knobs"] == {"dispatch_segment": 500_000_000}
    # the knob actually reached the engine's segmentation: the
    # tuned run dispatched in more, shorter segments
    assert c_tuned.sim.cfg.experimental.dispatch_segment == \
        500_000_000


def test_adoption_refuses_fingerprint_mismatch(tmp_path):
    twin, H = _twin(_cfg())
    path = str(tmp_path / "PLAN_t.json")
    rec = _record(twin, H, {"dispatch_segment": 500_000_000})
    rec["workload"]["app_fp"] = "deadbeef0000"
    planmod.save_plan(rec, path)
    with pytest.raises(ValueError, match="tuned for"):
        Controller(_cfg(extra=f"  strategy_plan: {path}\n"))


def test_adoption_hand_set_wins_and_inapplicable_skipped(tmp_path):
    twin, H = _twin(_cfg())
    path = str(tmp_path / "PLAN_t.json")
    planmod.save_plan(
        _record(twin, H, {"dispatch_segment": 500_000_000,
                          "hybrid_judge_min_batch": 64}), path)
    # dispatch_segment hand-set in the config -> the plan must not
    # override it; hybrid_judge_min_batch gates on the hybrid policy
    # -> inapplicable on this tpu run
    c = Controller(_cfg(extra=("  dispatch_segment: 1s\n"
                               f"  strategy_plan: {path}\n")))
    prov = c.runner.strategy_plan
    assert prov["knobs"] == {}
    assert "hand-set" in prov["skipped"]["dispatch_segment"]
    assert "not applicable" in prov["skipped"]["hybrid_judge_min_batch"]
    assert c.sim.cfg.experimental.dispatch_segment == \
        simtime.from_seconds(1.0)


def test_adoption_on_hybrid_policy_tunes_the_judge(tmp_path):
    """The judge batching knob is the plan space's hybrid member
    (the ROADMAP's first concrete target): a hybrid-policy run must
    adopt it — through the Controller's hybrid branch, with the gate
    seeing the policy actually running — and reflect it into the
    DeviceJudge the manager consults."""
    twin, H = _twin(_cfg())
    path = str(tmp_path / "PLAN_t.json")
    planmod.save_plan(
        _record(twin, H, {"hybrid_judge_min_batch": 777,
                          "dispatch_segment": 500_000_000}), path)
    cfg = _cfg(extra=f"  strategy_plan: {path}\n")
    cfg.experimental.scheduler_policy = "hybrid"
    c = Controller(cfg)
    prov = c.strategy_plan
    assert prov["knobs"] == {"hybrid_judge_min_batch": 777}
    assert "not applicable" in prov["skipped"]["dispatch_segment"]
    assert c.manager.net_judge.min_batch == 777
    s = c.run()
    assert s.ok and s.strategy_plan == prov


def test_adoption_cadence_knob_uses_plan_tuned_from(tmp_path):
    """Cadence knobs only exist on configs that set them, so the
    hand-set reference is the baseline the plan was tuned FROM (its
    recorded default), not the schema zero: a config still at the
    tuned-from cadence adopts the coarsened one; a config the
    operator moved since tuning keeps its value."""
    extra = ("  checkpoint_save: {dir}/ck.npz\n"
             "  checkpoint_every: 500ms\n")
    cfg = _cfg(extra=extra.format(dir=tmp_path))
    twin, H = _twin(cfg)
    rec = _record(twin, H, {"checkpoint_every": 1_000_000_000})
    rec["default"] = {"checkpoint_every": 500_000_000}
    path = str(tmp_path / "PLAN_t.json")
    planmod.save_plan(rec, path)

    c = Controller(_cfg(extra=extra.format(dir=tmp_path)
                        + f"  strategy_plan: {path}\n"))
    assert c.runner.strategy_plan["knobs"] == {
        "checkpoint_every": 1_000_000_000}
    assert c.sim.cfg.experimental.checkpoint_every == 1_000_000_000

    # operator moved the cadence since tuning -> the plan loses
    moved = extra.format(dir=tmp_path).replace("500ms", "250ms")
    c2 = Controller(_cfg(extra=moved + f"  strategy_plan: {path}\n"))
    assert "hand-set" in \
        c2.runner.strategy_plan["skipped"]["checkpoint_every"]
    assert c2.sim.cfg.experimental.checkpoint_every == 250_000_000


# ---------------------------------------------------------------------
# trial harness
# ---------------------------------------------------------------------

def test_run_trial_scores_and_diagnoses(tmp_path):
    from shadow_tpu.tune.trials import run_trial

    cfg_path = str(tmp_path / "tgen_small.yaml")
    with open(cfg_path, "w") as f:
        f.write(TGEN_SMALL.format(stop="2s", extra=""))
    t = run_trial(cfg_path, {"dispatch_segment": 0},
                  window_ns=1_000_000_000)
    assert t.ok, t.error
    assert t.packets > 0 and t.pkts_per_s > 0
    assert t.signature
    # the per-phase diagnostic rides the ledger entry, and the score
    # wall excludes the one-time compile/plan costs
    assert "dispatch_s" in t.phases
    assert t.score_wall_s <= t.wall_s + 1e-6
    led = t.ledger()
    assert led["knobs"] == {"dispatch_segment": 0}
    assert led["ok"] is True
    json.dumps(led)             # JSON-able for the PLAN file

    # identical assignment, identical window -> identical signature
    # (the guard surface the searcher compares)
    t2 = run_trial(cfg_path, {"dispatch_segment": 250_000_000},
                   window_ns=1_000_000_000)
    assert t2.ok and t2.signature == t.signature


def test_run_trial_failure_is_disqualified_not_raised(tmp_path):
    from shadow_tpu.tune.trials import run_trial

    t = run_trial(str(tmp_path / "missing.yaml"), {}, 1_000)
    assert not t.ok
    assert t.error


@pytest.mark.slow
def test_tuner_search_writes_no_slower_plan(tmp_path):
    """The full search loop on a tiny budget: the returned body is a
    valid PLAN payload, every trial bit-matched the baseline, and
    the chosen assignment is never slower than the defaults by
    construction."""
    from shadow_tpu.tune.trials import Tuner

    cfg_path = str(tmp_path / "tgen_small.yaml")
    with open(cfg_path, "w") as f:
        f.write(TGEN_SMALL.format(stop="2s", extra=""))
    tuner = Tuner(cfg_path, window_ns=1_000_000_000, budget=3)
    body = tuner.search("coordinate_descent")
    assert body["policy"] == "tpu"
    assert body["space"] and body["trials"]
    assert not [t for t in body["trials"]
                if "diverged" in t.get("error", "")]
    assert set(body["knobs"]) == set(body["default"])
    if body["improved"]:
        assert body["score"]["speedup"] > 1.0
    else:
        assert body["knobs"] == body["default"]


# ---------------------------------------------------------------------
# trace_report --compare
# ---------------------------------------------------------------------

def test_trace_report_compare(tmp_path, capsys):
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import trace_report

    a = {"format": 1, "mode": "summary", "total_wall_s": 10.0,
         "phases": {"dispatch_s": 6.0, "host_s": 3.0,
                    "compile_s": 1.0},
         "dominant_phase": "dispatch", "spans": 3,
         "counters": {"packets": 1000}}
    b = {"format": 1, "mode": "summary", "total_wall_s": 5.0,
         "phases": {"dispatch_s": 1.5, "host_s": 3.0,
                    "compile_s": 0.5},
         "dominant_phase": "host", "spans": 3,
         "counters": {"packets": 1000}}
    pa, pb = tmp_path / "METRICS_a.json", tmp_path / "METRICS_b.json"
    pa.write_text(json.dumps(a))
    pb.write_text(json.dumps(b))
    trace_report.print_compare(trace_report.load_metrics(str(pa)),
                               trace_report.load_metrics(str(pb)),
                               str(pa), str(pb))
    out = capsys.readouterr().out
    assert "-4.500" in out          # dispatch delta
    assert "-75.0%" in out
    assert "2.00x" in out           # pkts/s ratio
    assert "shifted" in out         # dominant phase moved
    # the total row reconciles
    assert "-5.000" in out
