"""Campaign server (shadow_tpu/serve/): the durable submission
journal, the scheduler's admit/preempt/recover loop, and the
crash-safety contract — a kill at any instant loses no campaign, and
every resumed run bit-matches an uninterrupted standalone one.

The drills here run the server IN-PROCESS (tick() driven by the
test, ``crash_fn`` raising :class:`ServerCrash` instead of
``os._exit``), so the kill point is deterministic; the real
SIGKILL-a-daemon version of the same drill is the determinism gate's
``--server`` rung in CI.
"""

import json
import os
import time

import pytest

from shadow_tpu.config import load_config
from shadow_tpu.core.controller import Controller
from shadow_tpu.device.chaos import ChaosInjector, events_from_config
from shadow_tpu.serve import Campaign, Journal
from shadow_tpu.serve.server import (
    _ROTATION_RE,
    CampaignServer,
    ServerCrash,
    submit,
)

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


@pytest.fixture
def cfg_path(tmp_path):
    p = tmp_path / "run.yaml"
    p.write_text(YAML.format(extra=""))
    return str(p)


def standalone_sig(cfg_path, data_dir):
    cfg = load_config(cfg_path)
    cfg.general.data_directory = str(data_dir)
    c = Controller(cfg)
    stats = c.run()
    assert stats.ok
    return [[h.name, int(h.trace_checksum), int(h.events_executed),
             int(h.packets_sent), int(h.packets_dropped),
             int(h.packets_delivered)] for h in c.sim.hosts]


def drive(srv, timeout_s=240, until=None):
    """Tick the scheduler until idle (or `until` fires)."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        busy = srv.tick()
        if until is not None:
            if until():
                return
        elif not busy:
            return
        time.sleep(0.005)
    raise AssertionError("server drive timed out")


def journal_rows(spool):
    with open(os.path.join(spool, "journal.jsonl"),
              encoding="utf-8") as f:
        return [json.loads(line) for line in f if line.strip()]


def result_of(spool, cid):
    with open(os.path.join(spool, "campaigns", cid, "RESULT.json"),
              encoding="utf-8") as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# the journal: durable append + last-state-wins replay
# ---------------------------------------------------------------------------

def test_journal_replay_last_state_wins(tmp_path):
    j = Journal(str(tmp_path))
    j.server_event("server_start", restarts=1)
    j.transition("c0000", "QUEUED", config="a.yaml", priority=3,
                 seq=0)
    j.transition("c0001", "QUEUED", config="b.yaml", priority=0,
                 seq=1)
    j.transition("c0000", "RUNNING", attempts=1)
    j.transition("c0000", "PREEMPTED", resume_path="/x/ck.t1",
                 preemptions=1)
    campaigns, meta = j.replay()
    assert meta["server_starts"] == 1 and meta["torn_lines"] == 0
    c0 = campaigns["c0000"]
    assert (c0.state, c0.priority, c0.resume_path, c0.preemptions) \
        == ("PREEMPTED", 3, "/x/ck.t1", 1)
    assert campaigns["c0001"].state == "QUEUED"


def test_journal_rejects_unknown_state(tmp_path):
    with pytest.raises(ValueError, match="unknown campaign state"):
        Journal(str(tmp_path)).transition("c0000", "LIMBO")


def test_journal_tolerates_torn_final_line(tmp_path):
    j = Journal(str(tmp_path))
    j.transition("c0000", "QUEUED", config="a.yaml", seq=0)
    j.transition("c0000", "RUNNING", attempts=1)
    # the crash frontier: a kill mid-append tears the last line
    with open(j.path, "a", encoding="utf-8") as f:
        f.write('{"cid": "c0000", "state": "DO')
    campaigns, meta = j.replay()
    assert meta["torn_lines"] == 1
    # replay lands on the last DURABLE state, not the torn one
    assert campaigns["c0000"].state == "RUNNING"
    # and appending after a tear starts a fresh, parseable line
    j.transition("c0000", "PREEMPTED", resume_path="")
    campaigns, meta = j.replay()
    assert campaigns["c0000"].state == "PREEMPTED"


def test_replay_fields_round_trip(tmp_path):
    j = Journal(str(tmp_path))
    j.transition("c0000", "QUEUED", config="a.yaml", priority=2,
                 seq=5, overrides=["general.seed=7"], sub="sub_1.json",
                 submitted_wall=123.5)
    c = j.replay()[0]["c0000"]
    assert isinstance(c, Campaign)
    assert (c.config, c.priority, c.seq, c.overrides, c.sub,
            c.submitted_wall) == ("a.yaml", 2, 5,
                                  ["general.seed=7"], "sub_1.json",
                                  123.5)


# ---------------------------------------------------------------------------
# the scheduler: submit -> DONE, namespaced artifacts
# ---------------------------------------------------------------------------

def test_server_completes_campaign_bit_identical(tmp_path, cfg_path):
    ref = standalone_sig(cfg_path, tmp_path / "ref.data")
    spool = str(tmp_path / "spool")
    submit(spool, cfg_path, priority=1)
    srv = CampaignServer(spool, poll_s=0.0)
    srv.recover()
    drive(srv)
    srv._shutdown()
    res = result_of(spool, "c0000")
    assert res["state"] == "DONE" and res["attempts"] == 1
    # the robustness claim's baseline: a served run IS a standalone
    # run — same Controller path, same signature
    assert res["signature"] == ref
    states = [r.get("state") or r.get("event")
              for r in journal_rows(spool)]
    assert states == ["server_start", "QUEUED", "ADMITTED",
                      "RUNNING", "DONE", "server_stop"]
    cdir = os.path.join(spool, "campaigns", "c0000")
    # per-campaign namespacing: rotation checkpoints and telemetry
    # records live under the campaign directory
    assert any(n.startswith("ck.npz.t") for n in os.listdir(cdir))
    assert any(n.startswith("METRICS_")
               for n in os.listdir(os.path.join(cdir, "artifacts")))
    # the server SLO summary record
    slo = json.load(open(os.path.join(spool, "SLO_server.json")))
    assert slo["done"] == 1 and slo["failed"] == 0


def test_server_refuses_over_budget_with_readable_diagnostic(
        tmp_path):
    p = tmp_path / "hog.yaml"
    p.write_text(YAML.format(
        extra="  admission: strict\n  device_memory_budget: 4KiB"))
    spool = str(tmp_path / "spool")
    submit(spool, str(p))
    srv = CampaignServer(spool, poll_s=0.0)
    srv.recover()
    drive(srv)
    srv._shutdown()
    res = result_of(spool, "c0000")
    assert res["state"] == "REFUSED"
    # the diagnostic must carry the admission story (levers + budget),
    # not a bare traceback tail
    assert "admission" in res["diagnostic"]
    assert "budget" in res["diagnostic"]
    assert srv.slo["refused"] == 1 and srv.slo["failed"] == 0


def test_server_classifies_bad_config_as_failed(tmp_path):
    p = tmp_path / "broken.yaml"
    p.write_text("general:\n  stop_time: sideways\n")
    spool = str(tmp_path / "spool")
    submit(spool, str(p))
    srv = CampaignServer(spool, poll_s=0.0)
    srv.recover()
    drive(srv)
    srv._shutdown()
    res = result_of(spool, "c0000")
    assert res["state"] == "FAILED" and res["diagnostic"]


# ---------------------------------------------------------------------------
# crash-safety: kill the server mid-campaign, restart, bit-identical
# ---------------------------------------------------------------------------

def test_server_crash_recovery_resumes_bit_identical(tmp_path,
                                                     cfg_path):
    ref = standalone_sig(cfg_path, tmp_path / "ref.data")
    spool = str(tmp_path / "spool")
    submit(spool, cfg_path)

    def crash():
        raise ServerCrash()

    srv = CampaignServer(spool, poll_s=0.0, crash_fn=crash)
    srv.recover()
    cdir = os.path.join(spool, "campaigns", "c0000")

    def checkpointed():
        # arm the chaos server_crash drill the moment the first
        # rotation checkpoint lands — the next tick kills the server.
        # Only a finished entry counts: the atomic writer's
        # in-flight ``ck.npz.t<ns>.<pid>.tmp`` shares the prefix
        if srv.chaos is None and os.path.isdir(cdir) and any(
                _ROTATION_RE.match(n) for n in os.listdir(cdir)):
            srv.chaos = ChaosInjector(events_from_config(
                [{"kind": "server_crash", "tick": 0}]))
        return False

    with pytest.raises(ServerCrash):
        drive(srv, until=checkpointed)
    assert srv.chaos is not None, \
        "the run finished before its first rotation checkpoint"

    # restart: journal replay must requeue c0000 from the newest
    # readable checkpoint and complete it bit-identically
    srv2 = CampaignServer(spool, poll_s=0.0)
    srv2.recover()
    camp = srv2.campaigns["c0000"]
    assert camp.state == "PREEMPTED"
    assert camp.resume_path and os.path.exists(camp.resume_path)
    assert "restart" in camp.diagnostic
    drive(srv2)
    srv2._shutdown()
    res = result_of(spool, "c0000")
    assert res["state"] == "DONE" and res["attempts"] == 2
    assert res["signature"] == ref
    starts = sum(1 for r in journal_rows(spool)
                 if r.get("event") == "server_start")
    assert starts == 2
    assert srv2.slo["requeued_on_restart"] == 1


def test_recover_requeues_running_without_checkpoint_from_scratch(
        tmp_path, cfg_path):
    # the kill outran the first rotation save: no resume artifact
    # exists, so replay must restart the campaign from scratch —
    # losing progress, never the campaign
    spool = str(tmp_path / "spool")
    j = Journal(spool)
    j.server_event("server_start", restarts=1)
    j.transition("c0000", "QUEUED", config=cfg_path, seq=0)
    j.transition("c0000", "ADMITTED")
    j.transition("c0000", "RUNNING", attempts=1)
    srv = CampaignServer(spool, poll_s=0.0)
    srv.recover()
    camp = srv.campaigns["c0000"]
    assert camp.state == "PREEMPTED" and camp.resume_path == ""
    assert "scratch" in camp.diagnostic


# ---------------------------------------------------------------------------
# priority: a higher-priority arrival reclaims the slot via the drain
# ---------------------------------------------------------------------------

def test_priority_arrival_preempts_and_resumes_bit_identical(
        tmp_path, cfg_path):
    ref = standalone_sig(cfg_path, tmp_path / "ref.data")
    spool = str(tmp_path / "spool")
    submit(spool, cfg_path, priority=0)
    srv = CampaignServer(spool, poll_s=0.0)
    srv.recover()
    state = {"submitted": False}

    def inject_high_priority():
        # submit the urgent campaign once the low-priority one is
        # mid-flight (its runner's guard exists => it is draining-
        # capable); the scheduler must then request the rc-75 drain
        if not state["submitted"] and srv._slot is not None:
            runner = srv._runner_of(srv._slot)
            if runner is not None and getattr(runner, "guard",
                                              None) is not None:
                submit(spool, cfg_path, priority=5)
                state["submitted"] = True
        return state["submitted"]

    drive(srv, until=inject_high_priority)
    drive(srv)   # then run the queue dry
    srv._shutdown()
    lo, hi = result_of(spool, "c0000"), result_of(spool, "c0001")
    assert lo["state"] == "DONE" and hi["state"] == "DONE"
    assert lo["preemptions"] == 1 and lo["attempts"] == 2
    # the urgent campaign finished FIRST, and neither signature moved
    seq = [(r.get("cid"), r.get("state")) for r in journal_rows(spool)
           if r.get("state")]
    dones = [cid for cid, s in seq if s == "DONE"]
    assert dones == ["c0001", "c0000"]
    assert ("c0000", "PREEMPTED") in seq
    assert lo["signature"] == ref and hi["signature"] == ref
