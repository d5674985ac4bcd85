"""Device-state checkpoint / resume (device/checkpoint.py).

The reference has no checkpoint facility (SURVEY §5) — simulations
run start-to-finish. The device engine's state is an explicit array
pytree, so pause/save/resume is supported and must be bit-identical
to the uninterrupted run: window clamping stays on the global stop
(the same contract as heartbeat segmentation)."""

import pytest

from shadow_tpu.config import load_config_str
from shadow_tpu.core.controller import Controller

YAML = """
general:
  stop_time: 3s
  seed: 11
network:
  graph:
    type: gml
    inline: |
      graph [ directed 0
        node [ id 0 bandwidth_down "1 Gbit" bandwidth_up "1 Gbit" ]
        node [ id 1 bandwidth_down "1 Gbit" bandwidth_up "1 Gbit" ]
        edge [ source 0 target 0 latency "10 ms" packet_loss 0.1 ]
        edge [ source 0 target 1 latency "20 ms" packet_loss 0.1 ]
        edge [ source 1 target 1 latency "10 ms" packet_loss 0.1 ]
      ]
experimental:
  scheduler_policy: tpu
  event_capacity: 192
  outbox_capacity: 256
{extra}
hosts:
  server:
    network_node_id: 0
    processes:
    - path: model:tgen_server
      start_time: 10ms
  client:
    quantity: 6
    network_node_id: 1
    processes:
    - path: model:tgen_client
      args: server=server size=200KiB count=3 pause=150ms retry=250ms
      start_time: 100ms
"""


def _run(extra=""):
    c = Controller(load_config_str(YAML.format(extra=extra)))
    stats = c.run()
    return stats, c


def _sig(stats, c):
    return (stats.events_executed, stats.packets_sent,
            stats.packets_dropped, stats.packets_delivered,
            [(h.name, h.trace_checksum) for h in c.sim.hosts])


def test_pause_save_resume_bitmatches_uninterrupted(tmp_path):
    ck = str(tmp_path / "state.npz")
    full_stats, full_c = _run()
    assert full_stats.ok

    part_stats, _ = _run(
        f"  checkpoint_save: {ck}\n"
        f"  checkpoint_save_time: 1500ms")
    assert part_stats.ok
    # the pause point is mid-run: strictly less work than the full
    # run, and the reported end time is the pause, not the config stop
    assert part_stats.events_executed < full_stats.events_executed
    assert part_stats.end_time == 1_500_000_000

    res_stats, res_c = _run(f"  checkpoint_load: {ck}")
    assert res_stats.ok
    assert _sig(res_stats, res_c) == _sig(full_stats, full_c)

    # the meta carries ALL capacity knobs (a planned resume adopts
    # them — not just the two layout-determining fingerprint ones)
    from shadow_tpu.device import checkpoint
    caps = checkpoint.peek_meta(ck)["capacities"]
    assert set(caps) == {"event_capacity", "outbox_capacity",
                         "exchange_capacity", "exchange_capacity2",
                         "exchange_in_capacity", "outbox_compact"}


def test_tor_pause_resume_bitmatches(tmp_path):
    """Checkpoint/resume on the TOR app family (onion trains,
    relay burst pops, different app-state shape than tgen): a
    mid-bootstrap pause + resume of the small-Tor example must
    bit-match the uninterrupted run."""
    import os
    from shadow_tpu import simtime
    from shadow_tpu.config import load_config

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "examples", "tor_small.yaml")
    ck = str(tmp_path / "tor.npz")

    def run(extra=None):
        cfg = load_config(path)
        cfg.general.stop_time = simtime.from_seconds(12.0)
        if extra:
            for k, v in extra.items():
                setattr(cfg.experimental, k, v)
        c = Controller(cfg)
        stats = c.run()
        return stats, c

    full_stats, full_c = run()
    assert full_stats.ok
    run({"checkpoint_save": ck,
         "checkpoint_save_time": simtime.from_seconds(7.0)})
    res_stats, res_c = run({"checkpoint_load": ck})
    assert res_stats.ok
    assert _sig(res_stats, res_c) == _sig(full_stats, full_c)


def test_resume_with_heartbeat_segmentation(tmp_path):
    """Resume under hb/dispatch segmentation still bit-matches (the
    segmented loop starts at the saved t, heartbeat boundaries align
    past it)."""
    ck = str(tmp_path / "state.npz")
    full_stats, full_c = _run()
    _run(f"  checkpoint_save: {ck}\n"
         f"  checkpoint_save_time: 1200ms")
    res_stats, res_c = _run(
        f"  checkpoint_load: {ck}\n"
        f"  dispatch_segment: 700ms")
    assert res_stats.ok
    assert _sig(res_stats, res_c) == _sig(full_stats, full_c)


def test_fingerprint_mismatch_rejected(tmp_path):
    ck = str(tmp_path / "state.npz")
    _run(f"  checkpoint_save: {ck}\n"
         f"  checkpoint_save_time: 1500ms")
    bad = YAML.replace("seed: 11", "seed: 12")
    with pytest.raises(ValueError, match="does not match"):
        Controller(load_config_str(bad.format(
            extra=f"  checkpoint_load: {ck}"))).run()


def test_topology_edit_rejected(tmp_path):
    """A checkpoint resumed against an edited graph would replay the
    remaining events on different latencies/losses — the topology is
    part of the fingerprint, so the load must refuse."""
    ck = str(tmp_path / "state.npz")
    _run(f"  checkpoint_save: {ck}\n"
         f"  checkpoint_save_time: 1500ms")
    bad = YAML.replace('latency "20 ms"', 'latency "25 ms"')
    with pytest.raises(ValueError, match="does not match"):
        Controller(load_config_str(bad.format(
            extra=f"  checkpoint_load: {ck}"))).run()


def test_resume_at_different_burst_width(tmp_path):
    """burst_pops is a trace-invariant perf knob — retuning it across
    a save/resume pair (the on-chip tuning workflow) must neither be
    rejected by the fingerprint nor change the trace."""
    ck = str(tmp_path / "state.npz")
    full_stats, full_c = _run()
    _run(f"  checkpoint_save: {ck}\n"
         f"  checkpoint_save_time: 1500ms\n"
         f"  burst_pops: 4")
    res_stats, res_c = _run(f"  checkpoint_load: {ck}\n"
                            f"  burst_pops: 8")
    assert res_stats.ok
    assert _sig(res_stats, res_c) == _sig(full_stats, full_c)


def test_bandwidth_edit_rejected(tmp_path):
    """Per-host bandwidths steer packet timing (model NIC) — they are
    fingerprinted too, so an edited-bandwidth resume refuses."""
    ck = str(tmp_path / "state.npz")
    _run(f"  checkpoint_save: {ck}\n"
         f"  checkpoint_save_time: 1500ms")
    bad = YAML.replace('id 1 bandwidth_down "1 Gbit"',
                       'id 1 bandwidth_down "500 Mbit"')
    with pytest.raises(ValueError, match="does not match"):
        Controller(load_config_str(bad.format(
            extra=f"  checkpoint_load: {ck}"))).run()


def test_unwritable_save_path_fails_fast(tmp_path):
    with pytest.raises(ValueError, match="not writable"):
        _run("  checkpoint_save: "
             f"{tmp_path}/no-such-dir/state.npz")


def test_save_time_without_path_rejected():
    with pytest.raises(ValueError, match="checkpoint_save_time"):
        load_config_str(YAML.format(
            extra="  checkpoint_save_time: 1s"))


def test_checkpoint_requires_device_policy():
    with pytest.raises(ValueError, match="scheduler_policy: tpu"):
        load_config_str(YAML.format(
            extra="  checkpoint_save: /tmp/x.npz").replace(
            "scheduler_policy: tpu", "scheduler_policy: serial"))


def test_resume_at_or_past_stop_rejected(tmp_path):
    ck = str(tmp_path / "state.npz")
    _run(f"  checkpoint_save: {ck}")     # pauses at stop_time
    with pytest.raises(ValueError, match="nothing to resume"):
        _run(f"  checkpoint_load: {ck}")


def test_resume_toward_different_stop_rejected(tmp_path):
    """The saved prefix's windows were clamped on the run's global
    stop (final_stop, stamped in the npz meta) — resuming toward a
    different stop would not bit-match an uninterrupted run at that
    stop, so the load must refuse the mismatch."""
    ck = str(tmp_path / "state.npz")
    _run(f"  checkpoint_save: {ck}\n"
         f"  checkpoint_save_time: 1500ms")
    bad = YAML.replace("stop_time: 3s", "stop_time: 4s")
    with pytest.raises(ValueError, match="stop"):
        Controller(load_config_str(bad.format(
            extra=f"  checkpoint_load: {ck}"))).run()


def test_pre_telemetry_checkpoint_loads(tmp_path):
    """Checkpoints saved before the occ_* telemetry leaves existed
    lack them in the npz key list; the load fills the missing
    counters from the freshly-initialized template (zeros) instead of
    rejecting, and the resumed trace still bit-matches."""
    import json

    import numpy as np

    ck = str(tmp_path / "state.npz")
    full_stats, full_c = _run()
    _run(f"  checkpoint_save: {ck}\n"
         f"  checkpoint_save_time: 1500ms")

    with np.load(ck, allow_pickle=False) as z:
        meta = json.loads(str(z["__meta__"]))
        saved = {k: z[f"leaf_{i}"]
                 for i, k in enumerate(meta["keys"])}
    meta["keys"] = [k for k in meta["keys"] if "'occ_" not in k]
    arrays = {f"leaf_{i}": saved[k]
              for i, k in enumerate(meta["keys"])}
    with open(ck, "wb") as f:
        np.savez_compressed(f, __meta__=json.dumps(meta), **arrays)

    res_stats, res_c = _run(f"  checkpoint_load: {ck}")
    assert res_stats.ok
    assert _sig(res_stats, res_c) == _sig(full_stats, full_c)

    # a non-telemetry leaf going missing must still refuse loudly
    meta2 = dict(meta, keys=[k for k in meta["keys"]
                             if "'overflow'" not in k])
    arrays2 = {f"leaf_{i}": saved[k]
               for i, k in enumerate(meta2["keys"])}
    with open(ck, "wb") as f:
        np.savez_compressed(f, __meta__=json.dumps(meta2), **arrays2)
    with pytest.raises(ValueError, match="state layout changed"):
        _run(f"  checkpoint_load: {ck}")


def test_pop_iteration_counter_survives_save_and_resume(tmp_path):
    """occ_iters is cumulative: a resumed run carries the saved count
    on, ending where the uninterrupted run ends; a checkpoint saved
    before the counter existed still loads, the count then covering
    the resumed part only."""
    import json

    import numpy as np

    def iters(c):
        # per shard: each shard's pop loop counts its own iterations
        return np.asarray(c.runner.final_state["occ_iters"])

    ck = str(tmp_path / "state.npz")
    full_stats, full_c = _run()
    _, part_c = _run(f"  checkpoint_save: {ck}\n"
                     f"  checkpoint_save_time: 1500ms")
    assert (iters(part_c) > 0).any()
    assert (iters(part_c) <= iters(full_c)).all()
    assert (iters(part_c) < iters(full_c)).any()
    _, res_c = _run(f"  checkpoint_load: {ck}")
    np.testing.assert_array_equal(iters(res_c), iters(full_c))

    with np.load(ck, allow_pickle=False) as z:
        meta = json.loads(str(z["__meta__"]))
        saved = {k: z[f"leaf_{i}"] for i, k in enumerate(meta["keys"])}
    meta["keys"] = [k for k in meta["keys"] if k != "['occ_iters']"]
    assert len(meta["keys"]) == len(saved) - 1
    with open(ck, "wb") as f:
        np.savez_compressed(f, __meta__=json.dumps(meta), **{
            f"leaf_{i}": saved[k] for i, k in enumerate(meta["keys"])})
    old_stats, old_c = _run(f"  checkpoint_load: {ck}")
    assert old_stats.ok
    assert _sig(old_stats, old_c) == _sig(full_stats, full_c)
    np.testing.assert_array_equal(iters(old_c),
                                  iters(full_c) - iters(part_c))


@pytest.mark.slow
def test_resume_adopts_saved_capacities_under_plan(tmp_path,
                                                   monkeypatch):
    """capacity_plan under checkpoint_load skips planning and adopts
    the SAVED engine's capacities (peeked from the npz fingerprint):
    a checkpoint written by a planner-sized engine must stay loadable
    even though the planned capacities differ from the config's
    static knobs — and the resumed pair must still bit-match the
    uninterrupted run."""
    monkeypatch.setenv("SHADOW_TPU_OCC_DIR", str(tmp_path))
    ck = str(tmp_path / "state.npz")
    full_stats, full_c = _run()

    # save under an active plan: the saved fingerprint carries the
    # planner's capacities, not event_capacity: 192 from the YAML
    plan = ("  capacity_plan: auto\n"
            "  capacity_warmup: 2500ms\n")
    save_stats, _ = _run(plan +
                         f"  checkpoint_save: {ck}\n"
                         f"  checkpoint_save_time: 1500ms")
    assert save_stats.ok

    res_stats, res_c = _run(plan + f"  checkpoint_load: {ck}")
    assert res_stats.ok
    assert _sig(res_stats, res_c) == _sig(full_stats, full_c)

    # and a static-config resume of that planned save works too
    res2_stats, res2_c = _run(f"  checkpoint_load: {ck}\n"
                              f"  capacity_plan: auto")
    assert res2_stats.ok
    assert _sig(res2_stats, res2_c) == _sig(full_stats, full_c)
