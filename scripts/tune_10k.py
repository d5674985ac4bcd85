"""On-chip knob sweep for the 10k north-star rung.

Runs the fused 2.5 sim-s tgen_10000 slice across the perf knobs that
cannot be chosen off-chip (TPU gather/sort/VPU cost ratios differ from
CPU by >10x): pop_strategy x burst_pops x outbox_compact, printing
wall seconds + derived ms/round per combo and ONE final JSON line
with the best combo. pop/burst are trace-invariant by contract; a
combo that diverges anyway is flagged loudly and disqualified.
outbox_compact is CAPACITY-sensitive: too small fails loudly
(x_overflow) and is disqualified here. The sweep slice may not cover
steady state, so a chosen width must be validated on a full run
before it is set in a config.

When a measured occupancy record (artifacts/OCC_*.json, written by
any capacity_plan run — see device/capacity.py) exists
for a workload with this host count, compact widths below the
measured busiest-host outbox fill are PRUNED from the grid up front:
they can only overflow loudly, so sweeping them burns chip time to
learn what the record already says.

Usage: python scripts/tune_10k.py [stop_s] [config]
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

POPS = ("onehot", "gather")
BURSTS = (8, 16)
# outbox compaction shrinks the global merge's outbox block at the
# price of one per-host lane sort; too small fails LOUDLY
# (x_overflow) and the sweep just disqualifies that combo. The width
# is uniform and thus bounded by the BUSIEST host — on the hub-shaped
# 10k config a burst server legitimately fills its whole 40-row
# outbox (measured: compact=16 overflows 3k+ rows in the first
# traffic window), so the axis defaults OFF here; pass extra compact
# widths as trailing args for flatter workloads.
COMPACTS = (0,)


def prune_compacts(compacts: tuple, config: str, stop_ns: int) -> tuple:
    """Drop compact widths a measured occupancy record proves too
    small: the busiest host's outbox fill is a hard floor (a smaller
    compaction width x_overflows loudly and the combo is disqualified
    anyway — sweeping it just burns chip time). Records match on the
    device app class, host count, AND the workload fingerprint (app
    scalars + per-host parameter arrays) — a 10k-host phold record
    must never size a 10k-host tgen sweep, nor a heavy-traffic tgen
    record a light-traffic variant; among matches the longest
    measured window wins. A record covering a PREFIX of the sweep
    slice (stop_time <= `stop_ns`) proves the width overflows in the
    sweep itself; a longer record (e.g. a full run)
    proves it overflows at the real rung even if the shorter slice
    survives it — either way the width is not worth chip time.
    Outbox fill per phase is a property of the event windows, which
    are pop/burst-invariant (the knobs this sweep varies), so the
    floor transfers across combos. No record means no pruning."""
    import glob

    from shadow_tpu.config import load_config
    from shadow_tpu.core.controller import build
    from shadow_tpu.device import capacity
    from shadow_tpu.device.runner import NoDeviceTwin, device_twin

    if all(c == 0 for c in compacts):
        return compacts                 # nothing prunable on the axis
    try:
        sim = build(load_config(config))
        twin = device_twin(sim)
    except NoDeviceTwin:
        return compacts                 # sweep will fail loudly anyway
    app = type(twin).__name__
    app_fp = capacity.app_fingerprint(twin)
    n_hosts = len(sim.hosts)
    occ_dir = os.environ.get("SHADOW_TPU_OCC_DIR", "artifacts")
    best = None
    for path in sorted(glob.glob(os.path.join(occ_dir, "OCC_*.json"))):
        try:
            rec = capacity.load_record(path)
        except (OSError, ValueError):
            continue
        rec_stop = rec["workload"].get("stop_time", 0)
        if rec["workload"].get("n_hosts") == n_hosts and \
                rec["workload"].get("app") == app and \
                rec["workload"].get("app_fp") == app_fp \
                and rec_stop > 0 \
                and (best is None or rec_stop > best[2]):
            best = (path, rec, rec_stop)
    if best is None:
        return compacts
    path, rec, rec_stop = best
    floor = max(rec["measured"]["outbox_rows_max"],
                rec.get("final_measured", {}).get("outbox_rows_max", 0))
    keep = tuple(c for c in compacts if c == 0 or c >= floor)
    dropped = [c for c in compacts if c not in keep]
    if dropped:
        why = "they can only x_overflow in this sweep" \
            if rec_stop <= stop_ns else \
            (f"they x_overflow by {rec_stop / 1e9:g} sim-s even if "
             "this shorter slice survives them")
        print(f"  occupancy record {path}: busiest host fills {floor} "
              f"outbox rows — pruning compact widths {dropped} from "
              f"the sweep ({why})",
              file=sys.stderr, flush=True)
    return keep or (0,)


def main() -> int:
    stop_s = float(sys.argv[1]) if len(sys.argv) > 1 else 2.5
    config = sys.argv[2] if len(sys.argv) > 2 else \
        "examples/tgen_10000.yaml"
    compacts = tuple(int(a) for a in sys.argv[3:]) or COMPACTS

    from shadow_tpu._jax import jax
    from shadow_tpu import simtime
    from shadow_tpu.config import load_config
    from shadow_tpu.core.controller import Controller

    compacts = prune_compacts(compacts, config,
                              simtime.from_seconds(stop_s))

    platform = jax.devices()[0].platform
    results = []
    all_counts = []
    for pop, bp, cx in itertools.product(POPS, BURSTS, compacts):
        cfg = load_config(config)
        cfg.general.stop_time = simtime.from_seconds(stop_s)
        cfg.experimental.pop_strategy = pop
        cfg.experimental.burst_pops = bp
        cfg.experimental.outbox_compact = cx
        c = Controller(cfg)
        compile_s = 0.0
        try:
            # warm the compile BEFORE timing: with the persistent
            # compilation cache a previously-compiled combo would
            # otherwise skip ~50 s of compile inside its timed window
            # and win on that alone, crowning a combo by cache state,
            # not runtime
            t0 = time.perf_counter()
            st = c.runner.engine.init_state(c.sim.starts)
            c.runner.engine.run(
                st, stop=simtime.from_seconds(0.001))
            compile_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            stats = c.run()
            ok = bool(stats.ok)
            counts = (stats.events_executed, stats.packets_sent,
                      stats.packets_delivered, stats.packets_dropped)
            rounds = stats.rounds
        except Exception as e:          # noqa: BLE001
            print(f"  pop={pop} burst={bp} compact={cx}: "
                  f"RAISED {e}", file=sys.stderr, flush=True)
            ok, counts, rounds = False, None, 0
        wall = time.perf_counter() - t0
        row = {"pop": pop, "burst": bp, "compact": cx,
               "wall_s": round(wall, 2), "rounds": rounds,
               "compile_s": round(compile_s, 1),
               "ms_per_round": round(1e3 * wall / max(1, rounds), 2),
               "ok": ok}
        results.append(row)
        all_counts.append(counts)
        print(f"  pop={pop:7s} burst={bp:2d} compact={cx:2d}: "
              f"{wall:6.2f}s {row['ms_per_round']:7.2f} ms/round "
              f"{'' if ok else ' <== FAILED'}",
              file=sys.stderr, flush=True)

    # divergence is judged against the first SUCCESSFUL run — a
    # failed first combo must neither disqualify every good one nor
    # crown a divergent one (the knobs are trace-invariant, so every
    # ok run must agree)
    ref = next((c for r, c in zip(results, all_counts) if r["ok"]),
               None)
    for r, c in zip(results, all_counts):
        r["counts_match"] = bool(r["ok"] and c == ref)
        if r["ok"] and not r["counts_match"]:
            print(f"  DIVERGED: pop={r['pop']} burst={r['burst']} "
                  f"compact={r['compact']} {c} != {ref}",
                  file=sys.stderr, flush=True)
    good = [r for r in results if r["counts_match"]]
    best = min(good, key=lambda r: r["wall_s"]) if good else None
    print(json.dumps({"workload": config, "platform": platform,
                      "slice_sim_s": stop_s, "results": results,
                      "best": best}))
    return 0 if good else 1


if __name__ == "__main__":
    sys.exit(main())
