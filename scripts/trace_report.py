#!/usr/bin/env python
"""Where did the wall go: one-table breakdown of a flight-recorder
run (shadow_tpu/obs, docs/observability.md).

Reads a ``METRICS_*.json`` summary (or a ``TRACE_*.jsonl`` span log,
aggregated on the fly) and prints the per-phase wall attribution —
host / judge / dispatch / exchange / checkpoint / retry / compile /
plan / reshard / chaos / failover — with span counts, flags the
dominant phase, and names the lever it implicates — the evidence
the auto-tuning work cites.

``--compare A B`` diffs two records phase-by-phase (delta walls +
pkts/s) — the one-command before/after surface tuner trials and
A/B runs use: run A is the baseline, run B the candidate, negative
deltas mean B is cheaper.

Usage:
  python scripts/trace_report.py artifacts/METRICS_tpu_1000.json
  python scripts/trace_report.py artifacts/TRACE_tpu_1000.jsonl
  python scripts/trace_report.py --top 10 <file>   # slowest spans too
  python scripts/trace_report.py --compare METRICS_a.json METRICS_b.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from shadow_tpu.obs.trace import PHASES          # noqa: E402

# dominant phase -> the lever it implicates (the ROADMAP's open
# items), printed under the table so the report ends with an action
LEVERS = {
    "dispatch": "per-round dispatch latency dominates - batch more "
                "work per dispatch (dispatch_segment)",
    "dispatch.sync": "blocking waits for device results dominate - "
                     "the run is device-bound; attack the round "
                     "program itself",
    "dispatch.issue": "host-side dispatch enqueue dominates - raise "
                      "dispatch_segment (fewer, longer segments) or "
                      "device_batch_rounds to batch more work per "
                      "dispatch",
    "host": "host-side Python dominates - batch more work per "
            "dispatch (dispatch_segment), or move the workload to "
            "the device twin",
    "judge": "hybrid judge batching dominates - raise "
             "hybrid_judge_min_batch or move hosts to a device twin",
    "exchange": "cross-shard exchange dominates - try exchange: auto "
                "/ two_phase with a capacity plan (docs/exchange.md)",
    "checkpoint": "checkpointing dominates - raise checkpoint_every "
                  "or shrink the state (docs/operations.md)",
    "retry": "retry/backoff waits dominate - the device is "
             "unhealthy; see the dispatch error spans",
    "compile": "XLA compile dominates - warm the AOT cache "
               "(docs/compile_cache.md); repeat runs should hit",
    "plan": "capacity warm-up/re-plan dominates - save and reuse the "
            "OCC record (capacity_plan: <path>)",
    "reshard": "mesh-shrink failover cost dominates - devices died "
               "mid-run (drain + re-shard + recompile per shrink); "
               "fix the pool, or warm the AOT cache so the rebuilt "
               "program loads instead of recompiling",
    "chaos": "scripted fault injections (experimental.chaos) - this "
             "is a failover drill, not a production run",
    "failover": "hybrid-failover rerun overhead dominates - the "
                "device run died and replayed on CPU from t=0; "
                "failover: shrink keeps the survivors on-device "
                "(docs/operations.md#failover)",
}


def load_metrics(path: str) -> dict:
    """A METRICS_*.json summary, or one synthesized from a
    TRACE_*.jsonl span log (works on a hung run's .partial file
    too — the whole point of a streamed log)."""
    if path.endswith(".json"):
        with open(path) as f:
            m = json.load(f)
        if "phases" not in m:
            raise ValueError(
                f"{path} has no 'phases' key - not a METRICS record")
        return m
    walls: dict = {}
    counts: dict = {}
    spans = []
    n = 0
    torn = 0
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                # a SIGKILL/OOM tears the streamed log mid-line (the
                # writer's stdio buffer flushes on its own schedule
                # between explicit flushes) — the intact prefix IS
                # the post-mortem; a torn line must not abort it
                torn += 1
                continue
            n += 1
            # self_s where present: a span's bucket must not also
            # count the nested spans recorded inside it (the
            # tracer's own attribution rule)
            walls[rec["phase"]] = (walls.get(rec["phase"], 0.0)
                                   + rec.get("self_s", rec["dur_s"]))
            counts[rec["phase"]] = counts.get(rec["phase"], 0) + 1
            spans.append(rec)
    if not spans:
        raise ValueError(f"{path} holds no spans")
    if torn:
        print(f"note: {torn} unparseable line(s) skipped "
              "(truncated stream from a killed run?)",
              file=sys.stderr)
    # total = the last span's end offset (the log is stream-ordered);
    # host_s is the residual, exactly as the tracer computes it
    total = max(r["t0_s"] + r["dur_s"] for r in spans)
    phases = {f"{p}_s": round(walls.get(p, 0.0), 3)
              for p in PHASES if p != "host"}
    attributed = sum(phases.values())
    phases["host_s"] = round(max(0.0, total - attributed), 3)
    return {"mode": "jsonl", "total_wall_s": round(total, 3),
            "phases": phases, "spans": n,
            "span_counts": counts,
            "dominant_phase": max(phases, key=phases.get)[:-2],
            "_spans": spans}


def print_report(m: dict, top: int = 0) -> None:
    total = m["total_wall_s"] or 1e-12
    phases = m["phases"]
    counts = m.get("span_counts", {})
    run = m.get("run") or {}
    title = " ".join(f"{k}={v}" for k, v in run.items())
    print(f"flight-recorder report ({m.get('mode', '?')} mode"
          f"{', ' + title if title else ''})")
    print(f"total wall: {m['total_wall_s']:.3f}s over "
          f"{m.get('spans', '?')} span(s)")
    print()
    print(f"  {'phase':<14} {'wall_s':>10} {'share':>7} {'spans':>7}")
    print(f"  {'-' * 14} {'-' * 10} {'-' * 7} {'-' * 7}")
    rows = sorted(phases.items(), key=lambda kv: -kv[1])
    for key, wall in rows:
        phase = key[:-2]
        print(f"  {phase:<14} {wall:>10.3f} {wall / total:>6.1%} "
              f"{counts.get(phase, '-'):>7}")
    print(f"  {'-' * 14} {'-' * 10} {'-' * 7} {'-' * 7}")
    print(f"  {'sum':<14} {sum(phases.values()):>10.3f} "
          f"{sum(phases.values()) / total:>6.1%}")
    dom = m.get("dominant_phase") or rows[0][0][:-2]
    print()
    print(f"dominant phase: {dom} "
          f"({phases.get(dom + '_s', 0.0):.3f}s, "
          f"{phases.get(dom + '_s', 0.0) / total:.1%} of wall)")
    lever = LEVERS.get(dom)
    if lever:
        print(f"  -> {lever}")
    if (dom == "plan" and run.get("representation") == "dense"
            and int(run.get("n_hosts") or 0) >= 100_000):
        # a plan-dominant dense run at >=100k hosts is almost always
        # paying the [V,V] table build/upload — the factored tables
        # are the lever (docs/topology.md)
        print(f"  -> dense path tables at {run['n_hosts']} hosts: "
              "if the topology is hub-and-spoke, set "
              "network.topology.representation: hierarchical "
              "(docs/topology.md)")
    pipe = (m.get("counters") or {}).get("pipeline")
    if pipe:
        # the advance loop's dispatch summary: how much of its wall
        # the host spent blocked waiting for the device
        print(f"dispatch: {pipe.get('segments', '?')} segment(s); "
              f"sync {pipe.get('sync_wall_s', 0.0):.3f}s of "
              f"{pipe.get('advance_wall_s', 0.0):.3f}s advance wall")
    reshards = (m.get("counters") or {}).get("reshards")
    if reshards or phases.get("reshard_s"):
        # the shrink's degradation cost as a first-class line: wall
        # lost to the drain + re-shard + re-place (the rebuilt
        # program's compile wall lands in compile_s)
        print(f"mesh shrinks: {reshards or '?'} absorbed; reshard "
              f"wall {phases.get('reshard_s', 0.0):.3f}s "
              "(+ rebuild compile in compile_s)")
    if m.get("dropped_spans"):
        print(f"note: {m['dropped_spans']} span(s) dropped from the "
              "in-memory list (JSONL log is complete)")
    if top and m.get("_spans"):
        slow = sorted(m["_spans"], key=lambda r: -r["dur_s"])[:top]
        print()
        print(f"slowest {len(slow)} span(s):")
        for r in slow:
            window = ""
            if "sim_t0" in r:
                window = (f"  sim=({r['sim_t0']}, "
                          f"{r.get('sim_t1', '?')}] ns")
            print(f"  {r['dur_s']:8.3f}s  {r['phase']:<10} "
                  f"{r['name']}{window}")


def _pkts_per_s(m: dict):
    """packets/s of a record, when its counters carry packets (the
    Controller stamps events/packets/rounds into METRICS summaries);
    None otherwise — the compare table then shows walls only."""
    pkts = (m.get("counters") or {}).get("packets")
    total = m.get("total_wall_s") or 0.0
    if pkts is None or total <= 0:
        return None
    return pkts / total


def print_compare(a: dict, b: dict, name_a: str, name_b: str) -> None:
    """Phase-by-phase diff of two records: A is the baseline, B the
    candidate; delta = B - A (negative = B cheaper)."""
    pa, pb = a["phases"], b["phases"]
    keys = [f"{p}_s" for p in PHASES if f"{p}_s" in pa
            or f"{p}_s" in pb]
    keys += sorted((set(pa) | set(pb)) - set(keys))
    print("flight-recorder comparison")
    print(f"  A: {name_a}")
    print(f"  B: {name_b}")
    print()
    print(f"  {'phase':<14} {'A_s':>10} {'B_s':>10} {'delta_s':>10} "
          f"{'delta':>8}")
    print(f"  {'-' * 14} {'-' * 10} {'-' * 10} {'-' * 10} {'-' * 8}")
    rows = sorted(keys, key=lambda k: -(pa.get(k, 0.0)
                                        + pb.get(k, 0.0)))
    for key in rows:
        wa, wb = pa.get(key, 0.0), pb.get(key, 0.0)
        d = wb - wa
        rel = f"{d / wa:+.1%}" if wa > 0 else ("new" if wb else "-")
        print(f"  {key[:-2]:<14} {wa:>10.3f} {wb:>10.3f} {d:>+10.3f} "
              f"{rel:>8}")
    ta = a.get("total_wall_s", 0.0)
    tb = b.get("total_wall_s", 0.0)
    print(f"  {'-' * 14} {'-' * 10} {'-' * 10} {'-' * 10} {'-' * 8}")
    rel = f"{(tb - ta) / ta:+.1%}" if ta > 0 else "-"
    print(f"  {'total':<14} {ta:>10.3f} {tb:>10.3f} "
          f"{tb - ta:>+10.3f} {rel:>8}")
    ra, rb = _pkts_per_s(a), _pkts_per_s(b)
    print()
    if ra is not None and rb is not None:
        speed = f" ({rb / ra:.2f}x)" if ra > 0 else ""
        print(f"pkts/s: A {ra:,.0f} -> B {rb:,.0f}{speed}")
    elif ra is None and rb is None:
        print("pkts/s: n/a (no packet counters in either record)")
    else:
        # one-sided counters (e.g. a METRICS summary vs a raw JSONL
        # aggregation): show the known side, never silently drop the
        # throughput row
        fmt = ("n/a" if ra is None else f"{ra:,.0f}",
               "n/a" if rb is None else f"{rb:,.0f}")
        print(f"pkts/s: A {fmt[0]} -> B {fmt[1]} (one record has no "
              "packet counters)")
    dom_a, dom_b = a.get("dominant_phase"), b.get("dominant_phase")
    if dom_a and dom_b:
        print(f"dominant phase: A {dom_a} -> B {dom_b}"
              + ("" if dom_a == dom_b else "  <- shifted"))
    pipe_a = (a.get("counters") or {}).get("pipeline")
    pipe_b = (b.get("counters") or {}).get("pipeline")
    if pipe_a or pipe_b:
        def _pfmt(p):
            return (f"{p.get('sync_wall_s', 0.0):.3f}s"
                    if p else "n/a")
        print(f"sync wall: A {_pfmt(pipe_a)} -> B {_pfmt(pipe_b)}")
    rsh_a = (a.get("counters") or {}).get("reshards", 0)
    rsh_b = (b.get("counters") or {}).get("reshards", 0)
    if rsh_a or rsh_b or pa.get("reshard_s") or pb.get("reshard_s"):
        # the one-line answer to "what did the shrink cost": wall
        # lost to drain + re-shard + recompile, side by side
        wa = pa.get("reshard_s", 0.0)
        wb = pb.get("reshard_s", 0.0)
        print(f"shrink cost: A {rsh_a} shrink(s) / {wa:.3f}s -> "
              f"B {rsh_b} shrink(s) / {wb:.3f}s (drain + reshard + "
              "recompile; rebuild compile rides compile_s)")


def main() -> int:
    ap = argparse.ArgumentParser(
        description="per-phase wall breakdown of a flight-recorder "
                    "run")
    ap.add_argument("path", nargs="?",
                    help="METRICS_*.json or TRACE_*.jsonl "
                         "(.partial accepted)")
    ap.add_argument("--top", type=int, default=0,
                    help="also list the N slowest spans (jsonl input "
                         "only)")
    ap.add_argument("--compare", nargs=2, metavar=("A", "B"),
                    help="diff two METRICS/JSONL records phase-by-"
                         "phase (A = baseline, B = candidate)")
    args = ap.parse_args()
    if args.compare:
        if args.path:
            print("trace_report: --compare takes exactly its two "
                  "records (drop the positional path)",
                  file=sys.stderr)
            return 1
        try:
            a = load_metrics(args.compare[0])
            b = load_metrics(args.compare[1])
        except (OSError, ValueError, json.JSONDecodeError) as e:
            print(f"trace_report: cannot read comparison input: {e}",
                  file=sys.stderr)
            return 1
        print_compare(a, b, args.compare[0], args.compare[1])
        return 0
    if not args.path:
        print("trace_report: need a METRICS/TRACE path (or "
              "--compare A B)", file=sys.stderr)
        return 1
    try:
        m = load_metrics(args.path)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"trace_report: cannot read {args.path}: {e}",
              file=sys.stderr)
        return 1
    print_report(m, top=args.top)
    return 0


if __name__ == "__main__":
    sys.exit(main())
