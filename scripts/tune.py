#!/usr/bin/env python
"""Strategy autotuner CLI (shadow_tpu/tune/, docs/autotune.md).

Searches the execution-strategy plan space for one workload — short
bounded-sim-window trials through the normal Controller path, warm
via the AOT compile cache, scored on pkts/s with the flight
recorder's per-phase walls as the diagnostic — and persists the
winner as ``PLAN_<app>_<H>_<fp>.json`` next to the OCC records.
Production runs then adopt it with
``experimental.strategy_plan: auto``.

The plan is guaranteed no-slower-than-defaults (a candidate that
cannot beat the full-window default baseline keeps the defaults) and
bit-identical to the default-knob run (every trial's per-host
signature is checked against the default run's; a diverging combo is
disqualified loudly).

Usage:
  python scripts/tune.py examples/tgen_1000.yaml
  python scripts/tune.py CONFIG --window 4 --budget 16
  python scripts/tune.py CONFIG --strategy successive_halving
  python scripts/tune.py CONFIG --out artifacts/PLAN_custom.json

Prints a human trial log on stderr and ONE final JSON line (the plan
summary) on stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the tuner drives many short runs; the XLA machine-feature WARNING
# spam would drown the trial log
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")


def main() -> int:
    ap = argparse.ArgumentParser(
        description="search the execution-strategy plan space and "
                    "persist the winner per workload fingerprint")
    ap.add_argument("config", help="simulation config (YAML)")
    ap.add_argument("--window", type=float, default=0.0,
                    help="trial sim window in seconds (default: the "
                         "config's stop_time; shorter windows = "
                         "cheaper trials, noisier scores — make sure "
                         "the window reaches real traffic)")
    ap.add_argument("--budget", type=int, default=24,
                    help="max scored trials (default 24)")
    ap.add_argument("--strategy", default="auto",
                    choices=["auto", "coordinate_descent",
                             "successive_halving"],
                    help="search strategy (auto: halving when the "
                         "budget can race the grid, else descent)")
    ap.add_argument("--min-gain", type=float, default=0.02,
                    help="relative throughput gain a candidate must "
                         "show to unseat the incumbent (default "
                         "0.02)")
    ap.add_argument("--policy", default="",
                    help="scheduler policy for the trials (default: "
                         "the config's, coerced to tpu for CPU "
                         "policies; 'hybrid' tunes the judge knobs)")
    ap.add_argument("--out", default="",
                    help="PLAN record path (default: the canonical "
                         "PLAN_<app>_<H>_<fp>.json beside the OCC "
                         "records)")
    args = ap.parse_args()

    from shadow_tpu import simtime
    from shadow_tpu.config import load_config
    from shadow_tpu.core.controller import build
    from shadow_tpu.device.aotcache import backend_identity
    from shadow_tpu.device.runner import NoDeviceTwin, device_twin
    from shadow_tpu.tune import plan as planmod
    from shadow_tpu.tune.trials import Tuner
    from shadow_tpu.utils import slog

    slog.init_logging("info")

    # the workload fingerprint comes from the device twin — a config
    # without one has no fingerprint to key a plan on
    sim = build(load_config(args.config))
    try:
        twin = device_twin(sim)
    except NoDeviceTwin as e:
        print(f"tune: {args.config} has no device twin ({e}) — "
              "nothing to fingerprint a plan against", file=sys.stderr)
        return 1
    n_hosts = len(sim.hosts)
    del sim

    window_ns = (simtime.from_seconds(args.window) if args.window
                 else 0)
    tuner = Tuner(args.config, window_ns=window_ns,
                  budget=args.budget, min_gain=args.min_gain,
                  policy=args.policy)
    body = tuner.search(args.strategy)

    from shadow_tpu._jax import jax
    record = {
        "format": planmod.FORMAT,
        "workload": {
            **planmod.workload_stamp(twin, n_hosts),
            "stop_time": tuner.stop,
            "seed": int(tuner.cfg.general.seed),
        },
        "config": os.path.normpath(args.config),
        "backend": backend_identity(jax.devices()),
        "source": "scripts/tune.py",
        **body,
    }
    path = args.out or planmod.plan_path(twin, n_hosts)
    planmod.save_plan(record, path)
    print(f"tune: plan -> {path}", file=sys.stderr)

    summary = {k: record[k] for k in
               ("workload", "policy", "strategy", "space", "default",
                "knobs", "improved", "score")}
    summary["plan"] = path
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
