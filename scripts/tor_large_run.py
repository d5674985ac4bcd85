#!/usr/bin/env python
"""Full-state tor_large execution (BASELINE row 5 evidence).

Runs examples/tor_large.yaml — ALL 56,000 hosts, full event/outbox
capacities, the real device program — for a bounded sim interval, and
prints one JSON line with sim-s/wall-s so the committed artifact
records an actual full-state execution (not a slice). On a machine
without a TPU, run with JAX_PLATFORMS=cpu; the platform is recorded in
the line either way.

Usage: python scripts/tor_large_run.py [stop_sim_s] [config]
Default stop: 12 s (past the 10 s bootstrap window so steady-state
onion cells flow).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    stop_s = float(sys.argv[1]) if len(sys.argv) > 1 else 12.0
    config = sys.argv[2] if len(sys.argv) > 2 else \
        "examples/tor_large.yaml"

    from shadow_tpu._jax import jax
    from shadow_tpu import simtime
    from shadow_tpu.config import load_config
    from shadow_tpu.core.controller import Controller

    platform = jax.devices()[0].platform
    cfg = load_config(config)
    cfg.general.stop_time = simtime.from_seconds(stop_s)

    t0 = time.perf_counter()
    c = Controller(cfg)
    build_wall = time.perf_counter() - t0
    n_hosts = len(c.sim.hosts)
    print(f"tor_large: state built for {n_hosts} hosts in "
          f"{build_wall:.1f}s", file=sys.stderr, flush=True)

    t1 = time.perf_counter()
    stats = c.run()
    run_wall = time.perf_counter() - t1

    out = {
        "workload": config,
        "platform": platform,
        "n_hosts": n_hosts,
        "sim_s": stop_s,
        "build_wall_s": round(build_wall, 1),
        "run_wall_s": round(run_wall, 1),
        "sim_s_per_wall_s": round(stop_s / run_wall, 4),
        "ok": bool(stats.ok),
        "rounds": stats.rounds,
        "events_executed": stats.events_executed,
        "packets_sent": stats.packets_sent,
        "packets_delivered": stats.packets_delivered,
        "packets_dropped": stats.packets_dropped,
    }
    print(json.dumps(out), flush=True)
    return 0 if stats.ok else 1


if __name__ == "__main__":
    sys.exit(main())
