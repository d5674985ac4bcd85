"""Chip smoke: prove the device engine's main path runs on a TPU.

    python chip_smoke.py             # one chip: phases A and B
    python chip_smoke.py --chips 4   # four chips: the sharded engine only

Phase A drives ``examples/tgen_10000.yaml`` (10,000 hosts, the
``tpu`` scheduler policy) through ``Controller(cfg).run()``, the path
``python -m shadow_tpu.cli`` takes, with only ``general.stop_time``
cut so that the clients (which start at 2 s) move several simulated
seconds of traffic. Phase B runs ``examples/tgen_100.yaml`` on the
chip and under the ``serial`` oracle on the host, and requires
identical stats and per-host results. ``--chips 4`` runs tgen_10000 on
a 4-chip mesh and pinned to one chip (``experimental.mesh_shards=1``)
and requires identical results; it compiles the two programs at once,
in two threads, to halve the wall the four chips are held for.

Earlier stdout lines are one JSON object per phase. The last line is
exactly ``{"ok": true, "device": {...}}``, printed only when every
phase passed on a TPU. With no TPU, outside a checkout, or on any
failure the script exits non-zero and prints no such line. It runs in
one process and starts none.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SMOKE_STOP_S = 6.0


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


def _load(name: str, stop_s: float | None = None,
          policy: str | None = None, mesh_shards: int = 0):
    from shadow_tpu import simtime
    from shadow_tpu.config import load_config

    cfg = load_config(os.path.join(HERE, "examples", name))
    if stop_s is not None:
        cfg.general.stop_time = simtime.from_seconds(stop_s)
    if policy is not None:
        cfg.experimental.scheduler_policy = policy
    if mesh_shards:
        cfg.experimental.mesh_shards = mesh_shards
    return cfg


def _run(cfg, c=None):
    """One run through the user's entry point: (controller, stats,
    facts about the run). A controller built (and warmed) beforehand
    may be passed in; its compile then lies outside the timed wall."""
    from shadow_tpu import simtime
    from shadow_tpu.core.controller import Controller

    t0 = time.perf_counter()
    warm = c is not None
    c = c or Controller(cfg)
    stats = c.run()
    wall = time.perf_counter() - t0
    return c, stats, device_facts(
        c, stats, wall, simtime.to_seconds(cfg.general.stop_time),
        compile_in_wall=not warm)


def _warm_in_parallel(controllers) -> None:
    """Compile every controller's ``run`` program at once, one thread
    each (XLA compiles outside the GIL), through the AOT cache path
    that ``run()`` then finds resolved: several meshes cost one
    compile of wall time."""
    import threading

    errors = []

    def warm(c):
        try:
            engine = c.runner.engine
            fn, args = engine.lowerable_programs()["run"]
            engine._aot("run", fn, args)
        except Exception as e:      # noqa: BLE001 — re-raised below
            errors.append(e)

    threads = [threading.Thread(target=warm, args=(c,))
               for c in controllers]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


def device_facts(c, stats, wall_s: float, sim_s: float,
                 compile_in_wall: bool = True) -> dict:
    """What a device run says about itself: the runner and mesh it
    ran on, the traffic it moved, overflow, memory and compile."""
    from shadow_tpu.device.runner import DeviceRunner

    runner = c.runner
    facts = {"runner": type(runner).__name__,
             "ok": bool(stats.ok),
             "packets_routed": int(stats.packets_sent),
             "packets_delivered": int(stats.packets_delivered),
             "packets_dropped": int(stats.packets_dropped),
             "events_executed": int(stats.events_executed),
             "rounds": int(stats.rounds),
             "wall_s": wall_s,
             "sim_s": sim_s}
    if not isinstance(runner, DeviceRunner):
        return facts
    devs = list(runner.engine.mesh.devices.flat)
    final = runner.final_state
    facts.update({
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "mesh_devices": len(devs),
        "overflow": int(final["overflow"].sum()),
        "x_overflow": int(final["x_overflow"].sum()),
        "budget_source": (stats.admission or {}).get("budget_source"),
    })
    cc = stats.compile_cache or {}
    facts["compile_s"] = cc.get("compile_s")
    facts["compile_cache_hits"] = cc.get("hits")
    run_s = wall_s - (cc.get("load_s") or 0.0) - \
        ((cc.get("compile_s") or 0.0) if compile_in_wall else 0.0)
    facts["sim_s_per_wall_s"] = sim_s / wall_s
    facts["sim_s_per_wall_s_ex_compile"] = sim_s / run_s
    ms = devs[0].memory_stats() or {}
    facts["peak_bytes_in_use"] = ms.get("peak_bytes_in_use")
    facts["bytes_limit"] = ms.get("bytes_limit")
    return facts


def host_results(c) -> list:
    """Per-host (events, sent, dropped, delivered, trace checksum)."""
    return [(h.events_executed, h.packets_sent, h.packets_dropped,
             h.packets_delivered, h.trace_checksum)
            for h in c.sim.hosts]


def device_host_results(c) -> dict:
    """The same per-host columns, straight from a device run's final
    state (no Host objects built)."""
    n = len(c.sim.hosts)
    return {k: c.runner.final_state[k][:n]
            for k in ("n_exec", "n_sent", "n_drop", "n_deliv", "chk")}


def totals(stats) -> tuple:
    return (stats.ok, stats.events_executed, stats.packets_sent,
            stats.packets_dropped, stats.packets_delivered)


def check_device_run(facts: dict, platform: str = "tpu") -> list:
    """The failed checks of one device run (empty = it passed)."""
    bad = []
    if facts["runner"] != "DeviceRunner":
        bad.append(f"runner is {facts['runner']}, not DeviceRunner "
                   "(tpu -> hybrid fallback?)")
        return bad
    if facts["platform"] != platform:
        bad.append(f"mesh platform is {facts['platform']!r}")
    if not facts["ok"]:
        bad.append("stats.ok is false")
    if facts["packets_routed"] <= 0 or facts["packets_delivered"] <= 0:
        bad.append("no packets routed or delivered")
    if facts["overflow"] or facts["x_overflow"]:
        bad.append(f"overflow {facts['overflow']} / exchange overflow "
                   f"{facts['x_overflow']}")
    return bad


def phase_a() -> dict:
    """tgen_10000 at full width through Controller on the chip."""
    _, _, facts = _run(_load("tgen_10000.yaml", stop_s=SMOKE_STOP_S))
    bad = check_device_run(facts)
    if facts.get("budget_source") != "backend":
        bad.append("memory budget source is "
                   f"{facts.get('budget_source')!r}, not the backend's "
                   "bytes_limit")
    return {"phase": "A", "config": "examples/tgen_10000.yaml",
            **facts, "failed": bad}


def phase_b(name: str = "tgen_100.yaml", stop_s: float | None = None,
            platform: str = "tpu") -> dict:
    """The chip's results equal the serial oracle's on the host."""
    c_d, s_d, facts = _run(_load(name, stop_s=stop_s, policy="tpu"))
    bad = check_device_run(facts, platform)
    c_s, s_s, serial = _run(_load(name, stop_s=stop_s,
                                  policy="serial"))
    if totals(s_d) != totals(s_s):
        bad.append(f"stats differ: device {totals(s_d)} vs serial "
                   f"{totals(s_s)}")
    hd, hs = host_results(c_d), host_results(c_s)
    n_diff = sum(1 for a, b in zip(hd, hs) if a != b)
    if len(hd) != len(hs) or n_diff:
        bad.append(f"per-host results differ on {n_diff} of "
                   f"{len(hs)} hosts")
    return {"phase": "B", "config": f"examples/{name}",
            **facts, "serial_wall_s": serial["wall_s"],
            "hosts_compared": len(hs), "failed": bad}


def phase_mesh(n: int = 4, name: str = "tgen_10000.yaml",
               stop_s: float = SMOKE_STOP_S,
               platform: str = "tpu") -> dict:
    """The sharded engine on an n-chip mesh equals one chip."""
    import numpy as np

    from shadow_tpu.core.controller import Controller
    from shadow_tpu.device.runner import DeviceRunner

    cfgs = {shards: _load(name, stop_s=stop_s, mesh_shards=shards)
            for shards in (n, 1)}
    ctrls = {shards: Controller(cfg) for shards, cfg in cfgs.items()}
    t0 = time.perf_counter()
    _warm_in_parallel([c for c in ctrls.values()
                       if isinstance(c.runner, DeviceRunner)])
    warm_s = time.perf_counter() - t0
    runs = {}
    for shards in (n, 1):
        c, stats, facts = _run(cfgs[shards], ctrls[shards])
        bad = check_device_run(facts, platform)
        if facts.get("mesh_devices") != shards:
            bad.append(f"asked for {shards} chip(s), the mesh has "
                       f"{facts.get('mesh_devices')}")
        per_host = (device_host_results(c)
                    if facts["runner"] == "DeviceRunner" else {})
        runs[shards] = (facts, bad, totals(stats), per_host)
    bad = runs[n][1] + runs[1][1]
    if runs[n][2] != runs[1][2]:
        bad.append(f"stats differ: {n} chips {runs[n][2]} vs 1 chip "
                   f"{runs[1][2]}")
    differ = [k for k in runs[1][3]
              if not np.array_equal(runs[n][3].get(k), runs[1][3][k])]
    if differ:
        bad.append(f"per-host columns differ: {differ}")
    return {"phase": f"mesh{n}", "config": f"examples/{name}",
            "parallel_compile_wall_s": warm_s,
            f"chips_{n}": runs[n][0], "chips_1": runs[1][0],
            "failed": bad}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded 4-chip path and its "
                         "1-chip comparison")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(HERE, "shadow_tpu")):
        log(f"no shadow_tpu package beside {__file__}: run from a "
            "checkout of the repo")
        return 2
    sys.path.insert(0, HERE)
    try:
        from shadow_tpu._jax import jax

        devs = jax.devices()
    except Exception as e:          # noqa: BLE001 — no backend at all
        log(f"no jax backend: {e}")
        return 1
    platform = devs[0].platform
    if platform != "tpu":
        log(f"no TPU: jax found {len(devs)} {platform} device(s)")
        return 1
    if len(devs) < args.chips:
        log(f"--chips {args.chips} but jax found {len(devs)} chip(s)")
        return 1
    log(f"{len(devs)} x {devs[0].device_kind}")
    phases = ([lambda: phase_mesh(4)] if args.chips == 4
              else [phase_a, phase_b])
    ok = True
    for phase in phases:
        try:
            rec = phase()
        except Exception as e:      # noqa: BLE001 — a failed phase
            import traceback

            traceback.print_exc()
            rec = {"phase": getattr(phase, "__name__", "phase"),
                   "failed": [f"{type(e).__name__}: {e}"]}
        print(json.dumps(rec), flush=True)
        if rec["failed"]:
            log(f"phase {rec['phase']} FAILED: {rec['failed']}")
            ok = False
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
