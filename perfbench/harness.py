"""One run of one benchmark cell: find it by name, set it up, measure
a window, check the result against the plain reference, and reduce
what was recorded to the cell's metrics.

Everything that belongs to one configuration, traffic mix or metric is
a file of its own, found by the name `BENCHMARK.json` gives it:

* ``perfbench/configs/<file>.yaml`` -- the configuration as run, and
  beside it ``<file>.json``: its source, what was assumed or reduced,
  and the plain reference that checks it (``reference``, a module in
  ``perfbench/references/``, with keyword arguments ``compare``);
* ``perfbench/traffic/<traffic>.json`` -- the mix: args for each app
  model, the simulated horizon, the warm-up point and the segment
  length;
* ``perfbench/metrics/<metric>.py`` -- a reader ``read(rec)`` that
  takes one number from the run record, or None when there is
  nothing to read.

The window drives the program's own path: ``Controller`` builds the
``tpu`` policy's ``DeviceRunner``, and each segment is one call of
``supervise.advance`` that ends in a device sync, with the mix's
horizon as ``final_stop``, as ``Controller.run()`` passes the stop
time: the rounds are those of an unsegmented run, and the last round
of a segment may run past the segment's end. After the window the
state holds every event before the earliest one still pending (its
clock, which the engine keeps at or past the last segment's end), and
nothing else: that is what the reference recomputes up to, and what is
compared.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import tempfile
import time
from dataclasses import dataclass

import numpy as np
import yaml

from perfbench.references.common import COLUMNS

NS = 10**9
# a traced run measures a window this long at most: the profiler's
# trace grows with every device op, and the per-layer numbers need
# only a few segments
TRACE_SECONDS = 3.0
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
# a window in which this many segments in a row route no packet has
# run out of traffic: it would time idle simulated time
STALL_SEGMENTS = 2


class CellError(RuntimeError):
    """The cell cannot be measured as it stands (named in the text)."""


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


@dataclass
class Cell:
    name: str
    chips: int
    config_path: str
    meta: dict
    mix: dict
    end_to_end: list
    per_layer: list


def load_benchmark(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def resolve_cell(root: str, name: str) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r} in BENCHMARK.json")
    wl = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    path = os.path.join(root, conf["file"])
    with open(os.path.splitext(path)[0] + ".json") as f:
        meta = json.load(f)
    with open(os.path.join(root, "perfbench", "traffic",
                           wl["traffic"] + ".json")) as f:
        mix = json.load(f)

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return Cell(name, int(wl["chips"]), path, meta, mix,
                mine(bench["end_to_end"]), mine(bench["per_layer"]))


def apply_mix(raw: dict, mix: dict) -> dict:
    """Set each app model's args from the mix and the horizon as the
    stop time (the one general traffic generator)."""
    for group in raw["hosts"].values():
        for proc in group["processes"]:
            app = proc["path"].removeprefix("model:")
            if app in mix.get("apps", {}):
                args = dict(kv.split("=", 1)
                            for kv in str(proc.get("args", "")).split())
                args.update({k: str(v) for k, v in mix["apps"][app].items()})
                proc["args"] = " ".join(f"{k}={v}" for k, v in args.items())
    raw["general"]["stop_time"] = f"{mix['horizon_s']} s"
    return raw


def raw_config(cell: Cell, seed: int) -> dict:
    """The configuration as this run runs it: the cell's file, its
    mix, the seed, and the mesh pinned to the cell's chips."""
    with open(cell.config_path) as f:
        raw = apply_mix(yaml.safe_load(f), cell.mix)
    raw["general"]["seed"] = int(seed)
    raw.setdefault("experimental", {})["mesh_shards"] = cell.chips
    return raw


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metrics(root: str, metrics: list, rec: dict) -> dict:
    """{name: {"value", "unit"}} for each metric whose reader finds
    something to read in the run record."""
    out = {}
    for m in metrics:
        mod = load_module(os.path.join(root, "perfbench", "metrics",
                                       m["name"] + ".py"),
                          "perfbench_metric_" + m["name"].replace(".", "_"))
        value = mod.read(rec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def process_start() -> float:
    """time.time() at which this process started (Linux /proc), so
    that set-up counts the interpreter's own start too."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return time.time() - (up - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.time()


def compare(program: dict, ids: np.ndarray, ref: dict) -> dict:
    """The number compared, with its limit: hosts whose five result
    columns differ from the reference's (an exact comparison)."""
    bad = np.zeros(ids.size, bool)
    for c in COLUMNS:
        got = np.asarray(program[c])[ids].astype(np.uint64)
        bad |= got != ref[c].astype(np.uint64)
    return {"hosts_differing": {"value": int(bad.sum()), "limit": 0}}


def reference_results(root: str, cell: Cell, raw: dict, t_end: int,
                      seed: int):
    ref = load_module(os.path.join(root, "perfbench", "references",
                                   cell.meta["reference"] + ".py"),
                      "perfbench_ref_" + cell.meta["reference"])
    return ref.run(raw, t_end, pick=seed, **cell.meta.get("compare", {}))


def check_line(checks: dict) -> list:
    return [f"check {k} {v['value']} limit {v['limit']}"
            for k, v in checks.items()]


def run(root: str, name: str, seed: int, seconds: float, trace: bool,
        platform: str | None = "tpu", patch=None) -> dict:
    """One run of cell `name`; returns the result object. `platform`
    None skips the look for a chip (tests); `patch(runner)` may break
    the timed path underneath (tests)."""
    t_proc = process_start()
    cell = resolve_cell(root, name)
    from shadow_tpu._jax import jax, jnp

    devs = jax.devices()
    if platform is not None and (devs[0].platform != platform
                                 or len(devs) < cell.chips):
        raise NoChip(f"{name} needs {cell.chips} {platform} chip(s); "
                     f"jax found {len(devs)} {devs[0].platform} "
                     "device(s)")
    from shadow_tpu.config.loader import load_config_str
    from shadow_tpu.core.controller import Controller
    from shadow_tpu.device import supervise
    from shadow_tpu.device.runner import DeviceRunner

    span = jax.profiler.TraceAnnotation
    rec = {"chips": cell.chips, "setup": {}, "window": {}, "trace": None}
    t0 = time.perf_counter()
    with span("perfbench.build"):
        raw = raw_config(cell, seed)
        ctrl = Controller(load_config_str(yaml.safe_dump(raw,
                                                         sort_keys=False)))
    runner = ctrl.runner
    if not isinstance(runner, DeviceRunner):
        raise CellError(f"{name}: the config did not build a "
                        "DeviceRunner")
    if patch is not None:
        patch(runner)
    engine = runner.engine
    t1 = time.perf_counter()
    with span("perfbench.init_state"):
        state = engine.init_state(runner.sim.starts)
        jax.block_until_ready(state)
    t2 = time.perf_counter()
    rec["setup"].update(build_s=t1 - t0, init_state_s=t2 - t1)

    seg = int(round(cell.mix["segment_s"] * NS))
    warm = int(round(cell.mix["warmup_s"] * NS))
    horizon = int(round(cell.mix["horizon_s"] * NS))
    n_hosts = len(runner.sim.hosts)
    sent_of = jax.jit(lambda x: jnp.sum(x, dtype=jnp.int64))

    @jax.jit
    def clock_of(ht, head):
        """The earliest pending event time: every live heap slot."""
        live = jnp.arange(ht.shape[1])[None, :] >= head[:, None]
        return jnp.min(jnp.where(live, ht, jnp.iinfo(ht.dtype).max))

    failed = 0

    def segment(state, t):
        nxt = t + seg
        if nxt > horizon:
            raise CellError(f"{name}: the window reached the mix's "
                            f"horizon ({cell.mix['horizon_s']} sim-s); "
                            "raise horizon_s in a new mix")
        state, adv = supervise.advance(runner, state, t, nxt, horizon)
        bad = bool(adv.overflowed or adv.budget_hit or adv.retries
                   or adv.t_end != nxt)
        return state, adv, bad

    t = 0
    with span("perfbench.warmup"):
        while t < warm:
            state, adv, bad = segment(state, t)
            if bad:
                raise CellError(f"{name}: warm-up segment at {t} ns "
                                "overflowed or fell short")
            t = adv.t_end
        sent = int(sent_of(state["n_sent"]))
    rec["setup"]["cache"] = (runner.aot_cache.report()
                             if runner.aot_cache is not None else None)
    rec["setup"]["warmup_s"] = time.perf_counter() - t2

    compiles = []

    def on_event(event, duration, **_):
        if event == BACKEND_COMPILE:
            compiles.append(duration)

    trace_dir = tempfile.mkdtemp(prefix="perfbench_trace_") if trace \
        else ""
    limit = min(seconds, TRACE_SECONDS) if trace else seconds
    t_open, sent_open = t, sent
    segs, idle = [], 0
    tracing = done = False
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        if trace:
            # host spans come from TraceAnnotation and the runtime; the
            # Python tracer would record every call and slow the host
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing = True
        rec["setup"]["total_s"] = time.time() - t_proc
        w_open = time.perf_counter()
        with span("perfbench.window") if trace \
                else contextlib.nullcontext():
            while True:
                s0 = time.perf_counter()
                with span("perfbench.segment"):
                    state, adv, bad = segment(state, t)
                s1 = time.perf_counter()
                with span("perfbench.counter_read"):
                    now_sent = int(sent_of(state["n_sent"]))
                s2 = time.perf_counter()
                segs.append({"t0": t, "t1": adv.t_end, "wall_s": s2 - s0,
                             "advance_s": s1 - s0,
                             "sync_s": adv.pipeline["sync_wall_s"],
                             "rounds": int(np.max(adv.rounds)),
                             "packets": now_sent - sent, "failed": bad})
                failed += bad
                idle = idle + 1 if now_sent == sent else 0
                if idle >= STALL_SEGMENTS:
                    raise CellError(f"{name}: traffic stopped before "
                                    f"{adv.t_end} ns; the window would "
                                    "measure idle simulated time")
                t, sent = adv.t_end, now_sent
                if s2 - w_open >= limit or bad:
                    break
        wall = time.perf_counter() - w_open
        done = True
    finally:
        if tracing:
            jax.profiler.stop_trace()
        jax.monitoring.unregister_event_duration_listener(on_event)
        if trace_dir and not done:
            shutil.rmtree(trace_dir, ignore_errors=True)
    rec["window"] = {"wall_s": wall, "sim_s": (t - t_open) / NS,
                     "packets": sent - sent_open,
                     "rounds": sum(s["rounds"] for s in segs),
                     "sync_s": sum(s["sync_s"] for s in segs),
                     "segments": segs}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in engine.mesh.devices.flat)
    rec["memory"] = {"peak_bytes": peak}
    program = jax.device_get({c: state[c] for c in COLUMNS})
    program = {c: np.asarray(v)[:n_hosts] for c, v in program.items()}
    reached = int(clock_of(state["ht"], state["head"]))
    del state
    runner.final_state = None

    if trace:
        from perfbench import xplane

        try:
            rec["trace"] = xplane.reduce_dir(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        rec["trace"]["rounds"] = rec["window"]["rounds"]

    r0 = time.perf_counter()
    ids, ref = reference_results(root, cell, raw, reached, seed)
    checks = compare(program, ids, ref)
    # the engine pauses only once no event before the segment's end is
    # left: a clock short of it is a segment that did not run
    checks["clock_short_ns"] = {"value": max(0, t - reached), "limit": 0}
    ref_s = time.perf_counter() - r0
    correct = failed == 0 and all(v["value"] <= v["limit"]
                                  for v in checks.values())
    metrics = read_metrics(root, cell.per_layer if trace
                           else cell.end_to_end, rec)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": len(segs),
           "failed": int(failed), "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
        out["breakdown"] = {"device_ops": rec["trace"]["device_ops"],
                            "idle_gaps": rec["trace"]["idle_gaps"]}
    out["checks"] = checks
    cache = rec["setup"]["cache"] or {}
    out["_info"] = {
        "cell": name, "seed": int(seed), "segments": len(segs),
        "sim_s": rec["window"]["sim_s"], "t_end_ns": int(t),
        "t_reached_ns": reached,
        "compiles_in_window": len(compiles),
        "cache_hits": cache.get("hits"), "cache_misses": cache.get("misses"),
        "compile_s": cache.get("compile_s"), "load_s": cache.get("load_s"),
        "setup": {k: v for k, v in rec["setup"].items() if k != "cache"},
        "hosts_compared": int(ids.size),
        "reference_s": ref_s,
        "per_segment": segs}
    return out
