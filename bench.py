"""Benchmark: the tgen ladder on the device engine vs the CPU thread
policy (BASELINE.md's target comparison).

Prints ONE JSON line:
  {"metric": "packets_routed_per_sec_per_chip", "value": N,
   "unit": "packets/s", "vs_baseline": R, ...extras}

Method (honest-numbers rules):
* Workload: the repo's tgen ladder — examples/tgen_100.yaml,
  tgen_1000.yaml and the 10k-host tgen_10000.yaml (the BASELINE.md
  north-star config), unmodified except stop_time for the bounded
  slices below.
* Baseline: the CPU `thread` scheduler policy (thread-per-core; on
  this machine's core count), NOT the serial oracle.
* vs_baseline: device wall-clock vs thread-policy wall-clock on the
  IDENTICAL config and sim interval (a bounded slice so the CPU run
  finishes); reported per rung, headline ratio is the 10k rung's.
* value: device packets routed per wall second over the FULL 30 s
  tgen_10000 run (steady state included), divided by chip count.
* Runs in one process. With no TPU the run fails unless the CPU was
  asked for explicitly (JAX_PLATFORMS=cpu); it never falls back.
* Overflow, a failed rung or no TPU => nonzero exit; the JSON line is
  still emitted (with an "error" field).
"""

from __future__ import annotations

import json
import os
import sys
import time

# XLA's cpu_aot_loader logs a multi-KB machine-feature WARNING on
# every CPU start, which drowns every useful line of this bench's
# stderr tail. Suppress INFO + WARNING from the C++ layer before any
# jax import; errors still surface, and an explicit
# TF_CPP_MIN_LOG_LEVEL in the environment wins.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")

RUNGS = [
    # (name, config, slice_stop_s) — slice bounds the CPU baseline run
    ("tgen_100", "examples/tgen_100.yaml", 10.0),
    ("tgen_1000", "examples/tgen_1000.yaml", 4.0),
    ("tgen_10000", "examples/tgen_10000.yaml", 2.5),
]
HEADLINE = "tgen_10000"
FULL_STOP_S = 30.0

if os.environ.get("BENCH_SMOKE"):
    # mechanics-validation mode for CI/local runs (tiny ladder, no
    # full-length run); the driver's real benchmark never sets this
    RUNGS = [("tgen_100", "examples/tgen_100.yaml", 5.0)]
    HEADLINE = "tgen_100"
    FULL_STOP_S = 8.0


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def backend_record(devs) -> dict:
    """Backend identity stamped into every BENCH_*/MULTICHIP_*
    record: jax/jaxlib versions, platform, and device kinds. Without
    these, records from different backends (a cpu-platform run vs a
    chip run, or a jaxlib upgrade) are silently comparable —
    previously only the aotcache keys knew them. Delegates to the
    cache's own identity helper so the two surfaces agree."""
    from shadow_tpu.device.aotcache import backend_identity

    return backend_identity(devs)


def init_backend():
    """The devices the bench measures. The CPU platform is used only
    when it was asked for (JAX_PLATFORMS=cpu: CI smoke and tests);
    otherwise a run that finds no TPU fails, so a CPU number can never
    be recorded as a device number."""
    from shadow_tpu._jax import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and \
            os.environ.get("JAX_PLATFORMS", "").strip() != "cpu":
        raise RuntimeError(
            f"no TPU found (jax platform {platform!r}); set "
            "JAX_PLATFORMS=cpu to run on the CPU on purpose")
    log(f"backend: {platform} x{len(devs)} ({devs[0].device_kind})")
    return devs


# config path -> (artifacts/OCC_*.json path, occupancy record) from
# the most recent device run of that config (see run_device)
_occ_records: dict = {}
# config path -> the compile/cache attribution stamped when that
# config's engine was first built this process (later rungs reuse
# the in-process engine and must report the ORIGINAL cold/warm
# attribution, not a misleading zero)
_cache_stamps: dict = {}


def _cache_stamp(c, warm_wall: float = 0.0, since: int = 0) -> dict:
    """Compile/dispatch attribution for a rung record, from the AOT
    compile cache's per-program events (device/aotcache.py):

    * ``compile_s``  — lower+compile walls actually paid (0.0 on a
      full warm start); the old conflated "compile+first run" number
      is split from
    * ``first_dispatch_s`` — the warm-run wall minus every
      cache-layer wall (lower/compile/load/serialize) recorded in
      that window, i.e. the cost of the first real dispatch. `since`
      is the cache's event count when the timed window opened, so a
      capacity plan's warm-up walls (its own events land before the
      window) never masquerade as dispatch time;
    * ``cache_hit``  — True when every run-program build this rung
      hit the cache; None when the cache is off or the backend
      cannot serialize executables (stamped, never silent)."""
    cache = getattr(c.runner, "aot_cache", None)
    if cache is None:
        return {"compile_s": None, "cache_hit": None,
                "compile_cache": "off"}
    rep = cache.report()
    run_ev = [e for e in rep["events"]
              if e["program"] in ("run", "run_ens")]
    ensure_s = sum(e["lower_s"] + e["compile_s"] + e["load_s"]
                   + e["serialize_s"]
                   for e in rep["events"][since:])
    out = {
        "compile_s": rep["compile_s"],
        "cache_hit": (None if rep["unsupported"] or not run_ev
                      else all(e.get("hit") for e in run_ev)),
        "cache_load_s": rep["load_s"],
        "compile_cache": ("unsupported" if rep["unsupported"]
                          else rep["dir"]),
    }
    if warm_wall:
        out["first_dispatch_s"] = round(
            max(0.0, warm_wall - ensure_s), 2)
    return out


def _fmt_s(v) -> str:
    """Stamp value for a log line: 'n/a' when the cache is off or
    the field was not produced, never a garbled 'Nones'."""
    return "n/a" if v is None else f"{v}s"


def _plan_stamp(c, stats) -> dict:
    """Strategy-plan provenance for a rung record (shadow_tpu/tune/):
    which PLAN file steered the run and the knobs it applied — tuned
    and default records must be honestly distinguishable. Provenance
    comes from SimStats (both the device runners AND the Controller's
    hybrid branch populate it — a tpu rung that fell back to hybrid
    still stamps its adopted plan). The record on disk is RE-verified
    against the run's workload fingerprint (tune/plan.verify_workload,
    the same check adoption runs): bench never stamps provenance from
    a fingerprint-mismatched PLAN file, it stamps the refusal
    instead."""
    prov = getattr(stats, "strategy_plan", None)
    if prov is None:
        return {"plan": None}
    from shadow_tpu.device.runner import device_twin
    from shadow_tpu.tune import plan as planmod

    try:
        app = (c.runner.app if getattr(c, "runner", None) is not None
               else device_twin(c.sim))
        rec = planmod.load_plan(prov["path"])
        planmod.verify_workload(rec, app, len(c.sim.hosts),
                                path=prov["path"])
    except (OSError, ValueError) as e:
        log(f"NOT stamping plan provenance from "
            f"{prov.get('path')}: {e}")
        return {"plan": None, "plan_error": str(e)}
    return {"plan": {"path": prov["path"],
                     "knobs": prov["knobs"],
                     "skipped": prov["skipped"],
                     "score": prov.get("score")}}


def _admission_stamp(stats) -> dict:
    """Preflight admission provenance for a rung record
    (device/capacity.py admission_verdict): the verdict, the modeled
    per-device footprint, the budget it was compared against, any
    static overrides (lowered pipeline depth, replica-batch split),
    and the runtime degradation-ladder rung count — a benched wall
    that ran degraded must never be compared against full-footprint
    runs unnoticed."""
    adm = getattr(stats, "admission", None)
    out = {}
    if adm is not None:
        est = adm.get("estimate") or {}
        entry = {"mode": adm.get("mode"),
                 "action": adm.get("action"),
                 "budget": adm.get("budget"),
                 "budget_source": adm.get("budget_source"),
                 "footprint_per_device": est.get("per_device"),
                 "overrides": adm.get("overrides") or {}}
        if adm.get("replica_batch"):
            entry["replica_batch"] = adm["replica_batch"]
        out["admission"] = entry
    if getattr(stats, "degrades", 0):
        out["degrades"] = stats.degrades
    return out


def load(config_path: str, policy: str, stop_s: float):
    from shadow_tpu import simtime
    from shadow_tpu.config import load_config

    cfg = load_config(config_path)
    cfg.experimental.scheduler_policy = policy
    cfg.general.stop_time = simtime.from_seconds(stop_s)
    if policy == "tpu" and os.environ.get("BENCH_CAPACITY_PLAN"):
        # opt-in: size every capacity from a measured warm-up slice
        # (device/capacity.py) instead of the configs' static knobs.
        # Traces stay bit-identical unless something overflows, and
        # an overflow re-plans and retries instead of failing. The
        # warm-up must reach real traffic — tgen clients start at 2s
        # sim, so the default stop/8 would measure boot only and eat
        # a re-plan cycle per rung
        plan = os.environ["BENCH_CAPACITY_PLAN"]
        if plan not in ("static", "auto") and \
                not plan.endswith(".json"):
            # the schema's own check runs at load_config time; this
            # assignment is post-validation, so re-check here or a
            # typo dies minutes later as a raw FileNotFoundError
            raise SystemExit(
                f"BENCH_CAPACITY_PLAN={plan!r} is neither 'static', "
                "'auto', nor a path to a saved OCC_*.json record")
        cfg.experimental.capacity_plan = plan
        if cfg.experimental.capacity_plan == "auto":
            cfg.experimental.capacity_warmup = min(
                cfg.general.stop_time, simtime.from_seconds(3.0))
    if policy == "tpu" and os.environ.get("BENCH_STRATEGY_PLAN"):
        # opt-in: adopt a tuned strategy plan (shadow_tpu/tune/) —
        # auto|off|<PLAN_*.json path>. Traces stay bit-identical
        # (determinism_gate --tuned pins it); the records carry the
        # plan provenance so tuned and default rungs never silently
        # compare. The env lands after load_config's schema
        # validation, so re-run the knob's ONE shared check here
        # (schema._keyword_or_path — never a fourth copy of the
        # typo-rejection logic).
        from shadow_tpu.config.schema import _keyword_or_path
        try:
            cfg.experimental.strategy_plan = _keyword_or_path(
                "strategy_plan", os.environ["BENCH_STRATEGY_PLAN"],
                ("auto", "off"),
                "a path to a saved PLAN_*.json strategy record",
                json_record=True)
        except ValueError as e:
            raise SystemExit(f"BENCH_STRATEGY_PLAN: {e}") from e
    return cfg


def _plan_and_warm(c, cfg) -> tuple[float, float, dict]:
    """Plan capacities + compile + one boot-length warm run, OUTSIDE
    any timed benchmark window, returning (plan_s, warm_s, stamp).
    The first-dispatch window opens only after the plan and
    init_state, and only cache events recorded inside it are
    subtracted by _cache_stamp — the warm-up SIMULATION's wall (and
    the heap-builder compile) must never masquerade as dispatch
    time. One helper so the ladder and the multichip rung cannot
    drift on that ordering invariant."""
    from shadow_tpu import simtime

    t0 = time.perf_counter()
    c.runner._plan_capacities(cfg.general.stop_time)
    plan_s = time.perf_counter() - t0
    cache = getattr(c.runner, "aot_cache", None)
    ev0 = len(cache.events) if cache is not None else 0
    st = c.runner.engine.init_state(c.sim.starts)
    t0 = time.perf_counter()
    c.runner.engine.run(st, stop=simtime.from_seconds(0.001))
    warm = time.perf_counter() - t0
    return plan_s, warm, _cache_stamp(c, warm_wall=warm, since=ev0)


def run_device(config_path: str, stop_s: float,
               engine_cache: dict,
               segment_s: float = 0.0
               ) -> tuple[float, int, float, dict]:
    """Warm-compiled device run: (wall_s, packets, sim_s,
    cache_stamp). Raises on overflow — a failed capacity plan must
    fail the bench. stop_time is a runtime scalar of the compiled
    program, so one short warm-up run per config covers every slice
    length. segment_s bounds the sim-time of each device dispatch
    (trace-identical splitting), so long full runs do not go up as one
    mega-dispatch.

    cache_stamp splits the old conflated "compile+warm" wall into
    compile_s / first_dispatch_s / cache_hit (see _cache_stamp) so
    the perf trajectory tracks cold-start from now on."""
    from shadow_tpu import simtime
    from shadow_tpu.core.controller import Controller

    cfg = load(config_path, "tpu", stop_s)
    if segment_s:
        cfg.experimental.dispatch_segment = \
            simtime.from_seconds(segment_s)
    c = Controller(cfg)
    # under a capacity plan the runner rebuilds the engine from
    # measured occupancy, so a cached statically-sized engine would
    # just be thrown away — plan ahead of the timed window instead
    planned = cfg.experimental.capacity_plan != "static"
    if not planned and config_path in engine_cache:
        c.runner.engine = engine_cache[config_path]
        # the rung reuses the in-process engine: report the
        # attribution from when THIS config's engine was built —
        # including through SimStats, so the runner's loud summary
        # reflects the engine's real cache lineage, not the fresh
        # runner's empty one
        if getattr(c.runner.engine, "aot_cache", None) is not None:
            c.runner.aot_cache = c.runner.engine.aot_cache
        stamp = dict(_cache_stamps.get(config_path, {}))
    elif not planned:
        # compile + a minimal-length run (boot only) to warm the
        # cache; the timed window opens AFTER init_state so the
        # heap-builder compile never counts as dispatch time
        st = c.runner.engine.init_state(c.sim.starts)
        t0 = time.perf_counter()
        c.runner.engine.run(st, stop=simtime.from_seconds(0.001))
        warm = time.perf_counter() - t0
        stamp = _cache_stamp(c, warm_wall=warm)
        log(f"  compile+warm {warm:.1f}s (compile "
            f"{_fmt_s(stamp.get('compile_s'))}, load "
            f"{_fmt_s(stamp.get('cache_load_s'))}, first dispatch "
            f"{_fmt_s(stamp.get('first_dispatch_s'))}, cache_hit="
            f"{stamp.get('cache_hit')})")
        engine_cache[config_path] = c.runner.engine
        _cache_stamps[config_path] = stamp
    else:
        # plan + compile OUTSIDE the timed window, for parity with
        # the static path's warm cache: the warm-up slice, the static
        # engine's compile, and the planned engine's compile must not
        # land in `wall` (the cpu baseline pays none of them). run()
        # sees the runner already planned and skips re-planning.
        plan_s, warm, stamp = _plan_and_warm(c, cfg)
        _cache_stamps[config_path] = stamp
        log(f"  plan {plan_s:.1f}s + compile+warm {warm:.1f}s "
            f"(compile {_fmt_s(stamp.get('compile_s'))}, first "
            f"dispatch {_fmt_s(stamp.get('first_dispatch_s'))}, "
            f"cache_hit={stamp.get('cache_hit')})")
    t0 = time.perf_counter()
    stats = c.run()
    wall = time.perf_counter() - t0
    if not stats.ok:
        raise RuntimeError(
            f"device run of {config_path} (stop={stop_s}s) overflowed "
            "— the capacity plan is wrong; see log for the knob")
    stamp = dict(stamp)
    if stats.telemetry is not None:
        # the flight recorder's per-phase wall attribution
        # (shadow_tpu/obs): the headline record carries it so the
        # perf trajectory shows WHERE the wall went, not just how
        # long it was
        stamp["phase_walls"] = stats.telemetry.get("phases")
        stamp["dominant_phase"] = stats.telemetry.get(
            "dominant_phase")
    # segment-pipeline telemetry (supervise.advance): depth,
    # issue/drain counts, sync wall, overlap efficiency — rides
    # every device rung record so sync-bound vs device-bound wall
    # is attributable from the BENCH record alone
    stamp["pipeline"] = stats.pipeline
    # preflight admission verdict + modeled footprint (and any
    # degradation the run absorbed) ride every device rung record
    stamp.update(_admission_stamp(stats))
    if stats.reshards:
        # a bench run that survived device loss is NOT a clean perf
        # record: stamp the shrink count + the shrunken mesh so the
        # number is never compared against full-mesh runs unnoticed
        stamp["reshards"] = stats.reshards
        stamp["mesh_shards_final"] = c.runner.engine.n_shards
    # strategy-plan provenance (or its loud refusal) rides every
    # device rung record
    stamp.update(_plan_stamp(c, stats))
    if stats.occupancy is not None:
        # measured high-water marks + the capacities that held them;
        # the headline run's record is written to artifacts/ in main()
        # so scripts/tune_10k.py can prune its sweep grid from it
        from shadow_tpu.device import capacity
        _occ_records[config_path] = (
            capacity.record_path(c.runner.engine), stats.occupancy)
    return wall, stats.packets_sent, stop_s, stamp


def run_cpu_thread(config_path: str, stop_s: float
                   ) -> tuple[float, int, float]:
    from shadow_tpu.core.controller import Controller

    cfg = load(config_path, "thread", stop_s)
    t0 = time.perf_counter()
    stats = Controller(cfg).run()
    wall = time.perf_counter() - t0
    if not stats.ok:
        raise RuntimeError(f"cpu thread run of {config_path} failed")
    return wall, stats.packets_sent, stop_s


MULTICHIP_SLICES = {"tgen_100": 5.0, "tgen_1000": 3.0,
                    "tgen_10000": 2.5}


def run_multichip_rung(n_chips: int) -> dict:
    """Scale-out rung (n_chips > 1): the tgen workload sharded over
    the whole mesh with `exchange: auto` + an occupancy-driven
    capacity plan, recording per-round exchanged ICI volume alongside
    pkts/s. The dense comparison is the engine's blind 4x auto CAP at
    the same shapes — the padding the occ_x-driven plan replaces —
    so the record shows the exchanged-row reduction directly."""
    from shadow_tpu import simtime
    from shadow_tpu.core.controller import Controller
    from shadow_tpu.device.capacity import dense_auto_cap

    if n_chips < 2:
        return {"skipped": f"{n_chips} chip(s) visible — the "
                           "multichip rung needs a mesh"}
    # headline config on a real mesh; smoke shrinks to the tiny rung
    name = "tgen_100" if os.environ.get("BENCH_SMOKE") else "tgen_10000"
    from shadow_tpu._jax import jax as _jax

    config = f"examples/{name}.yaml"
    slice_s = MULTICHIP_SLICES[name]
    out = {"config": config, "slice_sim_s": slice_s,
           "n_chips": n_chips, **backend_record(_jax.devices())}
    cfg = load(config, "tpu", slice_s)
    cfg.experimental.exchange = "auto"
    cfg.experimental.capacity_plan = "auto"
    cfg.experimental.capacity_warmup = min(
        cfg.general.stop_time, simtime.from_seconds(3.0))
    c = Controller(cfg)
    # plan + compile outside the timed window (same parity rule as
    # the ladder's warm cache)
    plan_s, warm, stamp = _plan_and_warm(c, cfg)
    out.update({k: stamp.get(k) for k in
                ("compile_s", "first_dispatch_s", "cache_hit")})
    log(f"  multichip plan {plan_s:.1f}s + compile+warm {warm:.1f}s "
        f"(compile {_fmt_s(stamp.get('compile_s'))}, cache_hit="
        f"{stamp.get('cache_hit')})")
    t0 = time.perf_counter()
    stats = c.run()
    wall = time.perf_counter() - t0
    if not stats.ok:
        return {**out, "error": "multichip run overflowed"}
    out.update(_plan_stamp(c, stats))
    out.update(_admission_stamp(stats))
    eng = c.runner.engine
    eff = eng.effective
    occ = stats.occupancy or {}
    measured = dict(occ.get("measured") or {})
    measured.update(occ.get("final_measured") or {})
    phases = int(measured.get("phases", 0))
    rounds = max(1, stats.rounds)
    out.update({
        "exchange": eff["exchange"],
        "exchange_auto": occ.get("exchange_auto"),
        "planned": occ.get("planned"),
        "pkts": stats.packets_sent,
        "wall_s": round(wall, 2),
        "pkts_per_s": round(stats.packets_sent / wall, 1),
        "pkts_per_s_per_chip": round(
            stats.packets_sent / wall / n_chips, 1),
        "rounds": stats.rounds,
        "phases": phases,
        # per-shard ICI traffic: buffers ship at capacity, so the
        # static per-flush volume times the flush count IS the wire
        "ici_rows_per_flush": eff["ICI_rows_per_flush"],
        "ici_bytes_per_flush": eff["ICI_bytes_per_flush"],
        "ici_rows_per_round": round(
            eff["ICI_rows_per_flush"] * phases / rounds, 1),
    })
    # the dense blind-headroom pack this plan replaces: the engine's
    # auto 4x CAP at the STATIC config's shapes (occ["static"] — what
    # the pre-planner engine actually ran), not the planned engine's
    # possibly-wider outbox, so the reduction factor is honest
    S = eff["n_shards"]
    static = occ.get("static") or {}
    dense_rows = (S - 1) * dense_auto_cap(
        eng.H_loc,
        int(static.get("outbox_capacity", eff["OB"])),
        int(static.get("event_capacity", eff["E"])), S)
    out["dense_auto_rows_per_flush"] = dense_rows
    if eff["ICI_rows_per_flush"]:
        out["ici_reduction_vs_dense"] = round(
            dense_rows / eff["ICI_rows_per_flush"], 2)
    return out


ENSEMBLE_REPLICAS = 4
ENSEMBLE_SEEDS = [1, 7, 13, 42]
ENSEMBLE_CONFIG = "examples/tgen_100.yaml"
ENSEMBLE_STOP_S = 4.0 if os.environ.get("BENCH_SMOKE") else 5.0


def run_ensemble_rung() -> dict:
    """Ensemble rung: an R-replica seed-sweep campaign (ONE vmapped
    program) vs the cold standalone run R serial processes would each
    repeat. Both walls are COLD — compile included — because that is
    what a user running N processes actually pays; the campaign pays
    one compile for all R replicas, which is the amortization this
    rung makes visible (speedup_vs_r_serial_runs). Aggregate
    packets/s is the campaign's total routed packets over its wall.
    Runs on the cpu platform too when JAX_PLATFORMS=cpu asks for it
    (labeled by the record's platform field), so CI validates the
    campaign mechanics."""
    from shadow_tpu.config.schema import EnsembleOptions
    from shadow_tpu.core.controller import Controller

    R = ENSEMBLE_REPLICAS
    out = {"config": ENSEMBLE_CONFIG, "replicas": R,
           "seeds": ENSEMBLE_SEEDS, "slice_sim_s": ENSEMBLE_STOP_S}
    cfg = load(ENSEMBLE_CONFIG, "tpu", ENSEMBLE_STOP_S)
    cfg.general.seed = ENSEMBLE_SEEDS[0]
    t0 = time.perf_counter()
    c1 = Controller(cfg)
    s1 = c1.run()
    single_wall = time.perf_counter() - t0
    if not s1.ok:
        return {**out, "error": "standalone run overflowed"}
    if s1.packets_sent == 0:
        return {**out, "error": "standalone run routed 0 packets "
                                "(slice too short?)"}
    out["single_run_wall_s"] = round(single_wall, 2)
    out["single_run_pkts"] = s1.packets_sent
    out["single_run_pkts_per_s"] = round(
        s1.packets_sent / single_wall, 1)
    # the "cold" walls are honest only with the cache state stamped:
    # a repeat bench with a populated AOT cache starts warm, and
    # cache_hit marks exactly that
    s1_stamp = _cache_stamp(c1)
    out["single_run_compile_s"] = s1_stamp.get("compile_s")
    out["single_run_cache_hit"] = s1_stamp.get("cache_hit")

    cfg2 = load(ENSEMBLE_CONFIG, "tpu", ENSEMBLE_STOP_S)
    cfg2.ensemble = EnsembleOptions.from_dict(
        {"replicas": R, "vary": {"seed": ENSEMBLE_SEEDS}})
    t0 = time.perf_counter()
    c2 = Controller(cfg2)
    s2 = c2.run()
    ens_wall = time.perf_counter() - t0
    if not s2.ok:
        return {**out, "error": "campaign overflowed"}
    out["campaign_wall_s"] = round(ens_wall, 2)
    out.update(_admission_stamp(s2))
    s2_stamp = _cache_stamp(c2)
    out["campaign_compile_s"] = s2_stamp.get("compile_s")
    out["campaign_cache_hit"] = s2_stamp.get("cache_hit")
    out["aggregate_pkts"] = s2.packets_sent
    out["aggregate_pkts_per_s"] = round(s2.packets_sent / ens_wall, 1)
    out["r_x_single_run_pkts_per_s"] = round(
        R * out["single_run_pkts_per_s"], 1)
    # the campaign vs R cold serial runs of the same slice: > 1 means
    # the one-compile amortization is real on this platform
    out["speedup_vs_r_serial_runs"] = round(
        R * single_wall / ens_wall, 2)
    out["record"] = c2.runner.record_path()
    # the determinism contract rides along: campaign replica 0 must
    # bit-match the standalone run it was compared against
    import numpy as np
    H = len(c2.sim.hosts)
    chk_e = np.asarray(c2.runner.final_state["chk"])[0, :H]
    chk_s = np.array([h.trace_checksum for h in c1.sim.hosts])
    out["replica0_matches_single"] = bool((chk_e == chk_s).all())
    if not out["replica0_matches_single"]:
        out["error"] = "campaign replica 0 diverged from the " \
                       "standalone run with its seed"
    return out


# topology-representation rung ladder: (label, clusters, spokes/hub).
# The 1M point runs only outside BENCH_SMOKE (sub-second build, but
# the smoke ladder stays tiny on principle).
TOPOLOGY_RUNG_SIZES = [("1k", 20, 49), ("100k", 100, 999)]
TOPOLOGY_RUNG_1M = "examples/tgen_1000000.yaml"


def run_topology_rung() -> dict:
    """Topology-representation rung (docs/topology.md): build
    hierarchical star_clusters tables at 1k/100k vertices — and the
    million-host example config outside BENCH_SMOKE — stamping build
    wall, actual table bytes, and the dense-equivalent bytes
    (12 bytes/pair: int64 latency + float32 reliability). At the 1k
    point the dense pipeline also runs for a wall/byte comparison and
    the factored tables are checked bit-identical to it (the build
    already verifies at V <= 2048; a silent skip would make this rung
    meaningless). Pure host-side numpy — no device work, so the rung
    is identical on every backend."""
    import numpy as np

    from shadow_tpu.device.capacity import fmt_bytes
    from shadow_tpu.topology.generate import generate_star_clusters

    out = {"points": []}
    for label, C, S in TOPOLOGY_RUNG_SIZES:
        params = {"clusters": C, "spokes_per_cluster": S,
                  "hub_latency": "10 ms", "access_latency": "1 ms"}
        t0 = time.perf_counter()
        th = generate_star_clusters(params,
                                    representation="hierarchical")
        h_wall = time.perf_counter() - t0
        V = th.n_vertices
        dense_bytes = 12 * V * V
        pt = {"label": label, "n_vertices": V,
              "n_clusters": th.hier.n_clusters,
              "hier_build_s": round(h_wall, 3),
              "hier_table_bytes": th.table_nbytes(),
              "dense_table_bytes": dense_bytes,
              "reduction": round(dense_bytes / th.table_nbytes(), 1)}
        if V <= 2048:
            t0 = time.perf_counter()
            td = generate_star_clusters(params,
                                        representation="dense")
            pt["dense_build_s"] = round(time.perf_counter() - t0, 3)
            hlat, hrel = th.hier.dense()
            if not (np.array_equal(hlat, td.latency_ns)
                    and np.array_equal(hrel, td.reliability)):
                return {**out, "error": f"{label}: factored tables "
                        "diverged from the dense pipeline"}
        log(f"  topology {label}: V={V} hier "
            f"{fmt_bytes(pt['hier_table_bytes'])} in "
            f"{pt['hier_build_s']}s (dense "
            f"{fmt_bytes(dense_bytes)}, {pt['reduction']}x)")
        out["points"].append(pt)
    if not os.environ.get("BENCH_SMOKE"):
        # the million-host example, through the REAL config path
        # (schema -> load_topology -> generator -> representation)
        from shadow_tpu.config import load_config
        from shadow_tpu.core.controller import load_topology
        cfg = load_config(TOPOLOGY_RUNG_1M)
        t0 = time.perf_counter()
        top = load_topology(cfg)
        wall = time.perf_counter() - t0
        V = top.n_vertices
        budget = int(cfg.experimental.device_memory_budget)
        tb = top.table_nbytes()
        pt = {"label": "1M", "config": TOPOLOGY_RUNG_1M,
              "n_vertices": V, "n_clusters": top.hier.n_clusters,
              "hier_build_s": round(wall, 3),
              "hier_table_bytes": tb,
              "dense_table_bytes": 12 * V * V,
              "reduction": round(12 * V * V / tb, 1),
              "budget_bytes": budget,
              "tables_fit_budget": tb <= budget}
        log(f"  topology 1M: V={V} tables {fmt_bytes(tb)} in "
            f"{pt['hier_build_s']}s — "
            f"{'fit' if pt['tables_fit_budget'] else 'EXCEED'} the "
            f"{fmt_bytes(budget)} example budget (dense would be "
            f"{fmt_bytes(12 * V * V)})")
        out["points"].append(pt)
        if not pt["tables_fit_budget"]:
            out["error"] = "1M tables exceed the example's budget"
    return out


# columnar-boot rung ladder: config per point. The 1M example runs
# only outside BENCH_SMOKE (it boots in seconds now, but the smoke
# ladder stays tiny on principle).
BOOT_RUNG_POINTS = [("1k", "examples/tgen_1000.yaml"),
                    ("100k", "examples/tgen_100000.yaml")]
BOOT_RUNG_1M = ("1M", "examples/tgen_1000000.yaml")
BOOT_RUNG_PATH = os.path.join("artifacts", "BOOT_r16.json")
BOOT_1M_FLOOR_S = 60.0


def run_boot_rung() -> dict:
    """Columnar-boot rung (docs/host_plane.md): wall clock to stand up
    a runnable simulation — controller.build() (columnar host plane) +
    DeviceRunner construction + engine.init_state() — at 1k/100k
    hosts, plus the million-host example outside BENCH_SMOKE. Stamps
    per-stage walls and hosts/s into artifacts/BOOT_r16.json, and
    records whether the columnar fast path actually ran: an object
    build sneaking in would silently bench the wrong thing, so a
    refused plane is an error here, not a fallback. The acceptance
    floor rides along — the 1M point must boot in under 60 s."""
    import gc

    import jax as _jax

    from shadow_tpu.config import load_config
    from shadow_tpu.core.controller import build as build_sim
    from shadow_tpu.device.runner import DeviceRunner
    from shadow_tpu.utils.artifacts import atomic_write_json

    points = list(BOOT_RUNG_POINTS)
    if not os.environ.get("BENCH_SMOKE"):
        points.append(BOOT_RUNG_1M)
    out = {"points": []}
    for label, path in points:
        cfg = load_config(path)
        n = cfg.total_hosts()
        t0 = time.perf_counter()
        sim = build_sim(cfg)
        build_s = time.perf_counter() - t0
        columnar = sim.plane is not None
        t0 = time.perf_counter()
        runner = DeviceRunner(sim)
        engine_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        state = runner.engine.init_state(sim.starts)
        _jax.block_until_ready(state["ht"])
        init_s = time.perf_counter() - t0
        boot_s = build_s + engine_s + init_s
        pt = {"label": label, "config": path, "n_hosts": n,
              "columnar": columnar,
              "build_s": round(build_s, 3),
              "engine_s": round(engine_s, 3),
              "init_state_s": round(init_s, 3),
              "boot_s": round(boot_s, 3),
              "hosts_per_s": round(n / boot_s, 1)}
        log(f"  boot {label}: {n} hosts in {pt['boot_s']}s "
            f"({pt['hosts_per_s']:,.0f} hosts/s; build "
            f"{pt['build_s']}s, engine {pt['engine_s']}s, "
            f"init_state {pt['init_state_s']}s, "
            f"columnar={columnar})")
        out["points"].append(pt)
        if not columnar:
            out["error"] = (f"{label}: the columnar fast path was "
                            "refused — this rung benches the plane")
        elif label == "1M" and boot_s >= BOOT_1M_FLOOR_S:
            out["error"] = (f"1M boot took {boot_s:.1f}s — the "
                            f"<{BOOT_1M_FLOOR_S:.0f}s floor failed")
        # the 1M heaps are ~2.6 GB on the CPU platform: release them
        # before the next point (or whatever rung follows)
        del state, runner, sim
        gc.collect()
    try:
        atomic_write_json(out, BOOT_RUNG_PATH)
        log(f"  boot record -> {BOOT_RUNG_PATH}")
    except OSError as e:
        log(f"  could not write boot record: {e}")
    return out


PIPELINE_DEPTHS = (1, 2, 4)


def run_pipelined_rung(name: str, config_path: str, stop_s: float
                       ) -> dict:
    """Pipelined-dispatch rung (device/supervise.py segment
    pipeline): the headline workload in the SUPERVISED production
    posture — rotating validated checkpoints, heartbeats, and the
    state-audit word — at pipeline_depth 1/2/4 on one identical
    config. Depth 1 is the serial issue-then-sync loop; deeper
    windows overlap the drain's host-side boundary work (checkpoint
    fetch+compress+write, heartbeat syncs, audit reads) with device
    execution of the in-flight segments. Every depth must route
    identical traffic (bit-identity is the gate's job; the rung
    re-checks the cheap packet counters so a broken window can never
    publish a number).

    Honesty rules: all depths run WARM (one engine, compile excluded
    from every timed window — the serial leg must not pay the audit
    program's cold compile), and the record stamps host_cores:
    overlap converts host-side wall into device-shadowed wall only
    when the host and the device are separate hardware, so on a
    single-core cpu-platform box the depths measure flat and the
    rung's real-TPU number is the one the ROADMAP campaign item
    collects."""
    import tempfile

    from shadow_tpu import simtime
    from shadow_tpu.core.controller import Controller

    out: dict = {
        "workload": name,
        "slice_sim_s": stop_s,
        "depths_swept": list(PIPELINE_DEPTHS),
        "host_cores": len(os.sched_getaffinity(0))
        if hasattr(os, "sched_getaffinity") else os.cpu_count(),
        # the supervised posture (sim-seconds): segment/checkpoint/
        # heartbeat cadences scale with the slice so the smoke rung
        # and the full rung exercise the same boundary density
        "dispatch_segment_s": round(stop_s / 20, 3),
        "checkpoint_every_s": round(stop_s / 40, 3),
        "heartbeat_s": round(stop_s / 10, 3),
    }
    engine = None
    depths: dict = {}
    pkts0 = None
    with tempfile.TemporaryDirectory() as tmp:
        for depth in PIPELINE_DEPTHS:
            cfg = load(config_path, "tpu", stop_s)
            # this rung measures PIPELINING, not planning: a
            # BENCH_CAPACITY_PLAN=auto run would re-plan and rebuild
            # the engine inside depth 1's timed window (and hand the
            # stale engine to depths 2/4), breaking the one-warm-
            # engine rule the depth comparison depends on — pin the
            # static capacities for every depth instead
            cfg.experimental.capacity_plan = "static"
            cfg.experimental.capacity_warmup = 0
            cfg.general.heartbeat_interval = simtime.from_seconds(
                out["heartbeat_s"])
            ddir = os.path.join(tmp, f"d{depth}")
            os.makedirs(ddir, exist_ok=True)
            cfg.general.data_directory = os.path.join(ddir,
                                                      "shadow.data")
            cfg.experimental.dispatch_segment = simtime.from_seconds(
                out["dispatch_segment_s"])
            cfg.experimental.checkpoint_save = os.path.join(ddir,
                                                            "ck.npz")
            cfg.experimental.checkpoint_every = simtime.from_seconds(
                out["checkpoint_every_s"])
            cfg.experimental.state_audit = True
            cfg.experimental.pipeline_depth = depth
            c = Controller(cfg)
            if engine is None:
                # compile once (the audit word changes the program,
                # so the ladder's engine cache does not apply) and a
                # boot-length warm dispatch, both outside every
                # depth's timed window
                from shadow_tpu._jax import jax
                st = c.runner.engine.init_state(c.sim.starts)
                t0 = time.perf_counter()
                # run() is a pure async enqueue since PR 11: block
                # explicitly, or the warm segment's device work
                # would still be executing when depth 1's timed
                # window opens (and be charged to the serial leg)
                jax.block_until_ready(c.runner.engine.run(
                    st, stop=simtime.from_seconds(0.001)))
                out["compile_warm_s"] = round(
                    time.perf_counter() - t0, 2)
                engine = c.runner.engine
            else:
                c.runner.engine = engine
                if getattr(engine, "aot_cache", None) is not None:
                    c.runner.aot_cache = engine.aot_cache
            t0 = time.perf_counter()
            stats = c.run()
            wall = time.perf_counter() - t0
            if not stats.ok:
                return {**out, "error":
                        f"depth-{depth} run reported not-ok"}
            if pkts0 is None:
                pkts0 = stats.packets_sent
            elif stats.packets_sent != pkts0:
                # same config+seed at every depth must route the
                # same traffic; a divergent window is a determinism
                # bug, not a number worth publishing
                return {**out, "error":
                        f"depth {depth} routed {stats.packets_sent} "
                        f"packets but depth 1 routed {pkts0} on the "
                        "identical config"}
            rec = {
                "wall_s": round(wall, 2),
                "pkts_per_s": round(stats.packets_sent / wall, 1),
                "pipeline": dict(stats.pipeline or {}),
            }
            rec.update(_admission_stamp(stats))
            if stats.telemetry is not None:
                rec["phase_walls"] = stats.telemetry.get("phases")
                rec["dominant_phase"] = stats.telemetry.get(
                    "dominant_phase")
            depths[str(depth)] = rec
            log(f"  depth {depth}: {wall:.2f}s wall, overlap "
                f"{rec['pipeline'].get('overlap_efficiency', 0.0):.0%}"
                f" ({rec['pipeline'].get('issued')} issued, sync "
                f"{rec['pipeline'].get('sync_wall_s')}s)")
    out["depths"] = depths
    out["pkts"] = pkts0
    w1 = depths[str(PIPELINE_DEPTHS[0])]["wall_s"]
    wn = depths[str(PIPELINE_DEPTHS[-1])]["wall_s"]
    out["wall_delta_vs_serial_pct"] = round(100.0 * (w1 - wn) / w1, 1)
    if out["host_cores"] == 1:
        out["note"] = (
            "single-core host: the cpu-platform 'device' and the "
            "host share one core, so overlapped work cannot reduce "
            "wall here — the flat depths are expected; the real-TPU "
            "window (ROADMAP proof campaign) is where this rung's "
            "overlap converts to wall")
    return out


HYBRID_SWEEP = [40, 200, 1000]      # pairs per rung (VERDICT r4 #3)
HYBRID_BYTES = 100_000
HYBRID_SWEEP_BUDGET_S = 1200        # stop adding rungs past this

HYBRID_GML = """graph [ directed 0
  node [ id 0 bandwidth_down "1 Gbit" bandwidth_up "1 Gbit" ]
  node [ id 1 bandwidth_down "1 Gbit" bandwidth_up "1 Gbit" ]
  edge [ source 0 target 0 latency "10 ms" packet_loss 0.001 ]
  edge [ source 0 target 1 latency "25 ms" packet_loss 0.001 ]
  edge [ source 1 target 1 latency "10 ms" packet_loss 0.001 ]
]"""


def _hybrid_cfg(policy: str, data_dir: str, bins: dict,
                pairs: int) -> str:
    gml = "\n".join("      " + ln for ln in HYBRID_GML.splitlines())
    cfg = f"""
general:
  stop_time: 60s
  seed: 1
  data_directory: {data_dir}
network:
  graph:
    type: gml
    inline: |
{gml}
experimental:
  scheduler_policy: {policy}
hosts:
"""
    # servers register first -> sequential IPs from 11.0.0.1 (dns.py
    # _alloc_ip order, reserved .0/.255 skipped); client i dials its
    # own server's IP
    def nth_ip(i: int) -> str:
        ip = (11 << 24) | 1
        for _ in range(i):
            ip += 1
            while ip & 0xFF in (0, 255):
                ip += 1
        return ".".join(str((ip >> s) & 0xFF)
                        for s in (24, 16, 8, 0))

    for i in range(pairs):
        cfg += f"""  server{i}:
    network_node_id: 0
    processes:
    - {{path: {bins['tcp_server']}, args: 8080, start_time: 1s}}
"""
    for i in range(pairs):
        cfg += f"""  client{i}:
    network_node_id: 1
    processes:
    - {{path: {bins['tcp_client']}, args: {nth_ip(i)} 8080 {HYBRID_BYTES}, start_time: 2s}}
"""
    return cfg


def _compile_tcp_bins(tmp: str):
    import shutil
    import subprocess as sp

    cc = shutil.which("cc") or shutil.which("gcc")
    plug = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tests", "plugins")
    if cc is None or not os.path.isdir(plug):
        return None
    bins = {}
    for name in ("tcp_client", "tcp_server"):
        exe = os.path.join(tmp, name)
        sp.run([cc, "-O1", "-o", exe,
                os.path.join(plug, f"{name}.c")], check=True,
               capture_output=True)
        bins[name] = exe
    return bins


def _hybrid_rung(bins: dict, tmp: str, pairs: int) -> dict:
    """One sweep rung: `pairs` real tcp_client/tcp_server pairs
    (seccomp interposition, emulated TCP) under `hybrid` — adaptive
    judge: CPU below hybrid_judge_min_batch, device above — vs the
    identical config on the pure-CPU `thread` policy. Honest on both
    outcomes: judged packets, batch counts, and the wall ratio are
    recorded either way."""
    from shadow_tpu.config import load_config_str
    from shadow_tpu.core.controller import Controller

    out = {"pairs": pairs, "bytes_per_pair": HYBRID_BYTES}
    sums = {}
    for policy in ("thread", "hybrid"):
        data = os.path.join(tmp, f"{policy}{pairs}", "shadow.data")
        cfg = load_config_str(_hybrid_cfg(policy, data, bins, pairs))
        c = Controller(cfg)
        t0 = time.perf_counter()
        stats = c.run()
        wall = time.perf_counter() - t0
        if not stats.ok:
            return {"error": f"{policy} run failed", "pairs": pairs}
        sums[policy] = [h.trace_checksum for h in c.sim.hosts]
        out[f"{policy}_wall_s"] = round(wall, 2)
        if policy == "hybrid":
            j = c.manager.net_judge
            out["judged_packets"] = j.packets + j.cpu_packets
            out["device_batches"] = j.batches
            out["device_packets"] = j.packets
            out["cpu_batches"] = j.cpu_batches
            out["judge_min_batch"] = j.min_batch
            out["judged_pkts_per_s"] = round(
                (j.packets + j.cpu_packets) / wall, 1)
    if sums["thread"] != sums["hybrid"]:
        return {"error": "hybrid trace diverged from cpu thread",
                "pairs": pairs}
    out["hybrid_vs_thread"] = round(
        out["thread_wall_s"] / out["hybrid_wall_s"], 2)
    return out


def run_hybrid_sweep() -> dict:
    """VERDICT r4 #3: judged-pkts/s AND hybrid-vs-thread per batch
    scale — pairs in {40, 200, 1000} — so the crossover (or its
    absence) is measured, not asserted. Later rungs are skipped when
    the sweep exceeds its wall budget (recorded, never silent)."""
    import shutil
    import tempfile

    tmp = tempfile.mkdtemp(prefix="bench_hybrid_")
    try:
        bins = _compile_tcp_bins(tmp)
        if bins is None:
            return {"skipped": "no compiler or plugins"}
        sweep: dict = {"rungs": []}
        t0 = time.perf_counter()
        for pairs in HYBRID_SWEEP:
            elapsed = time.perf_counter() - t0
            if elapsed > HYBRID_SWEEP_BUDGET_S:
                sweep["skipped_rungs"] = [
                    p for p in HYBRID_SWEEP if p > pairs] + [pairs]
                sweep["skip_reason"] = (
                    f"sweep budget {HYBRID_SWEEP_BUDGET_S}s exceeded "
                    f"({elapsed:.0f}s)")
                break
            log(f"  hybrid rung: {pairs} pairs")
            r = _hybrid_rung(bins, tmp, pairs)
            log(f"    {r}")
            sweep["rungs"].append(r)
            if "error" in r:
                break
        return sweep
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main() -> int:
    result = {
        "metric": "packets_routed_per_sec_per_chip",
        "value": 0.0,
        "unit": "packets/s",
        # None = "no valid ratio" (errors/fallback); only a completed
        # device-vs-cpu ladder sets a number here
        "vs_baseline": None,
    }
    rc = 0
    try:
        devs = init_backend()
        n_chips = len({d.id for d in devs})
        # backend identity (jax/jaxlib/platform/device kind): records
        # from different backends must never be silently comparable
        result.update(backend_record(devs))
        rungs, headline, full_stop = RUNGS, HEADLINE, FULL_STOP_S
        engine_cache: dict = {}
        ladder = {}
        for name, path, slice_s in rungs:
            log(f"{name}: device slice ({slice_s}s sim)")
            d_wall, d_pkts, _, d_stamp = run_device(
                path, slice_s, engine_cache)
            log(f"  device: {d_pkts} pkts in {d_wall:.2f}s "
                f"({d_pkts / d_wall:,.0f}/s)")
            log(f"{name}: cpu thread slice ({slice_s}s sim)")
            c_wall, c_pkts, _ = run_cpu_thread(path, slice_s)
            log(f"  cpu: {c_pkts} pkts in {c_wall:.2f}s "
                f"({c_pkts / c_wall:,.0f}/s)")
            if d_pkts != c_pkts:
                # identical config+seed must route identical traffic;
                # a mismatch means the engines diverged — not a number
                # worth publishing
                raise RuntimeError(
                    f"{name}: device routed {d_pkts} packets but cpu "
                    f"routed {c_pkts} on the same config/seed")
            if d_pkts == 0 or c_pkts == 0:
                # a zero-packet rung makes the throughput ratio 0/0
                # (BENCH_r05's "float division by zero"): the tgen
                # clients start at 2s sim, so any slice that stops at
                # or before that measures boot, not routing — fail
                # with the config's arithmetic, never a bare ZeroDiv
                raise RuntimeError(
                    f"{name}: 0 packets routed on the {slice_s}s sim "
                    f"slice (device={d_pkts}, cpu={c_pkts}) — tgen "
                    "clients start at 2s sim, so the slice must stop "
                    "well past their start_time to carry traffic; "
                    "lengthen the slice or fix the config")
            ratio = (d_pkts / d_wall) / (c_pkts / c_wall)
            ladder[name] = {
                "slice_sim_s": slice_s,
                "device_pkts_per_s": round(d_pkts / d_wall, 1),
                "cpu_thread_pkts_per_s": round(c_pkts / c_wall, 1),
                "speedup": round(ratio, 2),
                # cold-start attribution (compile split from first
                # dispatch; cache_hit marks a warm start) — every
                # BENCH record carries it from now on, as does the
                # strategy-plan provenance (None = default knobs)
                **{k: d_stamp.get(k) for k in
                   ("compile_s", "first_dispatch_s", "cache_hit",
                    "plan", "admission", "degrades")},
            }
            log(f"  speedup vs thread policy: {ratio:.2f}x")

        log(f"{headline}: device full run ({full_stop}s sim, "
            "2.5s-sim dispatch segments)")
        headline_path = dict((n, p) for n, p, _ in rungs)[headline]
        f_wall, f_pkts, f_sim, f_stamp = run_device(
            headline_path, full_stop, engine_cache, segment_s=2.5)
        sim_per_wall = f_sim / f_wall
        log(f"  full: {f_pkts} pkts in {f_wall:.2f}s "
            f"({f_pkts / f_wall:,.0f}/s; {sim_per_wall:.2f} "
            "sim-s/wall-s)")

        result["value"] = round(f_pkts / f_wall / n_chips, 1)
        result["vs_baseline"] = ladder[headline]["speedup"]
        result["sim_s_per_wall_s"] = round(sim_per_wall, 3)
        result["n_chips"] = n_chips
        # headline cold-start attribution: compile_s / cache_hit let
        # the perf trajectory track warm starts (a repeat bench with
        # a populated cache must show cache_hit true and compile_s
        # collapsed)
        result["compile_s"] = f_stamp.get("compile_s")
        result["first_dispatch_s"] = f_stamp.get("first_dispatch_s")
        result["cache_hit"] = f_stamp.get("cache_hit")
        result["compile_cache"] = f_stamp.get("compile_cache")
        # strategy-plan provenance for the headline run (None =
        # default knobs; a fingerprint-mismatched PLAN stamps its
        # refusal as plan_error instead)
        result["plan"] = f_stamp.get("plan")
        if f_stamp.get("plan_error"):
            result["plan_error"] = f_stamp["plan_error"]
        # where the full run's wall went (flight recorder, default
        # summary mode): host/judge/dispatch/exchange/checkpoint/
        # retry/compile/plan walls + the dominant phase
        result["phase_walls"] = f_stamp.get("phase_walls")
        result["dominant_phase"] = f_stamp.get("dominant_phase")
        result["pipeline"] = f_stamp.get("pipeline")
        # preflight admission verdict + modeled footprint for the
        # headline run (and the degrade-rung count if it absorbed a
        # runtime OOM) — same comparability rule as the plan stamp
        result["admission"] = f_stamp.get("admission")
        if f_stamp.get("degrades"):
            result["degrades"] = f_stamp["degrades"]
        result["ladder"] = ladder

        if headline_path in _occ_records:
            # the full run's measured occupancy high-water marks —
            # scripts/tune_10k.py prunes its sweep grid from this
            # record, and capacity_plan: <path> replays it
            from shadow_tpu.device import capacity
            occ_path, occ = _occ_records[headline_path]
            try:
                # atomic tmp+os.replace (utils/artifacts.py): a bench
                # killed mid-write must not leave truncated JSON that
                # a later capacity_plan: <path> run chokes on
                capacity.save_record(occ, occ_path)
                result["occupancy_record"] = occ_path
                log(f"occupancy record -> {occ_path}")
            except OSError as e:
                log(f"could not write occupancy record: {e}")

        log(f"multichip rung: {n_chips} chip(s), exchange auto + "
            "occupancy plan")
        try:
            result["multichip"] = run_multichip_rung(n_chips)
            log(f"  multichip: {result['multichip']}")
            if "error" in result["multichip"]:
                rc = 1
        except Exception as e:          # noqa: BLE001
            result["multichip"] = {"error": str(e)}
            log(f"  multichip rung failed: {e}")
            rc = 1

        log(f"pipelined rung: {headline} at pipeline_depth "
            f"{PIPELINE_DEPTHS} (supervised posture, warm)")
        try:
            result["pipelined"] = run_pipelined_rung(
                headline, headline_path, full_stop)
            log(f"  pipelined: {result['pipelined']}")
            if "error" in result["pipelined"]:
                rc = 1
        except Exception as e:          # noqa: BLE001
            result["pipelined"] = {"error": str(e)}
            log(f"  pipelined rung failed: {e}")
            rc = 1

        log(f"ensemble rung: {ENSEMBLE_REPLICAS}-replica seed sweep "
            f"of {ENSEMBLE_CONFIG} ({ENSEMBLE_STOP_S}s sim, cold "
            "walls)")
        try:
            result["ensemble"] = run_ensemble_rung()
            log(f"  ensemble: {result['ensemble']}")
            if "error" in result["ensemble"]:
                rc = 1
        except Exception as e:          # noqa: BLE001
            result["ensemble"] = {"error": str(e)}
            log(f"  ensemble rung failed: {e}")
            rc = 1

        log("boot rung: columnar host-plane build + init_state "
            "ladder (docs/host_plane.md)")
        try:
            result["boot"] = run_boot_rung()
            if "error" in result["boot"]:
                log(f"  boot rung: {result['boot']['error']}")
                rc = 1
        except Exception as e:          # noqa: BLE001
            result["boot"] = {"error": str(e)}
            log(f"  boot rung failed: {e}")
            rc = 1

        log("topology rung: hierarchical vs dense table build "
            "(host-side, docs/topology.md)")
        try:
            result["topology"] = run_topology_rung()
            if "error" in result["topology"]:
                log(f"  topology rung: {result['topology']['error']}")
                rc = 1
        except Exception as e:          # noqa: BLE001
            result["topology"] = {"error": str(e)}
            log(f"  topology rung failed: {e}")
            rc = 1

        if not os.environ.get("BENCH_SMOKE"):
            log(f"hybrid sweep: pairs in {HYBRID_SWEEP} (adaptive "
                "judge vs cpu thread)")
            try:
                result["hybrid"] = run_hybrid_sweep()
                log(f"  hybrid: {result['hybrid']}")
                if any("error" in r
                       for r in result["hybrid"].get("rungs", ())):
                    rc = 1
            except Exception as e:          # noqa: BLE001
                result["hybrid"] = {"error": str(e)}
                log(f"  hybrid sweep failed: {e}")
                rc = 1
    except Exception as e:              # noqa: BLE001
        result["error"] = str(e)
        log(f"FAILED: {e}")
        rc = 1
    print(json.dumps(result), flush=True)
    return rc


if __name__ == "__main__":
    # drop known-noise XLA warning lines at the fd so the stderr tail
    # holds meaningful lines only
    from shadow_tpu.utils.stderrfilter import install_fd_filter

    install_fd_filter()
    sys.exit(main())
