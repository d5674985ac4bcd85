#!/usr/bin/env python
"""Determinism CI gate (ref src/test/determinism/ +
determinism1_compare.cmake): run the same config twice and
byte-compare every host's outputs.

Two layers of comparison, mirroring the reference's diff loop:
  1. per-host trace checksums + packet counters from the engine;
  2. every file under each host's data directory (managed-process
     stdout/stderr), byte for byte.

Exit 0 = bit-identical; 1 = divergence (the reproducibility bar the
reference enforces in CI).

Usage: python scripts/determinism_gate.py [config.yaml] [--policy P]
Defaults to examples/minimal.yaml with the serial policy.

`--policy` also takes a comma list ("serial,thread,tpu"): the gate
then runs the config once per policy and additionally requires every
policy's per-host signature to be bit-identical to the first's — the
cross-policy determinism matrix (the fault-injection CI rung pins
serial/thread/tpu on examples/tgen_faults.yaml this way). A tpu
entry may pin the exchange variant with a ":" suffix
("tpu:all_to_all,tpu:all_gather,tpu:two_phase,tpu:auto") — the
forced-multichip CI rung runs this matrix under
XLA_FLAGS=--xla_force_host_platform_device_count=4, pinning every
cross-shard exchange schedule bit-identical to the serial oracle;
"tpu:auto" turns on capacity_plan: auto so the choice resolves from
a measured occ_x record.

`--preempt` switches to the PREEMPTION gate (device/supervise.py):
run the config uninterrupted (tpu policy), then run it supervised in
a subprocess (periodic validated checkpoints + state audit), SIGTERM
it as soon as the first rotating checkpoint lands, require the
distinct preemption rc (75, EX_TEMPFAIL), resume from the rotation
base, and require the resumed trace to bit-match the uninterrupted
run. Combine with `--ensemble` to preempt a campaign mid-flight
instead (the resumed replica stack must bit-match the uninterrupted
campaign's).

`--compile-cache` switches to the WARM-START gate (the persistent
AOT compile cache, device/aotcache.py): run the config (tpu policy)
three times against one shared cache directory — cold (must miss and
store), warm (must HIT, skipping the compile), and with every cache
entry deliberately corrupted (must degrade to a loud recompile) —
and require all three runs bit-identical. This pins the cache
correctness contract: a cache hit is bit-identical to a fresh
compile, and a bad entry recompiles, never loads a wrong trace. On
backends without executable serialization the bit-identity legs
still run (stamped unsupported; the hit/miss pattern is waived).

`--telemetry` switches to the FLIGHT-RECORDER gate (shadow_tpu/obs):
run the config (tpu policy) under telemetry off / summary / trace
and require bit-identical per-host signatures — tracing must never
perturb the simulation. The trace run must leave a Perfetto-loadable
TRACE_*.trace.json, the streamed TRACE_*.jsonl span log, and a
METRICS_*.json whose per-phase walls sum to within 10% of the
recorded total; $TELEMETRY_TRACE_OUT receives a copy of the
.trace.json for CI artifact upload.

`--tuned` switches to the STRATEGY-AUTOTUNER gate (shadow_tpu/tune/):
a real mini-tune writes a PLAN record through the full
produce-persist-adopt pipeline; the adopted run and a COMPOSED
adversarial plan (every applicable knob at its most aggressive
candidate at once, reshaping ones included) must both bit-match the
default-knob run — a tuned plan changes wall time only, and the
composition of individually-pinned knobs stays pinned.

`--chaos` switches to the ELASTIC MESH-SHRINK gate (device/chaos.py
+ failover: shrink): on a forced >= 4-device mesh, a scripted device
loss (deterministic chaos injector) kills mesh device 1 at the 2nd
dispatch issue; retries exhaust, the run re-shards the last
validated state onto the 3 survivors and continues on-device under
the state-audit word. The shrunk run must bit-match BOTH the serial
oracle and an uninterrupted 3-shard run, for a standalone run AND
an ensemble campaign (`--chaos-ensemble` names the campaign
config); a post-shrink rotating checkpoint must stamp the shrunken
geometry and resume bit-identically on the full pool; and a
scripted corrupted-rotation-entry schedule must engage the
newest-readable fallback.

`--degrade` switches to the ADMISSION + DEGRADATION-LADDER gate
(device/capacity.py admission + device/supervise.py oom ladder): a
run must never OOM blind. `admission: strict` under a deliberately
tiny `device_memory_budget` must refuse with the readable "needs X,
budget Y on N devices" diagnostic before ANY compile; a scripted
RESOURCE_EXHAUSTED (chaos oom) at the 0th program compile (cold AOT
cache, so the compile really runs) and at the 2nd dispatch issue
must each walk the degradation ladder —
degrade >= 1, the retry budget NOT exhausted — and finish
bit-identical to the serial oracle; and `--chaos-ensemble`'s
campaign run in sequential replica batches
(ensemble.replica_batch=2) must bit-match the full-vmap campaign
and a standalone run of replica 0 (needs >= 4 devices).

`--ensemble` switches to the CAMPAIGN gate (shadow_tpu/ensemble/):
the config must carry an `ensemble:` block. The gate runs the
campaign twice (run-to-run bit-identity over every replica), then
extracts replica `--replica` (default 0) and requires its per-host
signature to bit-match a STANDALONE run with that replica's
parameters under each `--policy` entry (default serial,tpu) — the
replica-i == standalone-i contract the ensemble engine guarantees.
Standalone runs pin experimental.runahead to the campaign's shared
lookahead (the min over all replicas' tables), since the window
sequence is part of the trace.
"""

from __future__ import annotations

import argparse
import filecmp
import glob
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_once(config: str, policy: str, data_dir: str):
    """One gated run. `policy` may carry an exchange-variant suffix
    for the device engine — "tpu:two_phase", "tpu:all_gather",
    "tpu:auto", ... — the forced-multichip CI rung pins every
    exchange schedule bit-identical to the serial oracle this way.
    "tpu:auto" additionally turns on capacity_plan: auto so the
    choice actually resolves from a measured occ_x record."""
    from shadow_tpu.config import load_config
    from shadow_tpu.core.controller import Controller

    policy, _, exchange = policy.partition(":")
    cfg = load_config(config)
    cfg.experimental.scheduler_policy = policy
    if exchange:
        if policy != "tpu":
            print(f"FAIL: exchange suffix {exchange!r} only applies "
                  "to the tpu policy")
            sys.exit(1)
        # the suffix lands after load_config's schema validation, so
        # re-check it here — a typo must FAIL cleanly, not surface as
        # a deep engine traceback after the build work
        valid = ("all_to_all", "all_gather", "two_phase", "auto")
        if exchange not in valid:
            print(f"FAIL: exchange suffix {exchange!r} is not one of "
                  f"{list(valid)}")
            sys.exit(1)
        cfg.experimental.exchange = exchange
        if exchange == "auto" and \
                cfg.experimental.capacity_plan == "static":
            cfg.experimental.capacity_plan = "auto"
    cfg.general.data_directory = data_dir
    c = Controller(cfg)
    stats = c.run()
    if not stats.ok:
        print(f"FAIL: run reported not-ok ({policy})")
        sys.exit(1)
    sig = [(h.name, h.trace_checksum, h.events_executed,
            h.packets_sent, h.packets_dropped, h.packets_delivered)
           for h in c.sim.hosts]
    return sig, stats


def compare_trees(a: str, b: str) -> list[str]:
    """Byte-compare every file under both trees; return differences."""
    diffs = []
    for root, _, files in os.walk(a):
        rel = os.path.relpath(root, a)
        for f in files:
            fa = os.path.join(root, f)
            fb = os.path.join(b, rel, f)
            if not os.path.exists(fb):
                diffs.append(f"only in run 1: {os.path.join(rel, f)}")
            elif not filecmp.cmp(fa, fb, shallow=False):
                diffs.append(f"differs: {os.path.join(rel, f)}")
    for root, _, files in os.walk(b):
        rel = os.path.relpath(root, b)
        for f in files:
            if not os.path.exists(os.path.join(a, rel, f)):
                diffs.append(f"only in run 2: {os.path.join(rel, f)}")
    return diffs


def run_ensemble_gate(config: str, policies: list[str],
                      replica: int) -> int:
    """Campaign determinism gate: run-to-run bit-identity of the whole
    ensemble, plus replica-`replica` == standalone bit-identity under
    each policy."""
    import numpy as np

    from shadow_tpu.config import load_config
    from shadow_tpu.core.controller import Controller

    cfg0 = load_config(config)
    if cfg0.ensemble is None:
        print(f"FAIL: {config} has no ensemble: block "
              "(--ensemble needs a campaign config)")
        return 1
    R = cfg0.ensemble.replicas
    if not (0 <= replica < R):
        print(f"FAIL: --replica {replica} out of range (campaign has "
              f"{R} replicas)")
        return 1

    def run_campaign(data_dir: str):
        cfg = load_config(config)
        cfg.general.data_directory = data_dir
        # keep the campaign record out of the repo's artifacts/ (two
        # gate runs would also race onto one fingerprint-derived path)
        cfg.ensemble.record_path = os.path.join(data_dir,
                                                "ENSEMBLE.json")
        c = Controller(cfg)
        stats = c.run()
        if not stats.ok:
            print("FAIL: campaign run reported not-ok")
            sys.exit(1)
        return c, c.runner.final_state

    with tempfile.TemporaryDirectory() as tmp:
        c1, f1 = run_campaign(os.path.join(tmp, "e1", "shadow.data"))
        c2, f2 = run_campaign(os.path.join(tmp, "e2", "shadow.data"))
        rc = 0
        H = len(c1.sim.hosts)
        for key in ("chk", "n_exec", "n_sent", "n_drop", "n_deliv"):
            if not np.array_equal(np.asarray(f1[key]),
                                  np.asarray(f2[key])):
                rc = 1
                print(f"DETERMINISM FAILURE: campaign {key} differs "
                      "between two identical runs")
        desc = c1.runner.worlds.descriptors[replica]
        if desc["latency_scale"] != 1.0 or \
                desc["packet_loss_delta"] != 0.0:
            print(f"FAIL: replica {replica} varies "
                  "latency_scale/packet_loss_delta, which no "
                  "standalone config can reproduce — gate a replica "
                  "with the base tables (typically replica 0)")
            return 1
        ens_la = c1.runner.lookahead
        names = [h.name for h in c1.sim.hosts]
        sig_e = [(names[i], int(f1["chk"][replica, i]),
                  int(f1["n_exec"][replica, i]),
                  int(f1["n_sent"][replica, i]),
                  int(f1["n_drop"][replica, i]),
                  int(f1["n_deliv"][replica, i]))
                 for i in range(H)]
        for policy in policies:
            cfg = load_config(config)
            scheds = cfg.ensemble.fault_schedules
            sched = desc["fault_schedule"]
            cfg.ensemble = None
            cfg.experimental.scheduler_policy = policy
            cfg.experimental.runahead = ens_la
            cfg.general.seed = desc["seed"]
            if sched == "none":
                cfg.network.faults = []
            elif sched != "base":
                cfg.network.faults = list(scheds[sched])
            cfg.general.data_directory = os.path.join(
                tmp, f"alone_{policy}", "shadow.data")
            c = Controller(cfg)
            stats = c.run()
            if not stats.ok:
                print(f"FAIL: standalone {policy} run reported "
                      "not-ok")
                return 1
            sig_a = [(h.name, h.trace_checksum, h.events_executed,
                      h.packets_sent, h.packets_dropped,
                      h.packets_delivered) for h in c.sim.hosts]
            if sig_a != sig_e:
                rc = 1
                print(f"DETERMINISM FAILURE: campaign replica "
                      f"{replica} diverges from the standalone "
                      f"{policy} run with its parameters ({desc})")
                for a, b in zip(sig_e, sig_a):
                    if a != b:
                        print(f"  {a[0]}: ensemble {a[1:]} != "
                              f"standalone {b[1:]}")
        if rc == 0:
            print(f"ensemble determinism OK: {config} ({R} replicas "
                  f"bit-identical across 2 campaign runs; replica "
                  f"{replica} {desc} bit-matches standalone "
                  f"{','.join(policies)})")
        return rc


def _preempt_child(config: str, base: str, every_ns: int,
                   data_dir: str, ensemble: bool):
    """Launch the supervised run as a child CLI process (the gate
    needs a real SIGTERM against a real process, not an in-process
    flag), SIGTERM it once the first rotating checkpoint exists, and
    return its exit code."""
    import signal
    import subprocess
    import time

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    overrides = [
        "-o", f"experimental.checkpoint_save={base}",
        "-o", f"experimental.checkpoint_every={every_ns}ns",
        "-o", "experimental.state_audit=true",
        "-o", f"general.data_directory={data_dir}",
    ]
    if not ensemble:
        overrides += ["-o", "experimental.scheduler_policy=tpu"]
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, "-c",
         "import sys; from shadow_tpu.cli import main; "
         "sys.exit(main(sys.argv[1:]))", config] + overrides,
        env=env, cwd=repo)
    import glob as _glob
    deadline = time.monotonic() + 900
    signaled = False
    while proc.poll() is None and time.monotonic() < deadline:
        if not signaled and _glob.glob(_glob.escape(base) + ".t*"):
            proc.send_signal(signal.SIGTERM)
            signaled = True
        time.sleep(0.05)
    if proc.poll() is None:
        proc.kill()
        proc.wait()
        print("FAIL: supervised run hung past the gate deadline")
        return -1
    if not signaled:
        print("FAIL: the run finished before the first rotating "
              "checkpoint appeared — shrink checkpoint_every or grow "
              "stop_time so the gate can preempt mid-flight")
        return -1
    return proc.returncode


def run_preempt_gate(config: str, ensemble: bool) -> int:
    """SIGTERM mid-run -> resume must bit-match the uninterrupted
    run, and the preempted process must exit with the distinct
    preemption rc."""
    import numpy as np

    from shadow_tpu.config import load_config
    from shadow_tpu.core.controller import Controller
    from shadow_tpu.device.supervise import EXIT_PREEMPTED

    cfg0 = load_config(config)
    if ensemble and cfg0.ensemble is None:
        print(f"FAIL: {config} has no ensemble: block")
        return 1
    every_ns = max(1, cfg0.general.stop_time // 8)

    def run_full(data_dir: str, extra=None):
        # the policy override must ride load_config's override list:
        # schema validation (checkpoint knobs require the tpu policy)
        # runs during parsing, before any post-hoc attribute edit
        extra = list(extra or [])
        if not ensemble:
            extra.append("experimental.scheduler_policy=tpu")
        cfg = load_config(config, overrides=extra)
        cfg.general.data_directory = data_dir
        if ensemble:
            cfg.ensemble.record_path = os.path.join(data_dir,
                                                    "ENSEMBLE.json")
        c = Controller(cfg)
        stats = c.run()
        if not stats.ok:
            print("FAIL: run reported not-ok")
            sys.exit(1)
        if ensemble:
            f = c.runner.final_state
            return {k: np.asarray(f[k])
                    for k in ("chk", "n_exec", "n_sent", "n_drop",
                              "n_deliv")}
        return [(h.name, h.trace_checksum, h.events_executed,
                 h.packets_sent, h.packets_dropped,
                 h.packets_delivered) for h in c.sim.hosts]

    with tempfile.TemporaryDirectory() as tmp:
        sig_full = run_full(os.path.join(tmp, "full", "shadow.data"))
        base = os.path.join(tmp, "ck.npz")
        rc = _preempt_child(config, base, every_ns,
                            os.path.join(tmp, "pre", "shadow.data"),
                            ensemble)
        if rc != EXIT_PREEMPTED:
            print(f"FAIL: preempted run exited rc {rc}, expected "
                  f"the distinct preemption rc {EXIT_PREEMPTED}")
            return 1
        sig_res = run_full(
            os.path.join(tmp, "res", "shadow.data"),
            extra=[f"experimental.checkpoint_load={base}"])
        if ensemble:
            bad = [k for k in sig_full
                   if not np.array_equal(sig_full[k], sig_res[k])]
            if bad:
                print(f"DETERMINISM FAILURE: resumed campaign {bad} "
                      "diverge from the uninterrupted campaign")
                return 1
        elif sig_res != sig_full:
            print("DETERMINISM FAILURE: resumed run diverges from "
                  "the uninterrupted run")
            for a, b in zip(sig_full, sig_res):
                if a != b:
                    print(f"  {a[0]}: {a[1:]} != {b[1:]}")
            return 1
        kind = "ensemble campaign" if ensemble else "standalone tpu"
        print(f"preemption OK: {config} ({kind}: SIGTERM mid-run -> "
              f"rc {EXIT_PREEMPTED}, resume from the checkpoint "
              "rotation bit-matches the uninterrupted run)")
        return 0


def run_compile_cache_gate(config: str) -> int:
    """Warm-start gate (device/aotcache.py): cold run populates the
    cache, warm run must HIT and bit-match, a deliberately corrupted
    cache must degrade to a recompile that still bit-matches."""
    import glob as _glob

    from shadow_tpu.config import load_config
    from shadow_tpu.core.controller import Controller
    from shadow_tpu.device.aotcache import ENTRY_SUFFIX

    with tempfile.TemporaryDirectory() as tmp:
        cache_dir = os.path.join(tmp, "aot")

        def once(tag: str):
            cfg = load_config(config)
            cfg.experimental.scheduler_policy = "tpu"
            cfg.experimental.compile_cache = cache_dir
            cfg.general.data_directory = os.path.join(
                tmp, tag, "shadow.data")
            c = Controller(cfg)
            stats = c.run()
            if not stats.ok:
                print(f"FAIL: {tag} run reported not-ok")
                sys.exit(1)
            sig = [(h.name, h.trace_checksum, h.events_executed,
                    h.packets_sent, h.packets_dropped,
                    h.packets_delivered) for h in c.sim.hosts]
            return sig, (stats.compile_cache or {})

        sig_cold, rep_cold = once("cold")
        unsupported = rep_cold.get("unsupported", False)
        if not unsupported and not rep_cold.get("misses"):
            print("FAIL: cold run against an empty cache directory "
                  f"reported no compile miss ({rep_cold})")
            return 1

        sig_warm, rep_warm = once("warm")
        rc = 0
        if sig_warm != sig_cold:
            rc = 1
            print("DETERMINISM FAILURE: cache-hit run diverges from "
                  "the fresh-compile run")
            for a, b in zip(sig_cold, sig_warm):
                if a != b:
                    print(f"  {a[0]}: cold {a[1:]} != warm {b[1:]}")
        if not unsupported:
            if not rep_warm.get("hits") or rep_warm.get("misses"):
                rc = 1
                print("FAIL: warm run did not hit the populated "
                      f"cache (hits={rep_warm.get('hits')}, "
                      f"misses={rep_warm.get('misses')})")
            if rep_warm.get("compile_s", 0) != 0:
                rc = 1
                print("FAIL: warm run still paid "
                      f"{rep_warm['compile_s']}s of compile")

        # corrupt every entry mid-payload: the next run must warn,
        # recompile, and stay bit-identical — degradation is always
        # to a fresh compile, never to a wrong trace
        entries = _glob.glob(os.path.join(
            cache_dir, "*" + ENTRY_SUFFIX))
        if not unsupported and not entries:
            print("FAIL: no cache entries on disk after two runs")
            return 1
        for p in entries:
            size = os.path.getsize(p)
            with open(p, "r+b") as f:
                f.truncate(max(1, size // 3))
        sig_corrupt, rep_corrupt = once("corrupt")
        if sig_corrupt != sig_cold:
            rc = 1
            print("DETERMINISM FAILURE: the corrupted-cache run "
                  "diverges from the fresh-compile run")
        if not unsupported and rep_corrupt.get("hits"):
            rc = 1
            print("FAIL: a corrupted entry was reported as a cache "
                  "hit — the corruption check is not firing")

        if rc == 0:
            mode = ("bit-identity only; executable serialization "
                    "unsupported on this backend" if unsupported
                    else f"cold miss {rep_cold.get('compile_s')}s "
                         f"compile -> warm hit "
                         f"{rep_warm.get('load_s')}s load -> "
                         "corrupted entries recompiled")
            print(f"compile-cache OK: {config} (3 runs bit-identical"
                  f"; {mode})")
        return rc


def run_telemetry_gate(config: str) -> int:
    """Flight-recorder gate (shadow_tpu/obs): the same config under
    telemetry off / summary / trace (tpu policy) must produce
    bit-identical per-host signatures — tracing must never perturb
    the simulation. The trace run must additionally leave a
    Perfetto-loadable TRACE_*.trace.json, a streamed TRACE_*.jsonl,
    and a METRICS_*.json whose per-phase walls sum to within 10% of
    the recorded total. $TELEMETRY_TRACE_OUT (a file path) receives a
    copy of the .trace.json so CI can upload it as an artifact."""
    import glob as _glob
    import json
    import shutil

    from shadow_tpu.config import load_config
    from shadow_tpu.core.controller import Controller

    with tempfile.TemporaryDirectory() as tmp:
        sigs, summaries, tel_dirs = {}, {}, {}
        for mode in ("off", "summary", "trace"):
            cfg = load_config(config)
            cfg.experimental.scheduler_policy = "tpu"
            cfg.experimental.telemetry = mode
            tel_dirs[mode] = os.path.join(tmp, f"tel_{mode}")
            cfg.experimental.telemetry_path = tel_dirs[mode]
            cfg.general.data_directory = os.path.join(
                tmp, mode, "shadow.data")
            c = Controller(cfg)
            stats = c.run()
            if not stats.ok:
                print(f"FAIL: telemetry={mode} run reported not-ok")
                return 1
            sigs[mode] = [(h.name, h.trace_checksum,
                           h.events_executed, h.packets_sent,
                           h.packets_dropped, h.packets_delivered)
                          for h in c.sim.hosts]
            summaries[mode] = stats.telemetry
        rc = 0
        for mode in ("summary", "trace"):
            if sigs[mode] != sigs["off"]:
                rc = 1
                print(f"DETERMINISM FAILURE: telemetry={mode} "
                      "diverges from telemetry=off — tracing "
                      "perturbed the simulation")
                for a, b in zip(sigs["off"], sigs[mode]):
                    if a != b:
                        print(f"  {a[0]}: off {a[1:]} != {mode} "
                              f"{b[1:]}")
        if summaries["off"] is not None:
            rc = 1
            print("FAIL: telemetry=off still published a summary "
                  "(SimStats.telemetry must be None)")
        if not summaries["summary"] or \
                "phases" not in (summaries["summary"] or {}):
            rc = 1
            print("FAIL: telemetry=summary published no phase walls")
        traces = _glob.glob(os.path.join(tel_dirs["trace"],
                                         "TRACE_*.trace.json"))
        jsonls = _glob.glob(os.path.join(tel_dirs["trace"],
                                         "TRACE_*.jsonl"))
        metrics = _glob.glob(os.path.join(tel_dirs["trace"],
                                          "METRICS_*.json"))
        if not (traces and jsonls and metrics):
            print(f"FAIL: trace run left trace.json={traces} "
                  f"jsonl={jsonls} metrics={metrics} — expected all "
                  "three artifacts")
            return 1
        with open(traces[0]) as f:
            tr = json.load(f)
        if not tr.get("traceEvents"):
            rc = 1
            print(f"FAIL: {traces[0]} has no traceEvents — not a "
                  "loadable Chrome/Perfetto trace")
        with open(metrics[0]) as f:
            m = json.load(f)
        total = m.get("total_wall_s", 0.0)
        ssum = sum(m.get("phases", {}).values())
        if total <= 0 or abs(ssum - total) > 0.1 * total:
            rc = 1
            print(f"FAIL: METRICS phase walls sum to {ssum:.3f}s vs "
                  f"total {total:.3f}s — attribution is off by more "
                  "than 10%")
        out = os.environ.get("TELEMETRY_TRACE_OUT")
        if out:
            os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
            shutil.copyfile(traces[0], out)
            print(f"trace artifact copied -> {out}")
        if rc == 0:
            dom = m.get("dominant_phase")
            print(f"telemetry OK: {config} (off/summary/trace "
                  "bit-identical; trace run wrote "
                  f"{os.path.basename(traces[0])} + "
                  f"{os.path.basename(metrics[0])}, phase walls sum "
                  f"{ssum:.3f}s of {total:.3f}s total, dominant "
                  f"phase {dom})")
        return rc


def run_analyze_consistency_gate(config: str) -> int:
    """Static-analysis consistency gate (shadow_tpu/analyze): the
    collective registry Pass 1 audits against must match what the
    RUNTIME engine reports, so the static allowlist can never
    silently drift from the real program. Three cheap checks on the
    config's device engine:

    1. registry-vs-effective: ``engine.collective_registry()`` must
       pin exactly the exchange variant and capacities
       ``engine.effective{}`` resolved (mover primitive per variant,
       CAP/CAP2 buffer dims);
    2. the Pass-1 jaxpr audit of the built engine must come up clean
       (and, on a multi-shard mesh, must SEE the registered mover in
       the lowered program — registry says ppermute, program must
       contain ppermute);
    3. analyzer-perturbs-nothing: the config runs once, the audit
       traces every program in-process, the config runs again — both
       runs' per-host signatures must be bit-identical (the
       --telemetry-style spot check; the audit only lowers, never
       executes).
    """
    from shadow_tpu.analyze import jaxpr_audit
    from shadow_tpu.config import load_config
    from shadow_tpu.core.controller import Controller

    with tempfile.TemporaryDirectory() as tmp:
        os.environ.setdefault("SHADOW_TPU_OCC_DIR",
                              os.path.join(tmp, "occ"))
        cfg = load_config(config)
        cfg.experimental.scheduler_policy = "tpu"
        cfg.general.data_directory = os.path.join(
            tmp, "probe", "shadow.data")
        c = Controller(cfg)
        if c.runner is None or c.runner.engine is None:
            print("FAIL: config did not build a device engine "
                  "(--analyze-consistency needs a tpu-policy device "
                  "config)")
            return 1
        engine = c.runner.engine
        eff = engine.effective
        reg = engine.collective_registry()
        rc = 0

        # 1. registry <-> effective{}
        mover = jaxpr_audit.EXCHANGE_MOVER.get(eff["exchange"])
        if mover is None:
            print(f"FAIL: effective exchange {eff['exchange']!r} has "
                  "no registered mover mapping")
            rc = 1
        elif engine.n_shards > 1 and mover not in reg:
            print(f"FAIL: effective exchange {eff['exchange']!r} "
                  f"needs mover {mover!r} but the collective "
                  f"registry only pins {sorted(reg)}")
            rc = 1
        caps_want = {"all_to_all": (eff["CAP"],),
                     "two_phase": (eff["CAP"], eff["CAP2"])}
        want = caps_want.get(eff["exchange"])
        if engine.n_shards > 1 and want is not None:
            got = tuple(reg.get(mover, {}).get("caps") or ())
            if got != tuple(int(x) for x in want):
                print(f"FAIL: registry pins {mover} caps {got}, "
                      f"effective says {want}")
                rc = 1

        # 2. the static audit of the real engine (traces only)
        found = jaxpr_audit.audit_engine(engine, "gate")
        errors = [f for f in found if f.severity == "error"]
        for f in errors:
            print(f"FAIL: {f.format()}")
        rc = rc or (1 if errors else 0)

        # 3. bit-identity across an in-process audit: run, audit,
        # run again — the analyzer must perturb nothing
        d1 = os.path.join(tmp, "run1", "shadow.data")
        d2 = os.path.join(tmp, "run2", "shadow.data")
        sig1, stats1 = run_once(config, "tpu", d1)
        jaxpr_audit.audit_engine(engine, "gate-again")
        sig2, _ = run_once(config, "tpu", d2)
        if sig1 != sig2:
            rc = 1
            print("FAIL: per-host signatures differ across an "
                  "in-process jaxpr audit — the analyzer perturbed "
                  "the run")
            for a, b in zip(sig1, sig2):
                if a != b:
                    print(f"  {a[0]}: {a[1:]} != {b[1:]}")

        if rc == 0:
            print(f"analyze-consistency OK: {config} (exchange "
                  f"{eff['exchange']}, registry caps match "
                  f"CAP={eff['CAP']}/CAP2={eff['CAP2']}, engine "
                  f"audit clean, {stats1.events_executed} events "
                  "bit-identical across an in-process audit)")
        return rc


def run_tuned_gate(config: str) -> int:
    """Strategy-autotuner gate (shadow_tpu/tune/): a tuned plan must
    change WALL time only. Three legs against one config (tpu
    policy):

    1. a real mini-tune (tune/trials.py coordinate descent, small
       budget, quarter window) writes a PLAN record through
       tune/plan.py — the full produce-persist-adopt pipeline runs,
       and the record must carry the chosen knobs and the trial
       ledger;
    2. the adopted run (``strategy_plan: <plan>``) must bit-match
       the default-knob run and surface adoption provenance;
    3. a COMPOSED adversarial plan — every applicable knob moved to
       its most aggressive candidate at once, including the
       program-reshaping ones — must also bit-match: each knob is
       individually bit-identity-pinned, and this leg pins the
       composition the tuner relies on.
    """
    from shadow_tpu.config import load_config
    from shadow_tpu.core.controller import Controller, build
    from shadow_tpu.device.runner import device_twin
    from shadow_tpu.tune import plan as planmod
    from shadow_tpu.tune import space
    from shadow_tpu.tune.trials import Tuner

    cfg0 = load_config(config)
    stop = cfg0.general.stop_time
    sim = build(cfg0)
    twin = device_twin(sim)
    n_hosts = len(sim.hosts)
    del sim

    with tempfile.TemporaryDirectory() as tmp:
        os.environ.setdefault("SHADOW_TPU_OCC_DIR",
                              os.path.join(tmp, "occ"))

        def once(tag: str, strategy_plan: str):
            cfg = load_config(config)
            cfg.experimental.scheduler_policy = "tpu"
            cfg.experimental.strategy_plan = strategy_plan
            cfg.general.data_directory = os.path.join(
                tmp, tag, "shadow.data")
            c = Controller(cfg)
            stats = c.run()
            if not stats.ok:
                print(f"FAIL: {tag} run reported not-ok")
                sys.exit(1)
            sig = [(h.name, h.trace_checksum, h.events_executed,
                    h.packets_sent, h.packets_dropped,
                    h.packets_delivered) for h in c.sim.hosts]
            return sig, stats

        # leg 1: the real pipeline — tune, persist, reload
        tuner = Tuner(config, window_ns=max(1, stop // 4), budget=6)
        body = tuner.search("coordinate_descent")
        plan_file = os.path.join(tmp, "PLAN_gate.json")
        planmod.save_plan({
            "format": planmod.FORMAT,
            "workload": {**planmod.workload_stamp(twin, n_hosts),
                         "stop_time": int(stop),
                         "seed": int(cfg0.general.seed)},
            "source": "determinism_gate --tuned",
            **body,
        }, plan_file)
        rec = planmod.load_plan(plan_file)
        if "trials" not in rec or not rec["trials"]:
            print("FAIL: the PLAN record carries no trial ledger")
            return 1
        diverged = [t for t in rec["trials"]
                    if "diverged" in t.get("error", "")]
        if diverged:
            print(f"DETERMINISM FAILURE: {len(diverged)} trial(s) "
                  "diverged from the default-knob signature during "
                  "the mini-tune")
            return 1

        sig_def, _ = once("default", "off")
        sig_tuned, stats_tuned = once("tuned", plan_file)
        rc = 0
        if sig_tuned != sig_def:
            rc = 1
            print("DETERMINISM FAILURE: the tuned-plan run diverges "
                  "from the default-knob run")
            for a, b in zip(sig_def, sig_tuned):
                if a != b:
                    print(f"  {a[0]}: default {a[1:]} != tuned "
                          f"{b[1:]}")
        if stats_tuned.strategy_plan is None:
            rc = 1
            print("FAIL: the adopted run surfaced no strategy-plan "
                  "provenance (SimStats.strategy_plan is None)")

        # leg 3: the composed adversarial plan — every applicable
        # knob at its most aggressive candidate at once
        ctx = space.context(cfg0, n_shards=tuner.ctx["n_shards"])
        ctx["policy"] = "tpu"
        adversarial, adv_defaults = {}, {}
        for knob in space.applicable(cfg0, ctx):
            cur = space.current(cfg0, [knob])[knob.name]
            cands = [c for c in knob.candidates(cfg0, ctx)
                     if c != cur]
            if cands:
                adversarial[knob.name] = cands[-1]
                # the tuned-from baseline: without it, adoption's
                # hand-set check compares cadence knobs against the
                # SCHEMA default (0/None) and would spuriously skip
                # them on any config that enables supervision or
                # heartbeats
                adv_defaults[knob.name] = cur
        adv_file = os.path.join(tmp, "PLAN_adversarial.json")
        planmod.save_plan({
            "format": planmod.FORMAT,
            "workload": {**planmod.workload_stamp(twin, n_hosts),
                         "stop_time": int(stop),
                         "seed": int(cfg0.general.seed)},
            "default": adv_defaults,
            "knobs": adversarial,
            "source": "determinism_gate --tuned (composed)",
        }, adv_file)
        sig_adv, stats_adv = once("adversarial", adv_file)
        if sig_adv != sig_def:
            rc = 1
            print("DETERMINISM FAILURE: the composed adversarial "
                  f"plan {adversarial} diverges from the "
                  "default-knob run — a strategy-knob composition "
                  "changes the simulation")
            for a, b in zip(sig_def, sig_adv):
                if a != b:
                    print(f"  {a[0]}: default {a[1:]} != composed "
                          f"{b[1:]}")
        applied = (stats_adv.strategy_plan or {}).get("knobs", {})
        missing = sorted(set(adversarial) - set(applied))
        if missing:
            rc = 1
            print(f"FAIL: composed plan knobs {missing} were not "
                  f"applied (provenance: {stats_adv.strategy_plan})")
        if rc == 0:
            print(f"tuned-plan OK: {config} (mini-tune "
                  f"{rec['score']['trials']} trial(s) -> "
                  f"{rec['knobs']}; adopted run and composed "
                  f"adversarial plan {adversarial} both bit-match "
                  "the default-knob run)")
        return rc


def run_chaos_gate(config: str, ensemble_config: str) -> int:
    """Elastic mesh-shrink failover gate (device/chaos.py +
    failover: shrink): device loss must cost throughput, never the
    run — or the trace. Driven end to end by the deterministic chaos
    injector on a forced >= 4-device CPU mesh. Legs:

    1. oracle + uninterrupted M-shard: the serial oracle, then the
       tpu policy pinned to 3 shards (experimental.mesh_shards) —
       bit-identical, the baseline pair every shrink compares to;
    2. scripted device loss: a 4-shard run whose mesh device 1 dies
       at the 2nd dispatch issue (chaos device_loss), retries
       exhaust, the mesh shrinks 4 -> 3 and continues on-device
       under the state-audit word — the final signature must
       bit-match BOTH the serial oracle and the uninterrupted
       3-shard run, with >= 1 reshard reported and the engine left
       on 3 shards;
    3. post-shrink checkpoint resume: the shrink run writes rotating
       checkpoints; the newest entry must stamp the SHRUNKEN
       geometry (meta["geometry"].n_shards == 3), and resuming it on
       the full device pool must auto-adopt that geometry and
       bit-match the oracle;
    4. corrupted-rotation chaos: a supervised run whose LAST rotation
       entry is corrupted on disk by the schedule
       (chaos checkpoint_corrupt) — resolve_checkpoint must skip the
       decoy (newest-READABLE fallback) and the resume must
       bit-match;
    5. ensemble campaign shrink: the same 4 -> 3 device loss against
       `ensemble_config`'s campaign — every replica's counters and
       checksums must bit-match the uninterrupted 3-shard campaign
       (shrink keeps the vmapped replica axis intact; it is the one
       failover campaigns have).
    """
    import numpy as np

    from shadow_tpu._jax import jax
    from shadow_tpu.config import load_config
    from shadow_tpu.core.controller import Controller
    from shadow_tpu.device import checkpoint, supervise
    from shadow_tpu.device.chaos import ChaosEvent

    ndev = len(jax.devices())
    if ndev < 4:
        print(f"FAIL: --chaos needs >= 4 devices for the 4 -> 3 "
              f"shrink (run under XLA_FLAGS=--xla_force_host_"
              f"platform_device_count=4); found {ndev}")
        return 1
    cfg0 = load_config(config)
    stop = cfg0.general.stop_time
    seg_ns = max(1, stop // 8)

    def run_tpu(tag: str, tmp: str, shards: int, mutate=None,
                ensemble: bool = False, want_ok: bool = True):
        cfg = load_config(ensemble_config if ensemble else config)
        cfg.experimental.scheduler_policy = "tpu"
        cfg.experimental.mesh_shards = shards
        cfg.experimental.state_audit = True
        cfg.experimental.dispatch_segment = seg_ns
        cfg.general.data_directory = os.path.join(
            tmp, tag, "shadow.data")
        if ensemble:
            cfg.ensemble.record_path = os.path.join(
                tmp, tag, "ENSEMBLE.json")
            # the campaign config's own stop drives its segments
            cfg.experimental.dispatch_segment = max(
                1, cfg.general.stop_time // 8)
        if mutate:
            mutate(cfg)
        c = Controller(cfg)
        stats = c.run()
        if want_ok and not stats.ok:
            print(f"FAIL: {tag} run reported not-ok")
            sys.exit(1)
        if ensemble:
            f = c.runner.final_state
            sig = {k: np.asarray(f[k])
                   for k in ("chk", "n_exec", "n_sent", "n_drop",
                             "n_deliv")}
        else:
            sig = [(h.name, h.trace_checksum, h.events_executed,
                    h.packets_sent, h.packets_dropped,
                    h.packets_delivered) for h in c.sim.hosts]
        return sig, stats, c

    def loss_schedule(cfg):
        cfg.experimental.failover = "shrink"
        cfg.experimental.dispatch_retries = 1
        cfg.experimental.dispatch_retry_backoff = 0.0
        cfg.experimental.chaos = [
            ChaosEvent(kind="device_loss", segment=1, shard=1)]

    with tempfile.TemporaryDirectory() as tmp:
        os.environ.setdefault("SHADOW_TPU_OCC_DIR",
                              os.path.join(tmp, "occ"))
        rc = 0
        # leg 1: the baseline pair
        sig_oracle, stats_oracle = run_once(
            config, "serial", os.path.join(tmp, "oracle",
                                           "shadow.data"))
        sig_m, _, _ = run_tpu("alone3", tmp, shards=3)
        if sig_m != sig_oracle:
            print("DETERMINISM FAILURE: the uninterrupted 3-shard "
                  "run diverges from the serial oracle")
            return 1

        # leg 2 + 3: scripted device loss with rotating checkpoints
        base = os.path.join(tmp, "ck.npz")

        def shrink_mutate(cfg):
            loss_schedule(cfg)
            cfg.experimental.checkpoint_save = base
            cfg.experimental.checkpoint_every = seg_ns
            cfg.experimental.checkpoint_keep = 8

        sig_s, stats_s, c_s = run_tpu("shrink", tmp, shards=4,
                                      mutate=shrink_mutate)
        if sig_s != sig_oracle:
            rc = 1
            print("DETERMINISM FAILURE: the 4 -> 3 shrunk run "
                  "diverges from the serial oracle")
            for a, b in zip(sig_oracle, sig_s):
                if a != b:
                    print(f"  {a[0]}: oracle {a[1:]} != shrunk "
                          f"{b[1:]}")
        if stats_s.reshards < 1:
            rc = 1
            print(f"FAIL: the shrink run reported "
                  f"{stats_s.reshards} reshards — the scripted "
                  "device loss did not trigger a mesh shrink")
        if c_s.runner.engine.n_shards != 3:
            rc = 1
            print(f"FAIL: the shrink run finished on "
                  f"{c_s.runner.engine.n_shards} shard(s), "
                  "expected 3")

        entries = supervise.rotation_entries(base)
        post = [(t, p) for t, p in entries if t < stop]
        if not post:
            print("FAIL: the shrink run left no rotation entry "
                  "before stop — nothing to resume")
            return 1
        last_t, last_p = post[-1]
        geom = checkpoint.peek_geometry(checkpoint.peek_meta(last_p))
        if geom.get("n_shards") != 3:
            rc = 1
            print(f"FAIL: the post-shrink rotation entry {last_p} "
                  f"stamps geometry {geom}, expected n_shards=3")

        def resume_mutate(cfg):
            cfg.experimental.checkpoint_load = last_p

        # shards=0: the full pool — the runner must ADOPT the saved
        # shrunken geometry from the stamp
        sig_r, _, c_r = run_tpu("resume", tmp, shards=0,
                                mutate=resume_mutate)
        if sig_r != sig_oracle:
            rc = 1
            print("DETERMINISM FAILURE: the post-shrink checkpoint "
                  "resumed on the full pool diverges from the "
                  "oracle")
        if c_r.runner.engine.n_shards != 3:
            rc = 1
            print(f"FAIL: the resume rebuilt "
                  f"{c_r.runner.engine.n_shards} shard(s) — the "
                  "saved shrunken geometry was not adopted")

        # leg 4: corrupted-rotation chaos -> newest-readable fallback
        base2 = os.path.join(tmp, "ck2.npz")
        n_saves = (stop - 1) // seg_ns     # rotation saves at t<stop

        def corrupt_mutate(cfg):
            cfg.experimental.checkpoint_save = base2
            cfg.experimental.checkpoint_every = seg_ns
            cfg.experimental.checkpoint_keep = 8
            cfg.experimental.chaos = [
                ChaosEvent(kind="checkpoint_corrupt",
                           entry=n_saves - 1)]

        run_tpu("corrupt", tmp, shards=4, mutate=corrupt_mutate)
        # drop the end-of-run base save (simulating the crash the
        # rotation exists for) so resolution exercises the rotation
        os.unlink(base2)
        newest = supervise.rotation_entries(base2)[-1][1]
        resolved = supervise.resolve_checkpoint(base2)
        if resolved == newest:
            rc = 1
            print(f"FAIL: resolve_checkpoint returned the corrupted "
                  f"newest entry {newest} — the newest-readable "
                  "fallback did not engage")

        def resume2_mutate(cfg):
            cfg.experimental.checkpoint_load = base2

        sig_r2, _, _ = run_tpu("resume2", tmp, shards=4,
                               mutate=resume2_mutate)
        if sig_r2 != sig_oracle:
            rc = 1
            print("DETERMINISM FAILURE: the resume past the "
                  "corrupted rotation entry diverges from the "
                  "oracle")

        # leg 5: the ensemble campaign survives the same device loss
        ens_ref, _, _ = run_tpu("ens3", tmp, shards=3, ensemble=True)
        ens_s, ens_stats, ens_c = run_tpu(
            "ens_shrink", tmp, shards=4, mutate=loss_schedule,
            ensemble=True)
        bad = [k for k in ens_ref
               if not np.array_equal(ens_ref[k], ens_s[k])]
        if bad:
            rc = 1
            print(f"DETERMINISM FAILURE: the shrunk campaign's {bad} "
                  "diverge from the uninterrupted 3-shard campaign")
        if ens_stats.reshards < 1 or \
                ens_c.runner.engine.n_shards != 3:
            rc = 1
            print(f"FAIL: campaign shrink reported "
                  f"{ens_stats.reshards} reshards on "
                  f"{ens_c.runner.engine.n_shards} final shard(s) — "
                  "expected >= 1 on 3")

        if rc == 0:
            print(f"chaos OK: {config} (scripted 4 -> 3 device loss "
                  f"bit-matches the serial oracle "
                  f"[{stats_oracle.events_executed} events] and the "
                  "uninterrupted 3-shard run, standalone AND "
                  f"ensemble [{ensemble_config}]; post-shrink "
                  "checkpoint stamps n_shards=3 and resumes "
                  "bit-identically on the full pool; the corrupted "
                  "rotation entry fell back to the newest readable "
                  "one; audit word clean throughout)")
        return rc


def run_degrade_gate(config: str, ensemble_config: str) -> int:
    """Preflight-admission + degradation-ladder gate
    (device/capacity.py admission + device/supervise.py recover_oom):
    a run must never OOM blind — over-budget estimates are refused or
    degraded BEFORE any compile, and real allocator failures walk a
    bit-identical degradation ladder instead of burning the retry
    budget. Driven end to end by the deterministic chaos injector's
    oom seam on a forced >= 4-device CPU mesh. Legs:

    1. oracle: the serial run every degraded run compares to;
    2. strict refusal: ``admission: strict`` under a deliberately
       tiny ``device_memory_budget`` must raise the readable
       "needs X, budget Y on N devices" diagnostic before ANY
       compile — the leg's private cold AOT cache directory must
       stay empty;
    3. compile-seam oom: a scripted RESOURCE_EXHAUSTED at the 0th
       program compile (chaos oom against a COLD cache, so the
       compile actually runs — a warm hit compiles nothing and the
       seam never fires) repeats until the ladder engages a rung;
       the finished run must bit-match the oracle with degrade >= 1
       and the retry budget unexhausted;
    4. dispatch-seam oom: the same scripted oom at the 2nd dispatch
       issue — the FIRST failure charges
       one normal retry, the second consecutive identical one routes
       to the ladder (deterministic OOMs must never exhaust
       dispatch_retries), and the run bit-matches the oracle;
    5. replica batches: `ensemble_config`'s campaign run with
       ``ensemble.replica_batch: 2`` (sequential halves of the
       replica axis, each its own engine) must bit-match the
       full-vmap campaign over every replica's counters and
       checksums, stamp the admission verdict + batch split, and
       replica 0 must still bit-match a standalone serial run with
       its parameters (the batch never weakens the replica-i ==
       standalone-i contract).
    """
    import numpy as np

    from shadow_tpu._jax import jax
    from shadow_tpu.config import load_config
    from shadow_tpu.core.controller import Controller
    from shadow_tpu.device.chaos import OOM_ERROR, ChaosEvent

    ndev = len(jax.devices())
    if ndev < 4:
        print(f"FAIL: --degrade needs >= 4 devices for the forced "
              f"CPU mesh (run under XLA_FLAGS=--xla_force_host_"
              f"platform_device_count=4); found {ndev}")
        return 1
    cfg0 = load_config(config)
    stop = cfg0.general.stop_time
    seg_ns = max(1, stop // 8)

    def run_tpu(tag: str, tmp: str, mutate=None):
        cfg = load_config(config)
        cfg.experimental.scheduler_policy = "tpu"
        cfg.experimental.state_audit = True
        cfg.experimental.dispatch_segment = seg_ns
        cfg.experimental.compile_cache = os.path.join(tmp, "aot")
        cfg.general.data_directory = os.path.join(
            tmp, tag, "shadow.data")
        if mutate:
            mutate(cfg)
        c = Controller(cfg)
        stats = c.run()
        if not stats.ok:
            print(f"FAIL: {tag} run reported not-ok")
            sys.exit(1)
        sig = [(h.name, h.trace_checksum, h.events_executed,
                h.packets_sent, h.packets_dropped,
                h.packets_delivered) for h in c.sim.hosts]
        return sig, stats

    with tempfile.TemporaryDirectory() as tmp:
        os.environ.setdefault("SHADOW_TPU_OCC_DIR",
                              os.path.join(tmp, "occ"))
        rc = 0
        # leg 1: the serial oracle
        sig_oracle, stats_oracle = run_once(
            config, "serial", os.path.join(tmp, "oracle",
                                           "shadow.data"))

        # leg 2: strict refusal, before any compile
        strict_aot = os.path.join(tmp, "aot_strict")
        cfg = load_config(config)
        cfg.experimental.scheduler_policy = "tpu"
        cfg.experimental.admission = "strict"
        cfg.experimental.device_memory_budget = 4096   # 4 KiB: absurd
        cfg.experimental.compile_cache = strict_aot
        cfg.general.data_directory = os.path.join(
            tmp, "strict", "shadow.data")
        try:
            Controller(cfg).run()
        except ValueError as e:
            msg = str(e)
            for frag in ("admission", "needs", "budget", "device"):
                if frag not in msg:
                    rc = 1
                    print(f"FAIL: strict refusal diagnostic lacks "
                          f"{frag!r}: {msg}")
        else:
            rc = 1
            print("FAIL: admission: strict ADMITTED a run whose "
                  "footprint dwarfs a 4 KiB device budget")
        if os.path.isdir(strict_aot) and os.listdir(strict_aot):
            rc = 1
            print("FAIL: the strict refusal leg left entries in its "
                  "cold AOT cache — something compiled BEFORE the "
                  "admission decision")

        # leg 3: scripted oom at the 0th program compile (cold cache)
        def oom_compile(cfg):
            cfg.experimental.dispatch_retries = 3
            cfg.experimental.dispatch_retry_backoff = 0.0
            cfg.experimental.compile_cache = os.path.join(
                tmp, "aot_cold")
            cfg.experimental.chaos = [
                ChaosEvent(kind="oom", compile=0, error=OOM_ERROR)]

        sig_c, stats_c = run_tpu("oom_compile", tmp,
                                 mutate=oom_compile)
        if sig_c != sig_oracle:
            rc = 1
            print("DETERMINISM FAILURE: the compile-seam oom run "
                  "diverges from the serial oracle")
        if stats_c.degrades < 1:
            rc = 1
            print(f"FAIL: the scripted compile oom reported "
                  f"{stats_c.degrades} degrades — the ladder never "
                  "engaged")
        if stats_c.retries >= 3:
            rc = 1
            print(f"FAIL: the compile-seam oom burned "
                  f"{stats_c.retries} retries — the ladder must "
                  "engage before the budget of 3 exhausts")

        # leg 4: scripted oom at the 2nd dispatch issue
        def oom_dispatch(cfg):
            cfg.experimental.dispatch_retries = 3
            cfg.experimental.dispatch_retry_backoff = 0.0
            cfg.experimental.chaos = [
                ChaosEvent(kind="oom", segment=2, error=OOM_ERROR)]

        sig_d, stats_d = run_tpu("oom_dispatch", tmp,
                                 mutate=oom_dispatch)
        if sig_d != sig_oracle:
            rc = 1
            print("DETERMINISM FAILURE: the dispatch-seam oom run "
                  "diverges from the serial oracle")
            for a, b in zip(sig_oracle, sig_d):
                if a != b:
                    print(f"  {a[0]}: oracle {a[1:]} != degraded "
                          f"{b[1:]}")
        if stats_d.degrades < 1:
            rc = 1
            print(f"FAIL: the scripted dispatch oom reported "
                  f"{stats_d.degrades} degrades — the ladder never "
                  "engaged")
        if stats_d.retries > 1:
            rc = 1
            print(f"FAIL: the deterministic dispatch oom charged "
                  f"{stats_d.retries} retries — the second "
                  "consecutive identical failure must route to the "
                  "ladder after ONE charged retry, not drain "
                  "dispatch_retries")

        # leg 5: replica batches bit-match the full-vmap campaign
        def run_campaign(tag: str, batch: int = 0):
            cfg = load_config(ensemble_config)
            cfg.experimental.scheduler_policy = "tpu"
            cfg.experimental.state_audit = True
            cfg.experimental.dispatch_segment = max(
                1, cfg.general.stop_time // 8)
            cfg.experimental.compile_cache = os.path.join(
                tmp, "aot_ens")
            cfg.general.data_directory = os.path.join(
                tmp, tag, "shadow.data")
            cfg.ensemble.record_path = os.path.join(
                tmp, tag, "ENSEMBLE.json")
            if batch:
                cfg.ensemble.replica_batch = batch
            c = Controller(cfg)
            stats = c.run()
            if not stats.ok:
                print(f"FAIL: {tag} campaign reported not-ok")
                sys.exit(1)
            f = c.runner.final_state
            sig = {k: np.asarray(f[k])
                   for k in ("chk", "n_exec", "n_sent", "n_drop",
                             "n_deliv")}
            return sig, stats, c

        ens_full, _, _ = run_campaign("ens_full")
        ens_b, stats_b, c_b = run_campaign("ens_batch", batch=2)
        bad = [k for k in ens_full
               if not np.array_equal(ens_full[k], ens_b[k])]
        if bad:
            rc = 1
            print(f"DETERMINISM FAILURE: the replica-batched "
                  f"campaign's {bad} diverge from the full-vmap "
                  "campaign")
        pipe = stats_b.pipeline or {}
        if pipe.get("replica_batches") != 2 or \
                pipe.get("replica_batch") != 2:
            rc = 1
            print(f"FAIL: the batched campaign stamped pipeline "
                  f"{pipe} — expected replica_batch=2 over "
                  "replica_batches=2")
        adm = stats_b.admission
        if not isinstance(adm, dict) or \
                adm.get("replica_batch") != 2:
            rc = 1
            print(f"FAIL: the batched campaign's admission verdict "
                  f"{adm} does not stamp replica_batch=2")

        # ... and replica 0 still bit-matches a standalone serial run
        desc = c_b.runner.worlds.descriptors[0]
        names = [h.name for h in c_b.sim.hosts]
        sig_e = [(names[i], int(ens_b["chk"][0, i]),
                  int(ens_b["n_exec"][0, i]),
                  int(ens_b["n_sent"][0, i]),
                  int(ens_b["n_drop"][0, i]),
                  int(ens_b["n_deliv"][0, i]))
                 for i in range(len(names))]
        cfg = load_config(ensemble_config)
        cfg.ensemble = None
        cfg.experimental.scheduler_policy = "serial"
        cfg.experimental.runahead = c_b.runner.lookahead
        cfg.general.seed = desc["seed"]
        cfg.general.data_directory = os.path.join(
            tmp, "alone", "shadow.data")
        c_a = Controller(cfg)
        stats_a = c_a.run()
        if not stats_a.ok:
            print("FAIL: standalone replica-0 run reported not-ok")
            return 1
        sig_a = [(h.name, h.trace_checksum, h.events_executed,
                  h.packets_sent, h.packets_dropped,
                  h.packets_delivered) for h in c_a.sim.hosts]
        if sig_a != sig_e:
            rc = 1
            print(f"DETERMINISM FAILURE: replica 0 of the batched "
                  f"campaign diverges from the standalone serial "
                  f"run with its parameters ({desc})")

        if rc == 0:
            print(f"degrade OK: {config} (strict admission refused "
                  f"a 4 KiB budget before any compile; scripted "
                  f"RESOURCE_EXHAUSTED at compile 0 and dispatch 2 "
                  f"walked the ladder bit-identical to the serial "
                  f"oracle [{stats_oracle.events_executed} events, "
                  f"{stats_c.degrades}+{stats_d.degrades} degrades, "
                  f"retry budget intact]; {ensemble_config} in "
                  "replica batches of 2 bit-matches the full-vmap "
                  "campaign and standalone replica 0)")
        return rc


def run_host_plane_gate(config: str) -> int:
    """Columnar host plane gate (host/plane.py, docs/host_plane.md):
    on the forced multi-device mesh, the columnar build, the object-
    path build (SHADOW_TPU_HOST_PLANE=0), and the serial CPU oracle
    must produce bit-identical per-host signatures, and the two tpu
    legs' engines must carry identical checkpoint fingerprints.
    Vacuity-guarded: the columnar leg must actually have used the
    plane, and the object leg must not have."""
    from shadow_tpu.config import load_config
    from shadow_tpu.core.controller import Controller
    from shadow_tpu.device import checkpoint

    def leg(policy: str, data_dir: str, columnar: bool):
        old = os.environ.pop("SHADOW_TPU_HOST_PLANE", None)
        try:
            if not columnar:
                os.environ["SHADOW_TPU_HOST_PLANE"] = "0"
            cfg = load_config(config)
            cfg.experimental.scheduler_policy = policy
            cfg.general.data_directory = data_dir
            c = Controller(cfg)
            stats = c.run()
        finally:
            os.environ.pop("SHADOW_TPU_HOST_PLANE", None)
            if old is not None:
                os.environ["SHADOW_TPU_HOST_PLANE"] = old
        if not stats.ok:
            print(f"FAIL: {policy} leg reported not-ok")
            sys.exit(1)
        sig = [(h.name, h.trace_checksum, h.events_executed,
                h.packets_sent, h.packets_dropped,
                h.packets_delivered) for h in c.sim.hosts]
        return c, sig

    def diff(tag: str, a, b) -> None:
        print(f"HOST-PLANE FAILURE: {tag} signatures diverge")
        for x, y in zip(a, b):
            if x != y:
                print(f"  {x[0]}: {x[1:]} != {y[1:]}")

    with tempfile.TemporaryDirectory() as tmp:
        col, sig_col = leg("tpu", os.path.join(tmp, "columnar"), True)
        if col.sim.plane is None:
            print("FAIL: the columnar leg did not use the host plane "
                  "(eligibility refused this config, so the gate "
                  "would compare object vs object — fix the config "
                  "or the eligibility rule)")
            return 1
        obj, sig_obj = leg("tpu", os.path.join(tmp, "object"), False)
        if obj.sim.plane is not None:
            print("FAIL: SHADOW_TPU_HOST_PLANE=0 did not force the "
                  "object build")
            return 1
        _, sig_ser = leg("serial", os.path.join(tmp, "serial"), True)

        rc = 0
        if sig_col != sig_obj:
            rc = 1
            diff("columnar vs object", sig_col, sig_obj)
        if sig_col != sig_ser:
            rc = 1
            diff("columnar vs serial oracle", sig_col, sig_ser)
        fp_col = checkpoint._fingerprint(col.runner.engine)
        fp_obj = checkpoint._fingerprint(obj.runner.engine)
        if fp_col != fp_obj:
            rc = 1
            print("HOST-PLANE FAILURE: checkpoint fingerprints "
                  "diverge between the columnar and object engines")
            for k in fp_col:
                if fp_col.get(k) != fp_obj.get(k):
                    print(f"  {k}: {fp_col.get(k)} != {fp_obj.get(k)}")
        if rc == 0:
            import jax
            print(f"host-plane OK: {config} ({len(sig_col)} hosts, "
                  f"{len(jax.devices())} devices) — columnar, "
                  "object, and serial legs bit-identical; "
                  "checkpoint fingerprints match")
        return rc


def run_server_gate(config: str) -> int:
    """Campaign-server robustness gate (shadow_tpu/serve/), two legs
    on the forced multi-device mesh:

    1. kill -9 drill: submit two campaigns, run the daemon as a real
       child process, SIGKILL it once the first rotation checkpoint
       lands, restart with --idle-exit — journal replay must requeue
       the mid-flight campaign, BOTH must reach DONE, and every
       RESULT.json signature must bit-match an uninterrupted
       standalone run of the same config.
    2. priority drill: a higher-priority arrival preempts the running
       campaign through the rc-75 drain; the preempted campaign
       resumes after it and still bit-matches standalone.
    """
    import json as _json
    import signal as _signal
    import subprocess
    import time as _time

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")

    def daemon(spool, *extra):
        return subprocess.Popen(
            [sys.executable, "-m", "shadow_tpu.serve", "start", spool,
             "--poll", "0.05", "--log-level", "warning"] + list(extra),
            env=env, cwd=repo)

    def submit(spool, priority=0):
        rc = subprocess.run(
            [sys.executable, "-m", "shadow_tpu.serve", "submit",
             spool, config, "--priority", str(priority)],
            env=env, cwd=repo).returncode
        if rc != 0:
            raise RuntimeError(f"submit failed (rc {rc})")

    def journal_rows(spool):
        path = os.path.join(spool, "journal.jsonl")
        rows = []
        if os.path.exists(path):
            with open(path, "r", encoding="utf-8") as f:
                for line in f:
                    try:
                        rows.append(_json.loads(line))
                    except ValueError:
                        pass
        return rows

    def wait_for(pred, what, timeout_s=900):
        deadline = _time.monotonic() + timeout_s
        while _time.monotonic() < deadline:
            if pred():
                return True
            _time.sleep(0.05)
        print(f"FAIL: timed out waiting for {what}")
        return False

    def results(spool, n):
        out = {}
        for i in range(n):
            cid = f"c{i:04d}"
            path = os.path.join(spool, "campaigns", cid,
                                "RESULT.json")
            if not os.path.exists(path):
                print(f"FAIL: {path} missing")
                return None
            with open(path, "r", encoding="utf-8") as f:
                out[cid] = _json.load(f)
        return out

    with tempfile.TemporaryDirectory() as tmp:
        ref_sig, _ = run_once(config, "tpu",
                              os.path.join(tmp, "ref.data"))
        ref = [list(s) for s in ref_sig]

        # -- leg 1: SIGKILL mid-campaign, restart, both complete ----
        spool = os.path.join(tmp, "spool_kill")
        submit(spool)
        submit(spool)
        proc = daemon(spool)
        ck_glob = os.path.join(spool, "campaigns", "*", "ck.npz.t*")
        if not wait_for(lambda: glob.glob(ck_glob),
                        "the first rotation checkpoint"):
            proc.kill()
            return 1
        proc.send_signal(_signal.SIGKILL)   # the crash drill IS kill -9
        proc.wait()
        proc = daemon(spool, "--idle-exit")
        rc = proc.wait(timeout=900)
        if rc != 0:
            print(f"FAIL: restarted server exited rc {rc}")
            return 1
        res = results(spool, 2)
        if res is None:
            return 1
        starts = sum(1 for r in journal_rows(spool)
                     if r.get("event") == "server_start")
        if starts != 2:
            print(f"FAIL: journal replayed {starts} server starts, "
                  "want 2 (one per daemon leg)")
            return 1
        for cid, r in res.items():
            if r.get("state") != "DONE":
                print(f"FAIL: {cid} ended {r.get('state')} "
                      f"({r.get('diagnostic', '')})")
                return 1
            if r.get("signature") != ref:
                print(f"FAIL: {cid} signature diverges from the "
                      "standalone run after the kill -9 restart")
                return 1
        requeued = any(r.get("state") == "PREEMPTED" and "restart"
                       in r.get("diagnostic", "")
                       for r in journal_rows(spool))
        if not requeued:
            print("FAIL: journal replay never requeued the "
                  "mid-flight campaign (the kill missed the RUNNING "
                  "window — shrink checkpoint cadence)")
            return 1
        print(f"server kill -9 drill OK: {config} — 2 campaigns "
              "DONE across a restart, signatures bit-match "
              "standalone")

        # -- leg 2: higher priority preempts via the rc-75 drain ----
        spool = os.path.join(tmp, "spool_prio")
        submit(spool, priority=0)       # before the daemon, so
        proc = daemon(spool, "--idle-exit")   # idle-exit cannot race
        try:
            if not wait_for(
                    lambda: any(r.get("cid") == "c0000"
                                and r.get("state") == "RUNNING"
                                for r in journal_rows(spool)),
                    "c0000 to start running"):
                return 1
            submit(spool, priority=5)
            rc = proc.wait(timeout=900)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        if rc != 0:
            print(f"FAIL: priority-leg server exited rc {rc}")
            return 1
        res = results(spool, 2)
        if res is None:
            return 1
        rows = journal_rows(spool)
        states = [(r.get("cid"), r.get("state"))
                  for r in rows if r.get("state")]
        if ("c0000", "PREEMPTED") not in states:
            print("FAIL: the low-priority campaign was never "
                  "preempted (the high-priority submission lost the "
                  "race — grow stop_time)")
            return 1
        dones = [cid for cid, s in states if s == "DONE"]
        if dones and dones[0] != "c0001":
            print(f"FAIL: completion order {dones} — the "
                  "high-priority campaign must finish first")
            return 1
        for cid, r in res.items():
            if r.get("state") != "DONE" or r.get("signature") != ref:
                print(f"FAIL: {cid} ended {r.get('state')} or "
                      "diverged from standalone after the "
                      "preempt/resume cycle")
                return 1
        print(f"server priority drill OK: {config} — preempted "
              "campaign resumed bit-identical behind the "
              "high-priority one")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("config", nargs="?", default="examples/minimal.yaml")
    ap.add_argument("--policy", default=None)
    ap.add_argument("--ensemble", action="store_true",
                    help="campaign gate: replica bit-identity vs "
                         "standalone runs (config needs ensemble:)")
    ap.add_argument("--replica", type=int, default=0,
                    help="which replica to compare standalone "
                         "(--ensemble only; default 0)")
    ap.add_argument("--preempt", action="store_true",
                    help="preemption gate: SIGTERM a supervised run "
                         "mid-flight, resume, require bit-identity "
                         "with the uninterrupted run")
    ap.add_argument("--compile-cache", action="store_true",
                    help="warm-start gate: cold/warm/corrupted runs "
                         "against one shared AOT compile cache must "
                         "be bit-identical, with the warm run a "
                         "cache hit and the corrupted run a loud "
                         "recompile")
    ap.add_argument("--telemetry", action="store_true",
                    help="flight-recorder gate: telemetry off vs "
                         "summary vs trace must be bit-identical, "
                         "and the trace run must leave a Perfetto-"
                         "loadable trace + a METRICS record whose "
                         "phase walls sum to the total")
    ap.add_argument("--tuned", action="store_true",
                    help="strategy-autotuner gate: a mini-tuned PLAN "
                         "record and a composed adversarial plan "
                         "must both bit-match the default-knob run "
                         "(a tuned plan changes wall time only)")
    ap.add_argument("--chaos", action="store_true",
                    help="elastic mesh-shrink gate: scripted 4 -> 3 "
                         "device loss (deterministic chaos injector) "
                         "must bit-match the serial oracle and the "
                         "uninterrupted 3-shard run, standalone and "
                         "ensemble; post-shrink checkpoints stamp "
                         "the shrunken geometry and resume; a "
                         "corrupted rotation entry falls back to "
                         "the newest readable one (needs >= 4 "
                         "devices)")
    ap.add_argument("--chaos-ensemble",
                    default="examples/ensemble_seed_sweep.yaml",
                    help="campaign config for the --chaos / "
                         "--degrade ensemble legs (default "
                         "examples/ensemble_seed_sweep.yaml)")
    ap.add_argument("--degrade", action="store_true",
                    help="admission + degradation-ladder gate: "
                         "admission: strict must refuse a tiny "
                         "device_memory_budget with a readable "
                         "diagnostic before any compile; scripted "
                         "RESOURCE_EXHAUSTED at the 0th compile and "
                         "the 2nd dispatch issue must walk the "
                         "degradation ladder bit-identical to the "
                         "serial oracle without exhausting "
                         "dispatch_retries; the --chaos-ensemble "
                         "campaign in replica batches of 2 must "
                         "bit-match the full-vmap campaign and "
                         "standalone replica 0 (needs >= 4 devices)")
    ap.add_argument("--host-plane", action="store_true",
                    help="columnar host-plane gate: the vectorized "
                         "columnar build, the object-path build "
                         "(SHADOW_TPU_HOST_PLANE=0), and the serial "
                         "CPU oracle must be bit-identical on the "
                         "forced multi-device mesh, with matching "
                         "checkpoint fingerprints between the two "
                         "tpu legs")
    ap.add_argument("--analyze-consistency", action="store_true",
                    help="static-analysis consistency gate: the "
                         "collective registry shadowlint audits "
                         "against must match engine.effective{} at "
                         "runtime, the engine's jaxpr audit must be "
                         "clean, and an in-process audit must leave "
                         "run signatures bit-identical")
    ap.add_argument("--server", action="store_true",
                    help="campaign-server gate (shadow_tpu/serve/): "
                         "kill -9 the daemon mid-campaign and "
                         "restart — journal replay must complete "
                         "both campaigns bit-identical to standalone "
                         "runs; then a priority arrival must preempt "
                         "and the drained campaign resume "
                         "bit-identical (needs >= 4 devices)")
    args = ap.parse_args()

    default_policy = "serial,tpu" if args.ensemble else "serial"
    policies = [p.strip()
                for p in (args.policy or default_policy).split(",")
                if p.strip()]

    if args.server:
        if args.ensemble or args.preempt or args.policy or \
                args.compile_cache or args.telemetry or args.tuned \
                or args.analyze_consistency or args.chaos \
                or args.degrade:
            # the server gate drives whole daemon processes; the
            # standalone reference runs are baked into its legs
            print("FAIL: --server does not combine with other gate "
                  "flags (it runs its own standalone reference plus "
                  "the kill -9 and priority-preemption daemon legs)")
            return 1
        return run_server_gate(args.config)

    if args.degrade:
        if args.ensemble or args.preempt or args.policy or \
                args.compile_cache or args.telemetry or args.tuned \
                or args.analyze_consistency or args.chaos:
            # the degrade gate runs the serial oracle, both oom
            # seams, the strict refusal, and its own replica-batch
            # ensemble leg by construction
            print("FAIL: --degrade does not combine with other gate "
                  "flags (it runs serial + tpu oom/strict legs plus "
                  "its own replica-batch ensemble leg)")
            return 1
        return run_degrade_gate(args.config, args.chaos_ensemble)

    if args.chaos:
        if args.ensemble or args.preempt or args.policy or \
                args.compile_cache or args.telemetry or args.tuned \
                or args.analyze_consistency:
            # the chaos gate runs the serial oracle, the M-shard
            # comparison, the shrink/resume legs, and its own
            # ensemble leg by construction
            print("FAIL: --chaos does not combine with other gate "
                  "flags (it runs serial + tpu mesh_shards 3/4 plus "
                  "its own checkpoint/ensemble legs)")
            return 1
        return run_chaos_gate(args.config, args.chaos_ensemble)

    if args.host_plane:
        if args.ensemble or args.preempt or args.policy or \
                args.compile_cache or args.telemetry or args.tuned \
                or args.analyze_consistency:
            # the host-plane gate runs its own three legs (columnar
            # tpu, object tpu, serial oracle) by construction
            print("FAIL: --host-plane does not combine with other "
                  "gate flags (it runs columnar tpu + object tpu + "
                  "serial legs by construction)")
            return 1
        return run_host_plane_gate(args.config)

    if args.analyze_consistency:
        if args.ensemble or args.preempt or args.policy or \
                args.compile_cache or args.telemetry or args.tuned:
            # this gate runs the standalone tpu policy around an
            # in-process audit by construction
            print("FAIL: --analyze-consistency does not combine "
                  "with --ensemble/--preempt/--policy/"
                  "--compile-cache/--telemetry/--tuned")
            return 1
        return run_analyze_consistency_gate(args.config)

    if args.tuned:
        if args.ensemble or args.preempt or args.policy or \
                args.compile_cache or args.telemetry:
            # the tuned gate runs the standalone tpu policy against
            # its three plan legs by construction
            print("FAIL: --tuned does not combine with --ensemble/"
                  "--preempt/--policy/--compile-cache/--telemetry "
                  "(it runs the standalone tpu policy per plan leg)")
            return 1
        return run_tuned_gate(args.config)

    if args.telemetry:
        if args.ensemble or args.preempt or args.policy or \
                args.compile_cache:
            # the telemetry gate runs the standalone tpu policy under
            # its three modes by construction — dropping another
            # gate's flag silently would test the wrong thing
            print("FAIL: --telemetry does not combine with "
                  "--ensemble/--preempt/--policy/--compile-cache "
                  "(it runs the standalone tpu policy once per "
                  "telemetry mode)")
            return 1
        return run_telemetry_gate(args.config)

    if args.compile_cache:
        if args.ensemble or args.preempt or args.policy:
            # the warm-start gate runs the standalone tpu policy by
            # construction — dropping a composability flag silently
            # would test the wrong thing
            print("FAIL: --compile-cache does not combine with "
                  "--ensemble/--preempt/--policy (it runs the "
                  "standalone tpu policy three times against one "
                  "shared cache directory)")
            return 1
        return run_compile_cache_gate(args.config)

    if args.preempt:
        return run_preempt_gate(args.config, args.ensemble)

    if args.ensemble:
        return run_ensemble_gate(args.config, policies, args.replica)

    with tempfile.TemporaryDirectory() as tmp:
        d1 = os.path.join(tmp, "run1", "shadow.data")
        d2 = os.path.join(tmp, "run2", "shadow.data")
        sig1, stats1 = run_once(args.config, policies[0], d1)
        sig2, stats2 = run_once(args.config, policies[0], d2)

        rc = 0
        if sig1 != sig2:
            rc = 1
            print("DETERMINISM FAILURE: per-host signatures differ")
            for a, b in zip(sig1, sig2):
                if a != b:
                    print(f"  {a[0]}: {a[1:]} != {b[1:]}")
        diffs = compare_trees(d1, d2)
        if diffs:
            rc = 1
            print("DETERMINISM FAILURE: host files differ")
            for d in diffs[:20]:
                print(f"  {d}")

        # cross-policy matrix: every additional policy must reproduce
        # the first policy's per-host signature bit for bit
        for policy in policies[1:]:
            dp = os.path.join(tmp, f"run_{policy}", "shadow.data")
            sigp, _ = run_once(args.config, policy, dp)
            if sigp != sig1:
                rc = 1
                print(f"DETERMINISM FAILURE: policy {policy} diverges "
                      f"from {policies[0]}")
                for a, b in zip(sig1, sigp):
                    if a != b:
                        print(f"  {a[0]}: {a[1:]} != {b[1:]}")
            diffs = compare_trees(d1, dp)
            if diffs:
                rc = 1
                print(f"DETERMINISM FAILURE: host files differ "
                      f"({policies[0]} vs {policy})")
                for d in diffs[:20]:
                    print(f"  {d}")

        if rc == 0:
            across = f"across 2 runs of {policies[0]}"
            if len(policies) > 1:
                across += f" and policies {','.join(policies[1:])}"
            print(f"determinism OK: {args.config} "
                  f"({stats1.events_executed} events, "
                  f"{stats1.packets_sent} packets, bit-identical "
                  f"signatures and host files {across})")
        return rc


if __name__ == "__main__":
    sys.exit(main())
