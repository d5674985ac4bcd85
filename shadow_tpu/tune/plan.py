"""Strategy-plan persistence and adoption.

A PLAN record is the tuner's durable output: the winning knob
assignment for one workload fingerprint, plus the trial ledger that
chose it, written atomically to ``PLAN_<app>_<H>_<fp>.json`` next to
the OCC records (same directory, same ``$SHADOW_TPU_OCC_DIR``
override, same fingerprint discipline — two traffic-shape variants of
one app never share a plan).

Adoption (``experimental.strategy_plan``):

* ``off``   — stored plans are ignored;
* ``auto``  — the workload's canonical plan path is consulted; no
  file, no change (production runs self-tune once a plan exists);
* ``<path>``— an explicit record; a missing file is a loud error.

Either way the record's workload stamp must match the simulation
(app class + fingerprint + host count — the OCC-record rule) or
adoption REFUSES loudly: a plan tuned for different traffic must
never silently steer this run. Knobs the operator hand-set (config
value differs from the schema default) win over the plan, logged per
knob — a plan assists defaults, it does not fight explicit
configuration.
"""

from __future__ import annotations

import json
import os
from typing import Optional

from shadow_tpu.tune import space
from shadow_tpu.utils.slog import get_logger

log = get_logger("tune")

FORMAT = 1


def plan_path(app, n_hosts: int, directory: str = "") -> str:
    """Canonical PLAN record path for a workload: app class + host
    count + workload fingerprint, beside the OCC records."""
    from shadow_tpu.device import capacity

    directory = directory or os.environ.get("SHADOW_TPU_OCC_DIR",
                                            "artifacts")
    return os.path.join(
        directory,
        f"PLAN_{type(app).__name__}_{int(n_hosts)}"
        f"_{capacity.app_fingerprint(app)}.json")


def save_plan(record: dict, path: str) -> None:
    from shadow_tpu.obs import trace as obstrace
    from shadow_tpu.utils.artifacts import atomic_write_json

    atomic_write_json(record, path)
    obstrace.current().instant("plan.save", "plan", path=path)


def load_plan(path: str) -> dict:
    with open(path) as f:
        record = json.load(f)
    if record.get("format") != FORMAT:
        raise ValueError(
            f"strategy plan {path}: format {record.get('format')!r} "
            f"(this build reads format {FORMAT})")
    for key in ("workload", "knobs"):
        if key not in record:
            raise ValueError(f"strategy plan {path}: missing {key!r}")
    return record


def workload_stamp(app, n_hosts: int) -> dict:
    """The identity a plan is valid for — the OCC record's
    fingerprint discipline, reused verbatim."""
    from shadow_tpu.device import capacity

    return {"app": type(app).__name__,
            "app_fp": capacity.app_fingerprint(app),
            "n_hosts": int(n_hosts)}


def verify_workload(record: dict, app, n_hosts: int,
                    path: str = "") -> None:
    """Loud mismatch refusal: the record's workload stamp must match
    this simulation exactly (runner adoption goes through
    :func:`resolve_plan`)."""
    want = workload_stamp(app, n_hosts)
    got = {k: record.get("workload", {}).get(k) for k in want}
    if got != want:
        raise ValueError(
            f"strategy plan {path or '<record>'} was tuned for "
            f"{got}; this simulation is {want} — re-tune with "
            "scripts/tune.py (plans never transfer across workload "
            "fingerprints)")


def resolve_plan(mode: str, app, n_hosts: int
                 ) -> tuple[Optional[dict], str]:
    """``experimental.strategy_plan`` -> (record, path) or
    (None, ""). ``auto`` with no canonical file is a silent no-op
    (the self-tuning default must not nag un-tuned workloads); an
    explicit path that is missing or mismatched is a loud error."""
    if mode == "off":
        return None, ""
    if mode == "auto":
        path = plan_path(app, n_hosts)
        if not os.path.exists(path):
            return None, ""
    else:
        path = mode
        if not os.path.exists(path):
            raise ValueError(
                f"experimental.strategy_plan: {path!r} does not "
                "exist (write one with scripts/tune.py, or use "
                "'auto' to adopt the canonical record only when "
                "present)")
    record = load_plan(path)
    verify_workload(record, app, n_hosts, path=path)
    return record, path


def adopt(cfg, app, n_hosts: int, n_shards: int = 0,
          policy: str = "") -> Optional[dict]:
    """Apply a stored plan onto a validated config (the runners call
    this before building their engine; the Controller's hybrid
    branch calls it with ``policy="hybrid"`` so the judge knob's
    gate sees the policy actually RUNNING, not the config's pre-
    fallback one). Returns the provenance dict
    (``SimStats.strategy_plan``) or None when nothing was adopted.

    Skip rules, each logged: a knob whose config value differs from
    the plan's tuned-from baseline (its recorded default, else the
    schema default) is hand-set and wins over the plan; a knob
    whose applicability gate fails on this run shape (plan tuned on
    a mesh, adopted on one chip) is dropped rather than misapplied.
    """
    record, path = resolve_plan(cfg.experimental.strategy_plan, app,
                                n_hosts)
    if record is None:
        return None
    ctx = space.context(cfg, n_shards=n_shards)
    if policy:
        ctx["policy"] = policy
    plan_defaults = record.get("default") or {}
    assignment, skipped = {}, {}
    for name, value in record["knobs"].items():
        knob = space.KNOB_BY_NAME.get(name)
        if knob is None:
            skipped[name] = "unknown knob (newer/older plan space)"
            continue
        if not knob.applies(cfg, ctx):
            skipped[name] = "not applicable to this run shape"
            continue
        section = cfg.experimental if knob.section == "experimental" \
            else cfg.general
        cur = getattr(section, knob.name)
        # "hand-set wins": the reference is the baseline the plan
        # was tuned FROM (its recorded default) when the record
        # carries one, else the schema default — cadence knobs only
        # exist on configs that set them, so their tuned-from value,
        # not the schema's zero, is what "untouched since tuning"
        # means
        ref = space.schema_default(knob)
        if name in plan_defaults:
            try:
                ref = knob.coerce(plan_defaults[name])
            except (TypeError, ValueError):
                pass
        if cur != ref:
            skipped[name] = (f"hand-set to {cur!r} in the config "
                             f"(the plan tuned from {ref!r})")
            continue
        assignment[name] = value
    applied = space.apply_assignment(cfg, assignment)
    for name, why in skipped.items():
        log.info("strategy plan: knob %s=%r skipped (%s)", name,
                 record["knobs"][name], why)
    prov = {
        "path": path,
        "workload": dict(record["workload"]),
        "knobs": applied,
        "skipped": skipped,
        "score": record.get("score"),
    }
    if applied:
        log.info("strategy plan adopted from %s: %s (tuned %s)",
                 path, applied, record.get("score") or "un-scored")
    else:
        log.info("strategy plan %s matched but every knob was "
                 "skipped (%s)", path, skipped or "empty plan")
    return prov


def revalidate_after_reshard(cfg, provenance, n_shards: int):
    """A mesh-shrink failover changed the run shape the adopted plan
    was tuned and gate-validated against (plans are fingerprinted
    per shard count). Every plan-space knob is individually
    bit-identity-pinned, so nothing already applied can corrupt the
    trace — but knobs whose applicability gate fails under the NEW
    shard count (an exchange schedule tuned for a wider mesh, a
    pipeline depth sized to the old segment cost) are now merely
    inherited, not tuned. Re-run each applied knob's gate under the
    new geometry and stamp the survivors/stale ones into the
    provenance (``SimStats.strategy_plan``), so post-shrink records
    never read as 'tuned for this mesh'. The exchange geometry
    itself is re-planned for real by the runner
    (DeviceRunner._replan_for_shrink) — this is the audit trail."""
    if not provenance:
        return provenance
    ctx = space.context(cfg, n_shards=n_shards)
    ctx["policy"] = "tpu"
    stale = {}
    for name in (provenance.get("knobs") or {}):
        knob = space.KNOB_BY_NAME.get(name)
        if knob is not None and not knob.applies(cfg, ctx):
            stale[name] = (f"tuned for the pre-shrink mesh; gate "
                           f"fails at n_shards={n_shards}")
    out = dict(provenance)
    out["resharded_to"] = int(n_shards)
    if stale:
        out["stale_after_reshard"] = stale
        log.warning(
            "strategy plan: knob(s) %s were tuned for the pre-shrink "
            "mesh and no longer pass their applicability gate at %d "
            "shard(s) — values stay (each is bit-identity-pinned) "
            "but the plan should be re-tuned for the new geometry "
            "(scripts/tune.py)", sorted(stale), n_shards)
    else:
        log.info("strategy plan re-validated after the mesh shrink: "
                 "every adopted knob still applies at %d shard(s)",
                 n_shards)
    return out
