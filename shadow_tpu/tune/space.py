"""The strategy-plan space: a declared registry of tunable knobs.

Every knob the tuner may move is declared HERE, with its valid
candidate ladder, an applicability gate, and a flag for whether a
change reshapes the compiled program (a reshaping knob costs an XLA
compile — or an AOT cache load — per distinct value, so the trial
harness orders and budgets them differently from free runtime knobs).

The registry is the single source of truth three consumers share:

* the trial harness (tune/trials.py) enumerates candidates from it;
* plan adoption (tune/plan.py) applies a stored assignment through
  it — a knob absent from the registry can never enter a config via
  a PLAN file, and every value is re-coerced/validated on the way in
  (plan files are hand-editable JSON);
* the determinism gate's ``--tuned`` rung composes the most
  adversarial assignment from it to pin compositional bit-identity.

Inclusion rule: a knob joins the space only if it is individually
bit-identity-pinned (traces do not depend on it) — the tuner's
contract is that a plan changes WALL time only. Knobs that trade
identity for speed (burst_pops needs app support, capacities are the
capacity planner's job) stay out.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

from shadow_tpu.utils.slog import get_logger

log = get_logger("tune")


@dataclass(frozen=True)
class Knob:
    """One tunable execution knob.

    ``candidates(cfg, ctx)`` returns the ordered value ladder to try
    (deduplicated, current value included); ``applies(cfg, ctx)``
    gates the knob on the run shape (policy, mesh size, whether the
    feature is on at all); ``coerce`` re-validates a stored value at
    adoption time. ``reshapes`` marks knobs whose change recompiles
    the device program (exchange schedule, planned capacities)."""

    name: str                   # config field name
    section: str                # "experimental" | "general"
    reshapes: bool
    description: str
    candidates: Callable        # (cfg, ctx) -> tuple
    applies: Callable           # (cfg, ctx) -> bool
    coerce: Callable            # raw -> validated value (raises)


def _coerce_time_ns(v) -> int:
    n = int(v)
    if n < 0:
        raise ValueError(f"negative time {v!r}")
    return n


def _coerce_nonneg_int(v) -> int:
    n = int(v)
    if n < 0:
        raise ValueError(f"negative count {v!r}")
    return n


def _coerce_exchange(v) -> str:
    # "auto" is never a CANDIDATE (a searched plan is the resolved
    # choice) but it must round-trip as a value: the base assignment
    # mirrors the config, and `exchange: auto` is a valid config —
    # a defaults-keeping plan for such a config stores "auto" and
    # adoption re-applies it unchanged
    valid = ("all_to_all", "all_gather", "two_phase", "auto")
    if v not in valid:
        raise ValueError(f"exchange {v!r} is not one of {list(valid)}")
    return v


def _coerce_headroom(v) -> float:
    f = float(v)
    if f != 0.0 and f < 1.0:
        raise ValueError(f"capacity_headroom {v!r} must be 0 or >= 1")
    return f


def _seg_candidates(cfg, ctx) -> tuple:
    """Dispatch-segment ladder relative to the workload's stop time:
    unbounded (one mega-dispatch), plus halves/quarters/eighths —
    the trade is per-dispatch host latency (fewer, longer segments)
    vs dispatch overlap with host-side work and checkpoint/retry
    granularity (more, shorter segments)."""
    stop = int(ctx["stop"])
    cur = int(cfg.experimental.dispatch_segment)
    ladder = [0, stop // 2, stop // 4, stop // 8]
    out = [cur] + [s for s in ladder if s > 0 or cur != 0]
    seen, uniq = set(), []
    for s in out:
        if s not in seen:
            seen.add(s)
            uniq.append(s)
    return tuple(uniq)


def _judge_candidates(cfg, ctx) -> tuple:
    cur = int(cfg.experimental.hybrid_judge_min_batch)
    ladder = (0, 64, 192, 512, 1024)
    return tuple(dict.fromkeys((cur,) + ladder))


def _exchange_candidates(cfg, ctx) -> tuple:
    # the concrete schedules only — never "auto": candidates are the
    # things the search RESOLVES between. The config's current value
    # (possibly "auto") leads so the baseline assignment mirrors the
    # config exactly.
    cur = cfg.experimental.exchange
    return tuple(dict.fromkeys(
        (cur, "all_to_all", "all_gather", "two_phase"))) \
        if cur == "auto" else ("all_to_all", "all_gather",
                               "two_phase")


def _headroom_candidates(cfg, ctx) -> tuple:
    cur = float(cfg.experimental.capacity_headroom)
    return tuple(dict.fromkeys((cur, 0.0, 1.25, 2.0)))


def _ckpt_candidates(cfg, ctx) -> tuple:
    """Checkpoint cadence ladder: multiples of the configured
    interval (never below it — the configured cadence is the
    operator's durability floor, so the tuner may only trade MORE
    progress-at-risk for less checkpoint wall, explicitly)."""
    cur = int(cfg.experimental.checkpoint_every)
    stop = int(ctx["stop"])
    out = [cur]
    for m in (2, 4):
        c = cur * m
        if c < stop:
            out.append(c)
    return tuple(dict.fromkeys(out))


def _hb_candidates(cfg, ctx) -> tuple:
    """Heartbeat cadence: the configured interval and coarser
    multiples (each boundary costs per-host device_gets + log I/O).
    Never finer, and never off — the lines are the operator's live
    surface, the tuner only thins them."""
    cur = int(cfg.general.heartbeat_interval)
    stop = int(ctx["stop"])
    out = [cur]
    for m in (2, 4):
        c = cur * m
        if c < stop:
            out.append(c)
    return tuple(dict.fromkeys(out))


KNOBS: tuple[Knob, ...] = (
    Knob("dispatch_segment", "experimental", False,
         "max sim-time per device dispatch (ns; 0 = unbounded)",
         _seg_candidates,
         lambda cfg, ctx: ctx["policy"] == "tpu",
         _coerce_time_ns),
    Knob("hybrid_judge_min_batch", "experimental", False,
         "rounds smaller than this judge on the CPU, not the device",
         _judge_candidates,
         lambda cfg, ctx: ctx["policy"] == "hybrid",
         _coerce_nonneg_int),
    Knob("exchange", "experimental", True,
         "cross-shard exchange schedule",
         _exchange_candidates,
         lambda cfg, ctx: ctx["policy"] == "tpu"
         and ctx.get("n_shards", 1) > 1,
         _coerce_exchange),
    Knob("capacity_headroom", "experimental", True,
         "capacity-plan pad factor (0 = planner default 1.5)",
         _headroom_candidates,
         lambda cfg, ctx: ctx["policy"] == "tpu"
         and cfg.experimental.capacity_plan != "static",
         _coerce_headroom),
    Knob("checkpoint_every", "experimental", False,
         "rotating-checkpoint cadence (ns; only coarsened)",
         _ckpt_candidates,
         lambda cfg, ctx: ctx["policy"] == "tpu"
         and bool(cfg.experimental.checkpoint_every),
         _coerce_time_ns),
    Knob("heartbeat_interval", "general", False,
         "heartbeat cadence (ns; only coarsened)",
         _hb_candidates,
         lambda cfg, ctx: ctx["policy"] == "tpu"
         and bool(cfg.general.heartbeat_interval),
         _coerce_time_ns),
)

KNOB_BY_NAME = {k.name: k for k in KNOBS}


def context(cfg, n_shards: int = 0) -> dict:
    """The applicability context the gates read. ``n_shards`` comes
    from the caller (the runner knows its mesh; scripts/tune.py asks
    jax) — the space itself never touches a backend."""
    return {
        "policy": cfg.experimental.scheduler_policy,
        "stop": int(cfg.general.stop_time),
        "n_shards": int(n_shards),
    }


def applicable(cfg, ctx) -> list[Knob]:
    """The knobs this run shape can move, in registry order (free
    runtime knobs before reshaping ones — the coordinate-descent
    order that front-loads the cheap wins)."""
    free = [k for k in KNOBS if not k.reshapes and k.applies(cfg, ctx)]
    shaped = [k for k in KNOBS if k.reshapes and k.applies(cfg, ctx)]
    return free + shaped


def current(cfg, knobs) -> dict:
    """The config's current assignment over `knobs` — the hand-set /
    default baseline every trial and every adoption compares
    against."""
    out = {}
    for k in knobs:
        section = cfg.experimental if k.section == "experimental" \
            else cfg.general
        out[k.name] = getattr(section, k.name)
    return out


def schema_default(knob: Knob):
    """The knob's schema default (what an untouched config carries) —
    adoption uses it to tell hand-set values from defaults."""
    from shadow_tpu.config.schema import (
        ExperimentalOptions,
        GeneralOptions,
    )

    blank = (ExperimentalOptions() if knob.section == "experimental"
             else GeneralOptions())
    return getattr(blank, knob.name)


def apply_assignment(cfg, assignment: dict) -> dict:
    """Set an assignment's knobs onto a config (trial harness and
    plan adoption both funnel through here). Unknown knob names and
    invalid values fail loudly — PLAN files are hand-editable JSON
    and must never smuggle an unvalidated value into the engine.
    Returns the validated {name: value} actually applied."""
    applied = {}
    for name, raw in assignment.items():
        knob = KNOB_BY_NAME.get(name)
        if knob is None:
            raise ValueError(
                f"strategy plan names unknown knob {name!r} "
                f"(the plan space is {sorted(KNOB_BY_NAME)})")
        try:
            value = knob.coerce(raw)
        except (TypeError, ValueError) as e:
            raise ValueError(
                f"strategy plan: invalid value for {name}: "
                f"{e}") from e
        section = cfg.experimental if knob.section == "experimental" \
            else cfg.general
        setattr(section, knob.name, value)
        applied[name] = value
    return applied


def reshaping(names) -> list[str]:
    """Which of `names` recompile the program when changed."""
    return [n for n in names
            if n in KNOB_BY_NAME and KNOB_BY_NAME[n].reshapes]
