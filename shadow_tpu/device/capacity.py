"""Occupancy-driven capacity planning for the device engine.

Every hot-path cost in the device engine scales with a statically
provisioned capacity: heap merges are E + IN rows wide, the flush's
flat sort covers H*OB (or H*CX) rows, and the all_to_all exchange
ships [n_shards, CAP] buffers auto-sized with 4x headroom "for skewed
traffic" (engine.py) — so on sparse or bursty workloads most of the
sort width and ICI bandwidth moves padding. The engine now accumulates
per-segment occupancy HIGH-WATER MARKS in its state (state["occ_*"],
reductions only, no extra sorts); this module turns those measurements
into tight capacities and back:

* ``measure(engine, state)``  — occupancy record (a JSON-able dict)
  from a run's final state: measured maxima + the effective
  capacities that held them.
* ``plan(record, ...)``       — EngineConfig capacity overrides sized
  to the measurements with headroom.
* ``widen(knobs, dims, eff)`` — double the offending dimension(s)
  after a loud overflow (the runner's re-plan/retry loop).
* ``grow_heaps(host_state, new_e)`` / ``transfer(engine, starts,
  host_state)`` — carry a saved state into a re-planned engine whose
  event_capacity grew.

Safety argument: a plan that undershoots (the warm-up slice missed
steady state) trips the engine's LOUD overflow counters; the runner
re-plans with doubled headroom on the offending dimension and re-runs
the segment from the last known-good state instead of failing the
run. Traces are bit-identical across capacity choices whenever
nothing overflows (the engine's determinism contract, pinned by
tests), so planning is purely a performance lever.
"""

from __future__ import annotations

import json
import math
import os

import numpy as np

from shadow_tpu.utils.slog import get_logger

log = get_logger("capacity")

FORMAT = 1
# planned = ceil(measured * HEADROOM) + SLACK: the warm-up slice is a
# lower bound on steady-state occupancy, and the retry loop makes an
# undershoot cost one re-run, never the run
HEADROOM = 1.5
SLACK = 2
# re-plan attempts before the run is allowed to fail loudly (each
# attempt doubles the offending dimension, so 6 covers a 64x miss)
MAX_REPLANS = 6

# the engine's full capacity-knob surface, in one place: the planner
# plans them, checkpoints stamp them, resumes adopt them, and the
# runners snapshot the static baseline from them — a new knob joins
# here and every consumer follows.
CAPACITY_KNOBS = ("event_capacity", "outbox_capacity",
                  "exchange_capacity", "exchange_capacity2",
                  "exchange_in_capacity", "outbox_compact")

# overflow counter -> the capacity dimensions it implicates. The
# merge/arrival `overflow` counter cannot distinguish a short heap
# from a short arrival window, so both grow together; `x_overflow`
# covers the shard-pair CAP (both phases of a two_phase schedule)
# and the compaction width.
OVERFLOW_DIMS = {
    "overflow": ("event_capacity", "exchange_in_capacity"),
    "x_overflow": ("exchange_capacity", "exchange_capacity2",
                   "outbox_compact"),
}

# two_phase must beat the direct all_to_all's estimated ICI volume by
# this factor before `exchange: auto` picks it (two collectives + an
# extra on-device sort are only worth real bandwidth savings)
TWO_PHASE_MARGIN = 0.9


def dense_auto_cap(h_loc: int, outbox: int, event_capacity: int,
                   n_shards: int) -> int:
    """The engine's blind per-pair CAP when exchange_capacity is 0:
    4x the balanced share of the whole outbox, "for skewed traffic".
    ONE definition, shared by the engine's auto-sizing and by the
    bench/micro reports that quote the dense baseline a measured plan
    replaces — the reduction factor must never be computed against a
    stale copy of this heuristic."""
    r = h_loc * outbox
    return min(r, max(64, event_capacity,
                      (4 * r + n_shards - 1) // n_shards))


def group_split(n_shards: int) -> tuple[int, int]:
    """Two-phase exchange group factorization: n_shards = g * ng with
    g (the intra-group size, phase 1) the largest divisor <= sqrt —
    so both phases have as few peers as possible. A prime shard count
    degenerates to (1, n_shards): phase 1 is empty and phase 2 is the
    direct exchange, correct but profitless (auto never picks it)."""
    g = 1
    for d in range(2, int(math.isqrt(n_shards)) + 1):
        if n_shards % d == 0:
            g = d
    # isqrt catches d <= sqrt; the co-divisor may be the better g when
    # n_shards is a perfect square times a small factor — keep g as
    # the largest divisor not exceeding isqrt (g <= ng always)
    return g, n_shards // g


def app_scalars(app) -> dict:
    """The app's scalar config surface (bool/int/float/str instance
    attrs — device apps keep per-host state in the engine state dict,
    so scalars are the configuration surface). burst_pops is a
    trace-invariant lane-width knob and is excluded, so retuning
    width neither splits occupancy records nor poisons checkpoint
    fingerprints. Shared by app_fingerprint and the checkpoint
    fingerprint — an app knob that must join or leave the identity
    changes in exactly one place."""
    out = {k: v for k, v in sorted(vars(app).items())
           if isinstance(v, (bool, int, float, str))}
    out.pop("burst_pops", None)
    return out


def app_fingerprint(app) -> str:
    """Workload-variant fingerprint of a device app: its scalar
    config surface plus its per-host parameter arrays (tgen counts/
    pauses, tor relay ids, ...). Two same-class, same-host-count
    apps with different traffic shapes have different occupancy —
    they must not share a record."""
    import hashlib

    h = hashlib.sha256(
        json.dumps(app_scalars(app), sort_keys=True).encode())
    for k, v in sorted(vars(app).items()):
        if isinstance(v, np.ndarray):
            h.update(k.encode())
            h.update(str(v.shape).encode())
            h.update(np.ascontiguousarray(v).tobytes())
    return h.hexdigest()[:12]


def measure(engine, state, source: str = "run") -> dict:
    """Build an occupancy record from a (finished) run's state. The
    occ_* entries are a handful of small per-shard arrays — fetching
    them costs microseconds, never the [H, E] heaps."""
    from shadow_tpu._jax import jax

    H = engine.config.n_hosts
    occ = {k: np.asarray(jax.device_get(state[k]))
           for k in ("occ_heap", "occ_ob", "occ_in", "occ_x",
                     "occ_trips", "occ_phases", "occ_iters",
                     "occ_burst", "overflow", "x_overflow")}
    eff = dict(engine.effective)
    # the full per-(src shard, dst shard) high-water matrix rides the
    # record (a few ints per shard pair): the exchange planner sizes
    # the direct per-pair CAP from its max and the two_phase per-PHASE
    # caps from its row/column aggregates, and choose_exchange
    # compares the variants' estimated ICI volumes from it
    pairs = np.asarray(occ["occ_x"], dtype=np.int64)
    if pairs.ndim > 2:          # ensemble stacks reduce to worst-case
        pairs = pairs.max(axis=tuple(range(pairs.ndim - 2)))
    measured = {
        "heap_rows_max": int(occ["occ_heap"][:H].max(initial=0)),
        "outbox_rows_max": int(occ["occ_ob"][:H].max(initial=0)),
        "arrivals_per_flush_max": int(occ["occ_in"][:H].max(initial=0)),
        "exchange_rows_max": int(occ["occ_x"].max(initial=0)),
        "exchange_pairs": [[int(v) for v in row] for row in pairs],
        "pop_trips_max": int(occ["occ_trips"].max(initial=0)),
        "phases": int(occ["occ_phases"].max(initial=0)),
        "pop_iters": int(occ["occ_iters"].max(initial=0)),
        "burst_pops_used": int(occ["occ_burst"].sum()),
        "overflow": int(occ["overflow"][:H].sum()),
        "x_overflow": int(occ["x_overflow"][:H].sum()),
    }
    if "occ_arrivals" in state:
        # the window merge's flushes only: real arrival rows windowed,
        # of phases x H x IN slots a block (the rest is filler)
        measured["arrivals_windowed"] = int(np.asarray(
            jax.device_get(state["occ_arrivals"])).sum())
    return {
        "format": FORMAT,
        "source": source,
        "workload": {
            "app": type(engine.app).__name__,
            "app_fp": app_fingerprint(engine.app),
            "n_hosts": H,
            "seed": int(engine.config.seed),
            "stop_time": int(engine.config.stop_time),
        },
        "measured": measured,
        "effective": eff,
    }


def merged_measured(record: dict) -> dict:
    """The record's `measured` maxima merged with `final_measured`
    (elementwise for the pair matrix): a capacity_plan: <path> replay
    sizes for steady state, not just the warm-up prefix."""
    m = dict(record["measured"])
    for k, v in record.get("final_measured", {}).items():
        if k not in m:
            continue
        if k == "exchange_pairs":
            a = np.asarray(m[k], dtype=np.int64)
            b = np.asarray(v, dtype=np.int64)
            if a.shape == b.shape:
                m[k] = np.maximum(a, b).tolist()
        else:
            m[k] = max(m[k], v)
    return m


def pair_matrix(m: dict, n_shards: int) -> np.ndarray:
    """The per-(src shard, dst shard) high-water matrix of a merged
    `measured` dict. Records written before the matrix existed (or
    measured on a different shard count) fall back to the scalar
    per-pair max replicated everywhere off-diagonal — a safe upper
    bound that never undershoots what the scalar plan would have."""
    pairs = np.asarray(m.get("exchange_pairs", []), dtype=np.int64)
    if pairs.shape != (n_shards, n_shards):
        pairs = np.full((n_shards, n_shards),
                        int(m.get("exchange_rows_max", 0)),
                        dtype=np.int64)
        np.fill_diagonal(pairs, 0)
    return pairs


def two_phase_caps(pairs: np.ndarray, headroom: float = HEADROOM
                   ) -> tuple[int, int]:
    """Per-phase capacities of the hierarchical two_phase schedule
    from the pair high-water matrix. Shard s = (group a, rank b) with
    g = group_split(S)[0]:

    * phase 1 (intra-group): s ships ONE buffer per in-group rank r
      holding every row destined to rank r in ANY group, so CAP1 must
      hold max over (s, r) of sum_a pairs[s, a*g + r];
    * phase 2 (inter-group): intermediate (a, b) forwards its whole
      group's rows destined (a', b), so CAP2 must hold max over
      (a, b, a' != a) of sum_{s in group a} pairs[s, a'*g + b].

    Sums of per-pair high-water marks upper-bound the high-water of
    the sum, so a plan from these caps can only overshoot — an
    undershoot (the warm-up missed steady state) still fails loudly
    and re-plans, exactly like the direct CAP."""
    S = pairs.shape[0]
    g, ng = group_split(S)
    def pad(x: int) -> int:
        return int(math.ceil(int(x) * headroom)) + SLACK
    # [S, ng, g]: sender s -> (dst group a, dst rank r)
    by_dst = pairs.reshape(S, ng, g)
    cap1 = int(by_dst.sum(axis=1).max(initial=0))
    # [ng, g, ng, g]: (src group, src rank) -> (dst group, dst rank)
    by_both = pairs.reshape(ng, g, ng, g)
    # intermediate (a, b) -> dst group a': sum over src ranks in a of
    # rows destined (a', b); mask the a' == a diagonal (delivered in
    # phase 1, never forwarded)
    fwd = by_both.sum(axis=1)            # [a, a', b]
    eye = np.eye(ng, dtype=bool)[:, :, None]
    cap2 = int(np.where(eye, 0, fwd).max(initial=0))
    return max(8, pad(cap1)), max(8, pad(cap2))


def plan(record: dict, per_iter: int, floor_iters: int = 4,
         n_shards: int = 1, headroom: float = HEADROOM,
         exchange: str = "all_to_all") -> dict:
    """Measured occupancies -> EngineConfig capacity overrides.

    per_iter is the outbox row cost of one pop iteration (K_eff + T
    [+ READY]); outbox_capacity is planned in iterations so the
    engine's B = outbox // per_iter arithmetic lands exactly.

    Saved records carry both the warm-up slice maxima (`measured`)
    and, once the runner finishes, the full run's (`final_measured`)
    — plan from the elementwise max so a capacity_plan: <path> replay
    sizes for steady state, not just the warm-up prefix.

    `exchange` is the (resolved) exchange variant the engine will
    run: the direct all_to_all sizes one per-pair CAP from the occ_x
    high-water mark; two_phase sizes its two per-phase caps from the
    pair matrix aggregates (two_phase_caps); all_gather ships whole
    compacted outboxes and needs no CAP at all."""
    m = merged_measured(record)

    def pad(x: int) -> int:
        return int(math.ceil(x * headroom)) + SLACK

    event_capacity = max(2, pad(m["heap_rows_max"]))
    exchange_in = max(1, pad(m["arrivals_per_flush_max"]))
    # too few iterations per phase costs one collective exchange per
    # few events; too many only pads the (compactable) outbox
    iters = max(floor_iters, pad(m["pop_trips_max"]))
    outbox_capacity = iters * max(1, per_iter)
    # compaction wins only when the busiest host's real fan-out is
    # well under the outbox width (the lane sort must buy sort rows)
    cx = pad(m["outbox_rows_max"])
    outbox_compact = cx if cx < (3 * outbox_capacity) // 4 else 0
    # per shard-pair exchange rows: only meaningful multi-shard; 0
    # keeps the engine's own auto-sizing when nothing was measured
    exchange_capacity = 0
    exchange_capacity2 = 0
    if n_shards > 1 and m["exchange_rows_max"] > 0:
        if exchange == "two_phase":
            exchange_capacity, exchange_capacity2 = two_phase_caps(
                pair_matrix(m, n_shards), headroom)
        elif exchange != "all_gather":
            exchange_capacity = max(8, pad(m["exchange_rows_max"]))
    return {
        "event_capacity": event_capacity,
        "outbox_capacity": outbox_capacity,
        "exchange_capacity": exchange_capacity,
        "exchange_capacity2": exchange_capacity2,
        "exchange_in_capacity": exchange_in,
        "outbox_compact": outbox_compact,
    }


def estimate_ici_rows(record: dict, n_shards: int,
                      per_iter: int, floor_iters: int = 4,
                      headroom: float = HEADROOM) -> dict:
    """Estimated per-flush ICI rows each variant would ship per shard
    under a plan from this record (buffers ship at capacity — padding
    included — so the estimate is the planned cap times the peer
    count, exactly what the wire carries)."""
    m = merged_measured(record)
    S = n_shards
    if S <= 1:
        return {"all_to_all": 0, "two_phase": 0, "all_gather": 0}

    def pad(x: int) -> int:
        return int(math.ceil(x * headroom)) + SLACK

    pairs = pair_matrix(m, S)
    cap = max(8, pad(int(pairs.max(initial=0))))
    g, ng = group_split(S)
    cap1, cap2 = two_phase_caps(pairs, headroom)
    # all_gather replicates each shard's whole compacted outbox
    p = plan(record, per_iter, floor_iters, n_shards=S,
             headroom=headroom, exchange="all_gather")
    w = p["outbox_compact"] or p["outbox_capacity"]
    h_loc = -(-record["workload"]["n_hosts"] // S)
    return {
        "all_to_all": (S - 1) * cap,
        "two_phase": (g - 1) * cap1 + (ng - 1) * cap2,
        "all_gather": (S - 1) * h_loc * w,
    }


def choose_exchange(record: dict, n_shards: int, per_iter: int,
                    floor_iters: int = 4,
                    headroom: float = HEADROOM) -> tuple[str, dict]:
    """`exchange: auto` resolution from a measured occupancy record:
    compare the variants' estimated per-flush ICI rows and pick the
    cheapest. two_phase must beat the direct all_to_all by
    TWO_PHASE_MARGIN (its two collectives + extra on-device sort are
    only worth real bandwidth savings), and a degenerate group split
    (prime shard count) never qualifies. Returns (variant, info)."""
    est = estimate_ici_rows(record, n_shards, per_iter, floor_iters,
                            headroom)
    info = {"estimates": est, "n_shards": n_shards,
            "group_split": list(group_split(n_shards))}
    if n_shards <= 1:
        return "all_to_all", info
    choice = "all_to_all"
    if est["all_gather"] < est["all_to_all"]:
        choice = "all_gather"
    g, _ = group_split(n_shards)
    # two_phase must beat the DIRECT schedule by the margin (the
    # documented rule) and also be the overall minimum
    if g > 1 and est["two_phase"] < \
            TWO_PHASE_MARGIN * est["all_to_all"] and \
            est["two_phase"] < est[choice]:
        choice = "two_phase"
    info["chosen"] = choice
    return choice, info


def widen(knobs: dict, dims: tuple, effective: dict) -> dict:
    """Double the offending capacity dimension(s) after a loud
    overflow. `knobs` are the current EngineConfig overrides (may hold
    zeros meaning auto); `effective` supplies the auto-sized values so
    doubling always starts from what actually ran."""
    out = dict(knobs)
    for dim in dims:
        if dim == "event_capacity":
            out[dim] = 2 * max(out.get(dim) or 0, effective["E"])
        elif dim == "exchange_in_capacity":
            out[dim] = 2 * max(out.get(dim) or 0, effective["IN"])
        elif dim == "exchange_capacity":
            if effective["CAP"] > 0:
                out[dim] = 2 * max(out.get(dim) or 0, effective["CAP"])
        elif dim == "exchange_capacity2":
            # only live on the two_phase schedule (CAP2 > 0); the
            # x_overflow counter cannot tell which phase lost rows,
            # so both caps double together
            if effective.get("CAP2", 0) > 0:
                out[dim] = 2 * max(out.get(dim) or 0,
                                   effective["CAP2"])
        elif dim == "outbox_compact":
            # a compaction width that lost rows first doubles, then
            # turns off once it stops paying for itself
            cx, ob = effective["CX"], effective["OB"]
            if cx < ob:
                ncx = 2 * cx
                out[dim] = ncx if ncx < ob else 0
    return out


def overflow_dims(state) -> tuple:
    """Which capacity dimensions the state's loud counters implicate
    (empty tuple = clean). Costs two tiny device_gets."""
    from shadow_tpu._jax import jax

    dims = ()
    for counter, d in OVERFLOW_DIMS.items():
        if int(np.asarray(jax.device_get(state[counter])).sum()):
            dims += d
    return dims


def grow_heaps(host_state: dict, new_e: int) -> dict:
    """Pad the five [..., H, E] heap arrays of a host-side state
    snapshot to a larger event_capacity (rows are sorted; empty slots
    sort last, so tail padding preserves the heap invariant). Works
    on standalone [H, E] states and on ensemble [R, H, E] stacks —
    the slot axis is always last."""
    INF = np.int64(1) << np.int64(62)
    IMAX = np.int64(np.iinfo(np.int64).max)
    out = dict(host_state)
    *lead, e = host_state["ht"].shape
    if new_e < e:
        raise ValueError(f"cannot shrink event_capacity {e} -> {new_e} "
                         "on a live state")
    if new_e == e:
        return out
    fills = {"ht": INF, "hk": IMAX, "hm": 0, "hv": 0, "hw": 0}
    for k, fill in fills.items():
        pad = np.full(tuple(lead) + (new_e - e,), fill,
                      dtype=np.int64)
        out[k] = np.concatenate([np.asarray(host_state[k]), pad], -1)
    return out


# reshard_state's leaf classification: every key the engine may put
# in state must fall in exactly one class — an unregistered key fails
# loudly, so a new state leaf cannot be silently mis-resharded.
# (Per-host vector leaves — counters, seq/chk, occ_heap/ob/in, aud*,
# NIC scalars — are the residual class, shape-checked against the
# padded width.)
RESHARD_HOST_ROWS = ("ht", "hk", "hm", "hv", "hw", "app")
RESHARD_SHARD_ZERO = ("occ_x", "occ_trips", "occ_phases", "occ_iters",
                      "occ_burst", "occ_arrivals")
RESHARD_SHARD_SUM = ("path_cnt",)


def reshard_state(host_state: dict, n_hosts: int,
                  template_host: dict) -> dict:
    """Carry a host-side state snapshot across a mesh-geometry change
    (the elastic shrink failover's core transform): because
    ``H_pad = ceil(H / n_shards) * n_shards``, a different shard
    count means a different padded width, so every per-host leaf is
    re-padded row-for-row rather than transferred whole.

    ``template_host`` is a host-side copy of the TARGET engine's
    freshly initialized state (``device_get`` of ``init_state`` /
    ``init_ensemble_state``): its shapes define the new padded layout
    and its values supply the padding rows' contents (app init rows,
    INF/IMAX heap fills, zeroed counters) — exactly what an
    uninterrupted run on the target mesh holds for hosts that never
    execute. The first ``n_hosts`` rows along the host axis carry
    over verbatim, so per-host counters, event heaps, and trace
    checksums — the determinism surface — are untouched; combined
    with the engine's mesh-shape determinism contract, the resharded
    continuation is bit-identical to an uninterrupted run on the
    target mesh. Per-shard telemetry resets (high-water marks
    measured on the old geometry describe buffers that no longer
    exist) and the per-shard path histogram's partial sums
    re-aggregate onto shard 0 (row totals are the reported surface).
    Works on standalone ``[H, ...]`` states and ensemble
    ``[R, H, ...]`` stacks alike — the host axis position per leaf
    is fixed, only leading axes broadcast."""
    extra = set(host_state) - set(template_host)
    if any(not _aux_leaf(k) for k in extra):
        raise ValueError(
            "reshard_state: snapshot carries leaves the target "
            f"engine lacks: {sorted(extra)}")
    old_pad = np.asarray(host_state["ht"]).shape[-2]
    new_pad = np.asarray(template_host["ht"]).shape[-2]
    H = int(n_hosts)
    if not (0 < H <= old_pad and H <= new_pad):
        raise ValueError(
            f"reshard_state: n_hosts {H} does not fit the padded "
            f"widths (old {old_pad}, new {new_pad})")
    out = {}
    for k, tmpl in template_host.items():
        new = np.array(tmpl)        # the target padding, host-side
        if k not in host_state:
            if k == "aud_tx":
                # the snapshot predates the audit (a rotation entry
                # written with state_audit off): reseed the
                # conservation ledger from the saved counters, the
                # checkpoint.load_state rule — per-host, so the
                # global balance holds exactly at the resume point
                ht = np.asarray(host_state["ht"])
                head = np.asarray(host_state["head"])
                E = ht.shape[-1]
                live = ((np.arange(E) >= head[..., None]) &
                        (ht < (np.int64(1) << np.int64(62)))).sum(-1)
                recon = (np.asarray(host_state["n_exec"])
                         .astype(np.int64) + live
                         + np.asarray(host_state["overflow"])
                         .astype(np.int64)
                         + np.asarray(host_state["x_overflow"])
                         .astype(np.int64))
                new[..., :H] = recon[..., :H]
            elif not _aux_leaf(k):
                raise ValueError(
                    f"reshard_state: snapshot is missing leaf {k!r}")
            out[k] = new
            continue
        old = np.asarray(host_state[k])
        if k in RESHARD_HOST_ROWS:
            if old.shape[-1] != new.shape[-1] or \
                    old.shape[:-2] != new.shape[:-2] or \
                    old.shape[-2] != old_pad or \
                    new.shape[-2] != new_pad:
                raise ValueError(
                    f"reshard_state: leaf {k} is {old.shape}, target "
                    f"expects {new.shape} — reshard carries geometry "
                    "only, never capacity or replica changes")
            new[..., :H, :] = old[..., :H, :]
        elif k in RESHARD_SHARD_ZERO:
            new[...] = 0
        elif k in RESHARD_SHARD_SUM:
            new[...] = 0
            new[..., 0, :] = old.sum(axis=-2)
        elif old.shape[:-1] == new.shape[:-1] and \
                old.shape[-1] == old_pad and \
                new.shape[-1] == new_pad:
            new[..., :H] = old[..., :H]
        else:
            raise ValueError(
                f"reshard_state: leaf {k!r} ({old.shape} -> "
                f"{new.shape}) is not registered in any reshard "
                "class — classify it in capacity.RESHARD_* before "
                "adding state leaves")
        out[k] = new
    return out


def _aux_leaf(k: str) -> bool:
    """Auxiliary leaves that may differ between the saving and
    resuming engines without perturbing the trace (the
    checkpoint.load_state rule): occupancy telemetry and the
    invariant-audit word."""
    return k.startswith("occ_") or k.startswith("aud")


def transfer(engine, starts, host_state: dict,
             template: dict = None) -> dict:
    """Place a host-side state snapshot onto a (re-planned) engine:
    pads the heaps to the engine's event_capacity and device_puts
    every leaf with the sharding of a freshly built template state.
    `template` overrides the standalone init_state template (the
    ensemble runner passes its [R, ...] init_ensemble_state)."""
    from shadow_tpu._jax import jax

    host_state = grow_heaps(host_state, engine.config.event_capacity)
    if template is None:
        template = engine.init_state(starts)
    if set(template) != set(host_state):
        raise ValueError(
            "state keys changed across re-plan: "
            f"{sorted(set(template) ^ set(host_state))}")
    out = {}
    for k, tmpl in template.items():
        arr = np.asarray(host_state[k])
        if arr.shape != tmpl.shape or arr.dtype != np.dtype(tmpl.dtype):
            raise ValueError(
                f"state leaf {k} is {arr.shape}/{arr.dtype}, the "
                f"re-planned engine expects {tmpl.shape}/{tmpl.dtype}")
        out[k] = jax.device_put(arr, tmpl.sharding)
    return out


def record_path(engine, directory: str = "") -> str:
    """Canonical OCC record path for a workload: app class + host
    count + workload fingerprint (deterministic, so tune_10k.py and
    repeat runs find it; the fingerprint keeps two traffic-shape
    variants of the same app from clobbering each other's record).
    SHADOW_TPU_OCC_DIR overrides the directory (tests point it at a
    tmpdir so runs never litter the repo's artifacts/)."""
    directory = directory or os.environ.get("SHADOW_TPU_OCC_DIR",
                                            "artifacts")
    return os.path.join(
        directory,
        f"OCC_{type(engine.app).__name__}_{engine.config.n_hosts}"
        f"_{app_fingerprint(engine.app)}.json")


def save_record(record: dict, path: str) -> None:
    from shadow_tpu.obs import trace as obstrace
    from shadow_tpu.utils.artifacts import atomic_write_json

    atomic_write_json(record, path)
    # flight-recorder marker: OCC record writes are plan-phase
    # milestones worth a tick on the run timeline
    obstrace.current().instant("occ.save", "plan", path=path)


def load_record(path: str) -> dict:
    from shadow_tpu.obs import trace as obstrace

    with open(path) as f:
        record = json.load(f)
    if record.get("format") != FORMAT:
        raise ValueError(
            f"occupancy record {path}: format {record.get('format')} "
            f"(this build reads format {FORMAT})")
    for key in ("measured", "workload"):
        if key not in record:
            raise ValueError(f"occupancy record {path}: missing {key!r}")
    obstrace.current().instant("occ.load", "plan", path=path)
    return record


# ----------------------------------------------------------------------
# preflight admission: footprint estimate vs per-device budget
# ----------------------------------------------------------------------
# The byte model counts exactly what the engine pins on device: the
# sharded state pytree (state_structs), two live copies of it (the
# segment in flight plus the last validated snapshot), the replica
# axis R, the per-flush outbox and exchange buffers at their
# effective capacities, and the replicated world tables. XLA's
# transient workspace (sort scratch, fusion temporaries) is
# deliberately NOT modeled — the estimate is a floor on steady-state
# live bytes, and the honesty tests pin it to measured live bytes
# within FOOTPRINT_TOLERANCE.
FOOTPRINT_TOLERANCE = 4.0


def _nbytes(struct) -> int:
    """Bytes of one ShapeDtypeStruct (shape may be empty)."""
    n = 1
    for d in struct.shape:
        n *= int(d)
    return n * np.dtype(struct.dtype).itemsize


def footprint(engine, replicas: int = None) -> dict:
    """Static per-device byte model of an engine's resident state —
    from the same resolved inputs program_facts reports, with zero
    device work (admission must run BEFORE any compile).

    ``replicas`` overrides the engine's ensemble width (the
    replica-batch rungs of the degradation ladder estimate a k-replica
    batch against the full-R engine before building it)."""
    eff = engine.effective
    S = max(1, int(eff["n_shards"]))
    ens = getattr(engine, "ensemble", None)
    R_full = int(ens.R) if ens is not None else 1
    R = max(1, int(replicas if replicas is not None else R_full))
    # one copy of one replica's sharded state, per device
    structs = engine.state_structs()
    state_total = sum(_nbytes(v) for v in structs.values())
    state_dev = -(-state_total // S)
    # the advance loop holds the segment in flight plus the last
    # validated snapshot (rewind source) concurrently
    copies = 2
    # per-flush scratch: the 5 int64 outbox field arrays plus the
    # exchange send+receive buffers at the effective capacities
    H_pad, OB = engine._ob_shape_global
    outbox_dev = 5 * (-(-int(H_pad) // S)) * int(OB) * 8
    h_loc = -(-int(H_pad) // S)
    g, ng = (int(x) for x in eff["tp_groups"])
    if S <= 1:
        rows = 0
    elif eff["exchange"] == "two_phase":
        rows = g * int(eff["CAP"]) + ng * int(eff["CAP2"])
    elif eff["exchange"] == "all_gather":
        rows = S * h_loc * int(eff["CX"])
    else:
        rows = S * int(eff["CAP"])
    exchange_dev = 2 * rows * 6 * 8          # send + recv, ~6 fields
    scratch = (outbox_dev + exchange_dev) * R
    # world tables replicate on every device; ensemble stacks them
    # [R]. Under the hierarchical representation the latency /
    # reliability slots are TUPLES of factored leaves ([C,C] + [V]
    # vectors), so flatten the pytree and price the actual uploaded
    # arrays — the whole point of the representation is that this sum
    # is MBs where the dense [V,V] pair would be GBs.
    from shadow_tpu._jax import jax

    ws = engine.world_structs(ensemble=ens is not None)
    world_total = sum(_nbytes(s)
                     for s in jax.tree_util.tree_leaves(ws))
    if ens is not None and R_full:
        world_total = (world_total * R) // R_full
    hier = isinstance(getattr(engine, "latency", None), tuple)
    per_device = state_dev * copies * R + scratch + world_total
    return {
        "representation": "hierarchical" if hier else "dense",
        "per_device": int(per_device),
        "total": int(per_device * S),
        "state_bytes": int(state_dev),
        "scratch_bytes": int(scratch),
        "world_bytes": int(world_total),
        "copies": int(copies),
        "replicas": int(R),
        "n_devices": int(S),
    }


def fmt_bytes(n) -> str:
    """Human-readable byte count for admission diagnostics."""
    n = float(int(n))
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024.0:
            return (f"{int(n)} B" if unit == "B"
                    else f"{n:.1f} {unit}")
        n /= 1024.0
    return f"{n:.1f} TiB"


def device_budget(engine, xp) -> tuple:
    """(per-device budget bytes, source). The backend's reported
    bytes_limit wins when it exposes one (TPU/GPU); else the
    operator's experimental.device_memory_budget; else (0, "") —
    no budget, admission: auto skips and strict refuses."""
    try:
        dev = list(engine.mesh.devices.flat)[0]
        ms = dev.memory_stats()
        if ms and int(ms.get("bytes_limit", 0) or 0) > 0:
            return int(ms["bytes_limit"]), "backend"
    except Exception:
        pass
    b = int(getattr(xp, "device_memory_budget", 0) or 0)
    if b > 0:
        return b, "config"
    return 0, ""


def admission_diagnostic(est: dict, budget: int, source: str) -> str:
    return (
        f"admission: needs {fmt_bytes(est['per_device'])} per device, "
        f"budget {fmt_bytes(budget)} ({source}) on "
        f"{est['n_devices']} device(s) — state "
        f"{fmt_bytes(est['state_bytes'])} x {est['copies']} copies x "
        f"R={est['replicas']}, scratch "
        f"{fmt_bytes(est['scratch_bytes'])}, world "
        f"{fmt_bytes(est['world_bytes'])} "
        f"({est.get('representation', 'dense')} tables); raise the "
        "budget or lower ensemble.replicas / capacities")


def admission_verdict(engine, xp, batchable: bool = False) -> dict:
    """The preflight admission gate, shared by both runners.

    * ``strict``  — refuse an over-budget estimate outright (raises
      ValueError with the readable diagnostic) before any compile.
    * ``auto``    — degrade statically along the same ladder the
      runtime walks (split the ensemble into replica batches); if
      the estimate still exceeds the budget, admit LOUDLY — the
      runtime degradation ladder in supervise.advance is the
      backstop for what the static model cannot shed
      (dispatch_segment halving, failover).
    * ``off``     — skip entirely.

    Returns the verdict dict the runners stash on ``runner.admission``
    (SimStats.admission carries it; the campaign reads the
    replica_batch override)."""
    mode = str(getattr(xp, "admission", "auto"))
    ens = getattr(engine, "ensemble", None)
    R_full = int(ens.R) if ens is not None else 1
    est = footprint(engine)
    budget, source = device_budget(engine, xp)
    out = {"mode": mode, "budget": int(budget),
           "budget_source": source, "estimate": est,
           "action": "admit", "fits": True, "overrides": {}}
    if mode == "off":
        out["action"] = "off"
        return out
    if budget <= 0:
        if mode == "strict":
            raise ValueError(
                "experimental.admission: strict needs a per-device "
                "budget, but the backend reports none and "
                "experimental.device_memory_budget is unset")
        out["action"] = "no-budget"
        return out
    if est["per_device"] <= budget:
        log.info("admission: fits — %s per device of %s (%s)",
                 fmt_bytes(est["per_device"]), fmt_bytes(budget),
                 source)
        return out
    diag = admission_diagnostic(est, budget, source)
    if mode == "strict":
        raise ValueError(diag)
    # auto: statically walk the ladder's estimable rungs
    overrides = {}
    batch = R_full
    while est["per_device"] > budget and batchable and batch > 1:
        batch = (batch + 1) // 2
        overrides["replica_batch"] = batch
        est = footprint(engine, replicas=batch)
    out["estimate"] = est
    out["overrides"] = overrides
    out["fits"] = est["per_device"] <= int(budget)
    if out["fits"]:
        out["action"] = "degrade"
        log.warning("%s — degraded preflight to %s (now %s per "
                    "device)", diag, overrides,
                    fmt_bytes(est["per_device"]))
    else:
        out["action"] = "over"
        log.warning("%s — admitting anyway (admission: auto); the "
                    "runtime degradation ladder is the backstop",
                    diag)
    return out
