"""On-chip microbenchmarks for the device engine, one parameterized
driver (the former tpu_micro.py / tpu_micro2.py / tpu_micro3.py /
tpu_micro4.py clones, consolidated):

  python scripts/tpu_micro.py [--variant N] [variant args...]

variant 1 (default) — round-step cost attribution at a real config's
  shapes: fused run baseline, pipelined pop/flush phase timings, and
  the hot flush primitives (flat sort, merge sort, judge threefry,
  segment gathers) standalone. Args: [config] [stop_s] [reps].
variant 2 — multi-operand sorts vs gather recovery (the flush's
  ~10 ms-per-gather takes vs 1.6-2.6 ms sorts) at the 10k-rung
  shapes: 6-operand flat sort, 5-operand merge sort, window takes,
  row-stacked gathers, the filler-sort expand. Args: [reps] [tor];
  "tor" times instead the window merge's payload recovery at
  tor_56000's shapes ([56,000 x 160]: a (t, key, iota) row sort +
  three take_along_axis against a row sort that carries the
  payload) and the flush's arrival windows ([56,000 x 40] outbox
  rows into [56,000 x 64]: seg_take's gathers against one filler sort
  carrying the merge columns), exiting 1 if the forms disagree.
variant 3 — the candidate gatherless flush (double-sort merge) timed
  end-to-end at the 10k-rung shapes + a numpy oracle check at a small
  shape. Args: [reps].
variant 4 — the round's remaining gathers + one-hot pop head reads:
  host_vertex/table gathers vs unrolled one-hot sums, P=1 and P=8 pop
  reads, and the judge's lookups at R = 2, 9 and 128 host-vertex runs
  (gathers vs run-table selects vs per-host rows). Args: [reps].
variant 5 — the cross-shard exchange in isolation (IPU-dissection
  style attribution): the flush phase timed per exchange schedule —
  dense auto-sized all_to_all, occ_x-planned (compacted) all_to_all,
  two_phase, all_gather — at a real config's shapes on the visible
  mesh, with per-flush ICI rows/bytes from the engine's static
  accounting. Args: [config] [stop_s] [reps].
variant 6 — compile/dispatch attribution (IPU-dissection style,
  arxiv 1912.03413): per-program lower / compile / AOT-cache
  serialize+load / first-dispatch / steady walls for the round
  program and each profiling split (pop, flush), printed as ONE
  table — the cold-start budget the persistent AOT compile cache
  (device/aotcache.py) collapses, measured piece by piece.
  Args: [config] [stop_s] [reps].

Every variant prints ONE JSON line. Timings use pipelined (async)
dispatches with one final block so per-call overhead amortizes away —
the numbers are on-chip costs, not dispatch RTTs. The fused round's
per-stage device time comes from a profiler trace instead: its ops
carry `engine.*` scopes (docs/observability.md).
"""

from __future__ import annotations

import argparse
import json
import signal
import sys
import time

sys.path.insert(0, ".")

REPS = 30


def timed(label, fn, reps):
    """Pipelined repeat: dispatch `reps` identical calls, block once.
    Returns seconds per call."""
    from shadow_tpu._jax import jax
    out = fn()
    jax.block_until_ready(out)          # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / reps
    print(f"  [{label}] {1e3 * dt:.3f} ms/call", file=sys.stderr,
          flush=True)
    return dt


def timed_ms(label, fn, reps):
    return round(1e3 * timed(label, fn, reps), 3)


# ---------------------------------------------------------------------
# variant 1: round-step cost attribution at a real config's shapes
# ---------------------------------------------------------------------
def variant1(args: list[str]) -> int:
    cfg_path = args[0] if len(args) > 0 else "examples/tgen_10000.yaml"
    stop_s = float(args[1]) if len(args) > 1 else 2.5
    reps = int(args[2]) if len(args) > 2 else REPS

    from shadow_tpu import simtime
    from shadow_tpu._jax import jax, jnp
    from jax import lax
    from shadow_tpu.config import load_config
    from shadow_tpu.core.controller import Controller
    from shadow_tpu.device import prng
    from shadow_tpu.device.netsem import packet_drop_mask
    from shadow_tpu.device.engine import INF

    cfg = load_config(cfg_path)
    cfg.experimental.scheduler_policy = "tpu"
    cfg.general.stop_time = simtime.from_seconds(stop_s)
    c = Controller(cfg)
    eng = c.runner.engine
    ec = eng.config
    stop = simtime.from_seconds(stop_s)
    res = {"variant": 1, "config": cfg_path,
           "platform": jax.devices()[0].platform,
           "slice_sim_s": stop_s, "reps": reps}

    # ---- fused baseline --------------------------------------------
    st = eng.init_state(c.sim.starts)
    t0 = time.perf_counter()
    st_out, rounds = eng.run(st, stop=stop)
    jax.block_until_ready(st_out)
    res["fused_compile_plus_run_s"] = round(time.perf_counter() - t0, 3)
    st = eng.init_state(c.sim.starts)
    t0 = time.perf_counter()
    st_out, rounds = eng.run(st, stop=stop)
    jax.block_until_ready(st_out)
    fused_s = time.perf_counter() - t0
    rounds = int(rounds)
    res["fused_run_s"] = round(fused_s, 3)
    res["fused_rounds"] = rounds
    res["fused_ms_per_round"] = round(1e3 * fused_s / max(1, rounds), 3)
    print(f"fused: {fused_s:.3f}s / {rounds} rounds = "
          f"{res['fused_ms_per_round']:.1f} ms/round", file=sys.stderr,
          flush=True)

    # ---- mid-run state + a filled outbox for phase timing ----------
    st = eng.init_state(c.sim.starts)
    st_mid, _ = eng.run(st, stop=stop // 2, final_stop=stop)
    jax.block_until_ready(st_mid)
    from jax.sharding import NamedSharding
    repl = NamedSharding(eng.mesh, eng._repl_spec)
    shard = NamedSharding(eng.mesh, eng._shard_spec)
    hv = jax.device_put(jnp.asarray(eng.host_vertex), repl)
    wrld = eng.world()
    nxt, _ = map(int, eng._probe(st_mid))
    win_end = jnp.int64(min(nxt + max(1, ec.lookahead), stop))

    def fresh_ob():
        ob = {"t": jax.device_put(
            jnp.full(eng._ob_shape_global, INF, jnp.int64), shard)}
        for f in ("k", "m", "s", "v"):
            ob[f] = jax.device_put(
                jnp.zeros(eng._ob_shape_global, jnp.int64), shard)
        return ob

    ob0 = fresh_ob()
    st_pop, ob_full, _ = eng._pop_phase(st_mid, ob0, hv, wrld,
                                        win_end)
    jax.block_until_ready((st_pop, ob_full))

    # calibration: per-dispatch overhead of a trivial jitted call
    noop = jax.jit(lambda x: x + 1)
    res["noop_ms"] = timed_ms("noop", lambda: noop(jnp.int64(1)),
                              reps)

    res["pop_ms"] = timed_ms(
        "pop_phase", lambda: eng._pop_phase(
            st_mid, ob0, hv, wrld, win_end), reps)
    res["flush_ms"] = timed_ms(
        "flush_phase", lambda: eng._flush_phase(
            st_pop, ob_full, hv, wrld, win_end), reps)

    # ---- flush primitives at the engine's exact shapes -------------
    H_loc = eng.H_loc
    E = ec.event_capacity
    IN = ec.exchange_in_capacity or E
    app = eng.app
    K_eff = max(1, getattr(app, "burst_pops", 1)) \
        if getattr(app, "burst_pops", 1) > 1 else app.max_sends
    M_out = K_eff + app.max_timers
    B = max(1, ec.outbox_capacity // max(1, M_out))
    OB = B * M_out
    C = max(1, getattr(app, "max_train", 1))
    F = H_loc * OB
    res["shapes"] = {"H_loc": H_loc, "E": E, "IN": IN, "OB": OB,
                     "C": C, "F": F, "B": B}

    import numpy as np
    skey = jax.device_put(jnp.asarray(
        np.random.default_rng(0).integers(0, 1 << 60, F)
        .astype(np.int64)))
    iota = jnp.arange(F, dtype=jnp.int64)
    flat_sort = jax.jit(
        lambda k: lax.sort((k, iota), num_keys=1))
    res["flat_sort_ms"] = timed_ms(
        f"flat_sort F={F}", lambda: flat_sort(skey), reps)

    W = E + IN
    ct = jax.device_put(jnp.asarray(
        np.random.default_rng(1).integers(0, 1 << 60, (H_loc, W))
        .astype(np.int64)))
    ck = jax.device_put(jnp.asarray(
        np.random.default_rng(2).integers(0, 1 << 60, (H_loc, W))
        .astype(np.int64)))
    ci = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32)[None, :],
                          (H_loc, W))
    merge_sort = jax.jit(
        lambda a, b: lax.sort((a, b, ci), dimension=1, num_keys=2))
    res["merge_sort_ms"] = timed_ms(
        f"merge_sort [{H_loc},{W}]x3", lambda: merge_sort(ct, ck),
        reps)

    # payload recovery gathers (3x take_along_axis at merge width)
    cm = ck
    sie = jnp.asarray(
        np.random.default_rng(3).integers(0, W, (H_loc, E))
        .astype(np.int32))
    gat = jax.jit(lambda m: jnp.take_along_axis(m, sie, axis=1))
    res["merge_gather_ms"] = timed_ms(
        "merge_gather x1", lambda: gat(cm), reps)

    # seg_take: 5 fields, [H_loc*IN] random takes from F rows
    pidx = jnp.asarray(
        np.random.default_rng(4).integers(0, F, H_loc * IN)
        .astype(np.int64))
    segtake = jax.jit(lambda v: jnp.take(v, pidx))
    res["seg_take_ms_x1"] = timed_ms(
        "seg_take x1 field", lambda: segtake(skey), reps)

    # judge threefry: drop mask at [H_loc, OB, C]
    seed_pair = eng.seed_pair
    ft = jax.device_put(jnp.asarray(
        np.random.default_rng(5).integers(0, 1 << 40, (H_loc, OB))
        .astype(np.int64)))
    gid = jnp.arange(H_loc, dtype=jnp.int32)
    seqs3 = jnp.asarray(
        np.random.default_rng(6).integers(0, 1 << 30, (H_loc, OB, C))
        .astype(np.int32))
    relv = jnp.full((H_loc, OB, 1), 0.999, jnp.float32)

    def judge():
        from shadow_tpu.utils.rng import PURPOSE_PACKET_DROP
        hk1, hk2 = prng.purpose_id_key(seed_pair, PURPOSE_PACKET_DROP,
                                       gid)
        return packet_drop_mask(
            seed_pair, jnp.int64(0), ft[..., None],
            gid[:, None, None], seqs3, relv,
            src_key=(hk1[:, None, None], hk2[:, None, None]))

    judge_j = jax.jit(judge)
    res["judge_threefry_ms"] = timed_ms(
        f"judge [{H_loc},{OB},{C}]", judge_j, reps)

    # searchsorted over F at H_loc+1 boundaries
    hb = jnp.arange(H_loc + 1, dtype=jnp.int64) * (F // H_loc)
    ss = jax.jit(lambda k: jnp.searchsorted(k, hb))
    skey_sorted = jnp.sort(skey)
    res["searchsorted_ms"] = timed_ms(
        "searchsorted", lambda: ss(skey_sorted), reps)

    print(json.dumps(res), flush=True)
    return 0


# ---------------------------------------------------------------------
# variant 2: multi-operand sorts vs gather recovery
# ---------------------------------------------------------------------
def merge_payload_cases(H, E, IN, reps, rng):
    """The window merge's payload recovery over [H, E + IN] rows
    (engine.py `_exchange`, merge_payload): sort (t, key, iota) by
    (t, key) and take the three payload columns with take_along_axis
    (gather_ms; the sort alone: sort3_only_ms), against one sort that
    carries them (carry_ms, the third column riding as a u32 operand,
    as in the engine; carry_w64_ms keeps it i64). Live rows hold unique
    (t, key) pairs, a quarter of the rows are padding (t = INF).
    Returns the timings and whether the three forms agree on every
    live slot."""
    import numpy as np
    from shadow_tpu._jax import jax, jnp
    from jax import lax

    W = E + IN
    INF = np.int64(1) << 62
    t = rng.integers(0, 1 << 40, (H, W)).astype(np.int64)
    t[rng.random((H, W)) < 0.25] = INF
    k = (rng.integers(0, 1 << 20, (H, W)).astype(np.int64) << 32) \
        | np.arange(W, dtype=np.int64)[None, :]
    k = np.where(t < INF, k, np.iinfo(np.int64).max)
    ct, ck = (jax.device_put(jnp.asarray(a)) for a in (t, k))
    cm, cv = (jax.device_put(jnp.asarray(
        rng.integers(0, 1 << 62, (H, W)).astype(np.int64)))
        for _ in range(2))
    cw = jax.device_put(jnp.asarray(
        rng.integers(0, 1 << 32, (H, W)).astype(np.int64)))

    def gather(t, k, m, v, w):
        ci = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32)[None, :],
                              (H, W))
        st, sk, si = lax.sort((t, k, ci), dimension=1, num_keys=2)
        sie = si[:, :E]
        return (st[:, :E], sk[:, :E],
                jnp.take_along_axis(m, sie, axis=1),
                jnp.take_along_axis(v, sie, axis=1),
                jnp.take_along_axis(w, sie, axis=1))

    def sort_only(t, k):
        ci = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32)[None, :],
                              (H, W))
        st, sk, si = lax.sort((t, k, ci), dimension=1, num_keys=2)
        return st[:, :E], sk[:, :E], si[:, :E]

    def carry(t, k, m, v, w):
        st, sk, sm, sv, sw = lax.sort(
            (t, k, m, v, w.astype(jnp.uint32)), dimension=1, num_keys=2)
        return (st[:, :E], sk[:, :E], sm[:, :E], sv[:, :E],
                sw[:, :E].astype(jnp.int64))

    def carry64(t, k, m, v, w):
        out = lax.sort((t, k, m, v, w), dimension=1, num_keys=2)
        return tuple(o[:, :E] for o in out)

    tag = f"[{H},{W}]"
    fg, fs, fc, f64 = (jax.jit(f) for f in (gather, sort_only, carry,
                                            carry64))
    args = (ct, ck, cm, cv, cw)
    res = {"shape": [H, W], "E": E,
           "gather_ms": timed_ms(f"sort3 + 3 take_along {tag}",
                                 lambda: fg(*args), reps),
           "sort3_only_ms": timed_ms(f"sort3 alone {tag}",
                                     lambda: fs(ct, ck), reps),
           "carry_ms": timed_ms(f"carry sort5 (w u32) {tag}",
                                lambda: fc(*args), reps),
           "carry_w64_ms": timed_ms(f"carry sort5 (w i64) {tag}",
                                    lambda: f64(*args), reps)}
    outs = [[np.asarray(a) for a in f(*args)] for f in (fg, fc, f64)]
    live = outs[0][0] < INF
    res["live_equal"] = all(
        np.array_equal(o[c][live], outs[0][c][live])
        for o in outs[1:] for c in range(5))
    return res


def arrival_window_cases(H, OB, IN, reps, rng):
    """The flush's per-host arrival windows over [H, IN] (engine.py
    `_host_windows`) from H*OB unsorted outbox rows: the (key, iota)
    flat sort, searchsorted counts and seg_take's two-hop gathers of
    the five i64 fields (gather_ms), against a key-only flat sort for
    the counts and one filler sort carrying the five merge columns
    (sort_ms, engine.sorted_windows), and against the gathers cut to
    an i32 permutation and the packed merge words (fallback_ms).
    Arrivals follow Tor's fan-in: a tenth of the hosts are relays with
    Poisson(27) arrivals, the rest clients with at most one; host 0
    takes exactly IN, host 1 none. Returns the timings, each form's
    compile seconds and temp bytes, the filler share, and whether the
    three forms give equal windows."""
    import numpy as np
    from shadow_tpu._jax import jax, jnp
    from jax import lax
    from shadow_tpu.device.engine import (IMAX, INF, XF, merge_cols,
                                          seg_take, sorted_windows)

    F, SPAN = H * OB, np.int64(H) * OB
    want = np.where(np.arange(H) < H // 10,
                    np.minimum(rng.poisson(27, H), IN),
                    rng.binomial(1, 0.5, H))
    want[0], want[1] = IN, 0
    slots = rng.choice(F, int(want.sum()), replace=False)
    ukey = np.full(F, IMAX, np.int64)
    ukey[slots] = np.repeat(np.arange(H, dtype=np.int64), want) * SPAN \
        + slots
    rows = {f: rng.integers(0, 1 << 62, F).astype(np.int64) for f in XF}
    rows["t"] = np.where(ukey < IMAX, rows["t"] >> 22, INF)
    args = (jax.device_put(jnp.asarray(ukey)),
            {f: jax.device_put(jnp.asarray(v)) for f, v in rows.items()})
    hb = jnp.arange(H + 1, dtype=jnp.int64) * SPAN

    def counted(skey):
        edges = jnp.searchsorted(skey, hb)
        return edges[:-1], edges[1:] - edges[:-1]

    def gather(ukey, rows):
        skey, perm = lax.sort((ukey, jnp.arange(F, dtype=jnp.int64)),
                              num_keys=1)
        starts, counts = counted(skey)
        return merge_cols(seg_take(perm, rows, starts, counts, IN))

    def sort(ukey, rows):
        _, counts = counted(lax.sort(ukey, is_stable=False))
        return sorted_windows(ukey, merge_cols(rows), counts,
                              jnp.int64(0), SPAN, IN)

    def fallback(ukey, rows):
        skey, perm = lax.sort((ukey, jnp.arange(F, dtype=jnp.int32)),
                              num_keys=1)
        starts, counts = counted(skey)
        cols = merge_cols(rows)
        packed = dict(zip(XF, cols[:4] + (cols[4].astype(jnp.uint32),)))
        win = seg_take(perm, packed, starts, counts, IN)
        return tuple(win[f] for f in XF[:4]) + \
            (win["v"].astype(jnp.int64),)

    res = {"shape": [H, OB], "IN": IN,
           "filler_share": round(
               1 - float(np.minimum(want, IN).sum()) / (H * IN), 6)}
    outs = {}
    for name, fn in (("gather", gather), ("sort", sort),
                     ("fallback", fallback)):
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        res[f"{name}_compile_s"] = round(time.perf_counter() - t0, 3)
        res[f"{name}_temp_bytes"] = int(
            compiled.memory_analysis().temp_size_in_bytes)
        res[f"{name}_ms"] = timed_ms(f"arrival windows, {name} "
                                     f"[{H},{IN}]",
                                     lambda: compiled(*args), reps)
        outs[name] = [np.asarray(a) for a in compiled(*args)]
    res["windows_equal"] = all(
        np.array_equal(a, b) for o in ("sort", "fallback")
        for a, b in zip(outs[o], outs["gather"]))
    return res


def variant2(args: list[str]) -> int:
    reps = int(args[0]) if args else REPS
    H, OB = 10000, 36
    F = H * OB
    E = IN = 48
    W = E + IN

    import numpy as np
    from shadow_tpu._jax import jax, jnp
    from jax import lax

    res = {"variant": 2, "platform": jax.devices()[0].platform,
           "reps": reps}
    rng = np.random.default_rng(0)
    if "tor" in args[1:]:
        # tor_56000: 56,000 hosts, event_capacity 96, exchange_in 64
        res["tor_merge"] = merge_payload_cases(56000, 96, 64, reps, rng)
        # outbox_capacity 40: the flush's rows are [56,000 x 40]
        res["tor_windows"] = arrival_window_cases(56000, 40, 64, reps,
                                                  rng)
        print(json.dumps(res), flush=True)
        return 0 if res["tor_merge"]["live_equal"] \
            and res["tor_windows"]["windows_equal"] else 1

    def arr64(shape, hi=1 << 60):
        return jax.device_put(jnp.asarray(
            rng.integers(0, hi, shape).astype(np.int64)))

    skey = arr64(F)
    p1, p2, p3, p4, p5 = (arr64(F) for _ in range(5))

    # 6-operand flat sort: payload rides through the bitonic passes
    sort6 = jax.jit(lambda k, a, b, c, d, e:
                    lax.sort((k, a, b, c, d, e), num_keys=1))
    res["flat_sort6_ms"] = timed_ms(
        "flat sort 6-op F=360k",
        lambda: sort6(skey, p1, p2, p3, p4, p5), reps)

    # 2-operand for reference at same F
    sort2 = jax.jit(lambda k, a: lax.sort((k, a), num_keys=1))
    res["flat_sort2_ms"] = timed_ms(
        "flat sort 2-op F=360k", lambda: sort2(skey, p1), reps)

    # 5-operand merge sort [H, W]
    ct = arr64((H, W))
    ck = arr64((H, W))
    cm = arr64((H, W))
    cv = arr64((H, W))
    cw = arr64((H, W))
    msort5 = jax.jit(lambda t, k, m, v, w: lax.sort(
        (t, k, m, v, w), dimension=1, num_keys=2))
    res["merge_sort5_ms"] = timed_ms(
        "merge sort 5-op [10k,96]",
        lambda: msort5(ct, ck, cm, cv, cw), reps)

    # contiguous-window takes (1-hop, from sorted payload)
    starts = jnp.sort(arr64(H, hi=F - IN))
    idx = starts[:, None] + jnp.arange(IN, dtype=jnp.int64)[None, :]
    cidx = jnp.clip(idx, 0, F - 1).reshape(-1)
    win_take = jax.jit(lambda v: jnp.take(v, cidx).reshape(H, IN))
    res["window_take_ms_x1"] = timed_ms(
        "contiguous window take x1", lambda: win_take(p1), reps)

    # row-stacked gather: [F, 8] i64, gather H*IN rows
    mat = arr64((F, 8))
    ridx = jnp.asarray(rng.integers(0, F, H * IN).astype(np.int32))
    row_gather = jax.jit(lambda m: jnp.take(m, ridx, axis=0))
    res["row_gather_f8_ms"] = timed_ms(
        "row gather [F,8] x H*IN rows", lambda: row_gather(mat), reps)

    # row-stacked CONTIGUOUS window rows
    crow = jax.jit(lambda m: jnp.take(m, cidx.astype(jnp.int32),
                                      axis=0))
    res["row_gather_f8_contig_ms"] = timed_ms(
        "row gather [F,8] contiguous windows", lambda: crow(mat), reps)

    # dynamic_slice-per-row via vmap (windows)
    def _dsl(m, s):
        return lax.dynamic_slice(m, (s,), (IN,))
    vds = jax.jit(lambda v: jax.vmap(_dsl, (None, 0))(v, starts))
    res["vmap_dynslice_ms_x1"] = timed_ms(
        "vmap dynamic_slice windows x1", lambda: vds(p1), reps)

    # filler-sort expand: 2 stable sorts of (F + H*IN) x 6 operands
    FE = F + H * IN
    dkey = arr64(FE, hi=2 * H)
    q1, q2, q3, q4, q5 = (arr64(FE) for _ in range(5))
    sort6e = jax.jit(lambda k, a, b, c, d, e:
                     lax.sort((k, a, b, c, d, e), num_keys=1))

    def expand():
        r = sort6e(dkey, q1, q2, q3, q4, q5)
        return sort6e(r[1], r[0], r[2], r[3], r[4], r[5])

    res["filler_expand_2sorts_ms"] = timed_ms(
        "filler expand 2x sort6 @840k", expand, reps)

    # one-hot matmul take_along_axis [H, W] -> [H, E]
    sie = jnp.asarray(rng.integers(0, W, (H, E)).astype(np.int32))

    def onehot_gather(m):
        oh = (sie[:, :, None] ==
              jnp.arange(W, dtype=jnp.int32)[None, None, :]) \
            .astype(jnp.float32)                      # [H, E, W]
        lo = (m & 0xFFFFF).astype(jnp.float32)
        mid = ((m >> 20) & 0xFFFFF).astype(jnp.float32)
        hi = ((m >> 40) & 0xFFFFFF).astype(jnp.float32)
        parts = jnp.stack([lo, mid, hi], axis=-1)     # [H, W, 3]
        got = jnp.einsum("hew,hwc->hec", oh, parts,
                         preferred_element_type=jnp.float32)
        lo_, mid_, hi_ = (got[..., i].astype(jnp.int64)
                          for i in range(3))
        return lo_ | (mid_ << 20) | (hi_ << 40)

    ohg = jax.jit(onehot_gather)
    res["onehot_gather_ms_x1"] = timed_ms(
        "one-hot matmul take_along x1", lambda: ohg(cm), reps)

    # searchsorted at F for the window starts
    hb = jnp.arange(H + 1, dtype=jnp.int64) * OB
    skey_sorted = jnp.sort(skey)
    ss = jax.jit(lambda k: jnp.searchsorted(k, hb))
    res["searchsorted_ms"] = timed_ms(
        "searchsorted F@10k+1", lambda: ss(skey_sorted), reps)

    print(json.dumps(res), flush=True)
    return 0


# ---------------------------------------------------------------------
# variant 3: candidate gatherless flush (double-sort merge)
# ---------------------------------------------------------------------
def _build_gatherless_flush(jnp, lax, H, OB, E):
    INF = jnp.int64(1) << jnp.int64(62)
    F = H * OB
    N = F + H * E
    BIG = 1 << 62

    def seg_scan_sum(flags_new, vals):
        """Segmented cumsum: resets at rows where flags_new is True."""
        def comb(a, b):
            af, av = a
            bf, bv = b
            return af | bf, jnp.where(bf, bv, av + bv)
        _, out = lax.associative_scan(comb, (flags_new, vals))
        return out

    def flush(ob_t, ob_host, ob_k, ob_m, ob_v, ob_w,
              ht, hk, hm, hv, hw, head):
        # heap rows: consumed slots (col < head) present as INF
        live = jnp.arange(E)[None, :] >= head[:, None]
        mt = jnp.where(live, ht, INF).reshape(-1)
        mk = jnp.where(live, hk, (1 << 62) - 1).reshape(-1)
        hrow = jnp.broadcast_to(
            jnp.arange(H, dtype=jnp.int32)[:, None], (H, E)) \
            .reshape(-1)
        gt = jnp.concatenate([ob_t, mt])
        gk = jnp.concatenate([ob_k, mk])
        gm = jnp.concatenate([ob_m, hm.reshape(-1)])
        gv = jnp.concatenate([ob_v, hv.reshape(-1)])
        gw = jnp.concatenate([ob_w, hw.reshape(-1)])
        ghost = jnp.concatenate([ob_host, hrow])

        # sort1: (host, t, k) — 3 keys, payload rides
        sh, st_, sk_, sm_, sv_, sw_ = lax.sort(
            (ghost, gt, gk, gm, gv, gw), num_keys=3)

        is_new = jnp.concatenate(
            [jnp.ones((1,), bool), sh[1:] != sh[:-1]])
        rank = seg_scan_sum(is_new, jnp.ones(N, jnp.int32)) - 1
        kept = rank < E
        is_real = st_ < INF
        dropped_real = (~kept) & is_real
        # per-host dropped count rides to slot [h, 0] on the rank-0 row
        rev_new = jnp.concatenate(
            [(sh[1:] != sh[:-1]), jnp.ones((1,), bool)])
        rdrop = seg_scan_sum(rev_new[::-1],
                             dropped_real[::-1].astype(jnp.int32))[::-1]
        ov_carry = jnp.where(rank == 0, rdrop, 0)

        tgt = sh.astype(jnp.int64) * E + rank
        key2 = jnp.where(kept, tgt, BIG + jnp.arange(N,
                                                     dtype=jnp.int64))
        _, t2, k2, m2, v2, w2, ov2 = lax.sort(
            (key2, st_, sk_, sm_, sv_, sw_, ov_carry), num_keys=1)
        KEEP = H * E
        new_ht = t2[:KEEP].reshape(H, E)
        new_hk = k2[:KEEP].reshape(H, E)
        new_hm = m2[:KEEP].reshape(H, E)
        new_hv = v2[:KEEP].reshape(H, E)
        new_hw = w2[:KEEP].reshape(H, E)
        overflow = ov2[:KEEP].reshape(H, E)[:, 0]
        return new_ht, new_hk, new_hm, new_hv, new_hw, overflow

    return flush


def _variant3_oracle_check() -> bool:
    """The gatherless flush vs a per-host numpy sort at a tiny shape."""
    import numpy as np
    from shadow_tpu._jax import jax, jnp
    from jax import lax

    H, OB, E = 7, 5, 4
    F = H * OB
    flush = jax.jit(_build_gatherless_flush(jnp, lax, H, OB, E))
    rng = np.random.default_rng(7)
    INF = np.int64(1) << np.int64(62)
    valid = rng.random(F) < 0.4
    ob_t = np.where(valid, rng.integers(0, 100, F), INF) \
        .astype(np.int64)
    ob_host = np.where(valid, rng.integers(0, H, F),
                       np.int64(1 << 31)).astype(np.int64)
    ob_k = rng.integers(0, 1 << 20, F).astype(np.int64)
    ht = np.where(rng.random((H, E)) < 0.6,
                  rng.integers(0, 100, (H, E)), INF) \
        .astype(np.int64)
    ht = np.sort(ht, axis=1)
    hk = rng.integers(0, 1 << 20, (H, E)).astype(np.int64)
    head = rng.integers(0, 2, H).astype(np.int32)
    z = np.zeros(F, np.int64)
    zh = np.zeros((H, E), np.int64)
    out = flush(*[jnp.asarray(a) for a in
                  (ob_t, ob_host, ob_k, z, z, z,
                   ht, hk, zh, zh, zh, head)])
    new_ht, new_hk = np.asarray(out[0]), np.asarray(out[1])
    ovf = np.asarray(out[5])
    for h in range(H):
        rows = []
        for j in range(E):
            if j >= head[h] and ht[h, j] < INF:
                rows.append((int(ht[h, j]), int(hk[h, j])))
            elif j >= head[h]:
                rows.append((int(INF), int(hk[h, j])))
        for i in range(F):
            if ob_host[i] == h:
                rows.append((int(ob_t[i]), int(ob_k[i])))
        rows.sort()
        exp_drop = sum(1 for (t, _) in rows[E:] if t < INF)
        rows = rows[:E]
        got = [(int(new_ht[h, j]), int(new_hk[h, j]))
               for j in range(len(rows))]
        if [r[0] for r in rows] != [g[0] for g in got]:
            print(f"host {h}: time mismatch {rows} vs {got}",
                  file=sys.stderr)
            return False
        if exp_drop != int(ovf[h]):
            print(f"host {h}: overflow {exp_drop} vs {ovf[h]}",
                  file=sys.stderr)
            return False
    return True


def variant3(args: list[str]) -> int:
    reps = int(args[0]) if args else REPS
    H, OB, E = 10000, 36, 48
    F = H * OB

    import numpy as np
    from shadow_tpu._jax import jax, jnp
    from jax import lax

    res = {"variant": 3, "platform": jax.devices()[0].platform,
           "reps": reps}
    flush = jax.jit(_build_gatherless_flush(jnp, lax, H, OB, E))
    rng = np.random.default_rng(0)
    INF = np.int64(1) << np.int64(62)

    # realistic sparsity: ~2% of outbox rows valid
    valid = rng.random(F) < 0.02
    ob_t = np.where(valid, rng.integers(0, 1 << 40, F), INF) \
        .astype(np.int64)
    ob_host = np.where(valid, rng.integers(0, H, F),
                       np.int64(1 << 31)).astype(np.int64)
    ob_k = rng.integers(0, 1 << 60, F).astype(np.int64)
    ob_m = rng.integers(0, 1 << 60, F).astype(np.int64)
    ob_v = rng.integers(0, 1 << 60, F).astype(np.int64)
    ob_w = rng.integers(0, 1 << 30, F).astype(np.int64)
    # heap ~25% full
    ht = np.where(rng.random((H, E)) < 0.25,
                  rng.integers(0, 1 << 40, (H, E)), INF) \
        .astype(np.int64)
    ht = np.sort(ht, axis=1)
    hk = rng.integers(0, 1 << 60, (H, E)).astype(np.int64)
    hm = rng.integers(0, 1 << 60, (H, E)).astype(np.int64)
    hv = rng.integers(0, 1 << 60, (H, E)).astype(np.int64)
    hw = rng.integers(0, 1 << 30, (H, E)).astype(np.int64)
    head = rng.integers(0, 4, H).astype(np.int32)

    fargs = [jax.device_put(jnp.asarray(a)) for a in
             (ob_t, ob_host, ob_k, ob_m, ob_v, ob_w,
              ht, hk, hm, hv, hw, head)]
    res["gatherless_flush_ms"] = timed_ms(
        "gatherless flush @10k", lambda: flush(*fargs), reps)

    ok = _variant3_oracle_check()
    res["small_oracle_ok"] = ok
    print(json.dumps(res), flush=True)
    return 0 if ok else 1


# ---------------------------------------------------------------------
# variant 4: remaining gathers + one-hot pop head reads
# ---------------------------------------------------------------------
def variant4(args: list[str]) -> int:
    reps = int(args[0]) if args else REPS
    H, OB, E, V, Pw = 10000, 40, 48, 6, 8

    import numpy as np
    from shadow_tpu._jax import jax, jnp

    platform = jax.devices()[0].platform
    rng = np.random.RandomState(7)
    host_vertex = jnp.asarray(rng.randint(0, V, H).astype(np.int32))
    lat = jnp.asarray(rng.randint(5e6, 1.4e8, (V, V)).astype(np.int64))
    dst = jnp.asarray(rng.randint(0, H, (H, OB)).astype(np.int32))
    srcv = jnp.asarray(rng.randint(0, V, H).astype(np.int32))[:, None]

    r = {"variant": 4, "platform": platform, "H": H, "OB": OB,
         "E": E, "reps": reps}

    f_dstv = jax.jit(lambda d: host_vertex[jnp.clip(d, 0, H - 1)])
    r["a_hostvertex_gather"] = timed_ms("a host_vertex[dst]",
                                        lambda: f_dstv(dst), reps)
    dstv = f_dstv(dst)

    f_lat = jax.jit(lambda s, d: lat[s, d])
    r["b_table_gather"] = timed_ms("b lat[srcv,dstv]",
                                   lambda: f_lat(srcv, dstv), reps)

    lat_flat = lat.reshape(-1)

    def onehot_lookup(s, d):
        pair = s * V + d                              # [H,OB]
        acc = jnp.zeros(pair.shape, jnp.int64)
        for j in range(V * V):
            acc = acc + jnp.where(pair == j, lat_flat[j],
                                  jnp.int64(0))
        return acc

    f_oh = jax.jit(onehot_lookup)
    r["c_table_onehot"] = timed_ms("c one-hot table",
                                   lambda: f_oh(srcv, dstv), reps)
    assert bool(jnp.all(f_oh(srcv, dstv) == f_lat(srcv, dstv)))

    ht = jnp.asarray(
        np.sort(rng.randint(0, 1 << 40, (H, E)).astype(np.int64), 1))
    head = jnp.asarray(rng.randint(0, 4, H).astype(np.int64))
    INF = jnp.int64(1) << jnp.int64(62)

    def take_gather(arr, hd):
        v = jnp.take_along_axis(arr, jnp.minimum(hd, E - 1)[:, None],
                                axis=1)[:, 0]
        return jnp.where(hd < E, v, INF)

    def take_onehot(arr, hd):
        m = jnp.arange(E)[None, :] == hd[:, None]
        v = jnp.where(m, arr, jnp.zeros((), arr.dtype)).sum(axis=1)
        return jnp.where(hd < E, v, INF)

    fg, fo = jax.jit(take_gather), jax.jit(take_onehot)
    r["d_pop1_gather"] = timed_ms("d pop P=1 gather",
                                  lambda: fg(ht, head), reps)
    r["d_pop1_onehot"] = timed_ms("d pop P=1 onehot",
                                  lambda: fo(ht, head), reps)
    assert bool(jnp.all(fg(ht, head) == fo(ht, head)))

    offs = jnp.arange(Pw, dtype=head.dtype)

    def takeP_gather(arr, hd):
        idxs = hd[:, None] + offs
        v = jnp.take_along_axis(arr, jnp.minimum(idxs, E - 1), axis=1)
        return jnp.where(idxs < E, v, INF)

    def takeP_onehot(arr, hd):
        idxs = hd[:, None] + offs
        m = jnp.arange(E)[None, None, :] == idxs[:, :, None]
        v = jnp.where(m, arr[:, None, :],
                      jnp.zeros((), arr.dtype)).sum(axis=-1)
        return jnp.where(idxs < E, v, INF)

    fgP, foP = jax.jit(takeP_gather), jax.jit(takeP_onehot)
    r["d_pop8_gather"] = timed_ms("d pop P=8 gather",
                                  lambda: fgP(ht, head), reps)
    r["d_pop8_onehot"] = timed_ms("d pop P=8 onehot",
                                  lambda: foP(ht, head), reps)
    assert bool(jnp.all(fgP(ht, head) == foP(ht, head)))

    for R in (2, 9, 128):
        r.update(_judge_lookups(R, H, OB, V, rng, reps))

    print(json.dumps(r))
    return 0


def _judge_lookups(R, H, OB, V, rng, reps):
    """The judge's per-lane topology lookups at R host-vertex runs:
    the indexed gathers (host_vertex[dst], lat/rel[srcv, dstv]) against
    the run-table forms the engine can use instead. The run starts and
    vertices are jit arguments, as in the engine, not constants."""
    import numpy as np
    from shadow_tpu._jax import jax, jnp

    starts = np.sort(rng.choice(np.arange(1, H), R - 1, replace=False))
    vrun = np.zeros(R, np.int32)
    for j in range(1, R):           # adjacent runs differ
        vrun[j] = (vrun[j - 1] + 1 + rng.randint(V - 1)) % V
    hv_np = np.repeat(vrun, np.diff(np.r_[0, starts, H]))
    hv = jnp.asarray(hv_np.astype(np.int32))
    st, vr = jnp.asarray(starts.astype(np.int32)), jnp.asarray(vrun)
    lat = jnp.asarray(rng.randint(5e6, 1.4e8, (V, V)).astype(np.int32))
    rel = jnp.asarray(rng.uniform(0.99, 1.0, (V, V)).astype(np.float32))
    dst = jnp.asarray(rng.randint(0, H, (H, OB)).astype(np.int32))
    srcv = hv[:, None]
    out = {}

    def gather(hv, d):
        dv = hv[jnp.clip(d, 0, H - 1)]
        return (lat[srcv, dv].astype(jnp.int64), rel[srcv, dv])

    def run_select(st, vr, d):        # the step function by selects
        dv = jnp.broadcast_to(vr[0], d.shape)
        for j in range(R - 1):
            dv = jnp.where(d >= st[j], vr[j + 1], dv)
        return dv

    def run_stepsum(st, vr, d):       # v_0 + sum (d >= s_j) * delta_j
        dv = jnp.broadcast_to(vr[0], d.shape)
        for j in range(R - 1):
            dv = dv + (d >= st[j]).astype(jnp.int32) * (vr[j + 1] - vr[j])
        return dv

    def onehot(st, vr, d):            # run select + V*V one-hot sums
        pair = srcv * V + run_select(st, vr, d)
        lv = jnp.zeros(d.shape, jnp.int64)
        rv = jnp.zeros(d.shape, jnp.float32)
        lf, rf = lat.reshape(-1), rel.reshape(-1)
        for j in range(V * V):
            m = pair == j
            lv = lv + jnp.where(m, lf[j].astype(jnp.int64), 0)
            rv = rv + jnp.where(m, rf[j], jnp.float32(0))
        return lv, rv

    def rows(vr):                     # once per program invocation
        lr = lat[srcv, vr[None, :]]
        rr = rel[srcv, vr[None, :]]
        return lr, rr

    def row_select(st, lr, rr, d):    # R-way select per lane
        lv, rv = lr[:, :1], rr[:, :1]
        for j in range(R - 1):
            past = d >= st[j]
            lv = jnp.where(past, lr[:, j + 1:j + 2], lv)
            rv = jnp.where(past, rr[:, j + 1:j + 2], rv)
        return (jnp.broadcast_to(lv, d.shape).astype(jnp.int64),
                jnp.broadcast_to(rv, d.shape))

    f_dv = jax.jit(lambda hv, d: hv[jnp.clip(d, 0, H - 1)])
    f_sel, f_sum = jax.jit(run_select), jax.jit(run_stepsum)
    f_g, f_oh, f_rows, f_rs = (jax.jit(gather), jax.jit(onehot),
                               jax.jit(rows), jax.jit(row_select))
    lr, rr = f_rows(vr)
    want = f_g(hv, dst)
    for got in (f_oh(st, vr, dst), f_rs(st, lr, rr, dst)):
        assert all(bool(jnp.all(a == b)) for a, b in zip(want, got))
    assert bool(jnp.all(f_sel(st, vr, dst) == f_dv(hv, dst)))
    assert bool(jnp.all(f_sum(st, vr, dst) == f_dv(hv, dst)))
    out[f"e_R{R}_vertex_gather"] = timed_ms(
        f"e R={R} host_vertex[dst]", lambda: f_dv(hv, dst), reps)
    out[f"e_R{R}_vertex_select"] = timed_ms(
        f"e R={R} run select", lambda: f_sel(st, vr, dst), reps)
    out[f"e_R{R}_vertex_stepsum"] = timed_ms(
        f"e R={R} run step sum", lambda: f_sum(st, vr, dst), reps)
    out[f"f_R{R}_judge_gather"] = timed_ms(
        f"f R={R} judge lookups, gathers", lambda: f_g(hv, dst), reps)
    out[f"f_R{R}_judge_onehot"] = timed_ms(
        f"f R={R} judge lookups, run select + VxV one-hot",
        lambda: f_oh(st, vr, dst), reps)
    out[f"f_R{R}_judge_rows"] = timed_ms(
        f"f R={R} judge lookups, per-host rows",
        lambda: f_rs(st, lr, rr, dst), reps)
    out[f"f_R{R}_rows_build"] = timed_ms(
        f"f R={R} per-host row build", lambda: f_rows(vr), reps)
    return out


# ---------------------------------------------------------------------
# variant 5: the cross-shard exchange in isolation
# ---------------------------------------------------------------------
def variant5(args: list[str]) -> int:
    """Flush-phase wall + per-flush ICI volume per exchange schedule
    at a real config's shapes. Each schedule gets its own engine:
    `dense` is the blind 4x auto-sized all_to_all pack (the
    pre-planner baseline), `planned` sizes every capacity (CAP
    included) from a measured warm-up record, `two_phase` and
    `all_gather` run the alternative schedules under the same plan.
    Single-shard meshes still time the flush (sort/merge work), with
    ICI volume 0."""
    cfg_path = args[0] if len(args) > 0 else "examples/tgen_1000.yaml"
    stop_s = float(args[1]) if len(args) > 1 else 3.0
    reps = int(args[2]) if len(args) > 2 else REPS

    from shadow_tpu import simtime
    from shadow_tpu._jax import jax, jnp
    from jax.sharding import NamedSharding
    from shadow_tpu.config import load_config
    from shadow_tpu.core.controller import Controller
    from shadow_tpu.device.engine import INF

    stop = simtime.from_seconds(stop_s)
    res = {"variant": 5, "config": cfg_path,
           "platform": jax.devices()[0].platform,
           "n_devices": len(jax.devices()),
           "slice_sim_s": stop_s, "reps": reps, "schedules": {}}

    def build(label, exchange, planned):
        cfg = load_config(cfg_path)
        cfg.experimental.scheduler_policy = "tpu"
        cfg.general.stop_time = stop
        cfg.experimental.exchange = exchange
        if planned:
            cfg.experimental.capacity_plan = "auto"
            cfg.experimental.capacity_warmup = min(
                stop, simtime.from_seconds(3.0))
        else:
            # the dense baseline: blind auto CAP, no compaction
            cfg.experimental.outbox_compact = 0
            cfg.experimental.exchange_capacity = 0
        c = Controller(cfg)
        if planned:
            c.runner._plan_capacities(stop)
        return c

    for label, exchange, planned in (
            ("dense_all_to_all", "all_to_all", False),
            ("planned_all_to_all", "all_to_all", True),
            ("planned_two_phase", "two_phase", True),
            ("planned_all_gather", "all_gather", True)):
        t_build = time.perf_counter()
        c = build(label, exchange, planned)
        eng = c.runner.engine
        eff = dict(eng.effective)
        # mid-run state + one popped phase's outbox, flush timed alone
        st = eng.init_state(c.sim.starts)
        st_mid, _ = eng.run(st, stop=stop // 2, final_stop=stop)
        jax.block_until_ready(st_mid)
        repl = NamedSharding(eng.mesh, eng._repl_spec)
        shard = NamedSharding(eng.mesh, eng._shard_spec)
        hv = jax.device_put(jnp.asarray(eng.host_vertex), repl)
        wrld = eng.world()
        nxt, _ = map(int, eng._probe(st_mid))
        win_end = jnp.int64(min(nxt + max(1, eng.config.lookahead),
                                stop))
        ob = {"t": jax.device_put(
            jnp.full(eng._ob_shape_global, INF, jnp.int64), shard)}
        for f in ("k", "m", "s", "v"):
            ob[f] = jax.device_put(
                jnp.zeros(eng._ob_shape_global, jnp.int64), shard)
        st_pop, ob_full, _ = eng._pop_phase(st_mid, ob, hv, wrld,
                                            win_end)
        jax.block_until_ready(ob_full)
        ms = timed_ms(
            f"flush {label}", lambda: eng._flush_phase(
                st_pop, ob_full, hv, wrld, win_end), reps)
        res["schedules"][label] = {
            "flush_ms": ms,
            "build_s": round(time.perf_counter() - t_build, 1),
            "ici_rows_per_flush": eff["ICI_rows_per_flush"],
            "ici_bytes_per_flush": eff["ICI_bytes_per_flush"],
            "CAP": eff["CAP"], "CAP2": eff["CAP2"],
            "CX": eff["CX"], "OB": eff["OB"],
            "tp_groups": eff["tp_groups"],
        }
    dense = res["schedules"]["dense_all_to_all"]
    plan = res["schedules"]["planned_all_to_all"]
    if plan["ici_rows_per_flush"]:
        res["ici_reduction_planned_vs_dense"] = round(
            dense["ici_rows_per_flush"] / plan["ici_rows_per_flush"],
            2)
    print(json.dumps(res), flush=True)
    return 0


# ---------------------------------------------------------------------
# variant 6: compile/dispatch attribution (arxiv 1912.03413 style)
# ---------------------------------------------------------------------
def variant6(args: list[str]) -> int:
    """Where does the cold-start budget actually go? For the round
    program and each profiling split: jax tracing+lowering
    (``.lower()``), XLA compilation (``.compile()``), the AOT cache's
    serialize and deserialize-load walls (what a warm start pays
    instead of lower+compile), the first real dispatch, and the
    steady per-call dispatch — one table. Compiles are FRESH (the
    engine is built with the compile cache off and JAX's tracing
    cache bypassed), so the numbers are true cold costs."""
    cfg_path = args[0] if len(args) > 0 else "examples/tgen_1000.yaml"
    stop_s = float(args[1]) if len(args) > 1 else 3.0
    reps = int(args[2]) if len(args) > 2 else REPS

    import tempfile

    from shadow_tpu import simtime
    from shadow_tpu._jax import jax, jnp
    from jax.sharding import NamedSharding
    from shadow_tpu.config import load_config
    from shadow_tpu.core.controller import Controller
    from shadow_tpu.device import aotcache
    from shadow_tpu.device.engine import INF

    stop = simtime.from_seconds(stop_s)
    cfg = load_config(cfg_path)
    cfg.experimental.scheduler_policy = "tpu"
    cfg.experimental.compile_cache = "off"      # cold costs, measured
    cfg.general.stop_time = stop
    c = Controller(cfg)
    eng = c.runner.engine
    # the scratch cache for the serialize/load columns — constructing
    # it also disables jax's tracing cache for this process, so every
    # compile below is a TRUE cold compile
    cache = aotcache.AotCache(tempfile.mkdtemp(prefix="tpu_micro6_"))
    res = {"variant": 6, "config": cfg_path,
           "platform": jax.devices()[0].platform,
           "n_devices": len(jax.devices()),
           "slice_sim_s": stop_s, "reps": reps, "programs": {}}

    repl = NamedSharding(eng.mesh, eng._repl_spec)
    shard = NamedSharding(eng.mesh, eng._shard_spec)
    hv = jax.device_put(jnp.asarray(eng.host_vertex), repl)
    wrld = eng.world()
    st0 = eng.init_state(c.sim.starts)

    def fresh_ob():
        ob = {"t": jax.device_put(
            jnp.full(eng._ob_shape_global, INF, jnp.int64), shard)}
        for f in ("k", "m", "s", "v"):
            ob[f] = jax.device_put(
                jnp.zeros(eng._ob_shape_global, jnp.int64), shard)
        return ob

    win0 = jnp.int64(0)
    # per program: the jitted fn, its example args, and the
    # steady-state args (for `run`, the FINISHED state — the steady
    # number is the pure dispatch+probe floor, not a re-simulation)
    programs = [
        ("run", eng._run,
         (st0, hv, wrld, jnp.int64(stop), jnp.int64(stop))),
        ("pop_phase", eng._pop_phase,
         (st0, fresh_ob(), hv, wrld, win0)),
        ("flush_phase", None, None),      # args built from pop's out
    ]

    pop_out = None
    for name, jf, pargs in programs:
        if name == "flush_phase":
            jf = eng._flush_phase
            s_w, ob_w, _ = pop_out
            pargs = (s_w, ob_w, hv, wrld, win0)
        row = {}
        # _fresh_compile guards the cold-cost contract on EVERY
        # backend: when serialization is unsupported the AotCache
        # constructor leaves jax's tracing cache on, and a repeat
        # invocation would report a warm hit as "compile_s"
        with aotcache._fresh_compile():
            t0 = time.perf_counter()
            lowered = jf.lower(*pargs)
            t1 = time.perf_counter()
            compiled = lowered.compile()
            t2 = time.perf_counter()
        row["lower_s"] = round(t1 - t0, 3)
        row["compile_s"] = round(t2 - t1, 3)
        # the AOT cache's side of the ledger: what a warm start pays
        # (deserialize+load) vs what it skips (lower+compile)
        key = f"micro6_{name}"
        t0 = time.perf_counter()
        stored = cache.store(key, compiled, meta={"program": name})
        row["aot_serialize_s"] = round(time.perf_counter() - t0, 3)
        loaded = cache.load(key) if stored else None
        if loaded is not None:
            t0 = time.perf_counter()
            cache.load(key)
            row["aot_load_s"] = round(time.perf_counter() - t0, 3)
            row["warm_vs_cold"] = round(
                (row["lower_s"] + row["compile_s"])
                / max(1e-9, row["aot_load_s"]), 1)
        else:
            # backend cannot round-trip this program — stamped, so
            # the table never reports a load wall that failed
            row["aot_load_s"] = None
            row["warm_vs_cold"] = None
        t0 = time.perf_counter()
        out = compiled(*pargs)
        jax.block_until_ready(out)
        row["first_dispatch_s"] = round(time.perf_counter() - t0, 3)
        if name == "pop_phase":
            pop_out = out
        if name == "run":
            # steady = re-dispatch on the FINISHED state: the program
            # runs zero rounds, so this is the dispatch+loop floor
            steady_args = (out[0], hv, wrld, jnp.int64(stop),
                           jnp.int64(stop))
        else:
            steady_args = pargs
        row["steady_ms"] = timed_ms(
            f"{name} steady", lambda: compiled(*steady_args), reps)
        res["programs"][name] = row

    # the one table (1912.03413-style dissection)
    cols = ("lower_s", "compile_s", "aot_serialize_s", "aot_load_s",
            "first_dispatch_s", "steady_ms", "warm_vs_cold")
    hdr = f"{'program':<14}" + "".join(f"{h:>18}" for h in cols)
    print(hdr, file=sys.stderr)
    for name, row in res["programs"].items():
        line = f"{name:<14}" + "".join(
            f"{row[h] if row[h] is not None else '-':>18}"
            for h in cols)
        print(line, file=sys.stderr)
    cold = sum(r["lower_s"] + r["compile_s"]
               for r in res["programs"].values())
    loads = [r["aot_load_s"] for r in res["programs"].values()]
    warm_ok = all(v is not None for v in loads)
    warm = sum(v or 0 for v in loads)
    res["cold_start_s"] = round(cold, 3)
    res["warm_start_s"] = round(warm, 3) if warm_ok else None
    warm_txt = (f"warm start (AOT load): {warm:.2f}s" if warm_ok
                else "warm start: unsupported on this backend")
    print(f"cold start (lower+compile, all programs): {cold:.2f}s; "
          f"{warm_txt}", file=sys.stderr)
    import shutil
    shutil.rmtree(cache.directory, ignore_errors=True)
    print(json.dumps(res), flush=True)
    return 0


VARIANTS = {1: variant1, 2: variant2, 3: variant3, 4: variant4,
            5: variant5, 6: variant6}


def main() -> int:
    ap = argparse.ArgumentParser(
        description="on-chip device-engine microbenchmarks")
    ap.add_argument("--variant", type=int, default=1,
                    choices=sorted(VARIANTS),
                    help="1 round-step attribution (default), "
                         "2 sorts-vs-gathers, 3 gatherless flush, "
                         "4 remaining gathers + one-hot pop, "
                         "5 exchange-in-isolation, "
                         "6 compile/dispatch attribution")
    ap.add_argument("args", nargs="*",
                    help="variant args (v1/v5/v6: [config] [stop_s] "
                         "[reps]; v2: [reps] [tor]; v3-4: [reps])")
    ns = ap.parse_args()

    signal.signal(signal.SIGALRM, lambda *a: sys.exit(9))
    signal.alarm(30 * 60 if ns.variant in (1, 5, 6) else 20 * 60)
    return VARIANTS[ns.variant](ns.args)


if __name__ == "__main__":
    sys.exit(main())
