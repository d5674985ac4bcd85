"""The device simulation engine.

One jitted program advances the whole simulation: an outer while_loop
over conservative time windows (controller_run's round loop,
reference controller.c:392-424), an inner while_loop that pops and
executes at most one event per host per iteration (preserving each
host's serial (time, src, seq) order — the per-host sequentiality
invariant of event.c:109-152 — while all hosts advance in parallel),
and a per-round collective packet exchange:

  pop min event/host -> app handle (batched) -> counter-RNG drop rolls
  + latency gathers (worker_sendPacket semantics, worker.c:520-579) ->
  outbox -> collective exchange over the mesh axis -> merge into
  destination heaps (causality bump, host_single.c:174-220) -> pmin
  next event time.

Determinism: every stochastic decision is keyed by stable integer ids
(threefry counters), per-host event heaps merge by full-key sort, and
incoming packets are ordered by (src_gid, outbox column) — so results
are bit-identical across mesh shapes AND match the CPU serial oracle's
per-host schedule (verified by trace checksums in tests).

v2 data-structure design — NO SCATTERS. TPU scatters with computed
indices serialize per element and crash on multi-million-element
operands, so every hot-path op here is a sort, a contiguous
dynamic-slice, or a take: heaps are per-host SORTED rows popped by a
head cursor; each pop iteration appends its sends to a contiguous
per-iteration column block of the outbox; flushes regroup rows with
one flat sort by (dst, okey) + searchsorted segment starts + windowed
takes, and merge with one per-row lexicographic sort of
[live heap | incoming]. Everything is static-shape; the only dynamism
is while_loop trip counts.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import numpy as np

from shadow_tpu._jax import jax, jnp, shard_map
from jax import lax
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec,
    PartitionSpec as P,
)

from shadow_tpu import simtime
from shadow_tpu.core.event import (
    KIND_BOOT,
    KIND_PACKET,
    KIND_STOP,
    KIND_TIMER,
)
from shadow_tpu.device import prng
from shadow_tpu.device.apps import DeviceApp
from shadow_tpu.device.netsem import packet_drop_mask
from shadow_tpu.topology import hierarchy
from shadow_tpu.utils.rng import PURPOSE_APP, PURPOSE_PACKET_DROP

from shadow_tpu.utils.checksum import (
    CHK_KIND,
    CHK_MUL,
    CHK_SEQ,
    CHK_SRC,
    MASK63,
)
from shadow_tpu.utils.slog import get_logger

log = get_logger("device")

INF = np.int64(1) << np.int64(62)
# reserved outbox time marker: a drop-rolled send carried only for the
# per-path packet histogram (never exchanged or delivered)
DROP_T = INF - 1
IMAX = np.int64(np.iinfo(np.int64).max)

AXIS = "hosts"

NIC_KEYS = ("tx_free", "rx_free", "cd_fa", "cd_next", "cd_cnt",
            "cd_last", "cd_drop")

# on-device invariant audit (EngineConfig.audit / experimental.
# state_audit): per-host "health word" bitmask accumulated by cheap
# reductions compiled into the round program. A nonzero word marks the
# state corrupted — the supervisor (device/supervise.py) refuses to
# checkpoint it, so a bad state is never the one a run resumes from.
AUD_HEAP = 1       # heap rows out of (t, key) order, or head out of
                   # [0, E] — the pop loop would replay/skip events
AUD_CLOCK = 2      # a host popped an event earlier than one it
                   # already executed (per-host clock monotonicity)
AUD_COUNTER = 4    # a cumulative counter went negative (i32 wrap or
                   # corrupted arithmetic)
AUD_CONSERVE = 8   # event-row conservation broke: rows produced !=
                   # rows executed + rows live in heaps + rows counted
                   # lost — the exchange dropped something silently
AUD_KEYS = ("aud", "aud_t", "aud_tx")

# the flush's exchanged row fields, and what an empty slot holds
XF = ("t", "k", "m", "s", "v")
XF_FILL = {"t": INF, "k": IMAX, "m": 0, "s": 0, "v": 0}


def seg_take(perm, rows, starts, counts, width):
    """Contiguous per-segment windows of a SORTED order: row i of the
    result is sorted-rows[starts[i]:starts[i]+width], filled (XF_FILL)
    past counts[i] — a two-hop gather through the sort permutation
    `perm` (the rows, a dict of XF fields, stay unsorted)."""
    G = perm.shape[0]
    idx = starts[:, None] + jnp.arange(width, dtype=starts.dtype)
    ok = jnp.arange(width)[None, :] < \
        jnp.minimum(counts, width)[:, None]
    pidx = jnp.take(perm, jnp.clip(idx, 0, G - 1).reshape(-1))
    return {f: jnp.where(ok, jnp.take(v, pidx).reshape(idx.shape),
                         XF_FILL[f])
            for f, v in rows.items()}


def merge_cols(b):
    """The window merge's five heap columns of a block of XF rows:
    (t, k, kind << 32 | hi32(s), lo32(s) << 32 | lo32(v), the train
    survivor mask hi32(v)). The m word's train count is stripped."""
    u32 = jnp.int64(0xFFFFFFFF)
    return (b["t"], b["k"],
            ((b["m"] & 0xFF) << 32) | ((b["s"] >> 32) & u32),
            ((b["s"] & u32) << 32) | (b["v"] & u32),
            (b["v"] >> 32) & u32)


def sorted_windows(ukey, cols, counts, lo, span, width):
    """[n, width] arrival windows of hosts lo .. lo+n-1 with no gather:
    one flat sort of the source rows and n*width filler rows that
    carries the merge columns (merge_cols, aligned with `ukey`).

    `ukey` holds each row's unsorted key dst*span + okey (okey < span;
    IMAX = no row) and `counts` the rows keyed to each host. A real row
    of this range sorts at 2*key; host h gets width - min(counts[h],
    width) fillers at 2*(h+1)*span - 1, after its last real row and
    before the next host's first, and everything else sorts at IMAX.
    So when no count passes `width`, the first n*width sorted rows are
    exactly the per-host windows of seg_take (content, order and fill).
    A host past `width` takes all its rows, so every later host's
    window shifts by the excess (seg_take truncates only that host):
    those windows are garbage and only the counts the caller keeps are
    exact. The survivor mask rides as one u32 word."""
    n = counts.shape[0]
    lo = lo.astype(jnp.int64)
    mine = (ukey >= lo * span) & (ukey < (lo + n) * span)
    rkey = jnp.where(mine, 2 * ukey, IMAX)
    fok = jnp.arange(width)[None, :] < \
        (width - jnp.minimum(counts, width))[:, None]
    fkey = jnp.where(
        fok, (2 * (lo + 1 + jnp.arange(n, dtype=jnp.int64)) * span
              - 1)[:, None], IMAX).reshape(n * width)
    cols = cols[:4] + (cols[4].astype(jnp.uint32),)
    ops = [jnp.concatenate([fkey, rkey])] + [
        jnp.concatenate([jnp.full((n * width,), fill, c.dtype), c])
        for c, fill in zip(cols, (INF, IMAX, 0, 0, 0))]
    # unstable: rows that tie are fillers of one host, alike in every
    # column, or IMAX rows, which no window takes
    out = lax.sort(tuple(ops), num_keys=1, is_stable=False)[1:]
    out = tuple(o[:n * width].reshape(n, width) for o in out)
    return out[:4] + (out[4].astype(jnp.int64),)


@dataclass
class EngineConfig:
    n_hosts: int                 # real hosts
    event_capacity: int = 64
    outbox_capacity: int = 32
    lookahead: int = simtime.SIMTIME_ONE_MILLISECOND
    stop_time: int = simtime.SIMTIME_ONE_SECOND
    bootstrap_end: int = 0
    seed: int = 1
    max_rounds: int = 1 << 62    # safety valve
    # cross-shard packet exchange: "all_to_all" moves only each
    # (src shard, dst shard) pair's rows over ICI (sort by
    # destination shard, then lax.all_to_all on [n_shards, CAP]
    # buffers); "all_gather" replicates every shard's whole outbox
    # (simple, bandwidth ∝ H_pad*OB per device); "two_phase" is the
    # hierarchical schedule (direct-connect style, arxiv 2309.13541):
    # shards factor into groups of g = capacity.group_split(S)[0],
    # phase 1 exchanges intra-group by destination RANK, phase 2
    # forwards inter-group — per-phase buffers aggregate over whole
    # rank/group sets, so one hot pair borrows headroom from quiet
    # pairs instead of padding every pair to the worst.
    exchange: str = "all_to_all"
    # per (src shard, dst shard) row capacity; 0 = auto-size from the
    # outbox volume with 4x headroom for skewed traffic. Overflow is
    # counted per source host and fails the run, never silently lost.
    # Under two_phase this is the PHASE-1 per-peer buffer (rows per
    # destination rank, summed over destination groups).
    exchange_capacity: int = 0
    # two_phase phase-2 per-peer buffer (rows one intermediate
    # forwards to one destination group); 0 = auto-size. Unused by
    # the other exchange variants. Overflow is counted against the
    # ORIGINAL sending host (cross-shard: one scalar collective
    # decides the loss branch, then a psum'd histogram lands each
    # lost row on its sender's shard) and fails the run.
    exchange_capacity2: int = 0
    # per-host arrivals accepted per flush (merge width = E + this);
    # 0 = event_capacity. Overflow is counted and fails the run.
    exchange_in_capacity: int = 0
    # per-host outbox rows that survive to the flush's flat sort:
    # the outbox is mostly empty (each of B iterations reserves its
    # own column block), so compacting each host's row to its first
    # `outbox_compact` valid entries before the GLOBAL sort shrinks
    # the sort from H*OB to H*compact rows. 0 = off. Too small is
    # LOUD (x_overflow, attributed to the sending host).
    outbox_compact: int = 0
    # bandwidth + CoDel for raw sends (host/model_nic.py's fluid NIC):
    # TX serialization at send, RX serialization + event-driven CoDel
    # at delivery via a KIND_PACKET -> KIND_PACKET_READY two-stage pop
    model_bandwidth: bool = False
    # per-path packet counters (topology_incrementPathPacketCounter,
    # ref topology.c:1983): a [V,V] histogram of SENT packets
    # (drop-rolled included) accumulated at flush time. Costs one
    # extra flat sort per flush; requires V*V <= 65536.
    count_paths: bool = False
    # network-judgment placement: True = judge the whole phase's
    # outbox once at flush (fewer ops in the serial pop loop — the
    # right trade on TPU, where per-op dispatch in the while body
    # dominates); False = judge each pop iteration in-step (the right
    # trade on one CPU core, where the loop is cheap and the batched
    # judge's extra memory traffic is not). None = auto by platform.
    # Traces are bit-identical either way (tests pin both).
    judge_hoist: Optional[bool] = None
    # flush merge strategy: True = ONE global double sort of
    # [outbox rows | heap rows] keyed by (dst host, time, src/seq)
    # lands every row at its [host, slot] heap position with zero
    # gathers — on TPU a 500k-element take costs ~10 ms while a
    # 6-operand 840k-row sort costs ~3 ms, so the window path's
    # seg_take arrival gathers (5 takes per flush) are the round cost
    # there. False = the flat-sort + per-host window + row-merge path
    # (fewer/narrower sorts; the right trade on one CPU core where
    # sorts are the cost and takes are cheap; on TPU the choice where
    # the global sort is too long to compile, see merge_payload).
    # None = auto by platform. Traces are bit-identical either way
    # (tests pin both).
    merge_global: Optional[bool] = None
    # how the window merge (merge_global False) moves its payload
    # through its two sorts. "sort": both sorts carry it, no gathers,
    # the TPU side of the trade. The flush's arrival windows ([H, IN],
    # _host_windows) are ONE flat sort of the outbox rows and H*IN
    # filler rows carrying the merge columns (sorted_windows; on a
    # v5e seg_take's two-hop gathers over [56,000 x 64] took 377 ms
    # of a 438 ms Tor round), and the per-host row sort carries the
    # heap payload (m, v and the u32 train mask w) and slices the
    # first E columns (its three take_along_axis over [56,000 x 96]
    # took 329 ms of a 757 ms round). "gather": the sorts carry keys
    # and an iota, and the payload follows by gathers (seg_take, then
    # take_along_axis) — narrower sorts, the cheaper form on the CPU
    # backend (PERF.md section 6 has the CPU timings). None = by
    # platform; no config option sets it, tests pin both. Every live
    # heap slot is bit-identical either way
    # ((t, key) is a total order over live rows; padding slots may
    # hold other stale payload, which nothing reads) while no host's
    # arrivals pass IN. Past it "sort" shifts every later host's
    # window too, so the rest of the segment is garbage on those
    # hosts (and on a mesh may also trip x_overflow, widening the
    # exchange capacities on the re-plan); the overflow count is
    # exact and the segment is discarded either way.
    merge_payload: Optional[str] = None
    # pop head reads: True = one-hot masked reductions (compare a
    # column iota against head, select, reduce over E) — pure
    # elementwise+reduce VPU work, no gather; the pop loop's
    # take_along_axis head reads (5 operand takes + the loop-cond
    # take per iteration) are the same ~ms-class TPU gathers the
    # gatherless flush removed. False = take_along_axis (cheaper on
    # one CPU core, where gathers are a pointer chase and the E-wide
    # reduction is real work). None = auto by platform. Traces are
    # bit-identical either way (tests pin both).
    pop_onehot: Optional[bool] = None
    # the judge's topology lookups (a send lane's path latency and
    # reliability, lat/rel[host_vertex[src], host_vertex[dst]], and
    # the pop loop's self-latency): True = compare-selects, no gather
    # — host_vertex is a step function of the host id with R runs, so
    # a lane finds its destination's vertex by R-1 comparisons against
    # the run starts (derived once per program invocation) and the
    # (src, dst) pair by one-hot masked sums over the V*V table. Legal
    # for one fault epoch, dense tables, no model_bandwidth, V*V <= 128
    # and R <= 128; elsewhere the indexed gathers stay. False =
    # indexed gathers (the cheaper form on one CPU core). None = auto
    # by platform: on a v5e the three gathers take 6.9 ms for
    # [10000 x 40] lanes, the selects 0.4 ms at R = 2, 9 and 128
    # (scripts/tpu_micro.py --variant 4). Selection is exact, so
    # traces are bit-identical either way.
    table_onehot: Optional[bool] = None
    # on-device invariant audit (experimental.state_audit): compile a
    # per-host health word of cheap reductions into the round program
    # — heap order, per-host clock monotonicity, counter
    # non-negativity, and event-row conservation across the exchange
    # (see the AUD_* bits above). Off by default: the audited program
    # carries three extra state leaves and one extra collective per
    # round; with audit off the compiled program is byte-identical to
    # an un-audited build. Traces are bit-identical either way (the
    # audit only reads existing values).
    audit: bool = False


class DeviceEngine:
    """Builds and runs the jitted sharded simulation program."""

    def __init__(self, config: EngineConfig, app: DeviceApp,
                 host_vertex: np.ndarray, latency_ns: np.ndarray,
                 reliability: np.ndarray,
                 mesh: Optional[Mesh] = None,
                 bw_up_bits: Optional[np.ndarray] = None,
                 bw_down_bits: Optional[np.ndarray] = None,
                 epoch_times: Optional[np.ndarray] = None,
                 ensemble=None):
        self.config = config
        self.app = app
        # ensemble worlds (shadow_tpu/ensemble/spec.py EnsembleWorlds,
        # duck-typed to avoid the import cycle): stacked per-replica
        # (latency, reliability, epoch_times, seed keys). When set,
        # replica 0 is the engine's base world (standard program,
        # fingerprints) and _build_program additionally compiles the
        # vmapped R-replica campaign program. Compile-time branch
        # flags (ALL_REL1, the i32 latency bound) are evaluated over
        # the WHOLE stack — one lossy replica must not let the
        # lossless fast path skip every replica's drop rolls.
        self.ensemble = ensemble
        if ensemble is not None:
            # the stacked tables arrive i32/f32 — build_worlds
            # (ensemble/spec.py) enforces the i32 latency bound over
            # every replica before the cast, so no re-check here.
            # Hierarchical worlds stack each factored leaf [R,...]
            # instead of one [R,(T,)V,V] matrix.
            if isinstance(ensemble.latency, tuple):
                latency_ns = tuple(np.asarray(p[0])
                                   for p in ensemble.latency)
                reliability = tuple(np.asarray(p[0])
                                    for p in ensemble.reliability)
            else:
                latency_ns = np.asarray(ensemble.latency[0])
                reliability = np.asarray(ensemble.reliability[0])
            epoch_times = np.asarray(ensemble.epoch_times[0])
        # d2 survivor bitmasks are one uint32 word: a larger train
        # would silently lose packets (ADVICE r3 #2 — fail loudly)
        assert getattr(app, "max_train", 1) <= 32, \
            f"app.max_train={app.max_train} exceeds the 32-bit " \
            "survivor mask"
        if mesh is None:
            devs = jax.devices()
            mesh = Mesh(np.array(devs), (AXIS,))
        self.mesh = mesh
        self.n_shards = mesh.devices.size
        H = config.n_hosts
        self.H_pad = int(math.ceil(H / self.n_shards) * self.n_shards)
        self.H_loc = self.H_pad // self.n_shards

        # topology matrices are stored STACKED per fault epoch
        # [T,V,V] (shadow_tpu/faults.py epoch table) when a fault
        # schedule exists; the fault-free single epoch keeps the
        # plain [V,V] matrices so the compiled program (and its
        # gathers) is byte-identical to the pre-fault engine.
        # Under `network.topology.representation: hierarchical` the
        # matrices are replaced by factored leaf TUPLES
        # (cluster [C,C], cluster-of [V], access [V], self [V]) —
        # hierarchy.HierTables lat_parts()/rel_parts() — with the
        # epoch stack as a leading [T] axis on every leaf; the
        # per-packet lookup becomes hierarchy.gather_parts.
        hier = isinstance(latency_ns, tuple)
        if hier:
            latency_ns = tuple(np.asarray(p) for p in latency_ns)
            reliability = tuple(np.asarray(p) for p in reliability)
            n_epochs = latency_ns[0].shape[0] \
                if latency_ns[0].ndim == 3 else 1
        else:
            latency_ns = np.asarray(latency_ns)
            reliability = np.asarray(reliability)
            n_epochs = latency_ns.shape[0] if latency_ns.ndim == 3 \
                else 1
        if epoch_times is None:
            epoch_times = np.zeros(n_epochs, dtype=np.int64)
        self.epoch_times = np.asarray(epoch_times, dtype=np.int64)
        if len(self.epoch_times) != n_epochs:
            raise ValueError(
                f"epoch_times has {len(self.epoch_times)} entries but "
                f"the latency table has {n_epochs} epochs")
        if n_epochs == 1:
            if hier and latency_ns[0].ndim == 3:
                latency_ns = tuple(p[0] for p in latency_ns)
                reliability = tuple(p[0] for p in reliability)
            elif not hier and latency_ns.ndim == 3:
                latency_ns = latency_ns[0]
                reliability = reliability[0]
        if hier:
            if latency_ns[0].ndim == 3:
                over = max(hierarchy.max_composed_latency(
                    tuple(p[e] for p in latency_ns))
                    for e in range(n_epochs))
            else:
                over = hierarchy.max_composed_latency(latency_ns)
            if over > np.iinfo(np.int32).max:
                raise ValueError(
                    "path latencies above ~2.1 s don't fit the "
                    "i32 device latency matrix")
        elif (latency_ns > np.iinfo(np.int32).max).any():
            raise ValueError("path latencies above ~2.1 s don't fit the "
                             "i32 device latency matrix")
        self.host_vertex = np.zeros(self.H_pad, dtype=np.int32)
        self.host_vertex[:H] = host_vertex
        if hier:
            # int leaves (cluster/access/self latency + the i32
            # cluster-of vector) ride i32; reliability leaves f32
            # except the shared cluster-of index vector
            self.latency = tuple(np.asarray(p).astype(np.int32)
                                 for p in latency_ns)
            self.reliability = tuple(
                np.asarray(p).astype(
                    np.int32 if i == 1 else np.float32)
                for i, p in enumerate(reliability))
            self.n_vertices = int(self.latency[1].shape[-1])
        else:
            self.latency = latency_ns.astype(np.int32)
            self.n_vertices = int(latency_ns.shape[-1])
            self.reliability = reliability.astype(np.float32)
        if config.count_paths and self.n_vertices ** 2 > 65536:
            raise ValueError(
                "count_paths needs V*V <= 65536 (histogram boundaries "
                f"scale with V^2; this graph has V={self.n_vertices})")
        self.seed_pair = prng.seed_key(config.seed)
        # model-NIC bandwidths (bits/s), padded; 1 Gbit default keeps
        # the padded hosts' arithmetic harmless
        self.bw_up = np.full(self.H_pad, 10**9, dtype=np.int64)
        self.bw_down = np.full(self.H_pad, 10**9, dtype=np.int64)
        if bw_up_bits is not None:
            self.bw_up[:H] = np.maximum(1, bw_up_bits)
        if bw_down_bits is not None:
            self.bw_down[:H] = np.maximum(1, bw_down_bits)

        self._shard_spec = P(AXIS)
        self._repl_spec = P()
        self._heap_builder = None       # jitted lazily by init_state
        # persistent AOT compile cache (device/aotcache.py): the
        # runner attaches one shared AotCache after construction;
        # run()/run_ensemble() then dispatch each program
        # through a cached (or freshly AOT-compiled + stored)
        # executable resolved on first use. The executables live in
        # _aot_exec — the _run/_pop_phase/... jit attributes stay
        # untouched so tooling (and tests) can still .lower() them.
        # None = plain lazy jit.
        self.aot_cache = None
        self._aot_exec: dict = {}
        self._build_program()

    # ------------------------------------------------------------------
    # state construction (host side)
    # ------------------------------------------------------------------
    def init_state(self, starts: list[tuple]) -> dict:
        """starts: (host_id, start_time, stop_time|-1[, proc_idx]) per
        process, in registration order — seq consumption mirrors
        Manager.boot_hosts (device configs are single-process/host, so
        the index is ignored here).

        v2 state layout (scatter-free engine): per-host event heaps are
        SORTED rows of five packed i64 arrays —
          ht [H,E] time (INF = empty slot),
          hk [H,E] src<<32|seq  (the deterministic tiebreak key),
          hm [H,E] kind<<32|size,
          hv [H,E] d0<<32|d1,
          hw [H,E] d2 (train survivor bitmask; 0 otherwise),
        plus a per-host `head` cursor: slots < head are consumed; the
        next event of host h is always column head[h]. Rows re-sort
        only at flush (one lax.sort per phase) — no scatters anywhere.

        The [H,E] heaps are BUILT ON DEVICE from [H] boot/stop vectors,
        so only those vectors are uploaded (a few hundred KB, against
        ~20 MB of heaps at the 10k rung and ~250 MB at tor_large; what
        the upload would cost on the chip is not measured)."""
        H, E = self.H_pad, self.config.event_capacity
        if E < 2:
            raise ValueError("event_capacity must be >= 2 (boot+stop)")
        t0s = np.full(H, INF, dtype=np.int64)
        t1s = np.full(H, INF, dtype=np.int64)
        event_seq = np.zeros(H, dtype=np.int32)
        as_arrays = getattr(starts, "as_arrays", None)
        if as_arrays is not None:
            # columnar fast path (host/plane.py StartColumns): the
            # boot/stop vectors are already [n] aligned columns — fill
            # by slice instead of a million-iteration loop. One
            # process per host by construction.
            s0, s1 = as_arrays()
            n = s0.shape[0]
            bad = np.flatnonzero((s1 >= 0) & (s1 < s0))
            if bad.size:
                h = int(bad[0])
                raise ValueError(
                    f"host {h}: stop_time {int(s1[h])} precedes "
                    f"start_time {int(s0[h])}")
            has_stop = s1 >= 0
            t0s[:n] = s0
            t1s[:n] = np.where(has_stop, s1, INF)
            event_seq[:n] = np.where(has_stop, 2, 1).astype(np.int32)
        else:
            for entry in starts:
                h, t_start, t_stop = entry[0], entry[1], entry[2]
                if t0s[h] != INF:
                    raise ValueError(
                        f"host {h}: multiple processes per host are "
                        "not supported by the device engine")
                t0s[h] = t_start
                event_seq[h] = 1
                if t_stop is not None and t_stop >= 0:
                    if t_stop < t_start:
                        raise ValueError(
                            f"host {h}: stop_time {t_stop} precedes "
                            f"start_time {t_start}")
                    t1s[h] = t_stop
                    event_seq[h] = 2

        shard = NamedSharding(self.mesh, self._shard_spec)

        if self._heap_builder is None:
            def _build(t0, t1):
                hid = jnp.arange(H, dtype=jnp.int64)
                padt = jnp.full((H, E - 2), INF, jnp.int64)
                ht = jnp.concatenate([t0[:, None], t1[:, None], padt],
                                     1)
                # rows are (t, src, seq)-sorted by construction: boot
                # (seq 0) precedes stop (seq 1), validated host-side
                hk = jnp.concatenate([
                    jnp.where(t0 < INF, hid << 32, IMAX)[:, None],
                    jnp.where(t1 < INF, (hid << 32) | 1,
                              IMAX)[:, None],
                    jnp.full((H, E - 2), IMAX, jnp.int64)], 1)
                padz = jnp.zeros((H, E - 2), jnp.int64)
                hm = jnp.concatenate([
                    jnp.where(t0 < INF,
                              jnp.int64(KIND_BOOT) << 32, 0)[:, None],
                    jnp.where(t1 < INF,
                              jnp.int64(KIND_STOP) << 32, 0)[:, None],
                    padz], 1)
                z2 = jnp.zeros((H, E), jnp.int64)
                return ht, hk, hm, z2, z2

            self._heap_builder = jax.jit(_build,
                                         out_shardings=(shard,) * 5)

        ht, hk, hm, hv, hw = self._heap_builder(
            jax.device_put(jnp.asarray(t0s), shard),
            jax.device_put(jnp.asarray(t1s), shard))

        zeros_i32 = np.zeros(H, dtype=np.int32)
        small = {
            "head": zeros_i32.copy(),
            "event_seq": event_seq,
            "packet_seq": zeros_i32.copy(),
            "app_seq": zeros_i32.copy(),
            "app": np.asarray(self.app.init_state(H), dtype=np.int32),
            "n_exec": zeros_i32.copy(),
            "n_sent": zeros_i32.copy(),
            "n_drop": zeros_i32.copy(),
            "n_deliv": zeros_i32.copy(),
            "overflow": zeros_i32.copy(),
            "x_overflow": zeros_i32.copy(),
            "chk": np.zeros(H, dtype=np.int64),
            # occupancy telemetry (device/capacity.py consumes these):
            # per-segment high-water marks accumulated with reductions
            # only — never sorts — so they ride every run for free.
            #   occ_heap  [H]  max live heap rows per host (post-merge)
            #   occ_ob    [H]  max exchangeable outbox rows per phase
            #   occ_in    [H]  max arrivals accepted per flush
            "occ_heap": zeros_i32.copy(),
            "occ_ob": zeros_i32.copy(),
            "occ_in": zeros_i32.copy(),
            #   occ_x     [S,S] max rows per (src shard, dst shard)
            #   occ_trips [S]  max pop-loop iterations per phase
            #   occ_phases[S]  total flushes executed
            #   occ_iters [S]  total pop-loop iterations executed
            #   occ_burst [S]  total events run in burst columns 1+
            "occ_x": np.zeros((self.n_shards, self.n_shards),
                              dtype=np.int32),
            "occ_trips": np.zeros(self.n_shards, dtype=np.int32),
            "occ_phases": np.zeros(self.n_shards, dtype=np.int32),
            "occ_iters": np.zeros(self.n_shards, dtype=np.int32),
            "occ_burst": np.zeros(self.n_shards, dtype=np.int32),
        }
        if not self.program_facts["merge_global"]:
            #   occ_arrivals [S] total arrival rows windowed (the
            #                    window merge's flushes only)
            small["occ_arrivals"] = np.zeros(self.n_shards,
                                             dtype=np.int64)
        if self.config.audit:
            # invariant-audit leaves (AUD_* bits above):
            #   aud    [H] the health word (0 = every invariant held)
            #   aud_t  [H] last popped event time (clock monotonicity)
            #   aud_tx [H] cumulative event rows this host produced —
            #              seeded with the boot/stop rows so the
            #              conservation identity holds from round 0
            small["aud"] = zeros_i32.copy()
            small["aud_t"] = np.zeros(H, dtype=np.int64)
            small["aud_tx"] = ((t0s != INF).astype(np.int64)
                               + (t1s != INF).astype(np.int64))
        if self.config.count_paths:
            V = self.n_vertices
            small["path_cnt"] = np.zeros((self.n_shards, V * V),
                                         dtype=np.int64)
        if self.config.model_bandwidth:
            # model-NIC scalars (host/model_nic.py ModelNic twin)
            for k in NIC_KEYS:
                small[k] = np.zeros(H, dtype=np.int64)
        state = {k: jax.device_put(jnp.asarray(v), shard)
                 for k, v in small.items()}
        state.update(ht=ht, hk=hk, hm=hm, hv=hv, hw=hw)
        return state

    # ------------------------------------------------------------------
    # the jitted program (v2: scatter-free)
    # ------------------------------------------------------------------
    # TPU scatters with computed indices serialize per element (~1.4 us
    # each) and crash outright on multi-million-element operands; v1's
    # per-step heap/outbox scatters made iteration cost scale with H
    # and the exchange scatters killed the 10k-host rung. v2 uses only
    # TPU-fast primitives, all O(H)-parallel:
    #   pops     — the heap rows are kept sorted; the next event is
    #              column head[h] (take_along_axis, no argmin);
    #   appends  — each iteration owns a CONTIGUOUS column block of
    #              the outbox (lax.dynamic_update_slice at blk*M);
    #   exchange — one flat lax.sort by dst*SPAN+okey, segment starts
    #              via searchsorted, arrivals via contiguous takes;
    #   merge    — one per-row lax.sort of [live heap | incoming].
    def _build_program(self):
        cfg = self.config
        app = self.app
        if cfg.exchange not in ("all_to_all", "all_gather",
                                "two_phase"):
            # "auto" resolves in the runner (capacity.choose_exchange
            # over the OCC record) — the engine only compiles concrete
            # schedules
            raise ValueError(
                f"EngineConfig.exchange={cfg.exchange!r}: the engine "
                "needs a concrete variant (all_to_all | all_gather | "
                "two_phase); 'auto' is resolved by the runner")
        E = cfg.event_capacity
        K = app.max_sends
        T = app.max_timers
        D = max(1, app.max_draws)
        H_loc, H_pad = self.H_loc, self.H_pad
        n_shards = self.n_shards
        LOOKAHEAD = np.int64(max(1, cfg.lookahead))
        BOOT_END = np.int64(cfg.bootstrap_end)
        MB = bool(cfg.model_bandwidth)

        # outbox layout: each pop iteration owns M_out columns (K sends
        # + T timers + the model-NIC READY reinsert); a phase runs at
        # most B iterations between flushes
        C = max(1, getattr(app, "max_train", 1))
        CP = bool(cfg.count_paths)
        V = self.n_vertices
        # burst de-skew: an app may declare that its STATELESS
        # responder hosts (app.burst_mask) can pop up to P consecutive
        # in-window KIND_PACKET events per iteration, each answered on
        # its own send lane — a busy hub no longer holds every lane
        # hostage for N serial iterations (BASELINE round-3 diagnosis)
        P = max(1, getattr(app, "burst_pops", 1))
        if P > 1 and MB:
            # the fluid-NIC CoDel/tx state is sequential per event:
            # degrade to single pops rather than failing a config that
            # worked without bursts
            log.info("burst_pops=%d disabled: model_bandwidth needs "
                     "sequential per-event NIC state", P)
            P = 1
        if P > 1 and K != 1:
            raise ValueError("burst_pops requires max_sends == 1")
        K_eff = P if P > 1 else K
        M_out = K_eff + T + (1 if MB else 0)
        B = max(1, cfg.outbox_capacity // M_out)
        OB = B * M_out
        # per-flush arrivals per host: the merge width is E + IN (x2
        # on the multi-shard bypass path), so a tight IN is a
        # first-order flush win; too small is LOUD (overflow counter)
        IN = cfg.exchange_in_capacity or E
        SPAN = np.int64(H_pad) * OB   # okey < SPAN
        from shadow_tpu.device.capacity import (
            dense_auto_cap,
            group_split,
        )
        TP_G, TP_NG = (group_split(n_shards)
                       if cfg.exchange == "two_phase" else
                       (1, n_shards))
        if cfg.exchange == "all_to_all" and n_shards > 1:
            CAP = cfg.exchange_capacity or \
                dense_auto_cap(H_loc, OB, E, n_shards)
            CAP2 = 0
        elif cfg.exchange == "two_phase" and n_shards > 1:
            # phase-1 buffers aggregate a sender's rows per dst RANK
            # (over all groups); phase-2 buffers aggregate a whole
            # group's forwards per dst group. The blind auto sizes
            # assume 4x-of-balanced skew exactly like the direct
            # CAP's; the planner replaces both with measured sums.
            R = H_loc * OB
            CAP = cfg.exchange_capacity or \
                min(R, max(64, E, (4 * R + TP_G - 1) // TP_G))
            CAP2 = cfg.exchange_capacity2 or \
                min(TP_G * CAP,
                    max(64, E,
                        (4 * R * TP_G + n_shards - 1) // n_shards))
        else:
            CAP = CAP2 = 0

        # Judgment hoist: without the fluid NIC, a send's network
        # judgment (latency gather + drop rolls + causality bump) does
        # not feed back into the pop loop — the only in-loop consumer
        # is the dirty bit, which needs just the host's SELF-latency.
        # So the while-body writes raw send rows (depart time, train
        # count, live mask) and the whole phase is judged ONCE over
        # the outbox at flush time (_judge_outbox): ~40% fewer ops in
        # the serial loop, identical keys and values, bit-identical
        # traces. The fluid NIC keeps the legacy in-step path (its
        # tx/rx buckets are sequential per event).
        platform = self.mesh.devices.flat[0].platform
        HOIST = (not MB) and (cfg.judge_hoist
                              if cfg.judge_hoist is not None
                              else platform == "tpu")
        # gatherless flush merge (see EngineConfig.merge_global)
        MERGE_GLOBAL = (cfg.merge_global
                        if cfg.merge_global is not None
                        else platform == "tpu")
        # the window merge's payload recovery (see
        # EngineConfig.merge_payload)
        if cfg.merge_payload not in (None, "sort", "gather"):
            raise ValueError(
                f"merge_payload must be sort or gather, not "
                f"{cfg.merge_payload!r}")
        MERGE_PAYLOAD = cfg.merge_payload or (
            "sort" if platform == "tpu" else "gather")
        # gatherless pop head reads (see EngineConfig.pop_onehot)
        POP_ONEHOT = (cfg.pop_onehot
                      if cfg.pop_onehot is not None
                      else platform == "tpu")
        # on-device invariant audit (see the AUD_* bits): every audit
        # op sits behind this flag so the un-audited program is
        # byte-identical to a pre-audit build
        AUDIT = bool(cfg.audit)
        # fault epochs: the [T] epoch start times are part of the
        # compiled schedule exactly like the capacities, but ride the
        # program as a TRACED [T] vector (the `wrld` tuple below) so
        # the vmapped ensemble program can vary them per replica;
        # each lookup selects its epoch by SEND time with a
        # comparison count — the vectorized twin of the CPU model's
        # binary search (faults.FaultTable.epoch_of). T == 1 (no
        # faults) keeps the [V,V] matrices and the original 2-operand
        # gather, so the fault-free program is byte-identical.
        T_EP = len(self.epoch_times)

        def _ep_of(t, ept):
            return (t[..., None] >= ept).sum(-1).astype(jnp.int32) - 1

        # hierarchical representation: world tables are factored leaf
        # tuples; every lookup goes through the shared two-level
        # gather (topology/hierarchy.py gather_parts)
        HIER = isinstance(self.latency, tuple)

        def _tbl(tab, t, sv, dv, ept):
            """Topology-table gather at send time t; tab is [V,V]
            (single epoch) or [T,V,V] (fault schedule) — or, under
            the hierarchical representation, the factored leaf tuple
            with an optional leading [T] axis on every leaf."""
            if HIER:
                e = None if T_EP == 1 else _ep_of(t, ept)
                return hierarchy.gather_parts(tab, sv, dv, e=e)
            if T_EP == 1:
                return tab[sv, dv]
            return tab[_ep_of(t, ept), sv, dv]

        # gather-free topology lookups (see EngineConfig.table_onehot).
        # The run count of host_vertex (padding included) is a static
        # fact of the program; the run starts and vertices stay traced
        # values, derived on the device by _topo.
        hv_np = self.host_vertex
        N_RUNS = 1 + int(np.count_nonzero(hv_np[1:] != hv_np[:-1]))
        want_tab = (cfg.table_onehot if cfg.table_onehot is not None
                    else platform == "tpu")
        why_not = ("model_bandwidth judges in the step" if MB else
                   f"fault epoch table (T={T_EP})" if T_EP > 1 else
                   "hierarchical representation" if HIER else
                   f"V*V = {V * V} > 128" if V * V > 128 else
                   f"{N_RUNS} host-vertex runs > 128" if N_RUNS > 128
                   else None)
        TAB_ONEHOT = bool(want_tab) and why_not is None
        R_RUNS = N_RUNS if TAB_ONEHOT else 0
        if want_tab and not TAB_ONEHOT:
            log.info("table_onehot disabled: %s; the lookups gather",
                     why_not)
        # statically lossless topologies (all reliability == 1) never
        # drop: packet_drop_mask is False for every row regardless of
        # the roll, so the threefry batch is skipped outright. Under
        # an ensemble the check spans every replica's table — one
        # lossy replica keeps the rolls for all.
        if HIER:
            _rel_tab = (self.ensemble.reliability
                        if self.ensemble is not None
                        else self.reliability)
            ALL_REL1 = hierarchy.all_rel1(_rel_tab)
        else:
            ALL_REL1 = bool((np.asarray(
                self.ensemble.reliability if self.ensemble is not None
                else self.reliability) >= 1.0).all())

        # model-NIC constants (host/model_nic.py twins; keep in
        # lockstep with its arithmetic — trace equality depends on it)
        from shadow_tpu.host.model_nic import (
            CODEL_INTERVAL_NS as CD_INT,
            CODEL_TARGET_NS as CD_TGT,
            LAW,
            LAW_SIZE,
            MAX_SER_BYTES as MAX_SER,
        )
        from shadow_tpu.core.event import KIND_PACKET_READY
        # shadowlint: const-ok(LAW is a constant table from
        # host/model_nic.py, a CODE_DIGEST_MODULES member — an edit
        # invalidates every cached executable via the code digest)
        law_t = jnp.asarray(LAW)                       # [1024] i64
        # shadowlint: const-ok(the per-host bandwidth vectors are
        # deliberately baked, not threaded through wrld — aotcache
        # keys entries on their bw_digest under model_bandwidth)
        bw_up_t = jnp.asarray(self.bw_up)              # [H_pad] i64
        bw_down_t = jnp.asarray(self.bw_down)
        NSx8 = np.int64(8) * np.int64(1_000_000_000)

        U32 = jnp.int64(0xFFFFFFFF)

        def pack2(hi, lo):
            return ((hi.astype(jnp.int64) & U32) << 32) | \
                (lo.astype(jnp.int64) & U32)

        def hi32(x):
            return (x >> 32).astype(jnp.int32)

        def lo32(x):
            return (x & U32).astype(jnp.int32)

        hidx = jnp.arange(H_loc)

        def _take_head(arr, head, fill):
            if POP_ONEHOT:
                m = jnp.arange(E)[None, :] == head[:, None]
                v = jnp.where(m, arr,
                              jnp.zeros((), arr.dtype)).sum(axis=1)
                return jnp.where(head < E, v, fill)
            v = jnp.take_along_axis(
                arr, jnp.minimum(head, E - 1)[:, None], axis=1)[:, 0]
            return jnp.where(head < E, v, fill)

        def _pair(tab, sv, dv):
            """tab[sv, dv] of a dense [V,V] table by one-hot masked
            sums over its V*V entries (exact: one nonzero term)."""
            pair = sv * jnp.int32(V) + dv
            flat = tab.reshape(-1)
            out = jnp.zeros(pair.shape, tab.dtype)
            for j in range(V * V):
                out = out + jnp.where(pair == j, flat[j],
                                      jnp.zeros((), tab.dtype))
            return out

        def _topo(host_vertex, my_shard, wrld):
            """What the round's topology lookups read, built once per
            program invocation, outside the round loop: the host ->
            vertex table "hv", and under TAB_ONEHOT what replaces its
            per-lane gathers. host_vertex is constant on R_RUNS runs of
            host ids: "starts" [R-1] are the starts of the runs after
            the first, "vrun" [R] their vertices, "srcv" [H_loc] this
            shard's hosts' vertices (a contiguous slice) and "self"
            [H_loc] their self-latency."""
            topo = {"hv": host_vertex}
            if not TAB_ONEHOT:
                return topo
            if R_RUNS > 1:
                # the run starts in order, with each run's vertex: one
                # [H_pad] compare and a sort that puts the R-1 change
                # positions first
                chg = host_vertex[1:] != host_vertex[:-1]
                pos = jnp.arange(1, H_pad, dtype=jnp.int32)
                skey, sval = lax.sort(
                    (jnp.where(chg, pos, jnp.int32(H_pad)),
                     host_vertex[1:]), num_keys=1)
                topo["starts"] = skey[:R_RUNS - 1]
                topo["vrun"] = jnp.concatenate(
                    [host_vertex[:1], sval[:R_RUNS - 1]])
            else:
                topo["vrun"] = host_vertex[:1]
            srcv = lax.dynamic_slice(host_vertex, (my_shard * H_loc,),
                                     (H_loc,))
            topo["srcv"] = srcv
            topo["self"] = _pair(wrld[0], srcv, srcv).astype(jnp.int64)
            return topo

        def _lookup(topo, wrld, gid, t, dst):
            """Path (latency i64, reliability) of each send lane
            [H, n] from its host to dst at send time t. Under
            TAB_ONEHOT dst's vertex is that of the last run whose
            start is <= dst — a dst outside [0, H_pad) lands on the
            first or last run, as the gather's clip does — and the
            pair is read by _pair's one-hot sums."""
            lat, rel, _, _, ept = wrld
            if TAB_ONEHOT:
                vrun = topo["vrun"]
                dstv = jnp.broadcast_to(vrun[0], dst.shape)
                for j in range(R_RUNS - 1):
                    dstv = jnp.where(dst >= topo["starts"][j],
                                     vrun[j + 1], dstv)
                srcv = topo["srcv"][:, None]
                return (_pair(lat, srcv, dstv).astype(jnp.int64),
                        _pair(rel, srcv, dstv))
            hv = topo["hv"]
            srcv = hv[gid][:, None]
            dstv = hv[jnp.clip(dst, 0, H_pad - 1)]
            return (_tbl(lat, t, srcv, dstv, ept).astype(jnp.int64),
                    _tbl(rel, t, srcv, dstv, ept))

        # ---------------- inner loop body: one event per host ----------
        # (up to P events for an app's declared burst hosts)
        # `wrld` is the traced per-world tuple (lat, rel, seed k1,
        # seed k2, epoch times): everything a replica may vary without
        # changing shapes — the ensemble program vmaps over a stacked
        # axis of exactly these plus the state.
        def _step(carry, win_end, gid, topo, wrld):
            lat, _, sk1, sk2, ept = wrld
            seed_pair = (sk1, sk2)
            state, ob, blk, dirty = carry
            head = state["head"]
            if P > 1:
                offs = jnp.arange(P, dtype=head.dtype)
                idxs = head[:, None] + offs

                def _take_heads(arr, fill):
                    if POP_ONEHOT:
                        m = jnp.arange(E)[None, None, :] == \
                            idxs[:, :, None]
                        v = jnp.where(m, arr[:, None, :],
                                      jnp.zeros((), arr.dtype)) \
                            .sum(axis=-1)
                        return jnp.where(idxs < E, v, fill)
                    v = jnp.take_along_axis(
                        arr, jnp.minimum(idxs, E - 1), axis=1)
                    return jnp.where(idxs < E, v, fill)

                ptP = _take_heads(state["ht"], INF)
                pk2P = _take_heads(state["hk"], IMAX)
                pmP = _take_heads(state["hm"], jnp.int64(0))
                pvP = _take_heads(state["hv"], jnp.int64(0))
                pwP = _take_heads(state["hw"], jnp.int64(0))
                pt, pk2 = ptP[:, 0], pk2P[:, 0]
                pm, pv, pw = pmP[:, 0], pvP[:, 0], pwP[:, 0]
            else:
                pt = _take_head(state["ht"], head, INF)
                pk2 = _take_head(state["hk"], head, IMAX)
                pm = _take_head(state["hm"], head, jnp.int64(0))
                pv = _take_head(state["hv"], head, jnp.int64(0))
                pw = _take_head(state["hw"], head, jnp.int64(0))
            psrc, pseq = hi32(pk2), lo32(pk2)
            pkind, psize = hi32(pm), lo32(pm)
            pd0, pd1 = hi32(pv), lo32(pv)
            pd2 = lo32(pw)

            # a host with a possibly-in-window insert pending in the
            # outbox (dirty) must stall until the flush lands it, or
            # it would pop later events first (order violation)
            runnable = (pt < win_end) & ~dirty
            if P > 1:
                # burst hosts pop their RUN of consecutive in-window
                # packet events (the stateless-responder contract:
                # handling order within the run cannot feed back into
                # the run); everyone else pops one event as usual
                bm = app.burst_mask(state["app"])
                kindP = hi32(pmP)
                eligP = (ptP < win_end) & (kindP == KIND_PACKET)
                run = jnp.cumprod(eligP.astype(jnp.int32), axis=1)
                popcnt = jnp.where(
                    runnable,
                    jnp.where(bm & eligP[:, 0], run.sum(-1), 1),
                    0).astype(head.dtype)
                activeP = offs[None, :] < popcnt[:, None]   # [H,P]
                # events run in burst columns 1..P-1 (a reduction only)
                state["occ_burst"] = state["occ_burst"] + jnp.maximum(
                    popcnt - 1, 0).sum(dtype=jnp.int32)
            else:
                popcnt = runnable.astype(head.dtype)
            state["head"] = head + popcnt

            state["n_exec"] = state["n_exec"] + \
                popcnt.astype(jnp.int32)
            if AUDIT:
                # per-host clock monotonicity: popping an event older
                # than one already executed means the heap (or a
                # resume) handed events out of order
                prev_t = state["aud_t"]
                state["aud"] = state["aud"] | jnp.where(
                    runnable & (pt < prev_t),
                    jnp.int32(AUD_CLOCK), jnp.int32(0))
                if P > 1:
                    last_t = jnp.where(activeP, ptP,
                                       jnp.int64(0)).max(-1)
                else:
                    last_t = pt
                state["aud_t"] = jnp.where(
                    runnable, jnp.maximum(prev_t, last_t), prev_t)
            # with the model NIC, a packet pops twice: the RX stage
            # (KIND_PACKET: bandwidth+CoDel, no app) and the delivery
            # (KIND_PACKET_READY). Deliveries are the READY pops then.
            is_rx = runnable & (pkind == KIND_PACKET) if MB else \
                jnp.zeros_like(runnable)
            if P > 1:
                # delivered PACKETS: popcount(d2) survivors per popped
                # packet row, summed over the burst
                is_pktP = activeP & (kindP == KIND_PACKET)
                state["n_deliv"] = state["n_deliv"] + jnp.where(
                    is_pktP,
                    lax.population_count(lo32(pwP).astype(jnp.uint32))
                    .astype(jnp.int32), 0).sum(-1, dtype=jnp.int32)
                # the trace checksum folds each popped event exactly
                # as the serial oracle does — stepwise (the inter-step
                # MASK63 truncation makes a closed-form fold wrong)
                chk = state["chk"]
                srcPa, seqPa = hi32(pk2P), lo32(pk2P)
                for j in range(P):
                    mix_j = (ptP[:, j]
                             ^ (srcPa[:, j].astype(jnp.int64)
                                * CHK_SRC)
                             ^ (kindP[:, j].astype(jnp.int64)
                                * CHK_KIND)
                             ^ (seqPa[:, j].astype(jnp.int64)
                                * CHK_SEQ)) & MASK63
                    chk = jnp.where(activeP[:, j],
                                    (chk * CHK_MUL + mix_j) & MASK63,
                                    chk)
                state["chk"] = chk
            else:
                is_pkt = runnable & (pkind == (KIND_PACKET_READY if MB
                                               else KIND_PACKET))
                # delivered PACKETS: a train row carries popcount(d2)
                # survivors (ordinary packets carry d2 == 1)
                state["n_deliv"] = state["n_deliv"] + jnp.where(
                    is_pkt,
                    lax.population_count(pd2.astype(jnp.uint32))
                    .astype(jnp.int32), 0)
                mix = (pt ^ (psrc.astype(jnp.int64) * CHK_SRC)
                       ^ (pkind.astype(jnp.int64) * CHK_KIND)
                       ^ (pseq.astype(jnp.int64) * CHK_SEQ)) & MASK63
                state["chk"] = jnp.where(
                    runnable, (state["chk"] * CHK_MUL + mix) & MASK63,
                    state["chk"])

            # app dispatch (batched); masked hosts see kind=-1. Under
            # the model NIC the RX stage is engine-internal (app sees
            # -1) and READY pops present as KIND_PACKET to the app.
            with jax.named_scope("engine.app"):
                draw_seqs = state["app_seq"][:, None] + \
                    jnp.arange(D, dtype=jnp.int32)
                draws = prng.random_bits32(prng.chain_key(
                    seed_pair, PURPOSE_APP, gid[:, None], draw_seqs))
                seed_kw = {"seed_pair": seed_pair} if app.uses_seed \
                    else {}
                if P > 1:
                    # burst dispatch: the app sees all P popped columns
                    # (inactive ones as kind=-1) and answers each on its
                    # own send lane
                    kindP_app = jnp.where(activeP, kindP, -1)
                    out = app.handle_burst(
                        gid, ptP, kindP_app, srcPa, lo32(pmP),
                        hi32(pvP), lo32(pvP), lo32(pwP), state["app"],
                        draws, **seed_kw)
                    app_on = runnable
                else:
                    if MB:
                        app_kind = jnp.where(pkind == KIND_PACKET_READY,
                                             jnp.int32(KIND_PACKET),
                                             pkind)
                        app_kind = jnp.where(runnable & ~is_rx,
                                             app_kind, -1)
                    else:
                        app_kind = jnp.where(runnable, pkind, -1)
                    out = app.handle(gid, pt, app_kind,
                                     psrc, psize, pd0, pd1, pd2,
                                     state["app"], draws, **seed_kw)
                    app_on = runnable & ~is_rx if MB else runnable
            # apps may return [H,1] columns that broadcast over K/T
            out = out._replace(
                send_dst=jnp.broadcast_to(out.send_dst,
                                          (H_loc, K_eff)),
                send_size=jnp.broadcast_to(out.send_size,
                                           (H_loc, K_eff)),
                send_d0=jnp.broadcast_to(out.send_d0, (H_loc, K_eff)),
                send_d1=jnp.broadcast_to(out.send_d1, (H_loc, K_eff)),
                send_valid=jnp.broadcast_to(out.send_valid,
                                            (H_loc, K_eff)),
                timer_delay=jnp.broadcast_to(out.timer_delay,
                                             (H_loc, T)),
                timer_d0=jnp.broadcast_to(out.timer_d0, (H_loc, T)),
                timer_valid=jnp.broadcast_to(out.timer_valid,
                                             (H_loc, T)),
            )
            # send lane j of a burst departs at ITS popped event's
            # time (bit-identical bootstrap gating + delivery times)
            lane_t = ptP if P > 1 else pt[:, None]
            state["app"] = jnp.where(app_on[:, None], out.app_state,
                                     state["app"])
            state["app_seq"] = state["app_seq"] + \
                jnp.where(app_on, out.n_draws, 0)

            # sends -> network judgment (worker_sendPacket semantics)
            send_valid = out.send_valid & app_on[:, None]       # [H,K]
            vrank = jnp.cumsum(send_valid, axis=-1) - send_valid
            if C > 1:
                counts = jnp.clip(
                    jnp.broadcast_to(out.send_count, (H_loc, K_eff))
                    if out.send_count is not None
                    else jnp.ones((H_loc, K_eff), jnp.int32), 1, C)
                vcnt = counts * send_valid
                state["packet_seq"] = state["packet_seq"] + \
                    vcnt.sum(-1).astype(jnp.int32)
            else:
                counts = jnp.ones((H_loc, K_eff), jnp.int32)
                vcnt = send_valid.astype(jnp.int32)
                state["packet_seq"] = state["packet_seq"] + \
                    send_valid.sum(-1).astype(jnp.int32)

            dst = out.send_dst                                   # [H,K]
            if HOIST:
                # raw rows only: depart time (== the popped event
                # time — also the drop-roll key time the judge
                # re-derives), train count, and the live-lane mask.
                # _judge_outbox settles drops/latency once per phase.
                depart = lane_t
                if out.send_mask is not None:
                    smask = jnp.broadcast_to(
                        out.send_mask, (H_loc, K_eff)).astype(jnp.int32)
                else:
                    smask = jnp.full((H_loc, K_eff), -1, jnp.int32)
            else:
                with jax.named_scope("engine.judge"):
                    if C > 1:
                        ccum = jnp.cumsum(vcnt, axis=-1) - vcnt
                        pkt_seq = state["packet_seq"][:, None] - \
                            vcnt.sum(-1).astype(jnp.int32)[:, None] + ccum
                    else:
                        pkt_seq = state["packet_seq"][:, None] - \
                            send_valid.sum(-1).astype(jnp.int32)[:, None] \
                            + vrank
                    # epoch keyed on the SEND time (lane_t), matching the
                    # CPU model's judge(now=send time) under faults
                    latv, relv = _lookup(topo, wrld, gid, lane_t,
                                         dst)                    # [H,K]
                    if C > 1:
                        # packet TRAINS: one drop roll per packet, keyed by the
                        # exact (src, pkt_seq0+j) sequence individual sends
                        # would consume — loss statistics are bit-identical to
                        # per-packet sends; survivors become the d2 bitmask
                        js = jnp.arange(C, dtype=jnp.int32)              # [C]
                        if ALL_REL1:
                            # statically lossless: the roll can never drop
                            drop3 = jnp.zeros((H_loc, K_eff, C), bool)
                        else:
                            seqs3 = pkt_seq[..., None] + js              # [H,K,C]
                            drop3 = packet_drop_mask(
                                seed_pair, BOOT_END, lane_t[..., None],
                                gid[:, None, None], seqs3, relv[..., None])
                        win3 = js[None, None, :] < counts[..., None]
                        if out.send_mask is not None:
                            # forwarding a previous hop's survivors: only LIVE
                            # lanes are packets (seq consumption + roll keys
                            # still span all `counts` lanes — twin alignment)
                            smask = jnp.broadcast_to(
                                out.send_mask, (H_loc, K_eff)) \
                                .astype(jnp.uint32)
                            live3 = win3 & (jnp.right_shift(
                                smask[..., None],
                                js.astype(jnp.uint32)[None, None, :])
                                & jnp.uint32(1)).astype(bool)
                        else:
                            live3 = win3
                        lost3 = drop3 & live3 & send_valid[..., None]
                        surv = jnp.where(
                            ~drop3 & live3,
                            jnp.left_shift(jnp.uint32(1),
                                           js.astype(jnp.uint32)),
                            jnp.uint32(0)).sum(-1, dtype=jnp.uint32)     # [H,K]
                        surv = jnp.where(send_valid, surv, 0)
                        dropped = send_valid & (surv == 0)
                        n_lost = lost3.sum((-2, -1)).astype(jnp.int32)
                        livecnt = (live3 & send_valid[..., None]).sum(
                            -1, dtype=jnp.int32)                         # [H,K]
                    else:
                        dropped = send_valid & (
                            jnp.zeros((H_loc, K_eff), bool) if ALL_REL1
                            else packet_drop_mask(
                                seed_pair, BOOT_END, lane_t, gid[:, None],
                                pkt_seq, relv))
                        surv = jnp.where(send_valid & ~dropped,
                                         jnp.uint32(1), jnp.uint32(0))
                        n_lost = dropped.sum(-1).astype(jnp.int32)
                        livecnt = vcnt
            if MB:
                # TX fluid bucket (ModelNic.tx_depart): a burst's sends
                # serialize in slot order; drop-rolled packets still
                # consume uplink time (the network drops them later)
                ser_up = jnp.where(
                    send_valid,
                    (jnp.clip(out.send_size, 1,
                              MAX_SER).astype(jnp.int64)
                     * NSx8) // bw_up_t[gid][:, None],
                    jnp.int64(0))                                # [H,K]
                tx_base = jnp.maximum(pt, state["tx_free"])      # [H]
                cum = jnp.cumsum(ser_up, axis=-1)
                depart = tx_base[:, None] + (cum - ser_up)
                state["tx_free"] = jnp.where(
                    runnable, tx_base + cum[:, -1], state["tx_free"])
            elif not HOIST:
                depart = lane_t
            if not HOIST:
                with jax.named_scope("engine.judge"):
                    delivered = send_valid & ~dropped
                    state["n_sent"] = state["n_sent"] + \
                        livecnt.sum(-1).astype(jnp.int32)
                    state["n_drop"] = state["n_drop"] + n_lost
                    deliver_t = depart + latv
                    cross = dst != gid[:, None]
                    # cross-host causality bump (host_single.c:174-220);
                    # self packets keep their true time — they may run this
                    # window (the flush + another phase makes them
                    # poppable)
                    deliver_t = jnp.where(cross,
                                          jnp.maximum(deliver_t, win_end),
                                          deliver_t)

            # event seq consumed per SEND (delivered or dropped alike),
            # matching the CPU engines — lets the CPU side defer drop
            # judgment to a batched device call without perturbing seqs
            ev_seq = state["event_seq"][:, None] + vrank
            n_snt = send_valid.sum(-1).astype(jnp.int32)

            # model-NIC RX stage (ModelNic.rx_deliver twin): the popped
            # KIND_PACKET row passes the download bucket + event-driven
            # CoDel; survivors re-enter via the outbox as READY rows at
            # their post-serialization delivery time (same src/seq)
            if MB:
                rxf = state["rx_free"]
                dq = jnp.maximum(pt, rxf)                       # [H]
                soj = dq - pt
                below = soj < CD_TGT
                fa = state["cd_fa"]
                fa0 = fa == 0
                above = ~below & ~fa0 & (dq >= fa)
                in_drop = state["cd_drop"] != 0
                drop_now = above & in_drop & (dq >= state["cd_next"])
                drop_first = above & ~in_drop
                rx_drop = is_rx & (drop_now | drop_first)
                rx_keep = is_rx & ~(drop_now | drop_first)

                delta = state["cd_cnt"] - state["cd_last"]
                first_cnt = jnp.where(
                    (dq - state["cd_next"] < CD_INT) & (delta > 1),
                    delta, jnp.int64(1))
                new_cnt = jnp.where(
                    drop_now, state["cd_cnt"] + 1,
                    jnp.where(drop_first, first_cnt, state["cd_cnt"]))
                law = law_t[jnp.clip(new_cnt, 0, LAW_SIZE - 1)]
                new_next = jnp.where(
                    drop_now, state["cd_next"] + law,
                    jnp.where(drop_first, dq + law, state["cd_next"]))
                new_last = jnp.where(drop_first, first_cnt,
                                     state["cd_last"])
                new_fa = jnp.where(below, jnp.int64(0),
                                   jnp.where(fa0, dq + CD_INT, fa))
                new_cd_drop = jnp.where(
                    below, jnp.int64(0),
                    jnp.where(fa0, state["cd_drop"],
                              jnp.where(above,
                                        jnp.where(in_drop,
                                                  state["cd_drop"],
                                                  jnp.int64(1)),
                                        jnp.int64(0))))

                ser_down = (jnp.clip(psize, 1, MAX_SER)
                            .astype(jnp.int64) * NSx8) \
                    // bw_down_t[gid]
                rx_deliver = dq + ser_down
                for f_, v_ in (("cd_cnt", new_cnt),
                               ("cd_next", new_next),
                               ("cd_last", new_last),
                               ("cd_fa", new_fa),
                               ("cd_drop", new_cd_drop)):
                    state[f_] = jnp.where(is_rx, v_, state[f_])
                state["rx_free"] = jnp.where(rx_keep, rx_deliver, rxf)
                state["n_drop"] = state["n_drop"] + rx_drop
            else:
                rx_keep = jnp.zeros_like(runnable)
                rx_deliver = pt

            # timers (self rows; may fire inside this window)
            timer_valid = out.timer_valid & app_on[:, None]     # [H,T]
            trank = jnp.cumsum(timer_valid, axis=-1) - timer_valid
            tseq = state["event_seq"][:, None] + n_snt[:, None] + trank
            state["event_seq"] = state["event_seq"] + n_snt + \
                timer_valid.sum(-1).astype(jnp.int32)
            timer_t = pt[:, None] + out.timer_delay

            # the iteration's outbox block: K sends | T timers | READY.
            # EVERY insert goes through the outbox (no heap scatters);
            # a self-destined row that could run inside this window
            # marks the host dirty so pop order is preserved.
            def cols(*parts):
                return jnp.concatenate(
                    parts[:2 + (1 if MB else 0)], axis=1)

            gcol = jnp.broadcast_to(gid[:, None], (H_loc, K_eff))
            gcolT = jnp.broadcast_to(gid[:, None], (H_loc, T))
            if HOIST:
                # raw rows: depart time, train COUNT in the kind field
                # (the judge rewrites it with the live count), and the
                # live-lane mask where the judge puts the survivors
                bvalid_send = send_valid
                send_t = depart
                kcnt = counts
                vhi = smask
            elif CP:
                # drop-rolled sends ride along under the reserved
                # DROP_T marker so the flush's path histogram counts
                # them (ref counts per SENT packet, worker.c:554)
                bvalid_send = send_valid
                send_t = jnp.where(delivered, deliver_t, DROP_T)
                kcnt = livecnt
                vhi = surv.astype(jnp.int32)
            else:
                bvalid_send = delivered
                send_t = deliver_t
                kcnt = livecnt
                vhi = surv.astype(jnp.int32)
            bvalid = cols(bvalid_send, timer_valid, rx_keep[:, None])
            bt = jnp.where(bvalid,
                           cols(send_t, timer_t,
                                rx_deliver[:, None]),
                           INF)
            bk = cols(pack2(gcol, ev_seq), pack2(gcolT, tseq),
                      pk2[:, None])
            bdst = cols(dst, gcolT, gid[:, None])
            # packet-kind rows carry their train count in bits 8+ of
            # the kind field (histogram weight; kind itself is <256)
            bkind = cols(
                jnp.full((H_loc, K_eff), KIND_PACKET, jnp.int32)
                | (kcnt << 8),
                jnp.full((H_loc, T), KIND_TIMER, jnp.int32),
                jnp.full((H_loc, 1), KIND_PACKET_READY, jnp.int32))
            bm = pack2(bdst, bkind)
            bs = pack2(cols(out.send_size,
                            jnp.zeros((H_loc, T), jnp.int32),
                            psize[:, None]),
                       cols(out.send_d0, out.timer_d0, pd0[:, None]))
            bv = pack2(cols(vhi,
                            jnp.zeros((H_loc, T), jnp.int32),
                            pd2[:, None]),
                       cols(out.send_d1,
                            jnp.zeros((H_loc, T), jnp.int32),
                            pd1[:, None]))

            col0 = blk * jnp.int32(M_out)
            for f, block in (("t", bt), ("k", bk), ("m", bm),
                             ("s", bs), ("v", bv)):
                ob[f] = lax.dynamic_update_slice(ob[f], block,
                                                 (jnp.int32(0), col0))

            if HOIST:
                # the judge hasn't run, so in-window detection uses the
                # host's SELF-latency (self rows never take the bump);
                # conservative over drop rolls — a later-dropped self
                # send still stalls the host one phase, which only
                # moves the phase boundary, never the per-host pop
                # order (the trace is bit-identical either way)
                if TAB_ONEHOT:
                    selflat = topo["self"][:, None]              # [H,1]
                else:
                    hvg = topo["hv"][gid][:, None]               # [H,1]
                    selflat = _tbl(lat, depart, hvg, hvg,
                                   ept).astype(jnp.int64)
                self_in = send_valid & (dst == gid[:, None]) & \
                    (depart + selflat < win_end)
                tim_in = timer_valid & (timer_t < win_end)
                dirty = dirty | (runnable &
                                 (self_in.any(-1) | tim_in.any(-1)))
            else:
                in_win = bvalid & (bt < win_end) & \
                    (bdst == gid[:, None])
                dirty = dirty | (runnable & in_win.any(-1))

            return state, ob, blk + 1, dirty

        # ---------------- flush: exchange + merge ----------------------
        # Deterministic arrival order — keyed by skey = dst*SPAN + okey
        # with okey = src_gid*OB + column — independent of mesh shape
        # and exchange strategy. Rows beyond a dst host's IN window (or
        # a shard pair's CAP) are counted in overflow/x_overflow and
        # fail the run — never silently lost (SURVEY hard-part #2).

        # Sorts move every operand through every bitonic pass, so on
        # one CPU core the window path's flat sort carries ONLY (key,
        # iota) and recovers arrival rows with gathers (seg_take) — the
        # profiler showed the old 6-operand flat sort dominating round
        # cost there. On TPU a gather costs several times a sort's
        # extra operands, so the arrival windows and the per-host merge
        # row sort both carry the payload instead (merge_payload
        # "sort").
        CX = min(cfg.outbox_compact or OB, OB)

        # effective (post-auto-sizing) capacities, for the occupancy
        # record and the planner's re-plan arithmetic. ICI_* is the
        # per-flush cross-chip traffic each shard SENDS (buffers ship
        # at capacity, padding included — that IS the wire cost), so
        # bench/tpu_micro report exchanged volume without touching
        # device state: rows/round = ICI_rows_per_flush * phases /
        # rounds.
        if n_shards <= 1:
            ici_rows, ici_arrays = 0, 0
        elif cfg.exchange == "all_to_all":
            # [n_shards, CAP] buffers; the self slot never crosses ICI
            ici_rows = (n_shards - 1) * int(CAP)
            # 5 field arrays, + the shipped sort keys on the window
            # merge path (the global merge re-derives order)
            ici_arrays = 5 if MERGE_GLOBAL else 6
        elif cfg.exchange == "two_phase":
            ici_rows = (TP_G - 1) * int(CAP) + \
                (TP_NG - 1) * int(CAP2)
            ici_arrays = 6          # keys route phase 2 on both paths
        else:                       # all_gather replicates everything
            ici_rows = (n_shards - 1) * H_loc * CX
            ici_arrays = 5 if MERGE_GLOBAL else 6   # + the keys
        self.effective = {"E": E, "B": B, "OB": OB, "IN": IN,
                          "CAP": int(CAP), "CAP2": int(CAP2),
                          "CX": CX, "M_out": M_out,
                          "n_shards": n_shards,
                          "exchange": cfg.exchange,
                          "tp_groups": [int(TP_G), int(TP_NG)],
                          "ICI_rows_per_flush": int(ici_rows),
                          "ICI_bytes_per_flush":
                              int(ici_rows) * ici_arrays * 8,
                          "table_onehot": bool(TAB_ONEHOT),
                          "vertex_runs": int(R_RUNS)}
        # the resolved compile-time surface of the traced programs:
        # every value the trace bakes in as a constant (capacities,
        # platform-resolved strategy flags, lookahead/bootstrap,
        # fault epoch count, audit, ensemble width, ...). The AOT
        # compile cache (device/aotcache.py) keys serialized
        # executables on this dict — a knob that newly shapes the
        # program must join here or stale cache entries would load
        # for the wrong trace. Runtime-scalar inputs (stop,
        # final_stop, seeds, the world tables' VALUES) stay out:
        # they are traced, not baked.
        self.program_facts = {
            "n_hosts": int(cfg.n_hosts),
            "h_pad": int(H_pad), "h_loc": int(H_loc),
            "n_shards": int(n_shards),
            "capacities": {"E": int(E), "OB": int(OB), "IN": int(IN),
                           "CAP": int(CAP), "CAP2": int(CAP2),
                           "CX": int(CX)},
            "exchange": cfg.exchange,
            "tp_groups": [int(TP_G), int(TP_NG)],
            "lookahead": int(LOOKAHEAD),
            "bootstrap_end": int(BOOT_END),
            "max_rounds": int(cfg.max_rounds),
            "fault_epochs": int(T_EP),
            "audit": bool(AUDIT),
            "model_bandwidth": bool(MB),
            "count_paths": bool(CP),
            "judge_hoist": bool(HOIST),
            "merge_global": bool(MERGE_GLOBAL),
            "merge_payload": MERGE_PAYLOAD,
            "pop_onehot": bool(POP_ONEHOT),
            "table_onehot": bool(TAB_ONEHOT),
            # the judge's run table is unrolled over the runs, so
            # tables with another run count never share an executable
            "vertex_runs": int(R_RUNS),
            "all_rel1": bool(ALL_REL1),
            "burst_pops": int(P),
            "lanes": {"K": int(K), "K_eff": int(K_eff), "T": int(T),
                      "D": int(D), "C": int(C), "M_out": int(M_out),
                      "B": int(B)},
            "n_vertices": int(V),
            # the factored-vs-dense world layout shapes the gather
            # trace, so two representations of the SAME topology must
            # never share a cached executable
            "representation": ("hierarchical" if HIER else "dense"),
            "n_clusters": (int(self.latency[0].shape[-1])
                           if HIER else 0),
            "ensemble_replicas": (int(self.ensemble.R)
                                  if self.ensemble is not None else 0),
        }
        log.info("engine strategies (%s): judge_hoist=%s "
                 "merge_global=%s merge_payload=%s pop_onehot=%s "
                 "table_onehot=%s vertex_runs=%d", platform, HOIST,
                 MERGE_GLOBAL, MERGE_PAYLOAD, POP_ONEHOT, TAB_ONEHOT,
                 R_RUNS)

        def _flat_rows(state, ob, gid):
            """The outbox as flat rows: (state, keys, rows), each row's
            key dst*SPAN + okey (IMAX = nothing to exchange), in outbox
            order — unsorted."""
            slot = jnp.arange(OB, dtype=jnp.int64)[None, :]
            okey2 = gid.astype(jnp.int64)[:, None] * OB + slot
            fdst2 = hi32(ob["m"]).astype(jnp.int64)
            # DROP_T rows exist only for the path histogram — they are
            # never exchanged or delivered
            valid2 = ob["t"] < DROP_T
            skey2 = jnp.where(valid2, fdst2 * SPAN + okey2, IMAX)
            if CX < OB:
                # two-level flush: each host's row compacts to its
                # first CX valid entries (a width-OB row sort — far
                # cheaper than pushing the ~98%-empty outbox through
                # the global sort), then the flat sort runs over
                # H*CX rows. Keys are unchanged, so the final order
                # is bit-identical whenever nothing overflows; the
                # loss is counted against the SENDING host.
                cols = jnp.broadcast_to(
                    slot, (H_loc, OB)).astype(jnp.int64)
                ssk, scol = lax.sort((skey2, cols), dimension=1,
                                     num_keys=1)
                state["x_overflow"] = state["x_overflow"] + \
                    (ssk[:, CX:] < IMAX).sum(-1).astype(jnp.int32)
                keep_col = scol[:, :CX].astype(jnp.int32)
                F = H_loc * CX
                flat = {f: jnp.take_along_axis(ob[f], keep_col,
                                               axis=1).reshape(F)
                        for f in XF}
                skey = ssk[:, :CX].reshape(F)
            else:
                F = H_loc * OB
                flat = {f: ob[f].reshape(F) for f in XF}
                skey = skey2.reshape(F)
            return state, skey, flat

        def _sorted_keys(ukey, with_perm=True):
            """(sorted keys, the sort permutation or None)."""
            if not with_perm:
                return lax.sort(ukey, is_stable=False), None
            return lax.sort((ukey, jnp.arange(ukey.shape[0],
                                              dtype=jnp.int64)),
                            num_keys=1)

        def _count_paths(state, ob, topo):
            """topology_incrementPathPacketCounter parity: a [V,V]
            histogram of SENT packets per (src_vertex, dst_vertex),
            drop-rolled packets included — scatter-free via one flat
            sort + prefix-sum segment totals."""
            F = H_loc * OB
            ft = ob["t"].reshape(F)
            fm = ob["m"].reshape(F)
            fk = ob["k"].reshape(F)
            kindf = lo32(fm)
            is_pkt = (ft < INF) & ((kindf & 0xFF) == KIND_PACKET)
            cnt = jnp.where(is_pkt, (kindf >> 8).astype(jnp.int64), 0)
            src = hi32(fk)
            dstf = hi32(fm)
            hv = topo["hv"]
            sv = hv[jnp.clip(src, 0, H_pad - 1)]
            dv = hv[jnp.clip(dstf, 0, H_pad - 1)]
            pair = jnp.where(is_pkt,
                             sv.astype(jnp.int64) * V + dv, V * V)
            spair, scnt = lax.sort((pair, cnt), num_keys=1)
            prefix = jnp.concatenate(
                [jnp.zeros((1,), jnp.int64), jnp.cumsum(scnt)])
            edges = jnp.searchsorted(
                spair, jnp.arange(V * V + 1, dtype=jnp.int64))
            state["path_cnt"] = state["path_cnt"] + \
                (prefix[edges[1:]] - prefix[edges[:-1]])[None, :]
            return state

        def _host_windows(state, ukey, rows, my_shard, srt=None):
            """My hosts' arrival rows -> the merge columns of their
            [H_loc, IN] windows (merge_cols) + overflow accounting
            (shared by the self-shard bypass and the post-exchange
            arrival step). `ukey` keys the unsorted `rows`; `srt` is
            _sorted_keys(ukey) where the caller has it. Also returns
            the per-host arrival counts (occupancy telemetry)."""
            if srt is None:
                srt = _sorted_keys(ukey, with_perm=MERGE_PAYLOAD == "gather")
            skey, perm = srt
            base = my_shard.astype(jnp.int64) * H_loc
            hb = (base + jnp.arange(H_loc + 1, dtype=jnp.int64)) \
                * SPAN
            edges = jnp.searchsorted(skey, hb)
            starts, counts = edges[:-1], edges[1:] - edges[:-1]
            state["overflow"] = state["overflow"] + \
                jnp.maximum(0, counts - IN).astype(jnp.int32)
            if MERGE_PAYLOAD == "sort":
                cols = sorted_windows(ukey, merge_cols(rows), counts,
                                      base, SPAN, IN)
            else:
                cols = merge_cols(seg_take(perm, rows, starts, counts,
                                           IN))
            return state, cols, counts.astype(jnp.int32)

        def _judge_outbox(state, ob, gid, topo, wrld,
                          win_end):
            """Per-phase network judgment of the raw outbox — the
            worker_sendPacket semantics (ref worker.c:520-579) hoisted
            out of the pop loop: path lookups, per-packet drop rolls
            under EXACTLY the keys the in-step path would use (src,
            per-source packet seq, send time), causality bump, and the
            sent/dropped counters. Runs once per phase over [H, OB]
            instead of once per pop iteration over [H, K]."""
            _, _, sk1, sk2, _ = wrld
            seed_pair = (sk1, sk2)
            ft, fm, fv = ob["t"], ob["m"], ob["v"]
            kindrow = lo32(fm)
            is_send = (ft < INF) & ((kindrow & 0xFF) == KIND_PACKET)
            cnt = jnp.where(is_send, kindrow >> 8, 0)        # [H,OB]
            dst = hi32(fm)
            # epoch keyed on the row's depart time `ft` — equal to the
            # send time in the hoisted (no-fluid-NIC) path, so the
            # drop-roll reliability and the latency come from the same
            # epoch the CPU twin reads. Empty rows (ft == INF) read the
            # last epoch harmlessly — they are masked by is_send
            # everywhere downstream.
            latv, relv = _lookup(topo, wrld, gid, ft, dst)    # [H,OB]

            # per-row packet-seq base: state["packet_seq"] is already
            # the END of the phase; outbox columns sit in consumption
            # order (iteration block, then send lane), so an exclusive
            # prefix over the train counts recovers each row's base
            tot = cnt.sum(-1)
            base = (state["packet_seq"] - tot)[:, None] + \
                (jnp.cumsum(cnt, axis=-1) - cnt)

            # live lanes are a 2D popcount (mask ∩ count window); the
            # ONLY [H,OB,C] reduce is the survivor bitmask, and it is
            # the single consumer of the threefry product — extra
            # reduce roots would each re-read (or recompute) the
            # materialized 3D tensor, which measured 3x the whole
            # judge's budget on CPU
            wbits = jnp.where(
                cnt >= 32, jnp.uint32(0xFFFFFFFF),
                jnp.left_shift(jnp.uint32(1),
                               jnp.clip(cnt, 0, 31).astype(jnp.uint32))
                - jnp.uint32(1))
            livemask = hi32(fv).astype(jnp.uint32) & wbits   # [H,OB]
            livecnt = lax.population_count(livemask) \
                .astype(jnp.int32)
            if ALL_REL1:
                # statically lossless: the roll can never drop
                surv = livemask
            else:
                js = jnp.arange(C, dtype=jnp.int32)
                live3 = (jnp.right_shift(
                    livemask[..., None],
                    js.astype(jnp.uint32)[None, None, :])
                    & jnp.uint32(1)).astype(bool)            # [H,OB,C]
                seqs3 = base[..., None] + js
                hk1, hk2 = prng.purpose_id_key(
                    seed_pair, PURPOSE_PACKET_DROP, gid)     # [H] each
                drop3 = packet_drop_mask(
                    seed_pair, BOOT_END, ft[..., None],
                    gid[:, None, None], seqs3, relv[..., None],
                    src_key=(hk1[:, None, None], hk2[:, None, None]))
                surv = jnp.where(
                    live3 & ~drop3,
                    jnp.left_shift(jnp.uint32(1),
                                   js.astype(jnp.uint32)),
                    jnp.uint32(0)).sum(-1, dtype=jnp.uint32)
            lost = livecnt - lax.population_count(surv) \
                .astype(jnp.int32)
            state["n_sent"] = state["n_sent"] + \
                livecnt.sum(-1).astype(jnp.int32)
            state["n_drop"] = state["n_drop"] + \
                lost.sum(-1).astype(jnp.int32)

            deliver_t = ft + latv
            cross = dst != gid[:, None]
            # cross-host causality bump (host_single.c:174-220); self
            # rows keep their true time
            deliver_t = jnp.where(cross,
                                  jnp.maximum(deliver_t, win_end),
                                  deliver_t)
            dead = is_send & (surv == 0)
            dead_t = DROP_T if CP else INF
            new_t = jnp.where(
                is_send, jnp.where(dead, dead_t, deliver_t), ft)
            new_m = jnp.where(
                is_send,
                pack2(dst, jnp.int32(KIND_PACKET) | (livecnt << 8)),
                fm)
            new_v = jnp.where(
                is_send, pack2(surv.astype(jnp.int32), lo32(fv)), fv)
            return state, {**ob, "t": new_t, "m": new_m, "v": new_v}

        # ---------------- gatherless flush (merge_global) --------------
        # TPU takes with computed indices cost ~10 ms per 500k
        # elements while multi-operand sorts of the same data cost
        # ~3 ms (bitonic passes are bandwidth-bound; gathers
        # serialize). So on TPU the flush is TWO stable sorts and
        # zero gathers: sort [outbox | heap] rows by (host, t, key),
        # rank rows within each host segment with segmented scans,
        # then re-sort by target slot host*E+rank — every host
        # contributes exactly E heap rows (consumed slots masked to
        # INF), so ranks 0..E-1 exist for every host and the kept
        # prefix reshapes straight into the [H, E] heaps. Rows
        # ranked >= E are the merge overflow; their per-host count
        # rides the second sort to slot [h, 0] on the rank-0 row.
        # Arrival order within a host is (t, src<<32|seq) — a total
        # order, so traces are bit-identical to the window path
        # whenever neither path overflows (both fail loudly).
        # (host, t) pack into one i64 sort key: host in the top bits,
        # time below. Real times at or above T_CAP would alias the
        # INF encoding — they are counted into `overflow` (loud run
        # failure) rather than silently reordered; sims needing
        # >2^T_BITS ns of horizon must pin merge_strategy: window.
        H_BITS = max(1, int(math.ceil(math.log2(H_loc + 2))))
        T_BITS = 63 - H_BITS
        T_CAP = np.int64((1 << T_BITS) - 1)

        def _henc(host, t):
            return (host.astype(jnp.int64) << T_BITS) | \
                jnp.minimum(t, T_CAP)

        def _ob_rows(ft, fk, fm, fs, fv, lo, hi):
            """Outbox-format flat rows -> merge-format
            (hostt key, k, hm, hv, hw, poison); rows outside [lo, hi)
            or not exchangeable (t >= DROP_T) mask to the sentinel
            segment H_loc (sorts after every real host, lands past
            the kept prefix)."""
            dst = hi32(fm)
            kindb = lo32(fm) & 0xFF        # strip the train count
            m2 = pack2(kindb, hi32(fs))
            v2 = pack2(lo32(fs), lo32(fv))
            w2 = (fv >> 32) & U32
            mine = (ft < DROP_T) & (dst >= lo) & (dst < hi)
            host = jnp.where(mine, dst - lo,
                             jnp.int32(H_loc)).astype(jnp.int32)
            t = jnp.where(mine, ft, INF)
            k = jnp.where(mine, fk, IMAX)
            poison = ((t >= T_CAP) & (t < INF)).sum() \
                .astype(jnp.int32)
            return _henc(host, t), k, m2, v2, w2, poison

        def _merge_rows(state, parts):
            """The double-sort merge: `parts` are (hostt, k, m, v, w,
            poison) flat row tuples (already in heap field format)."""
            live = jnp.arange(E)[None, :] >= state["head"][:, None]
            mt = jnp.where(live, state["ht"], INF)
            mk = jnp.where(live, state["hk"], IMAX).reshape(-1)
            hrow = jnp.broadcast_to(
                jnp.arange(H_loc, dtype=jnp.int32)[:, None],
                (H_loc, E))
            poison = (((mt >= T_CAP) & (mt < INF)).sum()
                      .astype(jnp.int32)
                      + sum(p[5] for p in parts))
            ghk = jnp.concatenate([_henc(hrow, mt).reshape(-1)]
                                  + [p[0] for p in parts])
            gk = jnp.concatenate([mk] + [p[1] for p in parts])
            gm = jnp.concatenate([state["hm"].reshape(-1)]
                                 + [p[2] for p in parts])
            gv = jnp.concatenate([state["hv"].reshape(-1)]
                                 + [p[3] for p in parts])
            gw = jnp.concatenate([state["hw"].reshape(-1)]
                                 + [p[4] for p in parts])
            N = ghk.shape[0]

            shk, sk_, sm_, sv_, sw_ = lax.sort(
                (ghk, gk, gm, gv, gw), num_keys=2)
            # occupancy: arrivals per host this flush — each host's
            # sorted segment holds exactly E heap rows plus arrivals
            # (masked heap slots encode t=T_CAP, staying in-segment)
            hb2 = jnp.arange(H_loc + 1, dtype=jnp.int64) << T_BITS
            seg_n = jnp.searchsorted(shk, hb2)
            state["occ_in"] = jnp.maximum(
                state["occ_in"],
                (seg_n[1:] - seg_n[:-1] - E).astype(jnp.int32))
            sh = (shk >> T_BITS).astype(jnp.int64)
            idx = jnp.arange(N, dtype=jnp.int64)
            is_new = jnp.concatenate(
                [jnp.ones((1,), bool), sh[1:] != sh[:-1]])
            seg0 = lax.associative_scan(
                jnp.maximum, jnp.where(is_new, idx, 0))
            rank = idx - seg0
            kept = rank < E
            is_real = (shk & T_CAP) < T_CAP
            dropped_real = (~kept) & is_real

            tgt = sh * E + rank
            key2 = jnp.where(kept, tgt,
                             INF + idx)
            _, t2k, k2, m2, v2, w2 = lax.sort(
                (key2, shk, sk_, sm_, sv_, sw_), num_keys=1)
            KEEP = H_loc * E
            enc = (t2k[:KEEP] & T_CAP).reshape(H_loc, E)
            state["ht"] = jnp.where(enc == T_CAP, INF, enc)
            state["hk"] = k2[:KEEP].reshape(H_loc, E)
            state["hm"] = m2[:KEEP].reshape(H_loc, E)
            state["hv"] = v2[:KEEP].reshape(H_loc, E)
            state["hw"] = w2[:KEEP].reshape(H_loc, E)

            # overflow: per-host attribution is a sort + searchsorted
            # we only pay when something actually dropped (never in a
            # healthy run); the poison count (times aliasing T_CAP)
            # lands on host 0 — both fail the run loudly either way
            n_drop_tot = dropped_real.sum()

            def _attr(_):
                dh = lax.sort(jnp.where(dropped_real, sh, IMAX))
                hb = jnp.searchsorted(
                    dh, jnp.arange(H_loc + 1, dtype=jnp.int64))
                return (hb[1:] - hb[:-1]).astype(jnp.int32)

            ov = lax.cond(
                (n_drop_tot + poison) > 0, _attr,
                lambda _: jnp.zeros(H_loc, jnp.int32), 0)
            state["overflow"] = state["overflow"] + ov + \
                jnp.zeros(H_loc, jnp.int32).at[0].add(poison)
            state["head"] = jnp.zeros_like(state["head"])
            state["occ_heap"] = jnp.maximum(
                state["occ_heap"],
                (state["ht"] < INF).sum(-1).astype(jnp.int32))
            return state

        # pack plumbing shared by the direct and two-phase schedules:
        # BOTH must account shard segments, occ_x demand, and loud
        # per-sender loss identically, or the cross-variant
        # determinism/planner contracts silently desynchronize — so
        # each piece exists exactly once.
        def _shard_edges(skey):
            """Per-destination-shard [start, count) segments of a
            sorted key array."""
            bound = (jnp.arange(n_shards + 1, dtype=jnp.int64)
                     * H_loc * SPAN)
            edges = jnp.searchsorted(skey, bound)
            return edges[:-1], edges[1:] - edges[:-1]

        def _shard_segments(state, skey, my_shard):
            """_shard_edges with the self shard's count zeroed (the
            bypass owns those rows) and the occ_x pair telemetry
            updated — what exchange_capacity must hold per pair."""
            starts, counts = _shard_edges(skey)
            counts = jnp.where(jnp.arange(n_shards) != my_shard,
                               counts, 0)
            state["occ_x"] = jnp.maximum(
                state["occ_x"], counts.astype(jnp.int32)[None, :])
            return state, starts, counts

        def _within_shard_rank(skey):
            """(dst shard, within-segment rank) per sorted row — the
            position a row competes for inside its destination
            segment. Empty rows (IMAX keys) share the n_shards
            sentinel segment."""
            idx = jnp.arange(skey.shape[0], dtype=jnp.int64)
            shard_of = jnp.minimum(skey // (H_loc * SPAN),
                                   jnp.int64(n_shards))
            is_new = jnp.concatenate(
                [jnp.array([True]), shard_of[1:] != shard_of[:-1]])
            seg0 = lax.associative_scan(
                jnp.maximum, jnp.where(is_new, idx, 0))
            return shard_of, idx - seg0

        def _lost_to_local(state, lost_mask, skey, my_shard):
            """Attribute lost rows to the LOCAL sending host (it owns
            the sizing knob): 1-key sort + searchsorted histogram,
            scatter-free like everything else."""
            src_loc = (skey % SPAN) // OB \
                - my_shard.astype(jnp.int64) * H_loc
            lk = lax.sort(jnp.where(lost_mask, src_loc, IMAX))
            hb = jnp.searchsorted(
                lk, jnp.arange(H_loc + 1, dtype=jnp.int64))
            state["x_overflow"] = state["x_overflow"] + \
                (hb[1:] - hb[:-1]).astype(jnp.int32)
            return state

        def _pack_remote(state, skey, perm, rows, my_shard,
                         ship_keys):
            """Pack genuinely remote rows into [n_shards, CAP] and
            move them with one all_to_all; self-shard rows never
            enter the pack (zero ICI, zero CAP). CAP overflow is
            attributed to the SENDING host. `ship_keys` additionally
            moves each row's skey (the window merge re-sorts arrivals
            by it; the global merge orders by (t, key) and skips the
            extra operand)."""
            G = H_loc * CX
            state, starts, counts = _shard_segments(state, skey,
                                                    my_shard)
            shard_of, rank = _within_shard_rank(skey)
            lost_mask = (skey < IMAX) & (rank >= CAP) & \
                (shard_of != my_shard.astype(jnp.int64))
            state = _lost_to_local(state, lost_mask, skey, my_shard)
            win = seg_take(perm, rows, starts, counts, CAP)
            with jax.named_scope("engine.exchange"):
                moved = {f: lax.all_to_all(
                    win[f], AXIS, split_axis=0, concat_axis=0)
                    .reshape(n_shards * CAP) for f in XF}
            kmoved = None
            if ship_keys:
                kidx = jnp.clip(
                    starts[:, None] + jnp.arange(CAP,
                                                 dtype=jnp.int64),
                    0, G - 1)
                kwin = jnp.where(
                    jnp.arange(CAP)[None, :] <
                    jnp.minimum(counts, CAP)[:, None],
                    jnp.take(skey, kidx.reshape(-1)).reshape(
                        n_shards, CAP),
                    IMAX)
                with jax.named_scope("engine.exchange"):
                    kmoved = lax.all_to_all(
                        kwin, AXIS, split_axis=0,
                        concat_axis=0).reshape(n_shards * CAP)
            return state, moved, kmoved

        # ---------------- two-phase hierarchical exchange --------------
        # (exchange: two_phase) shard s = (group a, rank b) with
        # g = TP_G intra-group shards. Phase 1 ships each remote row
        # to the IN-GROUP peer whose rank matches the destination's
        # rank (rows destined inside the group arrive final there);
        # phase 2 forwards across groups at fixed rank. Both phases
        # decompose into peer-offset ppermutes (neighbor schedules, in
        # the spirit of the direct-connect all-to-all schedules,
        # arxiv 2309.13541), and both buffers AGGREGATE many
        # destination pairs — a skewed pair borrows headroom from
        # quiet pairs instead of padding every [src, dst] slot to the
        # worst pair, which is where the ICI volume win comes from.
        # Determinism: rows carry their skey through both hops and the
        # merge orders arrivals by it (window path) or by (t, key)
        # (global path) — the route cannot reorder anything, so traces
        # are bit-identical to the direct all_to_all whenever neither
        # overflows (both fail loudly).
        TP_FIELDS = ("key",) + XF       # stacked ppermute channels

        def _tp_mask(ch, vals, ok):
            return jnp.where(ok, vals,
                             IMAX if ch == "key" else XF_FILL[ch])

        def _pack_two_phase(state, skey, perm, rows, my_shard):
            """Returns (state, keys, rows) of everything this shard
            received over both phases: phase-1 arrivals (deliveries
            AND forwards — callers mask non-local destinations) plus
            phase-2 arrivals (always local). CAP/CAP2 overflow is
            LOUD: phase-1 loss lands on the local sending host;
            phase-2 loss happens at the intermediate, so its count is
            psum'd home to the original sender's shard (behind a
            uniform-predicate cond — healthy flushes pay one scalar
            collective, nothing more)."""
            G = skey.shape[0]
            g, ng = TP_G, TP_NG
            my64 = my_shard.astype(jnp.int64)
            my_g, my_b = my64 // g, my64 % g
            state, starts, counts = _shard_segments(state, skey,
                                                    my_shard)

            counts2 = counts.reshape(ng, g)      # [dst group, rank]
            ends2 = jnp.cumsum(counts2, axis=0)
            off2 = ends2 - counts2               # exclusive, by group
            tot_rank = ends2[-1]                 # [g]

            # phase-1 overflow: within one RANK buffer, a row's slot
            # is its within-dst-shard rank plus the offset of earlier
            # groups' blocks; slots >= CAP never ship — counted
            # against the local sending host, like the direct pack
            shard_of, rank1 = _within_shard_rank(skey)
            d_clip = jnp.clip(shard_of, 0, n_shards - 1)
            pos1 = rank1 + off2.reshape(-1)[d_clip]
            lost1 = (skey < IMAX) & (shard_of != my64) & (pos1 >= CAP)
            state = _lost_to_local(state, lost1, skey, my_shard)

            # phase-1 buffers, keyed by peer OFFSET o (slot o goes to
            # in-group peer (a, (b+o) % g)): concatenated per-group
            # blocks of the rows destined that peer's rank
            ranks = (my_b + jnp.arange(g, dtype=jnp.int64)) % g
            ends_o = jnp.take(ends2, ranks, axis=1).T     # [g, ng]
            off_o = jnp.take(off2, ranks, axis=1).T       # [g, ng]
            starts_o = jnp.take(starts.reshape(ng, g), ranks,
                                axis=1).T                 # [g, ng]
            j1 = jnp.arange(CAP, dtype=jnp.int64)[None, :]
            a_star = jnp.clip(
                (ends_o[:, None, :] <= j1[..., None]).sum(-1),
                0, ng - 1)                                # [g, CAP]
            srcpos = jnp.take_along_axis(starts_o, a_star, axis=1) \
                + (j1 - jnp.take_along_axis(off_o, a_star, axis=1))
            ok1 = j1 < tot_rank[ranks][:, None]
            cidx = jnp.clip(srcpos, 0, G - 1).reshape(-1)
            pidx = jnp.take(perm, cidx)
            chans = []
            for ch in TP_FIELDS:
                # keys live in SORTED order (cidx); payload rows stay
                # unsorted and go through the sort permutation (pidx)
                v = jnp.take(skey, cidx) if ch == "key" \
                    else jnp.take(rows[ch], pidx)
                chans.append(_tp_mask(ch, v.reshape(g, CAP), ok1))
            sbuf = jnp.stack(chans)                       # [C, g, CAP]

            parts1 = [sbuf[:, 0]]
            for o in range(1, g):
                perm_o = [(s, (s // g) * g + ((s % g) + o) % g)
                          for s in range(n_shards)]
                with jax.named_scope("engine.exchange"):
                    parts1.append(lax.ppermute(sbuf[:, o], AXIS,
                                               perm_o))
            C = len(TP_FIELDS)
            recv1 = jnp.stack(parts1, axis=1).reshape(C, g * CAP)

            # phase 2: re-sort the received rows by skey (dst-shard
            # segments; every received row is destined rank my_b), my
            # own segment stays as deliveries, each other group's
            # segment forwards in one offset ppermute
            RK = g * CAP
            rkey_s, rperm = lax.sort(
                (recv1[0], jnp.arange(RK, dtype=jnp.int64)),
                num_keys=1)
            starts_r, counts_r = _shard_edges(rkey_s)
            shard_r, rank2 = _within_shard_rank(rkey_s)
            lost2 = (rkey_s < IMAX) & (shard_r != my64) & \
                (rank2 >= CAP2)
            with jax.named_scope("engine.exchange"):
                n_lost2 = _axis_sum64(lost2.sum())

            def _attr2(_):
                # the lost rows' senders live on OTHER shards (this
                # shard is only the intermediate): histogram by
                # global source gid, psum over the mesh, and keep the
                # local window — each loss lands on its true sender
                sg = jnp.where(lost2, (rkey_s % SPAN) // OB, IMAX)
                sgs = lax.sort(sg)
                hbg = jnp.searchsorted(
                    sgs, jnp.arange(H_pad + 1, dtype=jnp.int64))
                with jax.named_scope("engine.exchange"):
                    hist = lax.psum(
                        (hbg[1:] - hbg[:-1]).astype(jnp.int32), AXIS)
                return lax.dynamic_slice(
                    hist, (my_shard * H_loc,), (H_loc,))

            state["x_overflow"] = state["x_overflow"] + lax.cond(
                n_lost2 > 0, _attr2,
                lambda _: jnp.zeros(H_loc, jnp.int32), 0)

            j2 = jnp.arange(CAP2, dtype=jnp.int64)
            parts2 = []
            for q in range(1, ng):
                dq = ((my_g + q) % ng) * g + my_b
                ok2 = j2 < jnp.minimum(counts_r[dq], CAP2)
                pidx2 = jnp.take(
                    rperm, jnp.clip(starts_r[dq] + j2, 0, RK - 1))
                buf2 = jnp.stack([
                    _tp_mask(ch, jnp.take(recv1[c], pidx2), ok2)
                    for c, ch in enumerate(TP_FIELDS)])
                perm_q = [(s, ((s // g + q) % ng) * g + s % g)
                          for s in range(n_shards)]
                with jax.named_scope("engine.exchange"):
                    parts2.append(lax.ppermute(buf2, AXIS, perm_q))

            out = jnp.concatenate([recv1] + parts2, axis=1)
            return state, out[0], \
                {f: out[c + 1] for c, f in enumerate(XF)}

        def _compact_flat(state, ob):
            """Gatherless outbox compaction for the GLOBAL merge
            (outbox_compact; the window path has its own in
            _flat_rows): one 5-operand lane sort brings each
            host's exchangeable rows (t < DROP_T — they sort before
            judged-drop DROP_T markers and empty INF slots) to the
            front, then a STATIC slice keeps the first CX columns —
            zero gathers. Real rows beyond CX count loudly into
            x_overflow against the sending host. Shrinks the merge's
            double sort from H*(OB+E) to H*(CX+E) rows."""
            if CX >= OB:
                return state, \
                    {f: ob[f].reshape(H_loc * OB) for f in XF}
            st, sk, sm, ss, sv = lax.sort(
                (ob["t"], ob["k"], ob["m"], ob["s"], ob["v"]),
                dimension=1, num_keys=1)
            state["x_overflow"] = state["x_overflow"] + \
                (st[:, CX:] < DROP_T).sum(-1).astype(jnp.int32)
            comp = {"t": st, "k": sk, "m": sm, "s": ss, "v": sv}
            return state, {f: comp[f][:, :CX].reshape(H_loc * CX)
                           for f in XF}

        def _exchange_global(state, ob, gid, my_shard):
            lo = my_shard.astype(jnp.int32) * H_loc
            hi = lo + H_loc
            if n_shards > 1 and cfg.exchange == "all_to_all":
                # remote rows pack per (src shard, dst shard) for the
                # all_to_all (x_overflow accounting shared with the
                # window path); self-shard rows bypass the pack and
                # feed the merge directly. _flat_rows already
                # compacts its returned rows to CX (and counts the
                # loss once) — reuse them for the self-shard part
                # instead of re-compacting ob
                state, ukey, rows = _flat_rows(state, ob, gid)
                skey, perm = _sorted_keys(ukey)
                state, moved, _ = _pack_remote(
                    state, skey, perm, rows, my_shard,
                    ship_keys=False)
                parts = [
                    _ob_rows(rows["t"], rows["k"], rows["m"],
                             rows["s"], rows["v"], lo, hi),
                    _ob_rows(moved["t"], moved["k"], moved["m"],
                             moved["s"], moved["v"], lo, hi),
                ]
            elif n_shards > 1 and cfg.exchange == "two_phase":
                # hierarchical exchange; the received block still
                # holds the forwards this shard relayed (and any
                # phase-2 loss) — _ob_rows' [lo, hi) destination mask
                # drops them, so only true arrivals reach the merge
                state, ukey, rows = _flat_rows(state, ob, gid)
                skey, perm = _sorted_keys(ukey)
                state, kout, rout = _pack_two_phase(
                    state, skey, perm, rows, my_shard)
                parts = [
                    _ob_rows(rows["t"], rows["k"], rows["m"],
                             rows["s"], rows["v"], lo, hi),
                    _ob_rows(rout["t"], rout["k"], rout["m"],
                             rout["s"], rout["v"], lo, hi),
                ]
            elif n_shards > 1:
                # all_gather fallback: replicate every shard's
                # (compacted) outbox rows — compaction also cuts the
                # replicated ICI volume OB -> CX; each shard keeps
                # its own via the [lo, hi) mask inside _ob_rows
                state, flat = _compact_flat(state, ob)
                W = flat["t"].shape[0]
                with jax.named_scope("engine.exchange"):
                    allf = {f: lax.all_gather(flat[f], AXIS)
                            .reshape(n_shards * W) for f in XF}
                parts = [_ob_rows(allf["t"], allf["k"], allf["m"],
                                  allf["s"], allf["v"], lo, hi)]
            else:
                state, flat = _compact_flat(state, ob)
                parts = [_ob_rows(flat["t"], flat["k"], flat["m"],
                                  flat["s"], flat["v"], lo, hi)]
            with jax.named_scope("engine.merge"):
                return _merge_rows(state, parts)

        def _exchange(state, ob, gid, my_shard, topo, wrld,
                      win_end):
            with jax.named_scope("engine.flush"):
                if HOIST:
                    with jax.named_scope("engine.judge"):
                        state, ob = _judge_outbox(state, ob, gid,
                                                  topo, wrld,
                                                  win_end)
                if CP:
                    state = _count_paths(state, ob, topo)
                # occupancy: exchangeable outbox rows per host this phase
                # (post-judge, the population outbox_compact must hold)
                state["occ_ob"] = jnp.maximum(
                    state["occ_ob"],
                    (ob["t"] < DROP_T).sum(-1).astype(jnp.int32))
                state["occ_phases"] = state["occ_phases"] + jnp.int32(1)
                if AUDIT:
                    # conservation ledger: every exchangeable row
                    # (post-judge t < DROP_T — sends, timers, READY
                    # reinserts) must land in some host's heap or be
                    # counted into overflow/x_overflow; _audit_round
                    # balances this ledger against pops + live rows
                    state["aud_tx"] = state["aud_tx"] + \
                        (ob["t"] < DROP_T).sum(-1).astype(jnp.int64)
                if MERGE_GLOBAL:
                    return _exchange_global(state, ob, gid, my_shard)
                state, ukey, rows = _flat_rows(state, ob, gid)

                inc2 = None
                arr2 = jnp.zeros(H_loc, jnp.int32)
                if n_shards > 1 and cfg.exchange in ("all_to_all",
                                                     "two_phase"):
                    # SELF-SHARD rows (timers, model-NIC READY reinserts,
                    # local sends — often half the outbox) never need to
                    # move: they bypass the pack entirely (zero ICI, zero
                    # CAP consumption) and reach the merge as a second
                    # incoming block below. Only genuinely remote rows
                    # pack for the exchange: into [n_shards, CAP] for
                    # the all_to_all, or through the two-phase route,
                    # whose received block still holds relayed
                    # forwards — their skeys fall outside this shard's
                    # host boundaries, and _host_windows never takes
                    # them. My own range: straight per-host windows.
                    srt = _sorted_keys(ukey)
                    state, inc2, arr2 = _host_windows(state, ukey, rows,
                                                      my_shard, srt)
                    if cfg.exchange == "all_to_all":
                        state, rows, ukey = _pack_remote(
                            state, *srt, rows, my_shard, ship_keys=True)
                    else:
                        state, ukey, rows = _pack_two_phase(
                            state, *srt, rows, my_shard)
                elif n_shards > 1:
                    # all_gather fallback: replicate every shard's rows
                    # and keys (debug / hub-heavy)
                    with jax.named_scope("engine.exchange"):
                        rows = {f: lax.all_gather(rows[f], AXIS)
                                .reshape(-1) for f in XF}
                        ukey = lax.all_gather(ukey, AXIS).reshape(-1)

                # my hosts' arrival rows -> [H_loc, IN] windows
                state, inc, arr = _host_windows(state, ukey, rows,
                                                my_shard)
                # occupancy: the self-shard bypass and the post-exchange
                # arrivals are windowed to IN separately, so the
                # capacity-relevant mark is the per-block max, not the sum
                state["occ_in"] = jnp.maximum(state["occ_in"],
                                              jnp.maximum(arr, arr2))
                # real rows windowed (the rest of phases * H * IN per
                # block is filler)
                state["occ_arrivals"] = state["occ_arrivals"] + (
                    jnp.minimum(arr, IN).sum()
                    + jnp.minimum(arr2, IN).sum()).astype(jnp.int64)

                with jax.named_scope("engine.merge"):
                    # merge: one lexicographic row sort of [live heap | inc
                    # (| self-shard inc)] by (time, src<<32|seq); the
                    # payload columns ride the sort (merge_payload
                    # "sort") or follow a column iota via
                    # take_along_axis ("gather")
                    blocks = [inc] if inc2 is None else [inc, inc2]
                    live = jnp.arange(E)[None, :] >= state["head"][:, None]
                    mt = jnp.where(live, state["ht"], INF)
                    mk = jnp.where(live, state["hk"], IMAX)
                    WID = E + IN * len(blocks)
                    ct = jnp.concatenate([mt] + [b[0] for b in blocks], axis=1)
                    ck = jnp.concatenate([mk] + [b[1] for b in blocks], axis=1)
                    cm = jnp.concatenate([state["hm"]] + [b[2] for b in blocks],
                                         axis=1)
                    cv = jnp.concatenate([state["hv"]] + [b[3] for b in blocks],
                                         axis=1)
                    cw = jnp.concatenate([state["hw"]] + [b[4] for b in blocks],
                                         axis=1)
                    if MERGE_PAYLOAD == "sort":
                        # w is a 32-bit mask: one u32 word in the sort
                        st, sk, sm, sv, sw = lax.sort(
                            (ct, ck, cm, cv, cw.astype(jnp.uint32)),
                            dimension=1, num_keys=2)
                        state["hm"] = sm[:, :E]
                        state["hv"] = sv[:, :E]
                        state["hw"] = sw[:, :E].astype(jnp.int64)
                    else:
                        ci = jnp.broadcast_to(
                            jnp.arange(WID, dtype=jnp.int32)[None, :],
                            (H_loc, WID))
                        st, sk, si = lax.sort((ct, ck, ci), dimension=1,
                                              num_keys=2)
                        sie = si[:, :E]
                        state["hm"] = jnp.take_along_axis(cm, sie, axis=1)
                        state["hv"] = jnp.take_along_axis(cv, sie, axis=1)
                        state["hw"] = jnp.take_along_axis(cw, sie, axis=1)
                    state["overflow"] = state["overflow"] + \
                        (st[:, E:] < INF).sum(-1).astype(jnp.int32)
                    state["ht"] = st[:, :E]
                    state["hk"] = sk[:, :E]
                    state["head"] = jnp.zeros_like(state["head"])
                    # occupancy: live heap rows after the merge — the rows
                    # event_capacity must hold
                    state["occ_heap"] = jnp.maximum(
                        state["occ_heap"],
                        (state["ht"] < INF).sum(-1).astype(jnp.int32))
                    return state

        # ---------------- round-end invariant audit --------------------
        # The health word: four cheap reduction-only checks folded
        # into each host's `aud` bitmask at the end of every round.
        # Reductions + one scalar all_gather only — no sorts, no
        # gathers — so an audited run costs a fraction of one flush.
        def _axis_sum64(x):
            return lax.all_gather(
                jnp.reshape(x.astype(jnp.int64), (1,)), AXIS).sum()

        def _audit_round(state):
            head, ht, hk = state["head"], state["ht"], state["hk"]
            # heap rows must be (t, key)-lexicographically sorted
            # (INF-padded tails sort last by construction) and the
            # head cursor in [0, E]
            ok_heap = ((ht[:, :-1] < ht[:, 1:]) |
                       ((ht[:, :-1] == ht[:, 1:]) &
                        (hk[:, :-1] <= hk[:, 1:]))).all(-1)
            ok_heap = ok_heap & (head >= 0) & (head <= E)
            neg = jnp.zeros(ht.shape[0], bool)
            for key in ("n_exec", "n_sent", "n_drop", "n_deliv",
                        "event_seq", "packet_seq", "app_seq"):
                neg = neg | (state[key] < 0)
            # event-row conservation: rows produced (boot/stop seed +
            # every exchanged outbox row) == rows popped + rows live
            # in heaps + rows loudly counted lost. The balance is
            # global (a packet leaves one shard and lands on
            # another), so the per-shard differences sum over the
            # mesh — a collective, uniform across shards exactly like
            # the round predicates around it.
            live = ((jnp.arange(E)[None, :] >= head[:, None]) &
                    (ht < INF)).sum()
            diff = state["aud_tx"].sum() - (
                state["n_exec"].astype(jnp.int64).sum()
                + live.astype(jnp.int64)
                + state["overflow"].astype(jnp.int64).sum()
                + state["x_overflow"].astype(jnp.int64).sum())
            conserved = _axis_sum64(diff) == 0
            aud = state["aud"]
            aud = aud | jnp.where(ok_heap, jnp.int32(0),
                                  jnp.int32(AUD_HEAP))
            aud = aud | jnp.where(neg, jnp.int32(AUD_COUNTER),
                                  jnp.int32(0))
            aud = aud | jnp.where(conserved, jnp.int32(0),
                                  jnp.int32(AUD_CONSERVE))
            state["aud"] = aud
            return state

        # ---------------- one round (window) ---------------------------
        # A window may take several phases: each phase pops up to B
        # events per host (or until every host is drained below
        # win_end / stalled on an in-window insert), then flushes. The
        # window advances only when no host has events under the
        # barrier; the predicate is a collective, so all shards agree.
        def _pop(state, ob, win_end, gid, topo, wrld):
            """One phase's pop loop: iterate _step until no host has a
            runnable event before win_end or the outbox is full (B
            iterations). Returns (state, ob, [1] iterations run)."""
            with jax.named_scope("engine.pop"):
                if ob is None:
                    ob = {"t": jnp.full((H_loc, OB), INF, jnp.int64)}
                    for f in ("k", "m", "s", "v"):
                        ob[f] = jnp.zeros((H_loc, OB), jnp.int64)
                dirty = jnp.zeros((H_loc,), bool)

                def cond(c):
                    state_, _, blk, dirty_ = c
                    nt = _take_head(state_["ht"], state_["head"], INF)
                    return ((nt < win_end) & ~dirty_).any() & \
                        (blk < B)

                state, ob, blk, _ = lax.while_loop(
                    cond,
                    lambda c: _step(c, win_end, gid, topo,
                                    wrld),
                    (state, ob, jnp.int32(0), dirty))
                blk = jnp.reshape(blk, (1,))
                state["occ_trips"] = jnp.maximum(state["occ_trips"], blk)
                state["occ_iters"] = state["occ_iters"] + blk
                return state, ob, blk

        def _round(state, win_end, gid, my_shard, topo, wrld):
            def _phase(state):
                state2, ob, _ = _pop(state, None, win_end, gid,
                                     topo, wrld)
                # skip the whole exchange when nothing was sent and no
                # slots were consumed (idle windows). The predicate is
                # COLLECTIVE: the flush contains all_to_all, so every
                # shard must take the same branch
                any_work = (ob["t"] < INF).any() | \
                    (state2["head"] > 0).any()
                go = _axis_min(jnp.where(any_work, jnp.int64(0),
                                         jnp.int64(1))) == 0
                return lax.cond(
                    go,
                    lambda s: _exchange(s, ob, gid, my_shard,
                                        topo, wrld,
                                        win_end),
                    lambda s: s,
                    state2)

            def more(state):
                return _axis_min(
                    jnp.where((state["ht"][:, 0] < win_end).any(),
                              jnp.int64(0), jnp.int64(1))) == 0

            if MERGE_GLOBAL:
                # the first phase peeled off the loop: a second copy of
                # the pop loop and the flush in the program. Nothing
                # shows the peel helps; it stays only until the unpeeled
                # form is measured in tgen and PHOLD (ROADMAP speed
                # item 11), then one round shape serves both merges
                state = _phase(state)
                go = more(state)
            else:
                # the first phase is the loop's first iteration, so the
                # program holds one copy of the phase: the code of each
                # sort in it counts against device memory (a second copy
                # of the window flush's filler sort cost tor_56000 32 MB)
                go = jnp.bool_(True)
            state, _ = lax.while_loop(
                lambda c: c[1],
                lambda c: (lambda s: (s, more(s)))(_phase(c[0])),
                (state, go))
            if AUDIT:
                with jax.named_scope("engine.audit"):
                    state = _audit_round(state)
            return state

        # ---------------- full run ------------------------------------
        # cross-shard min via all_gather: some TPU AOT toolchains lower
        # only Sum all-reduces, so pmin is expressed as gather+min
        # (identical result; the gathered vector is tiny: one scalar
        # per device)
        def _axis_min(x):
            return lax.all_gather(jnp.reshape(x, (1,)), AXIS).min()

        def _run_shard(state, host_vertex, wrld, stop, final_stop):
            # `stop` is where THIS invocation pauses (a traced scalar,
            # so one compiled program serves every slice length);
            # `final_stop` is the simulation end that window boundaries
            # clamp to — pausing at heartbeat boundaries therefore
            # yields the EXACT window sequence of an unsegmented run
            my_shard = lax.axis_index(AXIS)
            gid = (my_shard * H_loc + hidx).astype(jnp.int32)
            topo = _topo(host_vertex, my_shard, wrld)

            def next_time(state):
                # rows are sorted and slots < head are INF-free only
                # after a flush; take the per-host head element
                return _axis_min(
                    _take_head(state["ht"], state["head"], INF).min())

            def cond(c):
                state, nxt, rounds = c
                return (nxt < stop) & (rounds < cfg.max_rounds)

            def body(c):
                state, nxt, rounds = c
                win_end = jnp.minimum(nxt + LOOKAHEAD, final_stop)
                state = _round(state, win_end, gid, my_shard, topo,
                               wrld)
                return state, next_time(state), rounds + 1

            state, _, rounds = lax.while_loop(
                cond, body, (state, next_time(state), jnp.int64(0)))
            return state, rounds

        # one window as a standalone jitted step (also used by
        # __graft_entry__; works on any mesh size including 1)
        def _one_round(state, win_end, host_vertex, wrld):
            my_shard = lax.axis_index(AXIS)
            gid = (my_shard * H_loc + hidx).astype(jnp.int32)
            state = _round(state, win_end, gid, my_shard,
                           _topo(host_vertex, my_shard, wrld), wrld)
            nxt = _axis_min(
                _take_head(state["ht"], state["head"], INF).min())
            return state, nxt

        # ---------------- phase-split programs ------------------------
        # one phase's pop loop and flush as separate jits, for
        # micro-benchmarks of a stage in isolation (scripts/tpu_micro.py).
        # They carry the fused round's stage scopes and are traced
        # lazily (first call), so the normal path pays nothing.
        def _pop_shard(state, ob, host_vertex, wrld, win_end):
            my_shard = lax.axis_index(AXIS)
            gid = (my_shard * H_loc + hidx).astype(jnp.int32)
            return _pop(state, ob, win_end, gid,
                        _topo(host_vertex, my_shard, wrld), wrld)

        def _flush_shard(state, ob, host_vertex, wrld, win_end):
            my_shard = lax.axis_index(AXIS)
            gid = (my_shard * H_loc + hidx).astype(jnp.int32)
            return _exchange(state, ob, gid, my_shard,
                             _topo(host_vertex, my_shard, wrld), wrld,
                             win_end)

        spec_keys = ("ht", "hk", "hm", "hv", "hw", "head",
                     "event_seq", "packet_seq", "app_seq", "app",
                     "n_exec", "n_sent", "n_drop", "n_deliv",
                     "overflow", "x_overflow", "chk",
                     "occ_heap", "occ_ob", "occ_in", "occ_x",
                     "occ_trips", "occ_phases", "occ_iters",
                     "occ_burst") + \
            (() if MERGE_GLOBAL else ("occ_arrivals",)) + \
            (AUD_KEYS if AUDIT else ()) + \
            (NIC_KEYS if MB else ()) + \
            (("path_cnt",) if CP else ())
        specs = {k: self._shard_spec for k in spec_keys}
        ob_specs = {f: self._shard_spec for f in XF}
        repl = self._repl_spec
        wspec = (repl,) * 5          # (lat, rel, k1, k2, epoch_times)
        self._run = jax.jit(shard_map(
            _run_shard, mesh=self.mesh,
            in_specs=(specs, repl, wspec, repl, repl),
            out_specs=(specs, repl),
            check_vma=False,
        ))
        self._round_step = jax.jit(shard_map(
            _one_round, mesh=self.mesh,
            in_specs=(specs, repl, repl, wspec),
            out_specs=(specs, repl),
            check_vma=False,
        ))
        self._pop_phase = jax.jit(shard_map(
            _pop_shard, mesh=self.mesh,
            in_specs=(specs, ob_specs, repl, wspec, repl),
            out_specs=(specs, ob_specs, self._shard_spec),
            check_vma=False,
        ))
        self._flush_phase = jax.jit(shard_map(
            _flush_shard, mesh=self.mesh,
            in_specs=(specs, ob_specs, repl, wspec, repl),
            out_specs=specs,
            check_vma=False,
        ))
        self._ob_shape_global = (H_pad, OB)

        # ---------------- ensemble program -----------------------------
        # The R-replica campaign: the SAME per-shard round program,
        # vmapped over a leading replica axis of (state, world) INSIDE
        # the host shard_map — the replica axis composes outside the
        # mesh axis, so multichip exchange is untouched and each
        # replica's trace is the standalone program's, value for value
        # (vmap batches while_loops by freezing finished replicas'
        # carries with selects — it never re-executes their updates).
        # Only array VALUES vary per replica (seed keys, topology
        # tables, epoch times); every shape is shared.
        if self.ensemble is not None:
            # NB: `P` (the PartitionSpec alias) is shadowed by the
            # burst width in this scope — use the unaliased name
            ens_spec = PartitionSpec(None, *self._shard_spec)
            especs = {k: ens_spec for k in spec_keys}

            def _run_ens_shard(states, host_vertex, wrlds, stop,
                               final_stop):
                return jax.vmap(
                    lambda st, w: _run_shard(st, host_vertex, w,
                                             stop, final_stop),
                    in_axes=(0, 0))(states, wrlds)

            self._run_ens = jax.jit(shard_map(
                _run_ens_shard, mesh=self.mesh,
                in_specs=(especs, repl, wspec, repl, repl),
                out_specs=(especs, repl),
                check_vma=False,
            ))
            self._ens_spec = ens_spec

        def _probe(state):
            head = state["head"]
            nt = jnp.take_along_axis(
                state["ht"], jnp.minimum(head, E - 1)[:, None],
                axis=1)[:, 0]
            nt = jnp.where(head < E, nt, INF)
            return nt.min(), head.sum()

        self._probe = jax.jit(_probe)

    # ------------------------------------------------------------------
    def _aot(self, name: str, jit_fn, args):
        """Resolve program `name` through the AOT compile cache on
        first use (cached executable, or AOT-compile + store on a
        miss) and return the callable to dispatch — the original
        lazy jit when no cache is attached or the cache layer
        declined. One bookkeeping site for every cached program."""
        if self.aot_cache is not None and name not in self._aot_exec:
            self._aot_exec[name] = self.aot_cache.ensure(
                self, name, jit_fn, args)
        return self._aot_exec.get(name, jit_fn)

    def program_text(self, name: str) -> Optional[str]:
        """Optimized HLO text of program `name` ("run", "run_ens",
        "pop", "flush") as dispatched through the AOT cache; each op's
        metadata names its round stage (the `engine.*` scopes). None
        when the program has no compiled executable here: no cache is
        attached, it has not been dispatched yet, or the cache fell
        back to the lazy jit."""
        as_text = getattr(self._aot_exec.get(name), "as_text", None)
        return as_text() if as_text is not None else None

    def world(self):
        """The traced world tuple (lat, rel, seed k1, seed k2,
        epoch_times) for the engine's own base world, replicated over
        the mesh — everything a run may vary without changing shapes
        (the ensemble program stacks R of these). Cached: the arrays
        are fixed at construction, and run() calls it per
        segment — re-uploading the tables each dispatch would be pure
        waste."""
        if getattr(self, "_world_dev", None) is None:
            repl = NamedSharding(self.mesh, self._repl_spec)
            k1, k2 = self.seed_pair

            def put(a):
                return jax.device_put(jnp.asarray(a), repl)

            self._world_dev = (
                jax.tree_util.tree_map(put, self.latency),
                jax.tree_util.tree_map(put, self.reliability),
                put(k1), put(k2), put(self.epoch_times))
        return self._world_dev

    # ------------------------------------------------------------------
    # static-analysis surface (shadow_tpu/analyze, scripts/analyze.py)
    # ------------------------------------------------------------------
    # The jaxpr audit needs to TRACE every dispatchable program
    # without touching a device: these methods export the lowerable-
    # program registry (name -> (jit fn, abstract args)) plus the
    # collective registry (which cross-shard collectives this build is
    # ALLOWED to contain, with the capacities their buffers are pinned
    # to). determinism_gate --analyze-consistency cross-checks the
    # registry against effective{} at runtime so the static allowlist
    # cannot drift from the real program.
    def state_structs(self) -> dict:
        """jax.ShapeDtypeStruct pytree mirroring init_state's output —
        the abstract argument surface for .trace()/.lower() with zero
        device work (the analyzer must perturb nothing)."""
        import numpy as _np

        H, E = self.H_pad, self.config.event_capacity
        S = self.n_shards

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype)

        out = {k: sds((H, E), _np.int64)
               for k in ("ht", "hk", "hm", "hv", "hw")}
        for k in ("head", "event_seq", "packet_seq", "app_seq",
                  "n_exec", "n_sent", "n_drop", "n_deliv",
                  "overflow", "x_overflow",
                  "occ_heap", "occ_ob", "occ_in"):
            out[k] = sds((H,), _np.int32)
        out["chk"] = sds((H,), _np.int64)
        out["app"] = sds((H, int(self.app.n_state_words)), _np.int32)
        out["occ_x"] = sds((S, S), _np.int32)
        out["occ_trips"] = sds((S,), _np.int32)
        out["occ_phases"] = sds((S,), _np.int32)
        out["occ_iters"] = sds((S,), _np.int32)
        out["occ_burst"] = sds((S,), _np.int32)
        if not self.program_facts["merge_global"]:
            out["occ_arrivals"] = sds((S,), _np.int64)
        if self.config.audit:
            out["aud"] = sds((H,), _np.int32)
            out["aud_t"] = sds((H,), _np.int64)
            out["aud_tx"] = sds((H,), _np.int64)
        if self.config.count_paths:
            out["path_cnt"] = sds((S, self.n_vertices ** 2),
                                  _np.int64)
        if self.config.model_bandwidth:
            for k in NIC_KEYS:
                out[k] = sds((H,), _np.int64)
        return out

    def world_structs(self, ensemble: bool = False) -> tuple:
        """Abstract twin of world() / ensemble_worlds_device()."""
        import numpy as _np

        def sds(p):
            p = _np.asarray(p)
            return jax.ShapeDtypeStruct(p.shape, p.dtype)

        if ensemble:
            ens = self.ensemble
            if isinstance(ens.latency, tuple):
                # hierarchical leaves arrive final-dtyped from
                # build_worlds (i32 int leaves / f32 reliability)
                lat = jax.tree_util.tree_map(sds, ens.latency)
                rel = jax.tree_util.tree_map(sds, ens.reliability)
            else:
                lat = sds(_np.asarray(ens.latency, _np.int32))
                rel = sds(_np.asarray(ens.reliability, _np.float32))
            parts = (lat, rel,
                     sds(_np.asarray(ens.seed_k1, _np.uint32)),
                     sds(_np.asarray(ens.seed_k2, _np.uint32)),
                     sds(_np.asarray(ens.epoch_times, _np.int64)))
            return parts
        k1, k2 = self.seed_pair
        return (jax.tree_util.tree_map(sds, self.latency),
                jax.tree_util.tree_map(sds, self.reliability),
                sds(k1), sds(k2), sds(self.epoch_times))

    def lowerable_programs(self) -> dict:
        """name -> (jit fn, abstract args) for every program the
        engine dispatches — the same names the AOT cache keys on
        ("run", "run_ens", "pop", "flush"), so the audit surface and
        the cached surface cannot drift apart."""
        import numpy as _np

        s = self.state_structs()
        hv = jax.ShapeDtypeStruct((self.H_pad,), _np.int32)
        t = jax.ShapeDtypeStruct((), _np.int64)
        ob = {f: jax.ShapeDtypeStruct(self._ob_shape_global,
                                      _np.int64)
              for f in ("t", "k", "m", "s", "v")}
        w = self.world_structs()
        progs = {
            "run": (self._run, (s, hv, w, t, t)),
            "pop": (self._pop_phase, (s, ob, hv, w, t)),
            "flush": (self._flush_phase, (s, ob, hv, w, t)),
        }
        if self.ensemble is not None:
            R = int(self.ensemble.R)
            es = {k: jax.ShapeDtypeStruct((R,) + v.shape, v.dtype)
                  for k, v in s.items()}
            progs["run_ens"] = (
                self._run_ens,
                (es, hv, self.world_structs(ensemble=True), t, t))
        return progs

    def collective_registry(self) -> dict:
        """The cross-shard collectives this build is allowed to lower
        to: primitive name -> {"axis", "caps"} where caps pins the
        trailing buffer dimension of the capacity-carrying movers
        (None = shape not capacity-pinned: scalar reductions and
        whole-outbox replication). Derived from the SAME resolved
        config effective{} reports, so the runtime cross-check
        (determinism_gate --analyze-consistency) is exact."""
        eff = self.effective
        reg = {
            # axis_index / scalar all_gather reductions (_axis_min,
            # the audit's _axis_sum64) exist on every mesh size
            "axis_index": {"axis": AXIS, "caps": None},
            "all_gather": {"axis": AXIS, "caps": None},
        }
        if self.n_shards > 1:
            if eff["exchange"] == "all_to_all":
                reg["all_to_all"] = {"axis": AXIS,
                                     "caps": (int(eff["CAP"]),)}
            elif eff["exchange"] == "two_phase":
                reg["ppermute"] = {"axis": AXIS,
                                   "caps": (int(eff["CAP"]),
                                            int(eff["CAP2"]))}
                # phase-2 loss attribution psum: the histogram is
                # [H_pad]; the loss predicate is a scalar
                reg["psum"] = {"axis": AXIS,
                               "caps": (1, int(self.H_pad))}
            # exchange == all_gather reuses the all_gather entry
        return reg

    def audit_consts(self) -> dict:
        """The closure constants the jaxpr audit ACCEPTS in this
        engine's traced programs, by value. Every entry must carry a
        `# shadowlint: const-ok(reason)` comment at its capture site
        in this file (the audit cross-checks), and its bytes must be
        covered by the AOT cache key — via the code digest for
        module-level tables, via bw_digest for the bandwidth
        vectors. Anything else non-scalar captured by a trace is a
        leaked world value (stale-cache + broken-ensemble hazard)."""
        import numpy as _np

        from shadow_tpu.host.model_nic import LAW

        out = {"model_nic.LAW": _np.asarray(LAW)}
        if self.config.model_bandwidth:
            out["bw_up"] = _np.asarray(self.bw_up)
            out["bw_down"] = _np.asarray(self.bw_down)
        # per-host parameter arrays the app bakes into its traced
        # handle() (tgen client count/pause/retry vectors, tor relay
        # tables): capacity.app_fingerprint hashes EXACTLY the
        # ndarray attributes of the app into the cache key's
        # workload_fp, so using the same selection rule here makes
        # the allowance fingerprint-covered by construction (a test
        # pins that each array flips the fingerprint).
        for k, v in sorted(vars(self.app).items()):
            if isinstance(v, _np.ndarray):
                out[f"app:{k}"] = v
        return out

    def host_vertex_device(self):
        """The host->vertex table on device, replicated over the
        mesh — cached like world(): run()/run_ensemble() dispatch
        once per pipeline segment, and re-uploading the table on
        every issue would tax each enqueue with a device_put for
        nothing. The table is fixed at construction."""
        if getattr(self, "_hv_dev", None) is None:
            repl = NamedSharding(self.mesh, self._repl_spec)
            self._hv_dev = jax.device_put(
                jnp.asarray(self.host_vertex), repl)
        return self._hv_dev

    def live_bytes(self) -> int:
        """Measured live device bytes across this engine's mesh,
        attributed per buffer by its sharding (a buffer spanning k
        devices contributes nbytes/k per device; the return is the
        MAX per-device total — what admission compares to a
        per-device budget). Uses jax.live_arrays(), which works on
        every backend including cpu — the estimator honesty tests
        run on the forced-multi-device cpu mesh."""
        mesh_ids = {d.id for d in self.mesh.devices.flat}
        per_dev: dict = {}
        for arr in jax.live_arrays():
            try:
                devs = [d for d in arr.sharding.device_set
                        if d.id in mesh_ids]
                if not devs:
                    continue
                share = arr.nbytes // max(1, len(arr.sharding
                                                 .device_set))
            except Exception:       # deleted/donated buffers race
                continue
            for d in devs:
                per_dev[d.id] = per_dev.get(d.id, 0) + share
        return max(per_dev.values(), default=0)

    def device_memory_stats(self):
        """(bytes_in_use, bytes_limit) from the backend's allocator
        when it exposes them (TPU/GPU memory_stats), else None — the
        heartbeat lines print `n/a` then."""
        try:
            dev = list(self.mesh.devices.flat)[0]
            ms = dev.memory_stats()
            if not ms:
                return None
            in_use = int(ms.get("bytes_in_use", 0) or 0)
            limit = int(ms.get("bytes_limit", 0) or 0)
            if in_use <= 0 and limit <= 0:
                return None
            return in_use, limit
        except Exception:
            return None

    def run(self, state: dict, stop: Optional[int] = None,
            final_stop: Optional[int] = None):
        """Run to `stop` (default config.stop_time); returns
        (final_state, rounds) on device. Both stops are runtime
        scalars — every slice length reuses one compiled program.
        `final_stop` (default = stop) is the window-clamping horizon:
        pass the simulation end when pausing at intermediate
        boundaries (heartbeats) so the window sequence — and thus the
        trace — is identical to an unsegmented run.

        This call never synchronizes: it enqueues the compiled
        program and returns asynchronous device arrays, so the
        segment pipeline (supervise.advance) can keep several
        segments in flight while the host drains earlier ones."""
        hv = self.host_vertex_device()
        stop_v = jnp.int64(self.config.stop_time if stop is None
                           else stop)
        final_v = stop_v if final_stop is None else jnp.int64(final_stop)
        # warm start via the AOT cache: stops are runtime scalars, so
        # the one executable serves every slice
        args = (state, hv, self.world(), stop_v, final_v)
        return self._aot("run", self._run, args)(*args)

    # ------------------------------------------------------------------
    # ensemble campaign (shadow_tpu/ensemble/): R replicas in one
    # compiled program
    # ------------------------------------------------------------------
    def init_ensemble_state(self, starts: list[tuple]) -> dict:
        """[R, ...]-stacked initial state: every replica starts from
        the identical boot/stop schedule (vary axes change values —
        seeds, tables — never the start layout), so the stack is one
        on-device broadcast of the standalone initial state."""
        if self.ensemble is None:
            raise ValueError("engine was built without ensemble "
                             "worlds")
        base = self.init_state(starts)
        if getattr(self, "_ens_broadcaster", None) is None:
            # one jitted whole-dict broadcast, cached on the engine:
            # a fresh jit per leaf per call would retrace every leaf
            # on every init (warm-up, re-plan retries, resume
            # templates all re-init)
            R = int(self.ensemble.R)
            ens_shard = NamedSharding(self.mesh, self._ens_spec)
            self._ens_broadcaster = jax.jit(
                lambda tree: {
                    k: jnp.broadcast_to(v[None], (R,) + v.shape)
                    for k, v in tree.items()},
                out_shardings=ens_shard)
        return self._ens_broadcaster(base)

    def ensemble_worlds_device(self):
        """The stacked per-replica world tuple, replicated over the
        mesh (the replica axis is vmapped, not sharded). Cached like
        world(): run_ensemble is called once per heartbeat/dispatch
        segment, and the stacked tables never change after build."""
        if getattr(self, "_ens_world_dev", None) is None:
            ens = self.ensemble
            repl = NamedSharding(self.mesh, self._repl_spec)

            def put(a):
                return jax.device_put(jnp.asarray(a), repl)

            if isinstance(ens.latency, tuple):
                # hierarchical leaves are final-dtyped by build_worlds
                lat = jax.tree_util.tree_map(put, ens.latency)
                rel = jax.tree_util.tree_map(put, ens.reliability)
            else:
                lat = put(np.asarray(ens.latency, dtype=np.int32))
                rel = put(np.asarray(ens.reliability,
                                     dtype=np.float32))
            self._ens_world_dev = (
                lat, rel,
                put(np.asarray(ens.seed_k1, dtype=np.uint32)),
                put(np.asarray(ens.seed_k2, dtype=np.uint32)),
                put(np.asarray(ens.epoch_times, dtype=np.int64)),
            )
        return self._ens_world_dev

    def run_ensemble(self, states: dict, stop: Optional[int] = None,
                     final_stop: Optional[int] = None):
        """Advance all R replicas to `stop` in one dispatch of the
        vmapped program; returns ([R, ...] states, [R] rounds).
        Window clamping stays on `final_stop` exactly as in `run`, so
        segmented campaigns (heartbeats, dispatch_segment) replay the
        unsegmented window sequence per replica. Like `run`, this is
        a pure asynchronous enqueue — campaigns pipeline too."""
        hv = self.host_vertex_device()
        stop_v = jnp.int64(self.config.stop_time if stop is None
                           else stop)
        final_v = stop_v if final_stop is None else jnp.int64(final_stop)
        args = (states, hv, self.ensemble_worlds_device(), stop_v,
                final_v)
        return self._aot("run_ens", self._run_ens, args)(*args)
