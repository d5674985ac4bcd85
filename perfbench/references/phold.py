"""Plain reference for PHOLD: every host, one conservative window at a
time, in numpy.

PHOLD (Fujimoto 1990; Shadow's src/test/phold): every host boots with
`msgload` messages to peers drawn at random, and every message it
receives makes it send one more. A peer is drawn from the host's own
counter stream (purpose APP, host, draw number); a packet's loss roll
from (purpose DROP, host, packet number).

Events at one host run in the order (time, source, sequence). No
message arrives sooner than the shortest path latency L after it was
sent, so all events in [T, T + L), with T the earliest pending time,
can run before anything they cause: each window runs as one batch,
and within it each host's events run in that order.
"""

from __future__ import annotations

import numpy as np

from perfbench.references.common import (
    KIND_BOOT,
    KIND_PACKET,
    PURPOSE_APP,
    PURPOSE_DROP,
    bits32,
    chk_mix,
    fold_in,
    hosts,
    path_tables,
    seed_key,
    time_ns,
    uniform01,
)


def run(cfg: dict, t_end: int, pick: int = 0, control: bool = False):
    """Every host's results after every event before `t_end`: (host
    ids, {column: array}). `control` breaks the order guarantee:
    events at one instant run in reverse (source, sequence) order.
    `pick` is unused: PHOLD couples every host, so all are compared."""
    seed = int(cfg["general"]["seed"])
    boot_end = time_ns(cfg["general"].get("bootstrap_end_time", 0))
    index, lat, rel = path_tables(cfg["network"]["graph"]["inline"])
    hs, _ = hosts(cfg)
    H = len(hs)
    args = hs[0][4]
    msgload = int(args.get("msgload", 1))
    selfloop = int(args.get("selfloop", 0))
    if any(h[3] != "model:phold" or h[4] != args for h in hs):
        raise ValueError("phold reference: every host runs phold with "
                         "the same args")
    vert = np.array([index[h[2]] for h in hs], np.int64)
    L = int(lat.min())
    hid = np.arange(H, dtype=np.int64)
    root = seed_key(seed)
    app_key = fold_in(fold_in(root, np.uint32(PURPOSE_APP)), hid)
    drop_key = fold_in(fold_in(root, np.uint32(PURPOSE_DROP)), hid)
    lossy = bool((rel < 1.0).any())

    ev_seq = np.ones(H, np.int64)        # each boot event took seq 0
    pkt_seq = np.zeros(H, np.int64)
    app_seq = np.zeros(H, np.int64)
    cols = {c: np.zeros(H, np.int64)
            for c in ("n_exec", "n_sent", "n_drop", "n_deliv")}
    chk = np.zeros(H, np.uint64)
    # pending events: time, destination, source, sequence, kind
    ev = [np.array([h[5] for h in hs], np.int64), hid.copy(), hid.copy(),
          np.zeros(H, np.int64), np.full(H, KIND_BOOT, np.int64)]

    while ev[0].size:
        t0 = int(ev[0].min())
        if t0 >= t_end:
            break
        w = min(t0 + L, t_end)
        now = ev[0] < w
        t, dst, src, seq, kind = (a[now] for a in ev)
        ev = [a[~now] for a in ev]
        order = (np.lexsort((-seq, -src, t, dst)) if control
                 else np.lexsort((seq, src, t, dst)))
        t, dst, src, seq, kind = (a[order] for a in (t, dst, src, seq,
                                                     kind))
        n = t.size
        first = np.ones(n, bool)
        first[1:] = dst[1:] != dst[:-1]
        starts = np.flatnonzero(first)
        rank = np.arange(n) - np.repeat(starts, np.diff(np.append(starts,
                                                                  n)))
        # fold each host's events into its checksum in order
        for r in range(int(rank.max()) + 1):
            m = rank == r
            d = dst[m]
            chk[d] = chk_mix(chk[d], t[m].astype(np.uint64),
                             src[m].astype(np.uint64),
                             kind[m].astype(np.uint64),
                             seq[m].astype(np.uint64))
        np.add.at(cols["n_exec"], dst, 1)
        np.add.at(cols["n_deliv"], dst, (kind == KIND_PACKET))
        # each event sends msgload (boot) or one (packet) message; the
        # k-th send of a host in this window takes its k-th counters
        n_out = np.where(kind == KIND_BOOT, msgload, 1)
        excl = np.cumsum(n_out) - n_out
        off = excl - excl[starts][np.cumsum(first) - 1]
        s_host = np.repeat(dst, n_out)
        s_time = np.repeat(t, n_out)
        j = np.arange(n_out.sum()) - np.repeat(np.cumsum(n_out) - n_out,
                                               n_out)
        s_off = np.repeat(off, n_out) + j
        a_seq = app_seq[s_host] + s_off
        p_seq = pkt_seq[s_host] + s_off
        e_seq = ev_seq[s_host] + s_off
        per_host = np.bincount(s_host, minlength=H)
        app_seq += per_host
        pkt_seq += per_host
        ev_seq += per_host
        bits = bits32(fold_in((app_key[0][s_host], app_key[1][s_host]),
                              a_seq)).astype(np.int64)
        if selfloop or H == 1:
            peer = bits % H
        else:
            peer = (s_host + 1 + bits % (H - 1)) % H
        sv, dv = vert[s_host], vert[peer]
        cols["n_sent"] += per_host
        keep = np.ones(peer.size, bool)
        if lossy:
            r = rel[sv, dv]
            roll = (r < 1.0) & (s_time >= boot_end)
            u = uniform01(fold_in((drop_key[0][s_host],
                                   drop_key[1][s_host]), p_seq))
            keep = ~roll | (u < r)
            np.add.at(cols["n_drop"], s_host[~keep], 1)
        ev = [np.concatenate([a, b[keep]]) for a, b in zip(
            ev, (s_time + lat[sv, dv], peer, s_host, e_seq,
                 np.full(peer.size, KIND_PACKET, np.int64)))]
    cols["chk"] = chk
    return hid, cols
