"""Plain reference for tgen bulk downloads: one event at a time off a
heap, in Shadow's order (time, destination, source, sequence).

The semantics are Shadow's tgen client/server file transfer as this
simulator models it: a client asks its server for a chunk of at most
32 MSS-sized packets of a `size`-byte file; the server answers each
request with one train of those packets; every packet rolls its own
loss against the path's reliability; the client takes fresh packets
of the current chunk only, asks for the next chunk when the chunk is
whole, re-asks after `retry` when a chunk stalls, and pauses `pause`
between downloads, `count` times.

A client talks only to its own server, so each server with its
clients is a closed component, and a component's results do not
depend on any other. The reference simulates a sample of whole
components, drawn from the seed, and returns their hosts' results.
"""

from __future__ import annotations

import heapq

import numpy as np

from perfbench.references.common import (
    KIND_BOOT,
    KIND_PACKET,
    KIND_TIMER,
    MSS,
    PURPOSE_DROP,
    chk_mix,
    fold_in,
    hosts,
    path_tables,
    resolve,
    seed_key,
    size_bytes,
    time_ns,
    uniform01,
)

CHUNK = 32
TAG_REQ, TAG_DATA = 1, 2


class _Host:
    __slots__ = ("ev_seq", "pkt_seq", "n_exec", "n_sent", "n_drop",
                 "n_deliv", "chk", "client", "server", "size", "count",
                 "pause", "retry", "start", "got", "mask", "gen", "done",
                 "drop_key")

    def __init__(self):
        self.ev_seq = self.pkt_seq = 0
        self.n_exec = self.n_sent = self.n_drop = self.n_deliv = 0
        self.chk = 0
        self.client = False
        self.start = self.got = self.mask = self.gen = self.done = 0


def components(cfg: dict, per_group: int, pick: int):
    """Host ids of `per_group` servers of every server group, drawn
    from `pick`, and of all their clients."""
    hs, groups = hosts(cfg)
    by_name = {h[0]: i for i, h in enumerate(hs)}
    server_of = {i: resolve(h[4].get("server", "server"), i, by_name,
                            groups)
                 for i, h in enumerate(hs)
                 if h[3] == "model:tgen_client"}
    used = set(server_of.values())
    rng = np.random.default_rng(pick)
    chosen = set()
    for gname, members in groups.items():
        servers = [i for i in members if i in used]
        if servers:
            take = min(per_group, len(servers))
            chosen.update(int(i) for i in
                          rng.choice(servers, take, replace=False))
    clients = [c for c, s in server_of.items() if s in chosen]
    return sorted(chosen | set(clients)), server_of


def run(cfg: dict, t_end: int, pick: int, per_group: int = 3,
        control: bool = False):
    """Per-host results of the sampled components after every event
    before `t_end`: (host ids, {column: array}). `control` breaks the
    order guarantee: events at one instant run in reverse (source,
    sequence) order."""
    seed = int(cfg["general"]["seed"])
    boot_end = time_ns(cfg["general"].get("bootstrap_end_time", 0))
    index, lat, rel = path_tables(cfg["network"]["graph"]["inline"])
    hs, _ = hosts(cfg)
    ids, server_of = components(cfg, per_group, pick)
    vert = [index[h[2]] for h in hs]
    drop_root = fold_in(seed_key(seed), np.uint32(PURPOSE_DROP))
    st = {}
    heap = []

    def push(t, dst, src, seq, kind, data=(), npkts=1):
        key = (t, dst, -src, -seq) if control else (t, dst, src, seq)
        heapq.heappush(heap, key + (t, dst, src, seq, kind, data,
                                    npkts))

    for i in ids:
        h = st[i] = _Host()
        h.drop_key = fold_in(drop_root, np.uint32(i))
        args = hs[i][4]
        if i in server_of:
            h.client = True
            h.server = server_of[i]
            h.size = size_bytes(args.get("size", "1 MiB"))
            h.count = int(args.get("count", 1))
            h.pause = time_ns(args.get("pause", "1 s"))
            h.retry = time_ns(args.get("retry", 0))
        push(hs[i][5], i, i, h.ev_seq, KIND_BOOT)
        h.ev_seq += 1

    def send_train(now, h, src, dst, nbytes, data, count):
        """`count` packets as one delivery event; each packet rolls its
        own loss with its own packet sequence number."""
        seq0 = h.pkt_seq
        h.pkt_seq += count
        ev_seq = h.ev_seq
        h.ev_seq += 1
        r = rel[vert[src], vert[dst]]
        surv = (1 << count) - 1
        if r < 1.0 and now >= boot_end:
            u = uniform01(fold_in(h.drop_key,
                                  np.arange(seq0, seq0 + count)))
            surv = int(((u < r).astype(np.int64)
                        << np.arange(count)).sum())
        alive = bin(surv).count("1")
        h.n_sent += count
        h.n_drop += count - alive
        if alive:
            push(now + int(lat[vert[src], vert[dst]]), dst, src, ev_seq,
                 KIND_PACKET, data + (surv,), alive)

    def send(now, h, src, dst, data):
        seq = h.pkt_seq
        h.pkt_seq += 1
        ev_seq = h.ev_seq
        h.ev_seq += 1
        r = rel[vert[src], vert[dst]]
        h.n_sent += 1
        if r < 1.0 and now >= boot_end and \
                not uniform01(fold_in(h.drop_key, np.uint32(seq))) < r:
            h.n_drop += 1
            return
        push(now + int(lat[vert[src], vert[dst]]), dst, src, ev_seq,
             KIND_PACKET, data)

    def request(now, i, h):
        h.got = h.mask = 0
        h.gen += 1
        send(now, h, i, h.server, (TAG_REQ, h.start, h.size))
        if h.retry > 0:
            push(now + h.retry, i, i, h.ev_seq, KIND_TIMER, (h.gen,))
            h.ev_seq += 1

    while heap and heap[0][0] < t_end:
        now, i, src, seq, kind, data, npkts = heapq.heappop(heap)[4:]
        h = st[i]
        h.n_exec += 1
        h.chk = chk_mix(h.chk, now, src, kind, seq)
        if kind == KIND_PACKET:
            h.n_deliv += npkts
        if not h.client:
            if kind == KIND_PACKET and data[0] == TAG_REQ:
                start, total = data[1], data[2]
                npk = -(-total // MSS)
                cnt = min(CHUNK, npk - start)
                if cnt > 0:
                    last = total % MSS or MSS
                    nbytes = cnt * MSS if start + cnt < npk \
                        else (cnt - 1) * MSS + last
                    send_train(now, h, i, src, nbytes,
                               (TAG_DATA, start), cnt)
            continue
        if kind == KIND_BOOT:
            if h.count > 0:
                request(now, i, h)
        elif kind == KIND_TIMER:
            if data[0] >= 0:
                if data[0] == h.gen:       # the chunk still stalls
                    request(now, i, h)
            else:                          # the pause is over
                h.start = 0
                request(now, i, h)
        elif kind == KIND_PACKET and data[0] == TAG_DATA:
            npk = -(-h.size // MSS)
            chunk = min(CHUNK, npk - h.start)
            shift = data[1] - h.start
            surv = data[2]
            window = ((surv << shift) if shift > 0 else (surv >> -shift)) \
                & ((1 << chunk) - 1)
            fresh = window & ~h.mask
            if not fresh:
                continue
            h.mask |= fresh
            h.got += bin(fresh).count("1")
            if h.got < chunk:
                continue
            h.start += chunk
            if h.start < npk:
                request(now, i, h)
                continue
            h.done += 1
            h.start = 0
            h.gen += 1
            if h.done < h.count:
                push(now + h.pause, i, i, h.ev_seq, KIND_TIMER, (-1,))
                h.ev_seq += 1
    cols = {c: np.array([getattr(st[i], c) for i in ids],
                        np.uint64 if c == "chk" else np.int64)
            for c in ("n_exec", "n_sent", "n_drop", "n_deliv", "chk")}
    return np.array(ids, np.int64), cols
