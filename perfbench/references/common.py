"""What every plain reference shares: units, the GML graph and its path
tables, the threefry counter RNG, the per-host trace checksum, and the
hosts of a config in id order.

Written from the semantics the simulator documents (Shadow's event
order and network model), not from its code: nothing here imports
`shadow_tpu`, and nothing the program computed (tables, seeds, ids) is
read back. Inputs are the config dict as run and the seed.
"""

from __future__ import annotations

import re

import numpy as np

NS_PER = {"ns": 1, "us": 10**3, "ms": 10**6, "s": 10**9, "sec": 10**9,
          "min": 60 * 10**9}
SIZE_PER = {"B": 1, "KiB": 2**10, "MiB": 2**20, "GiB": 2**30,
            "KB": 10**3, "MB": 10**6, "GB": 10**9}
_NUM = re.compile(r"^\s*([0-9]+(?:\.[0-9]+)?)\s*([A-Za-z]*)\s*$")

# Shadow's MTU less the IP and TCP headers (definitions.h)
MSS = 1500 - 20 - 20

# event kinds, as the trace checksum folds them
KIND_BOOT, KIND_TIMER, KIND_PACKET = 0, 1, 2

# counter-RNG purposes
PURPOSE_DROP, PURPOSE_APP = 1, 3

MASK63 = (1 << 63) - 1
CHK_MUL, CHK_SRC, CHK_KIND, CHK_SEQ = 1000003, 2654435761, 1315423911, \
    2246822519

# the per-host result columns every reference returns
COLUMNS = ("n_exec", "n_sent", "n_drop", "n_deliv", "chk")


def time_ns(value) -> int:
    """'500ms', '5 ms', '2s' or a bare number of seconds."""
    if isinstance(value, (int, float)):
        return int(round(value * 10**9))
    num, unit = _NUM.match(value).groups()
    return int(round(float(num) * (NS_PER[unit] if unit else 10**9)))


def size_bytes(value) -> int:
    if isinstance(value, (int, float)):
        return int(value)
    num, unit = _NUM.match(value).groups()
    return int(round(float(num) * (SIZE_PER[unit] if unit else 1)))


def parse_args(text: str) -> dict:
    return dict(kv.split("=", 1) for kv in str(text or "").split())


# ---------------------------------------------------------------- graph
def parse_gml(text: str):
    """(directed, node ids in order, [(src id, dst id, latency ns,
    loss)]) from an inline GML graph."""
    toks = re.findall(r'"[^"]*"|\[|\]|[^\s\[\]]+', text)

    def block(i):
        out, key = {}, None
        lists = {}
        while toks[i] != "]":
            if key is None:
                key = toks[i]
                i += 1
                continue
            if toks[i] == "[":
                val, i = block(i + 1)
                lists.setdefault(key, []).append(val)
            else:
                out[key] = toks[i].strip('"')
                i += 1
            key = None
        out.update(lists)
        return out, i + 1

    assert toks[0] == "graph" and toks[1] == "["
    g, _ = block(2)
    nodes = [int(n["id"]) for n in g.get("node", [])]
    edges = [(int(e["source"]), int(e["target"]), time_ns(e["latency"]),
              float(e["packet_loss"])) for e in g.get("edge", [])]
    return g.get("directed", "0") == "1", nodes, edges


def path_tables(text: str):
    """(vertex index by GML id, latency [V,V] int64 ns, reliability
    [V,V] float32): latency-shortest paths with reliability multiplied
    along them; a vertex's own path is its self-loop, else its cheapest
    edge out and back; zero latencies clamp to 1 ms."""
    directed, nodes, edges = parse_gml(text)
    index = {gid: k for k, gid in enumerate(nodes)}
    V = len(nodes)
    dlat = np.zeros((V, V), np.int64)
    drel = np.zeros((V, V), np.float32)
    for s, d, lat, loss in edges:
        pairs = [(index[s], index[d])]
        if not directed:
            pairs.append((index[d], index[s]))
        for a, b in pairs:
            if dlat[a, b] == 0 or lat < dlat[a, b]:
                dlat[a, b], drel[a, b] = lat, np.float32(1.0 - loss)
    lat = np.where(dlat > 0, dlat.astype(np.float64), np.inf)
    rel = np.where(dlat > 0, drel.astype(np.float64), 0.0)
    np.fill_diagonal(lat, 0.0)
    np.fill_diagonal(rel, 1.0)
    for k in range(V):                       # Floyd-Warshall
        via = lat[:, k, None] + lat[None, k, :]
        better = via < lat
        lat = np.where(better, via, lat)
        rel = np.where(better, rel[:, k, None] * rel[None, k, :], rel)
    if np.isinf(lat).any():
        raise ValueError("graph is not connected")
    lat = np.rint(lat).astype(np.int64)
    for v in range(V):
        options = []
        if dlat[v, v] > 0:
            options.append((int(dlat[v, v]), float(drel[v, v])))
        options += [(int(2 * dlat[v, u]), float(drel[v, u]) ** 2)
                    for u in range(V) if u != v and dlat[v, u] > 0]
        lat[v, v], rel[v, v] = min(options) if options else (0, 1.0)
    zero = lat <= 0
    rel = np.where(zero, 1.0, rel)
    lat = np.where(zero, 10**6, lat)
    return index, lat, rel.astype(np.float32)


# ---------------------------------------------------------------- hosts
def hosts(cfg: dict):
    """Hosts in id order: (name, group, vertex GML id, path, args,
    start ns), with each group's member ids."""
    out, groups = [], {}
    for gname, g in cfg["hosts"].items():
        q = int(g.get("quantity", 1))
        proc = g["processes"][0]
        groups[gname] = range(len(out), len(out) + q)
        for i in range(q):
            out.append((gname if q == 1 else f"{gname}{i}", gname,
                        int(g.get("network_node_id", 0)), proc["path"],
                        parse_args(proc.get("args", "")),
                        time_ns(proc.get("start_time", 0))))
    return out, groups


def resolve(name: str, asker: int, by_name: dict, groups: dict) -> int:
    """A host name, or a group that fans its askers out by id."""
    if name in by_name:
        return by_name[name]
    members = groups[name]
    return members[asker % len(members)]


# ------------------------------------------------------------------ rng
_ROT = ((13, 15, 26, 6), (17, 29, 16, 24))


def threefry(k1, k2, x0, x1):
    """Threefry-2x32, 20 rounds, on uint32 arrays."""
    with np.errstate(over="ignore"):
        k1 = np.asarray(k1, np.uint32)
        k2 = np.asarray(k2, np.uint32)
        ks = (k1, k2, k1 ^ k2 ^ np.uint32(0x1BD11BDA))
        x0 = np.asarray(x0, np.uint32) + ks[0]
        x1 = np.asarray(x1, np.uint32) + ks[1]
        for block in range(5):
            for r in _ROT[block % 2]:
                x0 = x0 + x1
                x1 = ((x1 << np.uint32(r)) | (x1 >> np.uint32(32 - r))) \
                    ^ x0
            x0 = x0 + ks[(block + 1) % 3]
            x1 = x1 + ks[(block + 2) % 3] + np.uint32(block + 1)
        return x0, x1


def seed_key(seed: int):
    seed = int(seed) & 0xFFFF_FFFF_FFFF_FFFF
    return np.uint32(seed >> 32), np.uint32(seed & 0xFFFF_FFFF)


def fold_in(key, data):
    data = np.asarray(data).astype(np.uint32)
    return threefry(key[0], key[1], np.zeros_like(data), data)


def bits32(key):
    zero = np.zeros_like(np.asarray(key[0], np.uint32))
    b1, b2 = threefry(key[0], key[1], zero, zero)
    return b1 ^ b2


def uniform01(key):
    """A float32 in [0, 1) from the top 23 bits."""
    f = (bits32(key) >> np.uint32(9)) | np.uint32(0x3F800000)
    return f.view(np.float32) - np.float32(1.0)


# ------------------------------------------------------------- checksum
def chk_mix(chk, time, src, kind, seq):
    """Fold one executed event into a host's 63-bit trace checksum;
    works on Python ints and on uint64 arrays alike."""
    mix = (time ^ (src * CHK_SRC) ^ (kind * CHK_KIND)
           ^ (seq * CHK_SEQ)) & MASK63
    return (chk * CHK_MUL + mix) & MASK63
