"""Batched device network judgment for hybrid execution.

Hybrid mode keeps host emulation (syscall interposition, TCP/UDP
stacks, NIC token buckets) on the CPU and lifts the inter-host network
model — the hot path of the reference's worker_sendPacket
(src/main/core/worker.c:520-579: reliability lookup -> drop roll ->
latency lookup) — onto the device as one batched call per scheduling
round. The CPU drains egress packet metadata (now, src, dst, pkt_seq)
into arrays, the device gathers latency/reliability from the topology
matrices and rolls counter-RNG drops for the whole batch at once, and
the verdicts come back as (delivered, deliver_time) for the CPU to
schedule delivery events.

Determinism: the drop roll is the identical threefry chain used by the
CPU NetworkModel (utils/nprng.py) and the full device engine
(device/engine.py), keyed by stable (src_host, pkt_seq) — so a hybrid
run's event trace is bit-identical to a pure-CPU run of the same
config.

Batches are padded to power-of-two buckets so XLA compiles a handful of
shapes, not one per round.
"""

from __future__ import annotations

import numpy as np

from shadow_tpu._jax import jax, jnp
from shadow_tpu.device import prng
from shadow_tpu.device.netsem import packet_drop_mask
from shadow_tpu.topology import hierarchy

_MIN_BUCKET = 256


def _bucket(n: int) -> int:
    b = _MIN_BUCKET
    while b < n:
        b <<= 1
    return b


class DeviceJudge:
    """Holds the topology matrices on device and a jitted batch-judge."""

    def __init__(self, topology, host_vertex: np.ndarray, seed: int,
                 bootstrap_end: int = 0, min_batch: int = 192,
                 fault_table=None):
        if topology.hier is not None:
            if hierarchy.max_composed_latency(topology.hier.lat_parts()) \
                    > np.iinfo(np.int64).max // 2:
                raise ValueError("latency overflow")
        elif (topology.latency_ns > np.iinfo(np.int64).max // 2).any():
            raise ValueError("latency overflow")
        # fault epochs ride as stacked [T,V,V] matrices + the [T]
        # epoch start times; the fault-free case keeps the plain
        # [V,V] matrices and the original program — identical XLA to
        # before the fault layer. Under the hierarchical
        # representation the matrices are replaced by the factored
        # leaf tuples ([T,C,C] + [T,V] vectors when epoch-stacked)
        # and the gather goes through hierarchy.gather_parts.
        lat, rel, ep_times = hierarchy.world_tables(topology,
                                                    fault_table)
        hier = isinstance(lat, tuple)
        if ep_times is None:
            ep_times = np.zeros(1, dtype=np.int64)
        n_epochs = len(ep_times)
        ep_times_t = jnp.asarray(ep_times)
        self._hv = jnp.asarray(host_vertex.astype(np.int32))
        if hier:
            self._lat = tuple(jnp.asarray(p) for p in lat)
            self._rel = tuple(jnp.asarray(p) for p in rel)
        else:
            self._lat = jnp.asarray(lat)
            self._rel = jnp.asarray(rel)
        self._seed_pair = prng.seed_key(seed)
        boot_end = np.int64(bootstrap_end)
        seed_pair = self._seed_pair

        def _judge(now, src, dst, pseq, hv, lat, rel):
            sv = hv[src]
            dv = hv[dst]
            if n_epochs == 1:
                if hier:
                    latv = hierarchy.gather_parts(lat, sv, dv)
                    relv = hierarchy.gather_parts(rel, sv, dv)
                else:
                    latv, relv = lat[sv, dv], rel[sv, dv]
            else:
                # active epoch at SEND time: count of epoch starts <=
                # now, minus one — the vectorized twin of the CPU
                # model's binary search (faults.FaultTable.epoch_of)
                ep = (now[:, None] >= ep_times_t[None, :]) \
                    .sum(-1).astype(jnp.int32) - 1
                if hier:
                    latv = hierarchy.gather_parts(lat, sv, dv, e=ep)
                    relv = hierarchy.gather_parts(rel, sv, dv, e=ep)
                else:
                    latv, relv = lat[ep, sv, dv], rel[ep, sv, dv]
            dropped = packet_drop_mask(seed_pair, boot_end, now, src,
                                       pseq, relv)
            return ~dropped, now + latv

        self._judge = jax.jit(_judge)
        # adaptive crossover: rounds smaller than this are judged on
        # the CPU (the cost of a device dispatch on the chip is not
        # measured; a CPU judgment costs ~10 us/pkt). The manager
        # consults this.
        self.min_batch = min_batch
        # rounds-trip counters for observability (perf-timer analogue)
        self.batches = 0
        self.packets = 0
        self.cpu_batches = 0        # adaptive small-round fallbacks
        self.cpu_packets = 0

    def judge_batch(self, now: np.ndarray, src: np.ndarray,
                    dst: np.ndarray, pkt_seq: np.ndarray
                    ) -> tuple[np.ndarray, np.ndarray]:
        """All arrays shape [N] -> (delivered bool[N], deliver_time
        i64[N]). One device dispatch per power-of-two bucket size."""
        n = len(now)
        b = _bucket(n)
        pad = b - n

        def p(a, dtype):
            a = np.asarray(a, dtype=dtype)
            return np.pad(a, (0, pad)) if pad else a

        delivered, deliver_time = self._judge(
            jnp.asarray(p(now, np.int64)), jnp.asarray(p(src, np.int32)),
            jnp.asarray(p(dst, np.int32)),
            jnp.asarray(p(pkt_seq, np.int32)),
            self._hv, self._lat, self._rel)
        delivered = np.asarray(delivered)[:n]
        deliver_time = np.asarray(deliver_time)[:n]
        self.batches += 1
        self.packets += n
        return delivered, deliver_time
