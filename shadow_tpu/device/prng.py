"""Device-side threefry counter RNG (batched jnp form).

Identical algorithm to utils/nprng.py (which is itself bit-identical to
jax.random's threefry path) — implemented directly on uint32 arrays so
the engine can draw batches of decisions keyed by (purpose, host, seq)
without jax.random key-array plumbing inside shard_map'd code.
tests/test_device_engine.py asserts bit-identity with the numpy form.
"""

from __future__ import annotations

from shadow_tpu._jax import jax, jnp

_ROT_A = (13, 15, 26, 6)
_ROT_B = (17, 29, 16, 24)
_PARITY = 0x1BD11BDA


def _rotl(x, r):
    return (x << r) | (x >> (32 - r))


def threefry2x32(k1, k2, x0, x1):
    k1 = k1.astype(jnp.uint32)
    k2 = k2.astype(jnp.uint32)
    x0 = x0.astype(jnp.uint32)
    x1 = x1.astype(jnp.uint32)
    ks2 = k1 ^ k2 ^ jnp.uint32(_PARITY)
    ks = (k1, k2, ks2)
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for block in range(5):
        rots = _ROT_A if block % 2 == 0 else _ROT_B
        for r in rots:
            x0 = x0 + x1
            x1 = _rotl(x1, r) ^ x0
        x0 = x0 + ks[(block + 1) % 3]
        x1 = x1 + ks[(block + 2) % 3] + jnp.uint32(block + 1)
    return x0, x1


def seed_key(seed: int):
    """Python-int seed -> (k1, k2) scalar uint32 pair (host-side)."""
    seed = int(seed) & 0xFFFF_FFFF_FFFF_FFFF
    return (jnp.uint32(seed >> 32), jnp.uint32(seed & 0xFFFF_FFFF))


def fold_in(key, data):
    """data: any int array; broadcasts with key parts."""
    k1, k2 = key
    data = data.astype(jnp.uint32)
    zero = jnp.zeros_like(data)
    return threefry2x32(jnp.broadcast_to(k1, data.shape),
                        jnp.broadcast_to(k2, data.shape), zero, data)


def random_bits32(key):
    k1, k2 = key
    zero = jnp.zeros_like(k1)
    b1, b2 = threefry2x32(k1, k2, zero, zero)
    return b1 ^ b2


def uniform01(key):
    bits = random_bits32(key)
    float_bits = (bits >> jnp.uint32(9)) | jnp.uint32(0x3F800000)
    return jax.lax.bitcast_convert_type(float_bits, jnp.float32) \
        - jnp.float32(1.0)


def purpose_id_key(seed_pair, purpose, ids):
    """The first two chain_key folds — (purpose, id) — computed at the
    ids' own (small) shape. Combine with fold_seq for the final
    per-seq fold: fold_seq(purpose_id_key(s, p, ids), seqs) is
    bit-identical to chain_key(s, p, ids, seqs) but lets the caller
    amortize the id folds when seqs is a much larger broadcast (the
    optimization_barriers below otherwise force ALL three folds to
    materialize at the broadcast shape)."""
    ids = jnp.asarray(ids).astype(jnp.uint32)
    zero = jnp.zeros_like(ids)
    k1 = jnp.broadcast_to(seed_pair[0], ids.shape)
    k2 = jnp.broadcast_to(seed_pair[1], ids.shape)
    k = threefry2x32(k1, k2, zero,
                     jnp.full(ids.shape, purpose, jnp.uint32))
    k = jax.lax.optimization_barrier(k)
    k = threefry2x32(k[0], k[1], zero, ids)
    return jax.lax.optimization_barrier(k)


def fold_seq(key, seqs):
    """The last chain_key fold: fold_in(key, seqs) broadcast over
    seqs. See purpose_id_key."""
    seqs = jnp.asarray(seqs).astype(jnp.uint32)
    shape = jnp.broadcast_shapes(key[0].shape, seqs.shape)
    seqs = jnp.broadcast_to(seqs, shape)
    zero = jnp.zeros(shape, jnp.uint32)
    return threefry2x32(jnp.broadcast_to(key[0], shape),
                        jnp.broadcast_to(key[1], shape), zero, seqs)


def chain_key(seed_pair, purpose, ids, seqs):
    """fold(fold(fold(seed, purpose), id), seq) — vectorized over
    ids/seqs arrays (matches utils.rng.packet_key / nprng.packet_uniform:
    each fold_in(k, d) is threefry(k, (0, uint32(d))).

    The optimization_barriers between folds are value-identity: three
    chained threefrys (~150 add/xor/rotate ops) send XLA's algebraic
    simplifier into a canonicalization loop ("stuck in a circular
    simplification loop", 50-run bailout on every compile); breaking
    the expression at the fold boundaries stops the churn. Two-deep
    chains don't trigger it, so one barrier pair suffices."""
    ids = jnp.asarray(ids).astype(jnp.uint32)
    seqs = jnp.asarray(seqs).astype(jnp.uint32)
    shape = jnp.broadcast_shapes(ids.shape, seqs.shape)
    return fold_seq(
        purpose_id_key(seed_pair, purpose,
                       jnp.broadcast_to(ids, shape)),
        jnp.broadcast_to(seqs, shape))
