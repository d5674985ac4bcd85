"""Persistent AOT compile cache (device/aotcache.py).

The subsystem's contract, pinned:
* key sensitivity — every program-shaping input (workload, capacity
  knobs, exchange variant, fault epochs, audit flag, engine code)
  flips the cache key, so a stale entry can never load for the wrong
  trace;
* a cache-hit run is bit-identical to the fresh-compile run that
  wrote the entry;
* a corrupted/truncated entry degrades to a loud recompile (and the
  bad entry is atomically overwritten), never to a wrong trace or a
  crash;
* the cache is bounded: LRU eviction under a size cap;
* two processes racing onto one entry both land complete files
  (atomic tmp+rename — the loser's replace just lands second);
* an entry loads onto exactly the devices it was compiled for;
* one placement rule: both caches under $JAX_COMPILATION_CACHE_DIR
  when it is set, else under the checkout's .cache/jax.
"""

import os
import pickle
import subprocess
import sys
import time

import numpy as np
import pytest

from shadow_tpu.config import load_config_str
from shadow_tpu.core.controller import Controller
from shadow_tpu.device import aotcache
from shadow_tpu.device.apps import PholdDevice
from shadow_tpu.device.engine import DeviceEngine, EngineConfig

YAML = """
general:
  stop_time: 600ms
  seed: 11
network:
  graph:
    type: 1_gbit_switch
experimental:
  scheduler_policy: tpu
  event_capacity: 48
  compile_cache: {cache}
{extra}
hosts:
  left:
    quantity: 3
    processes:
    - {{path: model:phold, args: msgload=2, start_time: 10ms}}
  right:
    quantity: 3
    processes:
    - {{path: model:phold, args: msgload=2, start_time: 10ms}}
"""


def _run(cache_dir, extra=""):
    c = Controller(load_config_str(
        YAML.format(cache=cache_dir, extra=extra)))
    stats = c.run()
    return stats, c


def _sig(stats, c):
    return (stats.events_executed, stats.packets_sent,
            stats.packets_dropped, stats.packets_delivered,
            [(h.name, h.trace_checksum) for h in c.sim.hosts])


def _entries(cache_dir):
    return sorted(p for p in os.listdir(cache_dir)
                  if p.endswith(aotcache.ENTRY_SUFFIX))


# ---------------------------------------------------------------------------
# key sensitivity: flip each fingerprint component -> different key
# ---------------------------------------------------------------------------

def _engine(app=None, lat_epochs=1, bw_up=None, **cfg_kw):
    """A tiny engine (construction traces nothing, so this is cheap):
    4 hosts on a 1-vertex graph, optionally with a stacked fault
    epoch table."""
    if app is None:
        app = PholdDevice(n_hosts_total=4, msgload=2, size=100,
                          selfloop=False)
    if lat_epochs == 1:
        lat = np.full((1, 1), 10**6, dtype=np.int64)
        rel = np.ones((1, 1), dtype=np.float32)
        times = None
    else:
        lat = np.full((lat_epochs, 1, 1), 10**6, dtype=np.int64)
        rel = np.ones((lat_epochs, 1, 1), dtype=np.float32)
        times = np.arange(lat_epochs, dtype=np.int64) * 10**8
    return DeviceEngine(
        EngineConfig(n_hosts=4, **cfg_kw), app,
        host_vertex=np.zeros(4, dtype=np.int32),
        latency_ns=lat, reliability=rel, epoch_times=times,
        bw_up_bits=bw_up)


def test_program_key_flips_on_every_fingerprint_component(monkeypatch):
    base = aotcache.program_key(_engine(), "run")
    # deterministic: the identical engine reproduces the key
    assert aotcache.program_key(_engine(), "run") == base
    # a different program name is a different key
    assert aotcache.program_key(_engine(), "pop") != base

    variants = {
        # workload fingerprint (app scalars)
        "workload": _engine(app=PholdDevice(
            n_hosts_total=4, msgload=3, size=100, selfloop=False)),
        # capacity knobs (each of the six feeds program_facts; one
        # representative per overflow family)
        "event_capacity": _engine(event_capacity=128),
        "outbox_capacity": _engine(outbox_capacity=64),
        "exchange_in_capacity": _engine(exchange_in_capacity=7),
        "outbox_compact": _engine(outbox_compact=9),
        # exchange variant
        "exchange": _engine(exchange="all_gather"),
        # fault epoch count
        "fault_epochs": _engine(lat_epochs=2),
        # audit flag
        "audit": _engine(audit=True),
        # trace-shaping schedule constants
        "lookahead": _engine(lookahead=123456),
        # the fluid NIC bakes the bandwidth vectors into the trace —
        # under model_bandwidth they must key the entry
        "model_bandwidth": _engine(model_bandwidth=True),
        "bandwidths": _engine(model_bandwidth=True,
                              bw_up=np.full(4, 5 * 10**6,
                                            dtype=np.int64)),
    }
    keys = {name: aotcache.program_key(e, "run")
            for name, e in variants.items()}
    for name, key in keys.items():
        assert key != base, f"{name} did not change the program key"
    assert len(set(keys.values())) == len(keys), \
        "two distinct variants collided on one key"

    # engine-code digest: a code change invalidates every entry
    monkeypatch.setattr(aotcache, "code_digest", lambda: "deadbeef")
    assert aotcache.program_key(_engine(), "run") != base

    # backend identity (versions + platform + device ids) is in the
    # signature, so a jax upgrade or a different mesh can never
    # resurrect a stale executable
    sig = aotcache.program_signature(_engine(), "run")
    for field in ("jax", "jaxlib", "platform", "device_ids"):
        assert field in sig["backend"]


# ---------------------------------------------------------------------------
# hit bit-identity + corrupted-entry fallback (one compile, reused)
# ---------------------------------------------------------------------------

def test_hit_bitmatch_and_corrupt_entry_recompiles(tmp_path):
    cache_dir = str(tmp_path / "aot")

    # cold run: miss, compile, store
    s1, c1 = _run(cache_dir)
    assert s1.ok
    ref = _sig(s1, c1)
    rep1 = s1.compile_cache
    assert rep1["misses"] == 1 and rep1["hits"] == 0
    assert rep1["events"][0]["program"] == "run"
    assert rep1["events"][0]["stored"] is True
    assert rep1["compile_s"] > 0
    entries = _entries(cache_dir)
    assert len(entries) == 1

    # warm run: hit, no compile, bit-identical
    s2, c2 = _run(cache_dir)
    assert s2.ok
    assert _sig(s2, c2) == ref
    rep2 = s2.compile_cache
    assert rep2["hits"] == 1 and rep2["misses"] == 0
    assert rep2["compile_s"] == 0
    assert rep2["load_s"] > 0

    # corrupted entry: truncate it mid-payload — the run must warn,
    # recompile, overwrite, and stay bit-identical (degradation is
    # to a fresh compile, never a wrong trace)
    path = os.path.join(cache_dir, entries[0])
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size // 3)
    s3, c3 = _run(cache_dir)
    assert s3.ok
    assert _sig(s3, c3) == ref
    rep3 = s3.compile_cache
    assert rep3["hits"] == 0 and rep3["misses"] == 1
    # the overwrite healed the entry: a fourth run hits again
    assert os.path.getsize(path) > size // 3
    s4, c4 = _run(cache_dir)
    assert s4.compile_cache["hits"] == 1
    assert _sig(s4, c4) == ref

    # garbage that unpickles but is not an entry is equally survivable
    with open(path, "wb") as f:
        pickle.dump({"format": 999, "key": "wrong"}, f)
    s5, c5 = _run(cache_dir)
    assert s5.ok and _sig(s5, c5) == ref
    assert s5.compile_cache["hits"] == 0


def test_cache_off_runs_plain(tmp_path):
    s, c = _run("off")
    assert s.ok
    assert s.compile_cache is None


# ---------------------------------------------------------------------------
# LRU eviction under a size cap
# ---------------------------------------------------------------------------

def test_lru_eviction_under_tiny_cap(tmp_path):
    from shadow_tpu._jax import jax, jnp

    cache_dir = str(tmp_path / "lru")
    # compile three trivial distinct programs
    compiled = []
    for k in range(3):
        f = jax.jit(lambda x, k=k: x * (k + 2))
        compiled.append(f.lower(jnp.ones((4,))).compile())
    probe = aotcache.AotCache(cache_dir)
    assert probe.store("key0", compiled[0], {})
    entry_size = os.path.getsize(probe.entry_path("key0"))

    # cap admits two entries; storing a third evicts the LRU one
    cache = aotcache.AotCache(cache_dir,
                              cap_bytes=int(entry_size * 2.5))
    now = time.time()
    os.utime(cache.entry_path("key0"), (now - 300, now - 300))
    assert cache.store("key1", compiled[1], {})
    os.utime(cache.entry_path("key1"), (now - 200, now - 200))
    assert cache.store("key2", compiled[2], {})
    names = _entries(cache_dir)
    assert "key0" + aotcache.ENTRY_SUFFIX not in names, \
        "LRU entry survived past the cap"
    assert "key2" + aotcache.ENTRY_SUFFIX in names
    # a load TOUCHES the entry, protecting it from the next eviction
    assert cache.load("key1") is not None
    assert os.path.getmtime(cache.entry_path("key1")) >= now - 5


# ---------------------------------------------------------------------------
# concurrent writers: two processes racing on one entry
# ---------------------------------------------------------------------------

CHILD = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp
from shadow_tpu.device import aotcache
f = jax.jit(lambda x: x * 3 + 1)
compiled = f.lower(jnp.ones((8,))).compile()
cache = aotcache.AotCache({cache_dir!r})
ok = cache.store("shared_key", compiled, {{"writer": {tag}}})
print("stored", ok)
"""


def test_concurrent_writers_never_leave_a_torn_entry(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cache_dir = str(tmp_path / "race")
    procs = [
        subprocess.Popen(
            [sys.executable, "-c",
             CHILD.format(repo=repo, cache_dir=cache_dir, tag=i)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        for i in range(2)
    ]
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, err
        assert "stored True" in out
    # whoever's os.replace landed second won; the file is COMPLETE
    # either way (pid-tagged tmp files cannot interleave)
    names = _entries(cache_dir)
    assert names == ["shared_key" + aotcache.ENTRY_SUFFIX]
    cache = aotcache.AotCache(cache_dir)
    loaded = cache.load("shared_key")
    assert loaded is not None
    import jax.numpy as jnp
    assert np.array_equal(np.asarray(loaded(jnp.ones((8,)))),
                          np.full(8, 4.0))
    with open(cache.entry_path("shared_key"), "rb") as f:
        entry = pickle.load(f)
    assert entry["meta"]["writer"] in (0, 1)
    # no tmp debris from either writer
    assert not [n for n in os.listdir(cache_dir)
                if n.endswith(".tmp")]


# ---------------------------------------------------------------------------
# placement: one rule for the XLA cache and the AOT executables
# ---------------------------------------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PLACEMENT_CHILD = r"""
import sys
sys.path.insert(0, {repo!r})
from shadow_tpu._jax import jax
from shadow_tpu.device import aotcache
print(jax.config.jax_compilation_cache_dir)
print(aotcache.default_dir())
"""


@pytest.mark.parametrize("env_dir", [True, False])
def test_cache_placement_rule(tmp_path, env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla")
        root = str(tmp_path / "xla")
    else:
        root = os.path.join(REPO, ".cache", "jax")
    p = subprocess.run(
        [sys.executable, "-c", PLACEMENT_CHILD.format(repo=REPO)],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=120)
    assert p.returncode == 0, p.stderr
    xla_dir, aot_dir = p.stdout.split()
    assert xla_dir == root
    assert aot_dir == os.path.join(root, "aot")
    assert "~" not in xla_dir + aot_dir


def test_entry_loads_onto_its_compiled_devices(tmp_path):
    """A 1-device executable stored and loaded in an 8-device process
    runs on its one device (deserialize's default would spread it
    over every device and then reject 1-shard arguments)."""
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[3]
    x = jax.device_put(jnp.arange(8.0), dev)
    compiled = jax.jit(lambda v: v * 2).lower(x).compile()
    cache = aotcache.AotCache(str(tmp_path / "one"))
    assert cache.store("k", compiled, {})
    loaded = cache.load("k")
    assert loaded is not None
    out = loaded(x)
    assert np.array_equal(np.asarray(out), np.arange(8.0) * 2)
    assert out.devices() == {dev}


# ---------------------------------------------------------------------------
# schema validation
# ---------------------------------------------------------------------------

def test_schema_rejects_typod_compile_cache():
    bad = YAML.format(cache="atuo", extra="")
    with pytest.raises(ValueError, match="compile_cache"):
        load_config_str(bad)
    with pytest.raises(ValueError, match="compile_cache_cap_mb"):
        load_config_str(YAML.format(
            cache="auto", extra="  compile_cache_cap_mb: 0"))
    # keywords and path-looking values parse
    for ok in ("auto", "off", "./cache", "/tmp/x", "~/aot",
               "rel/dir"):
        cfg = load_config_str(YAML.format(cache=ok, extra=""))
        assert cfg.experimental.compile_cache == ok
