"""The engine's chip programs compile for a described TPU v5e.

Nothing runs: the TPU compiler, installed here, compiles for chips that
are described and not attached (the on-chip-measurement guide, section
2). Building the mesh from the described devices makes the engine
resolve its TPU defaults (judge_hoist, merge_global, merge_payload,
pop_onehot, table_onehot), the branches the CPU tests never take by
default. Two compiles, about a minute each here: ``run`` on one chip
and on a 2x2 mesh (a third, of ``round_step``, would add a minute and
cover nothing ``run`` does not contain).

Config: examples/tgen_1000.yaml with every host group cut tenfold to
100 hosts: the 10,000-host deployment's graph (6 cities, loss on every
edge), capacities and per-server fan-in. Compile time here grows with
the host count (the flush's flat sorts are H x 84 wide): tgen_10000's
``run`` takes about 290 s and tgen_1000's about 225 s, too long for a
tier-1 test file.
"""

import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "examples", "tgen_1000.yaml")
# tgen_1000's groups cut tenfold: 3 servers + 97 clients
TENTH = [f"hosts.server_{c}.quantity=1" for c in ("nyc", "lon", "sin")] \
    + [f"hosts.client_{c}.quantity=16"
       for c in ("nyc", "lon", "fra", "sfo", "sin")] \
    + ["hosts.client_syd.quantity=17"]
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    # libtpu would otherwise log under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    # a compile for a described chip is written to the cache but can
    # never be read back without one
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _engine(topo, n):
    from jax.sharding import Mesh

    from shadow_tpu.config import load_config
    from shadow_tpu.core.controller import build
    from shadow_tpu.device.engine import AXIS
    from shadow_tpu.device.runner import DeviceRunner

    cfg = load_config(CONFIG, TENTH)
    cfg.experimental.compile_cache = "off"
    mesh = Mesh(np.array(topo.devices[:n]), (AXIS,))
    engine = DeviceRunner(build(cfg), mesh=mesh).engine
    assert engine.config.n_hosts == 100
    assert engine.mesh.devices.flat[0].platform == "tpu"
    facts = engine.program_facts
    assert facts["judge_hoist"] and facts["merge_global"] \
        and facts["pop_onehot"] and facts["table_onehot"], facts
    assert facts["vertex_runs"] > 1, facts
    # the window merge (pinned where the global sort is too long to
    # compile) carries its payload through the row sort on the TPU,
    # and lays out its arrival windows with one filler sort
    assert facts["merge_payload"] == "sort", facts
    return engine


def test_cpu_defaults_keep_the_gathers():
    """The same deployment on the CPU mesh resolves every strategy to
    its CPU side, the window merge's take_along_axis included."""
    from shadow_tpu.config import load_config
    from shadow_tpu.core.controller import build
    from shadow_tpu.device.runner import DeviceRunner

    cfg = load_config(CONFIG, TENTH)
    cfg.experimental.compile_cache = "off"
    engine = DeviceRunner(build(cfg)).engine
    assert engine.mesh.devices.flat[0].platform == "cpu"
    facts = engine.program_facts
    assert facts["merge_payload"] == "gather", facts
    assert not (facts["judge_hoist"] or facts["merge_global"]
                or facts["pop_onehot"] or facts["table_onehot"]), facts


def test_window_merge_carries_its_payload_on_v5e(topo,
                                                 no_persistent_cache):
    """The window merge's TPU side, which the deployment above does
    not take (it resolves to the global merge): a small PHOLD engine
    with merge_global False compiles for one described chip, and its
    filler-sorted arrival windows and row sort carrying the payload
    leave fewer gathers in the program than seg_take's window gathers
    and the take_along_axis recovery pinned on the same chip."""
    from jax.sharding import Mesh

    from shadow_tpu.device.apps import PholdDevice
    from shadow_tpu.device.engine import AXIS, DeviceEngine, EngineConfig

    H = 64
    lat = np.full((2, 2), 1_000_000, np.int64)
    rel = np.full((2, 2), 0.99, np.float32)
    gathers = {}
    for payload in (None, "gather"):
        engine = DeviceEngine(
            EngineConfig(n_hosts=H, lookahead=1_000_000,
                         stop_time=10_000_000, event_capacity=16,
                         outbox_capacity=8, merge_global=False,
                         merge_payload=payload),
            PholdDevice(n_hosts_total=H, msgload=2),
            np.arange(H, dtype=np.int32) % 2, lat, rel,
            mesh=Mesh(np.array(topo.devices[:1]), (AXIS,)))
        facts = engine.program_facts
        assert facts["merge_global"] is False, facts
        assert facts["merge_payload"] == (payload or "sort"), facts
        fn, args = engine.lowerable_programs()["run"]
        compiled = fn.lower(*args).compile()
        _fits(compiled)
        gathers[facts["merge_payload"]] = \
            compiled.as_text().count(" gather(")
    assert gathers["sort"] < gathers["gather"], gathers


def _fits(compiled):
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes + ma.generated_code_size_in_bytes
            - ma.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, ma
    return used


@pytest.mark.parametrize("n_chips", [1, 4])
def test_run_compiles_for_v5e(topo, no_persistent_cache, n_chips):
    engine = _engine(topo, n_chips)
    fn, args = engine.lowerable_programs()["run"]
    compiled = fn.lower(*args).compile()
    _fits(compiled)
    text = compiled.as_text()
    if n_chips > 1:
        # the cross-shard exchange (exchange: all_to_all by default)
        assert "all-to-all" in text
    else:
        assert "all-to-all" not in text

