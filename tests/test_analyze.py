"""shadowlint (shadow_tpu/analyze) — the three-pass static suite.

Each pass is exercised three ways: a seeded-defect fixture that MUST
be caught (a deliberately leaked closure const, an undigested traced
import, an unlocked shared-dict write), the real tree that MUST pass
clean, and the baseline round-trip (add -> suppress -> regress).
The digest test additionally pins the acceptance contract: deleting
ANY module from aotcache's code-digest list that the import walk
reaches fails the pass loudly.
"""

import os

import numpy as np
import pytest

from shadow_tpu._jax import jax, jnp
from shadow_tpu.analyze import findings as F
from shadow_tpu.analyze import concurrency as CC
from shadow_tpu.analyze import imports_audit as IA
from shadow_tpu.analyze import jaxpr_audit as JA


def _errors(found):
    return [f for f in found if f.severity == F.SEV_ERROR]


# ---------------------------------------------------------------------
# Pass 1 — jaxpr audit
# ---------------------------------------------------------------------
def test_leaked_closure_const_is_caught():
    # the seeded defect: a non-scalar, non-iota array captured by the
    # trace instead of arriving as an argument — the exact class
    # PR 6's bw_digest review fix patched by hand
    leak = jnp.asarray(np.array([3, 1, 4, 1, 5, 9, 2, 6], np.int64))
    fn = jax.jit(lambda x: x + leak)
    closed = fn.trace(
        jax.ShapeDtypeStruct((8,), np.int64)).jaxpr
    found = JA.audit_closed_jaxpr(closed, program="fixture:leak")
    assert [f.code for f in found] == ["SL101"]
    assert "wrld" in found[0].message
    assert "audit_consts" in found[0].hint


def test_benign_and_allowed_consts_pass():
    iota = jnp.asarray(np.arange(8, dtype=np.int64) * 3 + 1)
    fill = jnp.asarray(np.full(8, 7, np.int64))
    table = jnp.asarray(np.array([9, 1, 8, 2], np.int64))
    fn = jax.jit(lambda x: x + iota + fill + table[x % 4])
    closed = fn.trace(
        jax.ShapeDtypeStruct((8,), np.int64)).jaxpr
    found = JA.audit_closed_jaxpr(
        closed, program="fixture:allowed",
        allowed_consts={"table": np.array([9, 1, 8, 2], np.int64)})
    assert found == []


def test_const_classifier():
    assert JA.classify_const(np.int64(3)) == "scalar"
    assert JA.classify_const(np.full(5, 2.0)) == "fill"
    assert JA.classify_const(np.arange(6) * 7 - 2) == "iota"
    assert JA.classify_const(np.array([1, 2, 2, 1])) == "opaque"
    # a 2-element pair is NOT trivially 'affine' — it is data
    assert JA.classify_const(np.array([7, 12345])) == "opaque"
    # i64 values past 2^53 must not alias through float64 diffs
    big = np.array([0, 2 ** 60, 2 ** 61 + 1], np.int64)
    assert JA.classify_const(big) == "opaque"


def test_unpinned_primitive_is_caught(monkeypatch):
    monkeypatch.setattr(
        JA, "PRIMITIVE_ALLOWLIST",
        JA.PRIMITIVE_ALLOWLIST - {"sort"})
    fn = jax.jit(lambda x: jnp.sort(x))
    closed = fn.trace(
        jax.ShapeDtypeStruct((8,), np.int64)).jaxpr
    found = JA.audit_closed_jaxpr(closed, program="fixture:prim")
    assert any(f.code == "SL102" and f.obj == "sort" for f in found)


def _small_engine(**kw):
    return JA._build_engine(**kw)


def test_real_engine_programs_pass_clean():
    # the current engine must audit clean (post satellite fixes):
    # every program, consts + primitives + collectives
    import shadow_tpu.device.engine as engine_mod

    ok = JA.const_ok_targets(engine_mod.__file__)
    for label, eng in (
            ("base", _small_engine()),
            ("two_phase", _small_engine(exchange="two_phase")),
            ("mb", _small_engine(model_bandwidth=True)),
            # the window merge carrying its payload, its arrival
            # windows filler-sorted (tor_56000 on TPU)
            ("window_merge_sort", _small_engine(
                merge_global=False, merge_payload="sort")),
            # the compare-select lookups' run table is derived from
            # the traced host_vertex, never captured
            ("table_onehot", _small_engine(
                table_onehot=True,
                host_vertex=np.array([0, 1, 1, 0, 0, 0, 1, 0],
                                     np.int32)))):
        found = JA.audit_engine(eng, label, ok_targets=ok)
        assert found == [], [f.format() for f in found]


def test_collective_registry_violations_flagged():
    eng = _small_engine()
    if eng.n_shards <= 1:
        pytest.skip("needs the forced multi-device mesh")
    jit_fn, args = eng.lowerable_programs()["flush"]
    closed = jit_fn.trace(*args).jaxpr
    # wrong capacity pin: the real CAP is not 999
    bad = {"axis_index": {"axis": "hosts", "caps": None},
           "all_gather": {"axis": "hosts", "caps": None},
           "all_to_all": {"axis": "hosts", "caps": (999,)}}
    found = JA.audit_closed_jaxpr(closed, program="fixture:caps",
                                  registry=bad)
    assert any(f.code == "SL103" and "dim=" in f.obj for f in found)
    # unregistered collective primitive
    none = {"axis_index": {"axis": "hosts", "caps": None}}
    found = JA.audit_closed_jaxpr(closed, program="fixture:unreg",
                                  registry=none)
    assert any(f.code == "SL103" and f.obj == "all_to_all"
               for f in found)
    # registered mover that never lowers
    ghost = {"axis_index": {"axis": "hosts", "caps": None},
             "all_gather": {"axis": "hosts", "caps": None},
             "all_to_all": {"axis": "hosts", "caps": None},
             "ppermute": {"axis": "hosts", "caps": None},
             "__expect_mover__": "ppermute"}
    found = JA.audit_closed_jaxpr(closed, program="fixture:ghost",
                                  registry=ghost)
    assert any(f.code == "SL104" and f.obj == "ppermute"
               for f in found)


def test_collective_registry_matches_effective():
    # the static registry derives from the same resolved config as
    # effective{} — the consistency the gate pins per-config
    eng = _small_engine(exchange="two_phase")
    if eng.n_shards <= 1:
        pytest.skip("needs the forced multi-device mesh")
    reg = eng.collective_registry()
    eff = eng.effective
    assert reg["ppermute"]["caps"] == (eff["CAP"], eff["CAP2"])
    eng2 = _small_engine()
    assert eng2.collective_registry()["all_to_all"]["caps"] == \
        (eng2.effective["CAP"],)


def test_const_ok_comment_enforced():
    # every audit_consts entry with a declared capture site must have
    # its # shadowlint: const-ok(...) comment in engine.py
    import shadow_tpu.device.engine as engine_mod

    ok = JA.const_ok_targets(engine_mod.__file__)
    assert {"law_t", "bw_up_t", "bw_down_t"} <= ok
    # strip the comment coverage -> the MB engine's LAW capture must
    # trip SL105
    eng = _small_engine(model_bandwidth=True)
    jit_fn, args = eng.lowerable_programs()["run"]
    closed = jit_fn.trace(*args).jaxpr
    found = JA.audit_closed_jaxpr(
        closed, program="fixture:no-comment",
        allowed_consts=eng.audit_consts(), ok_targets=set())
    assert any(f.code == "SL105" and f.obj == "model_nic.LAW"
               for f in found)


def test_bw_and_app_arrays_are_fingerprint_covered():
    # the suppression contract behind audit_consts: every allowed
    # baked array must flip the AOT cache key when its bytes change
    from shadow_tpu.device import aotcache
    from shadow_tpu.device.capacity import app_fingerprint

    eng = _small_engine(model_bandwidth=True)
    k1 = aotcache.program_key(eng, "run")
    sig = aotcache.program_signature(eng, "run")
    assert "bw_digest" in sig
    eng.bw_up = eng.bw_up.copy()
    eng.bw_up[0] += 1
    assert aotcache.program_key(eng, "run") != k1

    # app parameter arrays are hashed by app_fingerprint — the same
    # selection rule audit_consts uses (vars(app) ndarrays), so the
    # allowance is covered by construction
    from shadow_tpu.device.apps import TgenDevice

    app = TgenDevice(roles=np.array([0, 1, 1, 1], np.int32),
                     server_gid=np.zeros(4, np.int32),
                     count=np.array([1, 2, 3, 4], np.int32))
    fp1 = app_fingerprint(app)
    for name in ("_count", "_pause", "_retry", "roles"):
        assert isinstance(vars(app)[name], np.ndarray)
    app._count = np.array([1, 2, 3, 5], np.int32)
    assert app_fingerprint(app) != fp1


def test_state_structs_match_init_state():
    # the abstract mirror must not drift from the real state (the
    # audit would otherwise trace a program variant that is never
    # dispatched): shapes AND dtypes, across the optional leaves
    for eng in (_small_engine(),
                _small_engine(model_bandwidth=True, audit=True,
                              count_paths=True)):
        real = eng.init_state(
            [(i, 0, 10_000_000)
             for i in range(eng.config.n_hosts)])
        mirror = eng.state_structs()
        assert set(real) == set(mirror)
        for k, v in real.items():
            assert (tuple(v.shape), np.dtype(v.dtype)) == \
                (tuple(mirror[k].shape), np.dtype(mirror[k].dtype)), k
        wr = eng.world()
        wm = eng.world_structs()
        for a, b in zip(wr, wm):
            assert (tuple(np.asarray(a).shape),
                    np.asarray(a).dtype) == \
                (tuple(b.shape), np.dtype(b.dtype))

    ens_eng = _small_engine(ensemble=JA._tiny_ensemble())
    real = ens_eng.init_ensemble_state(
        [(i, 0, 10_000_000) for i in range(8)])
    _, args = ens_eng.lowerable_programs()["run_ens"]
    mirror = args[0]
    assert set(real) == set(mirror)
    for k, v in real.items():
        assert (tuple(v.shape), np.dtype(v.dtype)) == \
            (tuple(mirror[k].shape), np.dtype(mirror[k].dtype)), k
    for a, b in zip(ens_eng.ensemble_worlds_device(),
                    ens_eng.world_structs(ensemble=True)):
        assert (tuple(np.asarray(a).shape), np.asarray(a).dtype) == \
            (tuple(b.shape), np.dtype(b.dtype))


# ---------------------------------------------------------------------
# Pass 2 — fingerprint completeness
# ---------------------------------------------------------------------
FIXPKG = {
    "__init__.py": "",
    "engine.py": ("import fixpkg.helper\n"
                  "from fixpkg import boundary\n"
                  "def f():\n"
                  "    from fixpkg.late import g\n"
                  "    return g\n"),
    "helper.py": "X = 1\n",
    "boundary.py": "import fixpkg.hidden\n",
    "hidden.py": "",
    "late.py": "def g():\n    return 0\n",
    "stale.py": "",
}


def _fixtree(tmp_path):
    pkg = tmp_path / "fixpkg"
    pkg.mkdir()
    for name, src in FIXPKG.items():
        (pkg / name).write_text(src)
    return {"fixpkg": str(pkg)}


def _ia_run(pkg_roots, digest, boundary=()):
    return IA.run(
        roots=("fixpkg.engine",),
        boundary={b: "fixture boundary" for b in boundary}
        if not isinstance(boundary, dict) else boundary,
        digest=digest, pkg_roots=pkg_roots, rel_prefix="fixture")


def test_undigested_traced_import_is_caught(tmp_path):
    roots = _fixtree(tmp_path)
    # helper.py and the FUNCTION-LEVEL late.py import both reach the
    # walk; leaving either out of the digest is the seeded defect
    full = ["fixpkg.engine", "fixpkg.helper", "fixpkg.late",
            "fixpkg.boundary", "fixpkg.hidden", "fixpkg"]
    found = _ia_run(roots, digest=full)
    assert found == [], [f.format() for f in found]
    for missing in ("fixpkg.helper", "fixpkg.late"):
        found = _ia_run(roots,
                        digest=[m for m in full if m != missing])
        assert [f.code for f in _errors(found)] == ["SL201"]
        assert _errors(found)[0].obj == missing


def test_boundary_prunes_and_conflicts(tmp_path):
    roots = _fixtree(tmp_path)
    # boundary.py declared a value boundary: its own import of
    # hidden.py must NOT be followed, and neither needs digesting
    digest = ["fixpkg.engine", "fixpkg.helper", "fixpkg.late",
              "fixpkg"]
    found = _ia_run(roots, digest=digest,
                    boundary=("fixpkg.boundary",))
    assert found == [], [f.format() for f in found]
    # declaring AND digesting the same module is a contradiction
    found = _ia_run(roots, digest=digest + ["fixpkg.boundary"],
                    boundary=("fixpkg.boundary",))
    assert any(f.code == "SL203" for f in found)
    # a digested module the walk never reaches is stale (warning)
    found = _ia_run(roots, digest=digest + ["fixpkg.stale"],
                    boundary=("fixpkg.boundary",))
    stale = [f for f in found if f.code == "SL202"]
    assert len(stale) == 1 and stale[0].severity == F.SEV_WARNING
    assert not _errors(found)


def test_real_digest_walk_clean():
    assert IA.run() == []


def test_deleting_any_digested_module_fails():
    # the acceptance pin: every module in the shipped digest list is
    # load-bearing — deleting it makes the analyze rung fail
    from shadow_tpu.device import aotcache

    for mod in aotcache.CODE_DIGEST_MODULES:
        digest = [m for m in aotcache.CODE_DIGEST_MODULES
                  if m != mod]
        found = IA.run(digest=digest)
        assert any(f.code == "SL201" and f.obj == mod
                   for f in _errors(found)), mod


# ---------------------------------------------------------------------
# Pass 3 — concurrency lint
# ---------------------------------------------------------------------
FIX_SRC = '''
import threading

SHARED = {}
ANNOTATED: dict = {}

class M:
    def __init__(self):
        self._streams = {}
        self._streams_lock = threading.Lock()
        def late(k, v):
            self._streams[k] = v
        self.late = late
        self.later = lambda k: self._streams.pop(k)

    def locked_write(self, k, v):
        with self._streams_lock:
            self._streams[k] = v

    def unlocked_write(self, k, v):
        self._streams[k] = v

    def unlocked_mutator(self, k):
        return self._streams.pop(k, None)

    def suppressed(self, k):
        del self._streams[k]  # shadowlint: unlocked-ok(test only)

    def module_write(self, k):
        SHARED[k] = 1

    def annotated_write(self, k):
        ANNOTATED[k] = 1

SHARED["import-time"] = 0
'''


def test_unlocked_shared_dict_write_is_caught():
    reg = {"self._streams": "self._streams_lock"}
    sup = []
    found = CC.lint_source(FIX_SRC, "fixture.py", registry=reg,
                           suppressed_out=sup)
    by_obj = {f.obj: f for f in found}
    # the seeded defects
    assert "self._streams@unlocked_write" in by_obj
    assert "self._streams@unlocked_mutator" in by_obj
    assert by_obj["self._streams@unlocked_write"].code == "SL301"
    # the generic module-level rule (function body write; the
    # import-time population two lines later stays legal), incl.
    # PEP 526-annotated module mutables
    assert by_obj["SHARED@module_write"].code == "SL302"
    assert by_obj["ANNOTATED@annotated_write"].code == "SL302"
    # a nested def / lambda DEFINED in __init__ runs later on
    # whatever thread calls it — no construction-site exemption
    assert "self._streams@late" in by_obj
    assert "self._streams@<lambda>" in by_obj
    # direct __init__ writes and locked writes are fine; the
    # suppressed delete is absent but carries its reason out
    assert not any(o.endswith("@locked_write") or "__init__" in o
                   or "suppressed" in o for o in by_obj)
    assert len(found) == 6
    assert sup == [{"path": "fixture.py", "line": 27,
                    "reason": "test only"}]


def test_real_tree_concurrency_clean():
    assert CC.run() == [], \
        [f.format() for f in CC.run()]


def test_registry_lock_names_verified(tmp_path, monkeypatch):
    # a registry entry whose lock never appears in the file is itself
    # flagged — the registry cannot drift from the code silently
    repo = tmp_path / "repo"
    (repo / "shadow_tpu" / "core").mkdir(parents=True)
    (repo / "shadow_tpu" / "core" / "manager.py").write_text(
        "x = 1\n")
    monkeypatch.setattr(CC, "LOCK_REGISTRY", {
        "shadow_tpu/core/manager.py":
            {"self._streams": "self._ghost_lock"}})
    monkeypatch.setattr(CC, "SCAN_GLOBS",
                        ("shadow_tpu/core/manager.py",))
    found = CC.run(repo_root=str(repo))
    assert any(f.code == "SL301" and f.obj == "self._ghost_lock"
               for f in found)


# ---------------------------------------------------------------------
# findings + baseline round-trip
# ---------------------------------------------------------------------
def test_baseline_round_trip(tmp_path):
    base = str(tmp_path / "baseline.json")
    f1 = F.Finding(code="SL301", severity=F.SEV_ERROR,
                   path="a.py", obj="self.x@f", line=3,
                   message="unlocked write")
    f2 = F.Finding(code="SL201", severity=F.SEV_ERROR,
                   path="aotcache", obj="pkg.mod",
                   message="undigested")

    # add: both findings are new against the empty baseline
    new, sup, stale = F.apply_baseline([f1, f2], F.load_baseline(
        str(tmp_path / "missing.json")))
    assert len(new) == 2 and not sup and not stale

    # suppress: grandfather them, both now suppressed with reasons
    F.write_baseline(base, [f1, f2], reason="staged in PR 10")
    new, sup, stale = F.apply_baseline([f1, f2], F.load_baseline(base))
    assert not new and len(sup) == 2 and not stale
    assert all(s["reason"] == "staged in PR 10" for s in sup)

    # regress: f2 is fixed -> its suppression reads stale; a NEW
    # finding at a different site still fails
    f3 = F.Finding(code="SL301", severity=F.SEV_ERROR,
                   path="b.py", obj="self.y@g",
                   message="fresh bug")
    new, sup, stale = F.apply_baseline([f1, f3], F.load_baseline(base))
    assert [f.key for f in new] == [f3.key]
    assert len(sup) == 1 and len(stale) == 1
    assert stale[0]["key"] == f2.key

    # line drift must NOT invalidate a suppression
    f1_moved = F.Finding(code="SL301", severity=F.SEV_ERROR,
                         path="a.py", obj="self.x@f", line=99,
                         message="unlocked write")
    new, sup, _ = F.apply_baseline([f1_moved], F.load_baseline(base))
    assert not new and len(sup) == 1


def test_baseline_rejects_reasonless_and_malformed(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text('{"version": 1, "suppressions": [{"key": "x"}]}')
    with pytest.raises(ValueError, match="reason"):
        F.load_baseline(str(bad))
    bad.write_text('["not", "a", "dict"]')
    with pytest.raises(ValueError):
        F.load_baseline(str(bad))


def test_record_shape():
    f1 = F.Finding(code="SL101", severity=F.SEV_ERROR, path="p",
                   obj="o", message="m")
    rec = F.record([f1], [f1], [], [], ["jaxpr"],
                   {"jaxpr": 1.234})
    assert rec["ok"] is False
    assert rec["counts"]["new_errors"] == 1
    assert rec["findings"][0]["key"] == "SL101:p:o"
    rec = F.record([], [], [], [], ["jaxpr"], {})
    assert rec["ok"] is True


def test_subset_run_does_not_flag_other_passes_stale(tmp_path):
    # a --pass subset run cannot judge the other passes' suppressions
    # stale (their findings were never computed)
    import subprocess
    import sys

    base = tmp_path / "baseline.json"
    f_jaxpr = F.Finding(code="SL101", severity=F.SEV_ERROR,
                        path="engine[x]:run", obj="const[8]:int64:ab",
                        message="leak")
    F.write_baseline(str(base), [f_jaxpr], reason="fork staging")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cmd = [sys.executable,
           os.path.join(repo, "scripts", "analyze.py"),
           "--baseline", str(base), "--strict-baseline",
           "--pass", "digest", "--pass", "concurrency"]
    p = subprocess.run(cmd, capture_output=True, text=True,
                       timeout=180,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stdout + p.stderr
    assert "stale suppression:" not in p.stdout
    assert "0 stale" in p.stdout


def test_shipped_baseline_is_valid_and_empty():
    data = F.load_baseline()
    assert data["suppressions"] == []


def test_unknown_pass_rejected():
    from shadow_tpu import analyze

    with pytest.raises(ValueError, match="unknown pass"):
        analyze.run_pass("nope")


# ---------------------------------------------------------------------
# the full matrix + driver (slow: builds every engine variant)
# ---------------------------------------------------------------------
@pytest.mark.slow
def test_full_jaxpr_matrix_clean():
    found = JA.run()
    assert _errors(found) == [], [f.format() for f in found]


@pytest.mark.slow
def test_analyze_driver_end_to_end(tmp_path):
    import json
    import subprocess
    import sys

    out = tmp_path / "findings.json"
    repo = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    p = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "analyze.py"),
         "--json", str(out), "--strict-baseline"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stdout + p.stderr
    rec = json.loads(out.read_text())
    assert rec["ok"] is True
    assert set(rec["passes"]) == {"jaxpr", "digest", "concurrency"}
