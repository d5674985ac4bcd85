"""Pass 1 — jaxpr audit of the device engine's traced programs.

The engine's determinism and cache-soundness contracts are properties
of the TRACED program, so this pass inspects exactly that: every
dispatchable program (``engine.lowerable_programs()`` — the same
names the AOT cache keys on) is traced abstractly
(``jit.trace(ShapeDtypeStruct...)``, zero device work, nothing
compiled or executed) and its ClosedJaxpr is walked for three bug
classes:

* **SL101 leaked closure constant** — a non-scalar array captured by
  the trace instead of threaded through the ``wrld`` tuple. A leaked
  world value is invisible to the program fingerprint (stale AOT
  cache entries would load for the wrong world) and frozen across
  ensemble replicas (every replica silently simulates replica 0's
  world). Allowed captures are value-matched against
  ``engine.audit_consts()`` and must carry a
  ``# shadowlint: const-ok(reason)`` comment at the capture site.
* **SL102 unpinned primitive** — an op outside PRIMITIVE_ALLOWLIST.
  The allowlist is the reviewed set of known-deterministic,
  TPU-friendly primitives the engine lowers to today; a new primitive
  appearing is exactly the event a human should look at (is it
  bit-deterministic across backends? is it a scatter sneaking into
  the hot path?).
* **SL103/SL104 collective drift** — a cross-shard collective whose
  axis or buffer capacity is not in ``engine.collective_registry()``,
  or a registered exchange mover that never appears in the lowered
  program. ``determinism_gate --analyze-consistency`` cross-checks
  the same registry against ``engine.effective{}`` at runtime.
"""

from __future__ import annotations

import hashlib
import re

import numpy as np

from shadow_tpu.analyze.findings import (
    SEV_ERROR,
    SEV_WARNING,
    Finding,
)
from shadow_tpu.utils.slog import get_logger

log = get_logger("analyze")

# The pinned allowlist: every primitive the engine's programs lower
# to today, reviewed for determinism. Notes on the entries a reader
# will squint at:
#   * sort        — jax lax.sort is stable; the engine's whole
#                   determinism story rides on it;
#   * scatter / scatter-add — app-level state updates
#                   (``app_state.at[:, k].set/add``) lower to per-host
#                   ROW scatters on tiny [H, words] operands; the
#                   engine hot path (heaps/outbox/exchange) stays
#                   scatter-free per the v2 design, and a scatter
#                   appearing elsewhere still trips SL102 on any NEW
#                   primitive variant (scatter-mul, scatter-min, ...);
#   * threefry2x32 rides inside jit calls (counter-based, stateless);
#   * optimization_barrier — prng.chain_key's fold barriers;
#   * stop_gradient — value identity; jax 0.9's vmap of a lax.cond
#                   with a batched predicate (the ensemble program's
#                   exchange skip) emits it around the select.
PRIMITIVE_ALLOWLIST = frozenset({
    "add", "all_gather", "all_to_all", "and", "axis_index",
    "bitcast_convert_type", "broadcast_in_dim", "concatenate",
    "cond", "convert_element_type", "copy", "cumprod", "cumsum",
    "device_put", "div", "dynamic_slice", "dynamic_update_slice",
    "eq", "gather", "ge", "gt", "iota", "le", "le_to", "lt", "max",
    "min", "mul", "ne", "neg", "not", "optimization_barrier", "or",
    "jit", "pad", "population_count", "ppermute", "psum",
    "reduce_and", "reduce_max", "reduce_min", "reduce_or",
    "reduce_sum", "rem", "reshape", "scan", "scatter", "scatter-add",
    "select_n", "shard_map", "shift_left", "shift_right_arithmetic",
    "shift_right_logical", "sign", "slice", "sort", "squeeze",
    "stop_gradient", "sub", "threefry2x32", "transpose", "while", "xor",
})

# collective primitives whose axis/shape the registry pins
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmin", "pmax", "ppermute", "all_to_all", "all_gather",
    "reduce_scatter", "pbroadcast", "axis_index",
})

# which exchange variant must lower to which mover primitive — the
# presence half of the collective check (SL104)
EXCHANGE_MOVER = {
    "all_to_all": "all_to_all",
    "all_gather": "all_gather",
    "two_phase": "ppermute",
}

# audit_consts() entry -> the capture-site variable in engine.py that
# must carry the const-ok comment (the suppression is source-visible,
# the value match is machine-checked)
CAPTURE_SITES = {
    "model_nic.LAW": "law_t",
    "bw_up": "bw_up_t",
    "bw_down": "bw_down_t",
}

_ENGINE_REL = "shadow_tpu/device/engine.py"


# ---------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------
def _sub_jaxprs(val):
    """Yield (jaxpr, consts|None) for any jaxpr-valued eqn param."""
    vals = val if isinstance(val, (list, tuple)) else [val]
    for x in vals:
        if hasattr(x, "eqns"):                       # open Jaxpr
            yield x, None
        elif hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
            yield x.jaxpr, getattr(x, "consts", None)  # ClosedJaxpr


def walk_jaxpr(closed):
    """Flatten one ClosedJaxpr: returns (consts, eqns) over the whole
    nested program (while bodies, cond branches, jit calls,
    shard_map inner jaxprs, ...)."""
    consts, eqns = list(closed.consts), []

    def _walk(jx):
        for eqn in jx.eqns:
            eqns.append(eqn)
            for v in eqn.params.values():
                for sub, sub_consts in _sub_jaxprs(v):
                    if sub_consts:
                        consts.extend(sub_consts)
                    _walk(sub)

    _walk(closed.jaxpr)
    return consts, eqns


def _collective_axis(eqn):
    ax = eqn.params.get("axis_name", None)
    if ax is None:
        ax = eqn.params.get("axes", None)
    if isinstance(ax, (tuple, list)):
        ax = ax[0] if len(ax) == 1 else tuple(ax)
    return ax


# ---------------------------------------------------------------------
# constant classification
# ---------------------------------------------------------------------
def classify_const(arr) -> str:
    """'scalar' | 'fill' | 'iota' | 'opaque' — only opaque constants
    need an explicit allowance (fills and affine iotas are shape
    artifacts of the static program, carrying no world data).

    The iota class is deliberately narrow: exact integer arithmetic
    for integer dtypes (float64 diffs would alias i64 values past
    2^53) and a constant stride over at least 3 elements — any
    2-element pair is trivially 'affine', so pairs only qualify as
    the literal unit iota [0, 1] (what a 2-wide jnp.arange
    materializes to). Residual risk — a LEAKED table whose values
    happen to be evenly spaced (e.g. a uniform epoch_times vector)
    classifies as iota; the world()-threading convention plus the
    --analyze-consistency gate's real-config audit are the backstop
    for that corner."""
    a = np.asarray(arr)
    if a.size <= 1:
        return "scalar"
    flat = a.ravel()
    if (flat == flat.flat[0]).all():
        return "fill"
    if flat.size == 2 and np.issubdtype(flat.dtype, np.number) and \
            flat[0] == 0 and flat[1] == 1:
        return "iota"
    if flat.size >= 3 and np.issubdtype(flat.dtype, np.number):
        if np.issubdtype(flat.dtype, np.integer):
            d = np.diff(flat.astype(object))   # exact, no 2^53 alias
        else:
            d = np.diff(flat.astype(np.float64))
        if (d == d[0]).all():
            return "iota"                  # affine: arange * k + b
    return "opaque"


def _const_matches(arr, allowed: dict):
    a = np.asarray(arr)
    for name, ref in allowed.items():
        r = np.asarray(ref)
        if a.shape == r.shape and a.dtype == r.dtype and \
                np.array_equal(a, r):
            return name
    return None


def const_ok_targets(path: str) -> set[str]:
    """Assignment targets covered by a ``# shadowlint: const-ok(...)``
    comment: the comment block covers the run of simple assignments
    immediately following it (so one comment can cover a pair like
    bw_up_t/bw_down_t on consecutive lines)."""
    import ast

    with open(path) as f:
        src = f.read()
    lines = src.splitlines()
    marks = [i + 1 for i, ln in enumerate(lines)
             if re.search(r"#\s*shadowlint:\s*const-ok\(", ln)]
    if not marks:
        return set()
    assigns = []                       # (lineno, [target names])
    for node in ast.walk(ast.parse(src)):
        if isinstance(node, ast.Assign):
            names = [t.id for t in node.targets
                     if isinstance(t, ast.Name)]
            if names:
                assigns.append((node.lineno, names))
    assigns.sort()
    covered: set[str] = set()
    for m in marks:
        run_prev = None
        for ln, names in assigns:
            if ln <= m:
                continue
            # the first assignment within a short window after the
            # comment starts the covered run; consecutive assignment
            # lines extend it
            if run_prev is None:
                if ln - m > 6:
                    break
            elif ln - run_prev > 1:
                break
            covered.update(names)
            run_prev = ln
    return covered


# ---------------------------------------------------------------------
# per-program audit
# ---------------------------------------------------------------------
def audit_closed_jaxpr(closed, *, program: str,
                       allowed_consts: dict | None = None,
                       registry: dict | None = None,
                       ok_targets: set | None = None,
                       capture_sites: dict | None = None,
                       ) -> list[Finding]:
    """Audit one traced program. Separated from the engine matrix so
    tests can feed deliberately-broken fixture programs."""
    allowed = dict(allowed_consts or {})
    sites = (CAPTURE_SITES if capture_sites is None
             else capture_sites)
    consts, eqns = walk_jaxpr(closed)
    out = []

    for c in consts:
        kind = classify_const(c)
        if kind != "opaque":
            continue
        a = np.asarray(c)
        name = _const_matches(a, allowed)
        if name is None:
            # the content digest joins the identity key: a baseline
            # suppression of one known const must not grandfather a
            # DIFFERENT future leak of the same shape and dtype
            digest = hashlib.sha256(
                np.ascontiguousarray(a).tobytes()).hexdigest()[:8]
            out.append(Finding(
                code="SL101", severity=SEV_ERROR, path=program,
                obj=f"const{list(a.shape)}:{a.dtype}:{digest}",
                message=(
                    f"non-scalar closure constant {a.shape} "
                    f"{a.dtype} is baked into the trace but not "
                    "threaded through the wrld tuple — invisible to "
                    "the program fingerprint (stale-cache hazard) "
                    "and frozen across ensemble replicas"),
                hint=("thread the array through the traced wrld "
                      "tuple (engine.world()), or — if the bytes "
                      "are covered by the cache key another way — "
                      "register it in engine.audit_consts() and mark "
                      "the capture site with "
                      "# shadowlint: const-ok(<reason>)")))
        elif ok_targets is not None:
            site = sites.get(name)
            if site is not None and site not in ok_targets:
                out.append(Finding(
                    code="SL105", severity=SEV_ERROR, path=program,
                    obj=name,
                    message=(
                        f"allowed constant {name!r} (capture site "
                        f"{site!r}) has no "
                        "# shadowlint: const-ok(...) comment"),
                    hint=(f"add # shadowlint: const-ok(<reason>) "
                          f"above the {site} assignment in "
                          f"{_ENGINE_REL}")))

    prims = sorted({e.primitive.name for e in eqns})
    for p in prims:
        if p not in PRIMITIVE_ALLOWLIST:
            out.append(Finding(
                code="SL102", severity=SEV_ERROR, path=program,
                obj=p,
                message=(f"primitive {p!r} is outside the pinned "
                         "deterministic allowlist"),
                hint=("review the op for cross-backend bit-"
                      "determinism (and the no-scatters hot-path "
                      "rule), then add it to PRIMITIVE_ALLOWLIST in "
                      "shadow_tpu/analyze/jaxpr_audit.py with a "
                      "note")))

    if registry is not None:
        seen_prims = set()
        for eqn in eqns:
            p = eqn.primitive.name
            if p not in COLLECTIVE_PRIMS:
                continue
            seen_prims.add(p)
            ax = _collective_axis(eqn)
            ent = registry.get(p)
            if ent is None:
                out.append(Finding(
                    code="SL103", severity=SEV_ERROR, path=program,
                    obj=p,
                    message=(f"collective {p!r} is not in the "
                             "engine's collective registry for this "
                             "build"),
                    hint=("teach engine.collective_registry() about "
                          "the new collective (and pin its buffer "
                          "capacity) — then determinism_gate "
                          "--analyze-consistency keeps it honest")))
                continue
            if ax != ent["axis"]:
                out.append(Finding(
                    code="SL103", severity=SEV_ERROR, path=program,
                    obj=f"{p}:axis={ax!r}",
                    message=(f"collective {p!r} runs over axis "
                             f"{ax!r}, registry pins "
                             f"{ent['axis']!r}"),
                    hint="collectives must stay on the mesh axis"))
            caps = ent.get("caps")
            if caps:
                for v in eqn.invars:
                    shp = tuple(getattr(v.aval, "shape", ()))
                    last = shp[-1] if shp else 1
                    if last not in caps:
                        out.append(Finding(
                            code="SL103", severity=SEV_ERROR,
                            path=program,
                            obj=f"{p}:dim={last}",
                            message=(
                                f"{p!r} buffer trailing dim {last} "
                                f"not in the pinned capacities "
                                f"{sorted(caps)} — the exchange is "
                                "moving an unplanned buffer"),
                            hint=("size the buffer from the "
                                  "planned capacity (engine."
                                  "effective CAP/CAP2) or update "
                                  "collective_registry()")))
                        break
        mover = registry.get("__expect_mover__")
        if mover and mover not in seen_prims:
            out.append(Finding(
                code="SL104", severity=SEV_ERROR, path=program,
                obj=mover,
                message=(f"exchange mover {mover!r} is registered "
                         "for this build but absent from the "
                         "lowered program"),
                hint=("the static registry and the real program "
                      "drifted — rebuild the registry from the "
                      "resolved config")))
    return out


# ---------------------------------------------------------------------
# the engine matrix
# ---------------------------------------------------------------------
def _build_engine(exchange="all_to_all", app=None, ensemble=None,
                  epochs=1, **cfg_kw):
    from shadow_tpu.device.apps import PholdDevice
    from shadow_tpu.device.engine import DeviceEngine, EngineConfig

    H = cfg_kw.pop("H", 8)
    hv = cfg_kw.pop("host_vertex", np.zeros(H, np.int32))
    cfg_kw.setdefault("event_capacity", 8)
    cfg_kw.setdefault("outbox_capacity", 8)
    cfg = EngineConfig(n_hosts=H, lookahead=1_000_000,
                       stop_time=10_000_000, exchange=exchange,
                       **cfg_kw)
    app = app or PholdDevice(n_hosts_total=H, msgload=2)
    lat = np.full((2, 2), 1_000_000, np.int64)
    rel = np.ones((2, 2), np.float32)
    rel[0, 1] = 0.9                 # keep the drop rolls in the trace
    ept = None
    if epochs > 1:
        lat = np.stack([lat] * epochs)
        rel = np.stack([rel] * epochs)
        ept = (np.arange(epochs) * 5_000_000).astype(np.int64)
    return DeviceEngine(cfg, app, hv, lat, rel,
                        epoch_times=ept, ensemble=ensemble)


def _tiny_ensemble(R=2):
    """Duck-typed EnsembleWorlds (the engine only reads arrays + R)."""
    from shadow_tpu.ensemble.spec import seed_key_np

    class _W:
        pass

    w = _W()
    w.R = R
    lat = np.full((2, 2), 1_000_000, np.int32)
    rel = np.ones((2, 2), np.float32)
    rel[0, 1] = 0.9
    w.latency = np.stack([lat] * R)
    w.reliability = np.stack([rel] * R)
    w.epoch_times = np.zeros((R, 1), np.int64)
    ks = [seed_key_np(s) for s in range(1, R + 1)]
    w.seed_k1 = np.array([k[0] for k in ks], np.uint32)
    w.seed_k2 = np.array([k[1] for k in ks], np.uint32)
    return w


def engine_matrix() -> list[tuple[str, object]]:
    """Representative engine builds spanning every traced-code branch
    family: exchange schedules, the fluid NIC (LAW/bw consts), fault
    epochs, the audit word, both merge/pop strategies, path counting,
    burst apps, and the vmapped ensemble program."""
    from shadow_tpu.device.apps import TgenDevice

    H = 8
    tgen = TgenDevice(roles=np.array([0] + [1] * (H - 1), np.int32),
                      server_gid=np.zeros(H, np.int32),
                      size=1 << 16)
    bw = np.full(H, 5 * 10 ** 8, np.int64)

    builds = [
        ("base", _build_engine()),
        ("model_bandwidth", _build_engine(model_bandwidth=True)),
        ("count_paths", _build_engine(count_paths=True)),
        ("audited", _build_engine(audit=True)),
        ("two_phase", _build_engine(exchange="two_phase")),
        ("all_gather", _build_engine(exchange="all_gather")),
        ("window_merge", _build_engine(merge_global=False,
                                       pop_onehot=False,
                                       judge_hoist=False)),
        # tor_56000's TPU stack: the window merge carrying its payload,
        # its arrival windows laid out by the filler sort
        ("window_merge_sort", _build_engine(merge_global=False,
                                            merge_payload="sort",
                                            pop_onehot=True,
                                            judge_hoist=True)),
        ("tpu_strategies", _build_engine(merge_global=True,
                                         pop_onehot=True,
                                         judge_hoist=True,
                                         outbox_compact=4)),
        ("table_onehot", _build_engine(
            table_onehot=True, judge_hoist=True,
            host_vertex=np.array([0, 1, 1, 0, 0, 0, 1, 0], np.int32))),
        ("tgen_faults", _build_engine(app=tgen, epochs=2,
                                      event_capacity=16,
                                      outbox_capacity=16)),
        ("ensemble", _build_engine(ensemble=_tiny_ensemble())),
    ]
    # the fluid NIC with real (non-fill) bandwidth vectors, so the
    # bw_up/bw_down consts are exercised as opaque captures
    from shadow_tpu.device.apps import PholdDevice
    from shadow_tpu.device.engine import DeviceEngine, EngineConfig

    cfg = EngineConfig(n_hosts=H, event_capacity=8,
                       outbox_capacity=8, lookahead=1_000_000,
                       stop_time=10_000_000, model_bandwidth=True)
    bw_var = bw.copy()
    bw_var[1] = 10 ** 9
    eng = DeviceEngine(cfg, PholdDevice(n_hosts_total=H, msgload=2),
                       np.zeros(H, np.int32),
                       np.full((2, 2), 1_000_000, np.int64),
                       np.ones((2, 2), np.float32),
                       bw_up_bits=bw_var, bw_down_bits=bw)
    builds.append(("model_bandwidth_vec", eng))
    return builds


def audit_engine(engine, label: str,
                 ok_targets: set | None = None) -> list[Finding]:
    out = []
    registry = dict(engine.collective_registry())
    if engine.n_shards > 1:
        registry["__expect_mover__"] = \
            EXCHANGE_MOVER[engine.effective["exchange"]]
    allowed = engine.audit_consts()
    for name, (jit_fn, args) in engine.lowerable_programs().items():
        closed = jit_fn.trace(*args).jaxpr
        reg = registry
        if name in ("pop",):
            # the pop phase contains no exchange; presence is only
            # required of programs that flush
            reg = {k: v for k, v in registry.items()
                   if k != "__expect_mover__"}
        out.extend(audit_closed_jaxpr(
            closed, program=f"engine[{label}]:{name}",
            allowed_consts=allowed, registry=reg,
            ok_targets=ok_targets))
    return out


def run() -> list[Finding]:
    """Audit the whole engine matrix. Pure tracing: no compile, no
    dispatch, no device state — the determinism_gate --telemetry-
    style spot check in CI confirms analysis runs perturb nothing."""
    import shadow_tpu.device.engine as engine_mod
    from shadow_tpu._jax import jax

    ok_targets = const_ok_targets(engine_mod.__file__)
    findings = []
    if len(jax.devices()) == 1:
        findings.append(Finding(
            code="SL104", severity=SEV_WARNING, path="jaxpr",
            obj="mesh",
            message=("single-device backend: cross-shard collectives "
                     "never lower, so the collective audit is "
                     "vacuous this run"),
            hint=("run under XLA_FLAGS=--xla_force_host_platform_"
                  "device_count=4 (scripts/analyze.py does this by "
                  "default)")))
    for label, eng in engine_matrix():
        found = audit_engine(eng, label, ok_targets=ok_targets)
        log.info("jaxpr audit: engine[%s] -> %d finding(s)", label,
                 len(found))
        findings.extend(found)
    return findings
