"""Pass 3 — concurrency lint over the host-side layers.

The CPU scheduler runs hosts on worker threads; the Manager, the
Controller, and the host/ emulation layers therefore carry a handful
of genuinely shared mutable structures (the cross-host TCP stream
registry, the hybrid judge's pending-packet list, the shared trace
list, the path-packet histogram). PR 2's ``_streams`` create-vs-
teardown race was found by hand during review; this pass makes the
class mechanical:

* :data:`LOCK_REGISTRY` declares, per file, which attribute is
  shared-mutable and which lock guards it. Every WRITE to a
  registered attribute — mutation calls (``append``/``update``/
  ``pop``/...), subscript stores/deletes, and rebinds — must sit
  inside a ``with <lock>`` region naming the registered lock
  (SL301). Construction sites (``__init__``/``__post_init__``) are
  exempt: the object is not yet shared there (happens-before via the
  thread start).
* Module-level dicts/lists/sets written from inside any function
  body without an enclosing lock are flagged generically (SL302) —
  import-time population is fine, post-import mutation from
  per-host/per-worker code paths is the bug class.
* ``# shadowlint: unlocked-ok(reason)`` on the write line suppresses
  either finding in place (single-threaded-by-construction or
  idempotent-latch paths); each suppression is logged with its
  captured reason when the pass runs, and the reason lives at the
  write site where a reviewer reads it.
"""

from __future__ import annotations

import ast
import os
import re

from shadow_tpu.analyze.findings import SEV_ERROR, Finding
from shadow_tpu.utils.slog import get_logger

log = get_logger("analyze")

# the declared lock registry: file -> {shared attribute -> its lock}.
# Seeded from the structures the Manager/NetworkModel already guard;
# registering a NEW shared structure here is part of adding it.
LOCK_REGISTRY = {
    "shadow_tpu/core/manager.py": {
        "self._streams": "self._streams_lock",
        "self._pending": "self._pending_lock",
        "self.trace": "self._trace_lock",
    },
    "shadow_tpu/core/netmodel.py": {
        "self.path_packets": "self._lock",
    },
    # the chaos injector's schedule counters + dead-device set: the
    # injector is process-global (chaos.current()), so any thread
    # that reaches one of its seams shares them — every mutation
    # takes the lock
    "shadow_tpu/device/chaos.py": {
        "self._dead": "self._lock",
        "self._issues": "self._lock",
        "self._ck_saves": "self._lock",
        "self._stores": "self._lock",
        "self.fired": "self._lock",
    },
}

# files the pass scans (the generic module-level rule applies to all
# of them; the registry rule to the files registered above)
SCAN_GLOBS = (
    "shadow_tpu/core/manager.py",
    "shadow_tpu/core/controller.py",
    "shadow_tpu/core/netmodel.py",
    "shadow_tpu/device/chaos.py",
    "shadow_tpu/device/supervise.py",
    "shadow_tpu/host/*.py",
)

# method calls that mutate dicts/lists/sets/deques in place
MUTATORS = frozenset({
    "append", "extend", "insert", "remove", "pop", "popitem",
    "clear", "update", "setdefault", "add", "discard", "appendleft",
    "popleft", "sort", "reverse",
})

UNLOCKED_OK_RE = re.compile(
    r"#\s*shadowlint:\s*unlocked-ok\(([^)]*)\)")

_INIT_FUNCS = ("__init__", "__post_init__", "__new__")


def _base_expr(node):
    """The registry-matchable base of a write target: for
    ``self._streams[key]`` / ``self._streams.append`` /
    ``self._streams`` returns "self._streams"; for module-level
    ``TABLE[k]`` returns "TABLE"."""
    t = node
    while isinstance(t, ast.Subscript):
        t = t.value
    try:
        return ast.unparse(t)
    except Exception:           # noqa: BLE001 — exotic target
        return ""


class _Lint(ast.NodeVisitor):
    def __init__(self, relpath, src, registry, module_mutables):
        self.relpath = relpath
        self.lines = src.splitlines()
        self.registry = registry            # attr -> lock (this file)
        self.module_mutables = module_mutables
        self.with_stack: list[str] = []
        self.func_stack: list[str] = []
        self.findings: list[Finding] = []
        self.suppressed: list[dict] = []

    # -- structure tracking -------------------------------------------
    def visit_With(self, node):
        ctxs = []
        for item in node.items:
            try:
                ctxs.append(ast.unparse(item.context_expr))
            except Exception:   # noqa: BLE001
                pass
        self.with_stack.extend(ctxs)
        self.generic_visit(node)
        del self.with_stack[len(self.with_stack) - len(ctxs):]

    def _func(self, node):
        self.func_stack.append(getattr(node, "name", "<lambda>"))
        self.generic_visit(node)
        self.func_stack.pop()

    visit_FunctionDef = _func
    visit_AsyncFunctionDef = _func
    visit_Lambda = _func

    # -- write detection ----------------------------------------------
    def _held(self, lock: str) -> bool:
        return any(c == lock or c.endswith("." + lock)
                   for c in self.with_stack)

    def _suppressed_at(self, lineno: int) -> bool:
        m = UNLOCKED_OK_RE.search(self.lines[lineno - 1]) \
            if 1 <= lineno <= len(self.lines) else None
        if m:
            self.suppressed.append(
                {"path": self.relpath, "line": lineno,
                 "reason": m.group(1)})
            return True
        return False

    def _check_write(self, node, base: str, what: str):
        if not self.func_stack:
            return                          # import-time population
        if self.func_stack[-1] in _INIT_FUNCS:
            # construction site: the write executes DURING __init__ /
            # __post_init__, before the object is shared. Only the
            # innermost frame counts — a nested def or lambda defined
            # inside __init__ runs LATER, on whatever thread calls
            # it, and gets no exemption.
            return
        lock = self.registry.get(base)
        if lock is not None:
            if self._held(lock) or self._suppressed_at(node.lineno):
                return
            self.findings.append(Finding(
                code="SL301", severity=SEV_ERROR, path=self.relpath,
                obj=f"{base}@{self.func_stack[-1]}",
                line=node.lineno,
                message=(f"{what} of registered shared state "
                         f"{base!r} outside `with {lock}`"),
                hint=(f"wrap the write in `with {lock}:` (see the "
                      "lock registry in shadow_tpu/analyze/"
                      "concurrency.py), or mark the line "
                      "# shadowlint: unlocked-ok(<reason>) if the "
                      "path is single-threaded by construction")))
        elif base in self.module_mutables:
            if any(c.endswith("lock") or c.endswith("Lock()")
                   for c in self.with_stack) or \
                    self._suppressed_at(node.lineno):
                return
            self.findings.append(Finding(
                code="SL302", severity=SEV_ERROR, path=self.relpath,
                obj=f"{base}@{self.func_stack[-1]}",
                line=node.lineno,
                message=(f"{what} of module-level mutable {base!r} "
                         "from a function body without any lock"),
                hint=("register the structure (with its lock) in "
                      "LOCK_REGISTRY, make it per-instance state, "
                      "or mark the line "
                      "# shadowlint: unlocked-ok(<reason>)")))

    def _targets(self, t):
        if isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                yield from self._targets(e)
        else:
            yield t

    def visit_Assign(self, node):
        for t in node.targets:
            for tgt in self._targets(t):
                base = _base_expr(tgt)
                if isinstance(tgt, ast.Subscript):
                    self._check_write(node, base, "subscript store")
                elif base:
                    self._check_write(node, base, "rebind")
        self.generic_visit(node)

    def visit_AugAssign(self, node):
        self._check_write(node, _base_expr(node.target),
                          "augmented store")
        self.generic_visit(node)

    def visit_Delete(self, node):
        for t in node.targets:
            if isinstance(t, ast.Subscript):
                self._check_write(node, _base_expr(t),
                                  "subscript delete")
        self.generic_visit(node)

    def visit_Call(self, node):
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in MUTATORS:
            self._check_write(node, _base_expr(f.value),
                              f".{f.attr}()")
        self.generic_visit(node)


def _module_mutables(tree) -> set[str]:
    """Module-level names bound to a mutable container display or
    constructor at import time."""
    out = set()
    for node in tree.body:
        if isinstance(node, ast.Assign):
            targets = [t for t in node.targets
                       if isinstance(t, ast.Name)]
            v = node.value
        elif isinstance(node, ast.AnnAssign):      # PEP 526 style
            targets = ([node.target]
                       if isinstance(node.target, ast.Name) else [])
            v = node.value
        else:
            continue
        mutable = isinstance(v, (ast.Dict, ast.List, ast.Set)) or (
            isinstance(v, ast.Call) and isinstance(v.func, ast.Name)
            and v.func.id in ("dict", "list", "set", "defaultdict",
                              "OrderedDict", "deque"))
        if not mutable:
            continue
        for t in targets:
            out.add(t.id)
    return out


def lint_source(src: str, relpath: str,
                registry: dict | None = None,
                suppressed_out: list | None = None) -> list[Finding]:
    """Lint one file's source. `registry` defaults to this file's
    LOCK_REGISTRY entry; tests inject fixture registries.
    `suppressed_out` collects {path, line, reason} for every
    in-source unlocked-ok suppression that fired."""
    reg = (LOCK_REGISTRY.get(relpath, {}) if registry is None
           else registry)
    tree = ast.parse(src, filename=relpath)
    lint = _Lint(relpath, src, reg, _module_mutables(tree))
    lint.visit(tree)
    if suppressed_out is not None:
        suppressed_out.extend(lint.suppressed)
    return lint.findings


def scan_files(repo_root: str) -> list[str]:
    import glob as _glob

    out = []
    for pat in SCAN_GLOBS:
        out.extend(sorted(
            _glob.glob(os.path.join(repo_root, pat))))
    return out


def run(repo_root: str | None = None) -> list[Finding]:
    if repo_root is None:
        import shadow_tpu

        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(shadow_tpu.__file__)))
    findings = []
    for path in scan_files(repo_root):
        rel = os.path.relpath(path, repo_root)
        with open(path) as f:
            src = f.read()
        suppressed: list = []
        found = lint_source(src, rel, suppressed_out=suppressed)
        if found:
            log.info("concurrency lint: %s -> %d finding(s)", rel,
                     len(found))
        for s in suppressed:
            log.info("concurrency lint: %s:%d unlocked-ok(%s)",
                     s["path"], s["line"], s["reason"])
        findings.extend(found)
    # a registered lock that the file never takes is itself a smell
    # (the registry drifted from the code) — surface it loudly
    for rel, reg in LOCK_REGISTRY.items():
        path = os.path.join(repo_root, rel)
        if not os.path.exists(path):
            findings.append(Finding(
                code="SL301", severity=SEV_ERROR, path=rel,
                obj="<registry>",
                message=f"registered file {rel} does not exist",
                hint="update LOCK_REGISTRY"))
            continue
        with open(path) as f:
            src = f.read()
        for attr, lock in reg.items():
            bare = lock.split(".")[-1]
            if bare not in src:
                findings.append(Finding(
                    code="SL301", severity=SEV_ERROR, path=rel,
                    obj=lock,
                    message=(f"registered lock {lock!r} for {attr!r} "
                             "never appears in the file"),
                    hint="update LOCK_REGISTRY to the real lock"))
    return findings
