"""AST rule pack enforcing the CLAUDE.md engine contracts.

Each rule mechanizes one load-bearing invariant that previously existed
only as prose plus post-hoc review hardening. Rules are deliberately
narrow: they encode the exact anti-pattern each incident taught us, and
they must exit clean on the live tree — a rule that needs an allowlist
to pass HEAD is mis-specified.

Scanned surfaces: ``horovod_tpu/``, ``examples/``, and ``tests/`` (the
worker scripts spawn real engine worlds), plus the import-free
entrypoint (``horovod_tpu/run.py``).
"""

from __future__ import annotations

import ast
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Set, Tuple

from horovod_tpu.analysis.report import Finding

ASYNC_SUBMITS = ("allreduce_async", "allgather_async", "broadcast_async")

# Methods that mutate a numpy array in place through an attribute call.
_MUTATING_METHODS = {"fill", "sort", "put", "itemset", "partition",
                     "setflags", "resize"}


def _iter_py_files(root: str) -> Iterable[str]:
    for sub in ("horovod_tpu", "examples", "tests"):
        top = os.path.join(root, sub)
        if not os.path.isdir(top):
            continue
        for dirpath, dirnames, filenames in os.walk(top):
            dirnames[:] = [d for d in dirnames
                           if d not in ("__pycache__",)]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def _parse(path: str) -> Optional[ast.AST]:
    try:
        return ast.parse(open(path).read(), filename=path)
    except SyntaxError:
        return None


def _attr_name(func: ast.AST) -> Optional[str]:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


# ---------------------------------------------------------------------------
# Rule: tf-bridge-group
# ---------------------------------------------------------------------------

def _py_function_bodies(tree: ast.AST) -> List[ast.FunctionDef]:
    """FunctionDefs handed to ``tf.py_function`` (by name, anywhere in
    the file — the bridge idiom defines ``fn`` right next to the call)."""
    names: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and \
                _attr_name(node.func) == "py_function" and node.args:
            first = node.args[0]
            if isinstance(first, ast.Name):
                names.add(first.id)
        # tf.py_function(func=fn, ...) spelling
        if isinstance(node, ast.Call) and \
                _attr_name(node.func) == "py_function":
            for kw in node.keywords:
                if kw.arg == "func" and isinstance(kw.value, ast.Name):
                    names.add(kw.value.id)
    return [n for n in ast.walk(tree)
            if isinstance(n, ast.FunctionDef) and n.name in names]


def check_tf_bridge(tree: ast.AST, rel: str) -> List[Finding]:
    """TF runs py_function bodies strictly sequentially per process, in
    a schedule order that differs across ranks: a loop that submits one
    collective and BLOCKS on it before the next submit (per-tensor
    bridge) wedges cross-rank. Multi-tensor bodies must submit every
    handle first and wait after (``mpi_ops._bridge_group``)."""
    findings = []
    for fn in _py_function_bodies(tree):
        for loop in ast.walk(fn):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            has_submit = has_wait = False
            for node in ast.walk(loop):
                if isinstance(node, ast.Call):
                    name = _attr_name(node.func)
                    if name in ASYNC_SUBMITS:
                        has_submit = True
                    elif name == "synchronize":
                        has_wait = True
            if has_submit and has_wait:
                findings.append(Finding(
                    "tf-bridge-group", rel, loop.lineno,
                    f"py_function body {fn.name!r} submits and waits on "
                    "engine collectives inside one loop — a per-tensor "
                    "blocking bridge deadlocks cross-rank under TF's "
                    "sequential executor; submit every handle first, "
                    "then wait (see mpi_ops._bridge_group)"))
    return findings


# ---------------------------------------------------------------------------
# Rule: engine-lifecycle
# ---------------------------------------------------------------------------

def check_engine_lifecycle(tree: ast.AST, rel: str) -> List[Finding]:
    """Never destroy the C++ engine (waiters may still be inside
    WaitMeta — quiesce with hvd_engine_join, then leak), and abandon
    paths must not join anything: the whole point of abandon() is that
    a wedged thread never returns."""
    findings = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and \
                _attr_name(node.func) == "hvd_engine_destroy":
            findings.append(Finding(
                "engine-lifecycle", rel, node.lineno,
                "hvd_engine_destroy() call: destroying the engine can "
                "free a condition variable a synchronize() caller is "
                "still blocked on (UB) — hvd_engine_join then leak"))
    for fn in ast.walk(tree):
        if not (isinstance(fn, ast.FunctionDef)
                and fn.name.startswith("abandon")):
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = _attr_name(node.func)
            if name == "hvd_engine_join":
                findings.append(Finding(
                    "engine-lifecycle", rel, node.lineno,
                    f"{fn.name}() calls hvd_engine_join: the loop "
                    "thread is wedged in a dead backend by definition "
                    "of abandonment — the join never returns"))
            elif name == "join" and isinstance(node.func, ast.Attribute) \
                    and isinstance(node.func.value, ast.Attribute) \
                    and "thread" in node.func.value.attr:
                findings.append(Finding(
                    "engine-lifecycle", rel, node.lineno,
                    f"{fn.name}() joins {node.func.value.attr}: abandon "
                    "paths must signal and PARK, never join a possibly-"
                    "wedged thread"))
    return findings


# ---------------------------------------------------------------------------
# Rule: donate-mutate
# ---------------------------------------------------------------------------

def check_donate_mutate(tree: ast.AST, rel: str) -> List[Finding]:
    """``donate=True`` is an ownership handoff: the engine references
    the buffer in place and the caller must not write it again before
    the handle completes. Catch same-scope mutations between the donate
    submit and the next synchronize."""
    findings = []
    for fn in ast.walk(tree):
        if not isinstance(fn, ast.FunctionDef):
            continue
        # Line spans of ``with pytest.raises(...)`` blocks: a donate
        # submit in one is EXPECTED to be rejected, after which the
        # ownership handoff never happened and the caller may mutate
        # freely (the rejected-donation contract, test_zero_copy.py).
        rejected_spans: List[Tuple[int, int]] = []
        for node in ast.walk(fn):
            if isinstance(node, ast.With) and any(
                    isinstance(item.context_expr, ast.Call)
                    and _attr_name(item.context_expr.func) == "raises"
                    for item in node.items):
                last = max((getattr(n, "lineno", node.lineno)
                            for n in ast.walk(node)), default=node.lineno)
                rejected_spans.append((node.lineno, last))
        donates: List[Tuple[str, int]] = []  # (buffer name, lineno)
        sync_lines: List[int] = []
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = _attr_name(node.func)
            if name == "synchronize":
                sync_lines.append(node.lineno)
            if name not in ASYNC_SUBMITS:
                continue
            if any(a <= node.lineno <= b for a, b in rejected_spans):
                continue
            if not any(kw.arg == "donate"
                       and isinstance(kw.value, ast.Constant)
                       and kw.value.value is True
                       for kw in node.keywords):
                continue
            buf = None
            if len(node.args) >= 2 and isinstance(node.args[1], ast.Name):
                buf = node.args[1].id
            else:
                for kw in node.keywords:
                    if kw.arg == "tensor" and isinstance(kw.value, ast.Name):
                        buf = kw.value.id
            if buf is not None:
                donates.append((buf, node.lineno))
        for buf, at in donates:
            horizon = min((s for s in sync_lines if s > at),
                          default=float("inf"))
            for node in ast.walk(fn):
                line = getattr(node, "lineno", 0)
                if not at < line < horizon:
                    continue
                mutated = False
                if isinstance(node, ast.Assign):
                    mutated = any(
                        isinstance(t, ast.Subscript)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == buf for t in node.targets)
                elif isinstance(node, ast.AugAssign):
                    tgt = node.target
                    mutated = (isinstance(tgt, ast.Name)
                               and tgt.id == buf) or (
                        isinstance(tgt, ast.Subscript)
                        and isinstance(tgt.value, ast.Name)
                        and tgt.value.id == buf)
                elif isinstance(node, ast.Call):
                    f = node.func
                    if isinstance(f, ast.Attribute) and \
                            f.attr in _MUTATING_METHODS and \
                            isinstance(f.value, ast.Name) and \
                            f.value.id == buf:
                        mutated = True
                    elif _attr_name(f) == "copyto" and node.args and \
                            isinstance(node.args[0], ast.Name) and \
                            node.args[0].id == buf:
                        mutated = True
                    elif any(kw.arg == "out"
                             and isinstance(kw.value, ast.Name)
                             and kw.value.id == buf
                             for kw in node.keywords):
                        mutated = True
                if mutated:
                    findings.append(Finding(
                        "donate-mutate", rel, line,
                        f"{buf!r} was handed to the engine with "
                        f"donate=True at line {at} and is mutated "
                        "before synchronize — the engine may still be "
                        "reading it (donate-then-mutate is documented "
                        "UB)"))
    return findings


# ---------------------------------------------------------------------------
# Rule: eager-drain
# ---------------------------------------------------------------------------

def check_eager_drain(tree: ast.AST, rel: str) -> List[Finding]:
    """Trainer ``broadcast_state`` methods must broadcast HOST leaves
    and drain before returning: mesh-sharded inputs with async work in
    flight recompile the eager broadcast programs mid-flight and wedge
    the 8-device rendezvous (the r4 second-fit hang). The host-first
    pattern is: jax.device_get first, broadcast, block_until_ready."""
    findings = []
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        for fn in cls.body:
            if not (isinstance(fn, ast.FunctionDef)
                    and fn.name == "broadcast_state"):
                continue
            bcasts = [n.lineno for n in ast.walk(fn)
                      if isinstance(n, ast.Call)
                      and _attr_name(n.func) == "broadcast_pytree"]
            if not bcasts:
                continue
            pulls = [n.lineno for n in ast.walk(fn)
                     if isinstance(n, ast.Call)
                     and _attr_name(n.func) == "device_get"]
            drains = [n.lineno for n in ast.walk(fn)
                      if isinstance(n, ast.Call)
                      and _attr_name(n.func) == "block_until_ready"]
            if not pulls or min(pulls) > min(bcasts):
                findings.append(Finding(
                    "eager-drain", rel, fn.lineno,
                    f"{cls.name}.broadcast_state broadcasts state "
                    "without pulling it to host first (jax.device_get "
                    "before the first broadcast_pytree) — sharded "
                    "inputs recompile the eager programs and wedge the "
                    "rendezvous"))
            if not drains or max(drains) < max(bcasts):
                findings.append(Finding(
                    "eager-drain", rel, fn.lineno,
                    f"{cls.name}.broadcast_state returns without "
                    "draining (block_until_ready after the last "
                    "broadcast_pytree) — async work left in flight "
                    "races the next compile"))
    return findings


# ---------------------------------------------------------------------------
# Rule: lock-order
# ---------------------------------------------------------------------------

# Documented hierarchy (CLAUDE.md / docs/static-analysis.md): rank 1 =
# engine locks (Engine._lock, NativeEngine._stats_lock), rank 2 = pool
# lock (BufferPool._lock), rank 3 = telemetry leaf locks. Lower rank is
# OUTER: acquiring a lower-ranked lock (or calling a method that does)
# while holding a higher-ranked one is an inversion.
_ENGINE_CLASSES = {"Engine", "NativeEngine"}
_POOL_CLASSES = {"BufferPool"}
_TELEMETRY_LEAVES = {"inc", "set", "observe", "push"}
_REGISTRY_FACTORIES = {"counter", "gauge", "histogram"}


def _lock_rank(expr: ast.AST, cls_name: Optional[str]) -> Optional[int]:
    """Rank of a ``with <expr>:`` acquisition, or None if not a known
    lock. ``self._lock`` ranks by the enclosing class; ``<pool>._lock``
    ranks 2 by receiver name."""
    if not (isinstance(expr, ast.Attribute) and "lock" in expr.attr):
        return None
    recv = expr.value
    if isinstance(recv, ast.Name) and recv.id == "self":
        if cls_name in _POOL_CLASSES:
            return 2
        if cls_name in _ENGINE_CLASSES:
            return 1
        return None
    recv_name = ""
    if isinstance(recv, ast.Attribute):
        recv_name = recv.attr
    elif isinstance(recv, ast.Name):
        recv_name = recv.id
    if "pool" in recv_name.lower():
        return 2
    if "engine" in recv_name.lower():
        return 1
    return None


def _acquirer_table(trees: Dict[str, ast.AST]) -> Dict[str, int]:
    """Method name -> rank of the lock its body acquires directly (the
    table the call-under-lock check resolves names against)."""
    table: Dict[str, int] = {}
    for tree in trees.values():
        for cls in ast.walk(tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            for fn in cls.body:
                if not isinstance(fn, ast.FunctionDef):
                    continue
                for node in ast.walk(fn):
                    if isinstance(node, ast.With):
                        for item in node.items:
                            r = _lock_rank(item.context_expr, cls.name)
                            if r is not None:
                                prev = table.get(fn.name)
                                table[fn.name] = (r if prev is None
                                                  else min(prev, r))
    # One-level transitive closure: wrappers that call an acquirer of
    # their own class (BufferPool.checkout -> checkout_tracked).
    for tree in trees.values():
        for cls in ast.walk(tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            for fn in cls.body:
                if not isinstance(fn, ast.FunctionDef) or \
                        fn.name in table:
                    continue
                for node in ast.walk(fn):
                    if isinstance(node, ast.Call):
                        callee = _attr_name(node.func)
                        if callee in table:
                            table[fn.name] = table[callee]
    return table


def check_lock_order(trees: Dict[str, ast.AST]) -> List[Finding]:
    findings = []
    acquirers = _acquirer_table(trees)
    for rel, tree in trees.items():
        for cls in ast.walk(tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            for fn in ast.walk(cls):
                if not isinstance(fn, ast.FunctionDef):
                    continue
                for w in ast.walk(fn):
                    if not isinstance(w, ast.With):
                        continue
                    held = [r for item in w.items
                            for r in [_lock_rank(item.context_expr,
                                                 cls.name)]
                            if r is not None]
                    if not held:
                        continue
                    rank = min(held)
                    for node in [n for stmt in w.body
                                 for n in ast.walk(stmt)]:
                        inner: Optional[int] = None
                        where = getattr(node, "lineno", w.lineno)
                        what = ""
                        if isinstance(node, ast.With):
                            for item in node.items:
                                r = _lock_rank(item.context_expr, cls.name)
                                if r is not None:
                                    inner = r
                                    what = ast.unparse(item.context_expr)
                        elif isinstance(node, ast.Call):
                            callee = _attr_name(node.func)
                            if callee in _TELEMETRY_LEAVES or \
                                    callee in _REGISTRY_FACTORIES:
                                inner = 3
                                what = f"{callee}() [telemetry]"
                            elif callee in acquirers and \
                                    callee != fn.name:
                                inner = acquirers[callee]
                                what = f"{callee}()"
                        if inner is not None and inner < rank:
                            findings.append(Finding(
                                "lock-order", rel, where,
                                f"{cls.name}.{fn.name} acquires rank-"
                                f"{inner} lock via {what} while holding "
                                f"a rank-{rank} lock — inverts the "
                                "documented hierarchy (engine > pool > "
                                "telemetry)"))
    return findings


# ---------------------------------------------------------------------------
# Rule: fault-site-registry
# ---------------------------------------------------------------------------

# A faultline SPEC reference: site:mode:count with the real grammar's
# count shapes (N / * / P%, optional @M) — the count anchor is what keeps
# prose like "docs/running.md:32" from matching. Sites are dotted
# lowercase words, exactly as core/faultline.py declares them.
_FAULT_SPEC_RE = re.compile(
    r"\b([a-z_]+(?:\.[a-z_]+)+):([a-z]+):(\*|\d+%?)(?:@\d+)?")

# Text surfaces where chaos specs are referenced (beyond the python
# files the invariant scan already walks).
_FAULT_DOC_GLOBS = ("docs", "CLAUDE.md")


def _fault_registry(root: str):
    """Parse SITES, _MODES and the site->guard-helper map out of
    core/faultline.py. None when the file is absent (fixture roots)."""
    path = os.path.join(root, "horovod_tpu", "core", "faultline.py")
    if not os.path.exists(path):
        return None
    tree = _parse(path)
    if tree is None:
        return None
    sites: Tuple[str, ...] = ()
    modes: Dict[str, Tuple[str, ...]] = {}
    for node in tree.body:  # type: ignore[attr-defined]
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)):
            continue
        tgt = node.targets[0].id
        if tgt == "SITES" and isinstance(node.value, (ast.Tuple, ast.List)):
            sites = tuple(e.value for e in node.value.elts
                          if isinstance(e, ast.Constant))
        elif tgt == "_MODES" and isinstance(node.value, ast.Dict):
            for k, v in zip(node.value.keys, node.value.values):
                if isinstance(k, ast.Constant) and \
                        isinstance(v, (ast.Tuple, ast.List)):
                    modes[k.value] = tuple(
                        e.value for e in v.elts
                        if isinstance(e, ast.Constant))
    helpers: Dict[str, str] = {}  # site -> guard helper function name
    for fn in ast.walk(tree):
        if not isinstance(fn, ast.FunctionDef):
            continue
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) and \
                    _attr_name(node.func) == "check" and node.args and \
                    isinstance(node.args[0], ast.Constant) and \
                    node.args[0].value in sites:
                helpers.setdefault(node.args[0].value, fn.name)
    return sites, modes, helpers


def check_fault_sites(root: str) -> List[Finding]:
    """Every faultline site string referenced in tests/docs/specs must
    resolve to a site (and mode) the registry declares, and every
    declared site must actually be THREADED — its guard helper called
    from real source outside faultline.py. A renamed or unthreaded site
    would otherwise turn the chaos tests that reference it inert while
    they keep passing."""
    reg = _fault_registry(root)
    if reg is None:
        return []
    sites, modes, helpers = reg
    rel_flt = os.path.join("horovod_tpu", "core", "faultline.py")
    findings: List[Finding] = []
    for site in sites:
        if site not in helpers:
            findings.append(Finding(
                "fault-site-registry", rel_flt, 0,
                f"fault site {site!r} is declared in SITES but has no "
                "check(\"<site>\") guard helper in faultline.py"))
    # Threading: each guard helper must be invoked from non-faultline
    # source (horovod_tpu/ only — tests exercising a helper directly do
    # not make the site threaded in the product).
    called: Set[str] = set()
    pkg = os.path.join(root, "horovod_tpu")
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in filenames:
            if not fn.endswith(".py") or fn == "faultline.py":
                continue
            tree = _parse(os.path.join(dirpath, fn))
            if tree is None:
                continue
            for node in ast.walk(tree):
                if isinstance(node, ast.Call):
                    name = _attr_name(node.func)
                    if name:
                        called.add(name)
    for site, helper in sorted(helpers.items()):
        if helper not in called:
            findings.append(Finding(
                "fault-site-registry", rel_flt, 0,
                f"fault site {site!r} has a guard helper {helper}() "
                "that is never called from horovod_tpu/ source — the "
                "site is declared but not threaded, so chaos specs "
                "naming it inject nothing"))
    # Spec references: python files under the scanned trees plus the
    # markdown docs; every site:mode:count string must resolve.
    scan = list(_iter_py_files(root))
    docs_dir = os.path.join(root, _FAULT_DOC_GLOBS[0])
    if os.path.isdir(docs_dir):
        scan += [os.path.join(docs_dir, f)
                 for f in sorted(os.listdir(docs_dir))
                 if f.endswith(".md")]
    claude = os.path.join(root, _FAULT_DOC_GLOBS[1])
    if os.path.exists(claude):
        scan.append(claude)
    for path in scan:
        try:
            text = open(path).read()
        except OSError:
            continue
        rel = os.path.relpath(path, root)
        # Negative-grammar fixtures: a function that references
        # FaultSpecError is TESTING rejection — the deliberately-invalid
        # specs inside it are not site references.
        exempt: List[Tuple[int, int]] = []
        if path.endswith(".py"):
            tree = _parse(path)
            if tree is not None:
                for fn in ast.walk(tree):
                    if isinstance(fn, ast.FunctionDef) and any(
                            isinstance(n, (ast.Name, ast.Attribute))
                            and _attr_name(n) == "FaultSpecError"
                            for n in ast.walk(fn)):
                        last = max((getattr(n, "lineno", fn.lineno)
                                    for n in ast.walk(fn)),
                                   default=fn.lineno)
                        exempt.append((fn.lineno, last))
        for m in _FAULT_SPEC_RE.finditer(text):
            site, mode = m.group(1), m.group(2)
            line = text.count("\n", 0, m.start()) + 1
            if any(a <= line <= b for a, b in exempt):
                continue
            if site not in sites:
                findings.append(Finding(
                    "fault-site-registry", rel, line,
                    f"fault spec references site {site!r}, which "
                    "core/faultline.py SITES does not declare — a "
                    "renamed site silently turns this chaos spec "
                    "inert"))
            elif mode not in modes.get(site, ()):
                findings.append(Finding(
                    "fault-site-registry", rel, line,
                    f"fault spec references mode {mode!r} for site "
                    f"{site!r}; valid modes: "
                    f"{', '.join(modes.get(site, ()))}"))
    return findings


# ---------------------------------------------------------------------------
# Rule: entrypoint-imports
# ---------------------------------------------------------------------------

def check_entrypoint_imports(root: str,
                             entrypoints: Optional[List[str]] = None
                             ) -> List[Finding]:
    """``run.py`` (the launcher) must not import jax or any framework
    at module level: argparse errors must never pay the multi-second
    import, and the launcher must start on hosts where the frameworks
    are absent."""
    findings = []
    stdlib = getattr(sys, "stdlib_module_names", frozenset())
    for rel in entrypoints or (os.path.join("horovod_tpu", "run.py"),):
        path = os.path.join(root, rel)
        if not os.path.exists(path):
            findings.append(Finding(
                "entrypoint-imports", rel, 0,
                "import-free entrypoint is missing"))
            continue
        tree = _parse(path)
        if tree is None:
            findings.append(Finding("entrypoint-imports", rel, 0,
                                    "entrypoint does not parse"))
            continue
        for node in tree.body:
            mods: List[Tuple[str, int]] = []
            if isinstance(node, ast.Import):
                mods = [(a.name.split(".")[0], node.lineno)
                        for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                if node.module:
                    mods = [(node.module.split(".")[0], node.lineno)]
            for mod, line in mods:
                if mod not in stdlib:
                    findings.append(Finding(
                        "entrypoint-imports", rel, line,
                        f"module-level import of non-stdlib {mod!r} — "
                        "this entrypoint must stay import-free (defer "
                        "the import into the function that needs it)"))
    return findings


# ---------------------------------------------------------------------------
# Rule: phase-names
# ---------------------------------------------------------------------------

def check_phase_names(tree: ast.AST, rel: str) -> List[Finding]:
    """Every literal handed to ``phase(...)`` is a name of
    ``common/phases.py``'s vocabulary. ``phase`` refuses another name
    when the call is traced; a path that no test traces (a two-tier
    route, an error-feedback branch) would refuse in a user's step."""
    from horovod_tpu.common.phases import PHASES

    return [
        Finding("phase-names", rel, node.lineno,
                f"phase({node.args[0].value!r}) is not in the vocabulary "
                f"{PHASES}: use one of them or add the name to "
                "horovod_tpu/common/phases.py")
        for node in ast.walk(tree)
        if isinstance(node, ast.Call)
        and _attr_name(node.func) in ("phase", "_phase")
        and len(node.args) == 1 and isinstance(node.args[0], ast.Constant)
        and isinstance(node.args[0].value, str)
        and node.args[0].value not in PHASES]


# ---------------------------------------------------------------------------
# Entry
# ---------------------------------------------------------------------------

# Files whose lock usage participates in the documented hierarchy.
LOCK_SCOPE = (
    os.path.join("horovod_tpu", "core", "engine.py"),
    os.path.join("horovod_tpu", "core", "native_engine.py"),
    os.path.join("horovod_tpu", "core", "bufferpool.py"),
)


def check(root: str,
          files: Optional[List[str]] = None,
          lock_files: Optional[List[str]] = None,
          entrypoints: Optional[List[str]] = None) -> List[Finding]:
    findings: List[Finding] = []
    paths = files if files is not None else list(_iter_py_files(root))
    for path in paths:
        tree = _parse(path)
        if tree is None:
            continue
        rel = os.path.relpath(path, root)
        findings.extend(check_tf_bridge(tree, rel))
        findings.extend(check_engine_lifecycle(tree, rel))
        findings.extend(check_donate_mutate(tree, rel))
        findings.extend(check_eager_drain(tree, rel))
        findings.extend(check_phase_names(tree, rel))
    lock_trees: Dict[str, ast.AST] = {}
    for rel in lock_files or LOCK_SCOPE:
        path = os.path.join(root, rel)
        if os.path.exists(path):
            tree = _parse(path)
            if tree is not None:
                lock_trees[rel] = tree
    findings.extend(check_lock_order(lock_trees))
    findings.extend(check_entrypoint_imports(root, entrypoints))
    findings.extend(check_fault_sites(root))
    return findings
